"""siddhi-audit: compiled-plan cost fingerprints + baseline regression gate.

ROADMAP item 3 asks perf PRs to be gated "on flops/bytes from EXPLAIN,
not wall-clock alone" — this module is that gate.  For every app in the
audit corpus (analysis/corpus.py: the shipped samples + the
flagship/windowed_join/block-NFA bench shapes) it extracts a per-query
**plan fingerprint**:

- per compiled step: XLA flops, bytes accessed, transcendentals,
  argument/output/temp/peak memory, collective ops in the HLO, and the
  argument signature it was graded at;
- per query: hot-path totals, dispatch-program count, recompile
  signature arity (how many distinct programs first traffic will
  trace), state bytes by component, emission caps, fusion eligibility
  (+ the concrete exclusion reason), and the static type/null-flow
  summary (analysis/typeflow.py).

Extraction is the EXPLAIN re-lowering path (observability/explain.py
`step_cost`) fed with canonical synthesized signatures
(analysis/signatures.py) and run under `RECOMPILES.suppress()`: the
audit plans and lowers but NEVER dispatches a step, sends traffic, or
fetches device memory — `tests/test_audit.py` enforces all three.

`diff_fingerprints` grades a fresh extraction against the checked-in
`PLAN_BASELINE.json` with per-metric relative tolerances: cost-metric
*increases* beyond tolerance are regressions (decreases are reported as
improvements worth a baseline update), and structural facts — signature,
collectives, caps, fusion, state components, types — must match
exactly.  Exit-code contract (CLI in tools/audit.py): 0 clean,
1 regression, 2 error.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional, Tuple

BASELINE_VERSION = 1
DEFAULT_BASELINE = "PLAN_BASELINE.json"

# ---------------------------------------------------------------------------
# metric catalog — docgen renders this table; tolerances are RELATIVE
# (0.05 = +5% passes, more fails).  `gate`:
#   increase  — fail when current > baseline * (1 + tol)
#   exact     — any change fails (structural contract)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    tolerance: float
    gate: str            # 'increase' | 'exact'
    description: str


METRICS: List[Metric] = [
    Metric("flops", 0.05, "increase",
           "XLA cost_analysis flops per dispatch of the step program"),
    Metric("transcendentals", 0.05, "increase",
           "transcendental op count per dispatch"),
    Metric("bytes_accessed", 0.05, "increase",
           "XLA cost_analysis bytes accessed per dispatch — the "
           "bandwidth-bound hot paths live and die on this"),
    Metric("argument_bytes", 0.02, "increase",
           "bytes of device arguments the compiled step binds"),
    Metric("output_bytes", 0.05, "increase",
           "bytes of device outputs per dispatch"),
    Metric("temp_bytes", 0.25, "increase",
           "XLA temp allocation per dispatch (scheduler-sensitive, "
           "hence the loose tolerance)"),
    Metric("peak_bytes", 0.25, "increase",
           "argument+output+temp-alias live-at-once estimate"),
    Metric("state_bytes", 0.0, "increase",
           "per-component device state (shape×dtype arithmetic — "
           "deterministic, so zero tolerance)"),
    Metric("collectives", 0.0, "exact",
           "collective-op kinds in the compiled step HLO (sharded "
           "plans) — a new collective is a new mesh synchronization"),
    Metric("signature", 0.0, "exact",
           "canonical argument signature the step was graded at"),
    Metric("dispatch_programs", 0.0, "exact",
           "XLA programs one steady-state batch dispatches"),
    Metric("recompile_signature_arity", 0.0, "exact",
           "distinct step programs first traffic will trace (compile "
           "storms scale with this)"),
    Metric("emission_cap", 0.0, "exact",
           "per-dispatch emission row cap (None = uncapped sentinel)"),
    Metric("fusion", 0.0, "exact",
           "@fuse eligibility / active K / concrete exclusion reason"),
    Metric("types", 0.0, "exact",
           "static output column types + nullable set (typeflow pass)"),
    Metric("equi_fastpath", 0.0, "exact",
           "equi-join fast-path mode / key attrs / lane capacity (or "
           "the inapplicability reason) — a silently deactivated fast "
           "path is a 10-100x regression the float metrics would also "
           "catch, this names the cause"),
    Metric("merge", 0.0, "exact",
           "multi-query merge fact: group membership, shared/stacked "
           "mode, and the group's dispatch-program count (or the "
           "planner's ineligibility reason) — an accidentally unmerged "
           "group re-pays N dispatches per batch, which wall-clock "
           "benchmarks would catch late and this pins in CI"),
]

DEFAULT_TOLERANCES: Dict[str, float] = {m.name: m.tolerance
                                        for m in METRICS}

_STEP_FLOAT_METRICS = ("flops", "transcendentals", "bytes_accessed")
_MEM_FLOAT_METRICS = ("argument_bytes", "output_bytes", "temp_bytes",
                      "peak_bytes")


# ---------------------------------------------------------------------------
# fingerprint extraction
# ---------------------------------------------------------------------------

def _merge_fact(qr) -> Dict:
    """Multi-query-optimizer fact pinned per query (core/plan_facts.
    merge_facts): group membership + mode when merged, the exact
    ineligibility reason otherwise.  Exact-match gated — an accidental
    unmerge flips `merged` and fails the build."""
    from ..core.plan_facts import merge_facts
    try:
        return merge_facts(qr)
    except Exception:  # noqa: BLE001 — extraction must not kill audit
        return {"merged": False}


def query_fingerprint(rt, qname: str, typeflow_summary: Optional[Dict]
                      = None, collectives: bool = False) -> Dict:
    """One query's plan fingerprint from a live (never-run) runtime."""
    from ..core.plan_facts import render_cap
    from ..core import fusion as _fusion
    from ..observability.explain import _steps_of, step_cost
    from ..observability.memory import query_component_bytes
    from .signatures import primary_roles, synthesize

    qr = rt.query_runtimes[qname]
    kind = qr._kind
    synth = synthesize(qr, kind)
    cache = rt.__dict__.setdefault("_explain_cost_cache", {})
    mesh = qr.mesh or qr.keyed_mesh
    want_coll = collectives or mesh is not None
    steps: Dict[str, Dict] = {}
    for role, fn in _steps_of(qr, kind):
        c = step_cost(fn, cache, deep=True, specs=synth.get(role),
                      collectives=want_coll)
        if not c.get("available"):
            continue
        entry: Dict[str, Any] = {
            "signature": c.get("signature"),
            "flops": c.get("flops", 0.0),
            "transcendentals": c.get("transcendentals", 0.0),
            "bytes_accessed": c.get("bytes_accessed", 0.0),
        }
        mem = c.get("memory") or {}
        for k in _MEM_FLOAT_METRICS:
            entry[k] = mem.get(k, 0)
        if want_coll:
            entry["collectives"] = c.get("collectives", [])
        steps[role] = entry

    primaries = [r for r in primary_roles(qr, kind) if r in steps]
    totals = {
        k: sum(steps[r].get(k, 0) or 0 for r in primaries)
        for k in ("flops", "bytes_accessed")
    }
    totals["peak_bytes"] = max(
        (steps[r].get("peak_bytes", 0) or 0 for r in primaries),
        default=0)
    comp = query_component_bytes(qr)
    p = qr.planned
    coll_kinds = sorted({c for s in steps.values()
                         for c in s.get("collectives", ())})
    fp: Dict[str, Any] = {
        "kind": kind,
        "steps": steps,
        "totals": totals,
        "dispatch_programs": len(primaries),
        "recompile_signature_arity": len(steps),
        "collective_kinds": coll_kinds,
        "collective_steps": sum(1 for s in steps.values()
                                if s.get("collectives")),
        "state": {"components": dict(comp),
                  "total_bytes": sum(comp.values())},
        "emission": {
            "cap_rows": render_cap(p.compact_rows),
            "cap_explicit": bool(p.emit_explicit),
        },
        "fusion": _fusion.eligibility(qr, kind),
        "merge": _merge_fact(qr),
    }
    if hasattr(p, "fastpath_facts"):
        fp["equi_fastpath"] = p.fastpath_facts()
    if typeflow_summary is not None:
        fp["types"] = typeflow_summary
    return fp


def app_fingerprint(rt, collectives: bool = False) -> Dict[str, Dict]:
    """{query: fingerprint} for every query of a (never-run) runtime."""
    from .typeflow import infer_app, summarize
    try:
        flows = infer_app(rt.app).queries
    except Exception:  # noqa: BLE001 — inference must not block audit
        flows = {}
    out = {}
    for qname in sorted(rt.query_runtimes):
        tf = flows.get(qname)
        out[qname] = query_fingerprint(
            rt, qname,
            typeflow_summary=summarize(tf) if tf is not None else None,
            collectives=collectives)
    return out


def _mesh_of(n: int):
    import numpy as np
    if n <= 1:
        return None
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < n:
        return False          # environment cannot build this shape
    return Mesh(np.array(devs[:n]), ("shard",))


def corpus_fingerprints(samples_dir: Optional[str] = None,
                        include_bench: bool = True,
                        ) -> Tuple[Dict[str, Dict], List[str]]:
    """Fingerprint the whole corpus.  Returns ({corpus key:
    {devices, queries}}, [skipped keys]) — a shape is skipped (not
    failed) when the environment lacks the devices it needs."""
    from .. import SiddhiManager
    from .corpus import corpus as _corpus

    out: Dict[str, Dict] = {}
    skipped: List[str] = []
    for key, ql, devices in _corpus(samples_dir, include_bench):
        mesh = _mesh_of(devices)
        if mesh is False:
            skipped.append(key)
            continue
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(ql, mesh=mesh) \
                if mesh is not None else m.create_siddhi_app_runtime(ql)
            entry = {"devices": devices,
                     "queries": app_fingerprint(
                         rt, collectives=devices > 1)}
            if devices > 1:
                key = f"{key}@{devices}"
            out[key] = entry
        finally:
            m.shutdown()
    return out, skipped


def environment() -> Dict[str, str]:
    import jax
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend()}


def build_baseline(samples_dir: Optional[str] = None,
                   include_bench: bool = True,
                   tolerances: Optional[Dict[str, float]] = None
                   ) -> Dict:
    fps, skipped = corpus_fingerprints(samples_dir, include_bench)
    return {
        "version": BASELINE_VERSION,
        "generated_by": "python -m siddhi_tpu.tools.audit update",
        "environment": environment(),
        "tolerances": dict(tolerances or DEFAULT_TOLERANCES),
        "skipped_at_update": skipped,
        "corpus": fps,
    }


# ---------------------------------------------------------------------------
# baseline diff
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Delta:
    """One comparison outcome.  level: regression | improvement | note."""

    level: str
    shape: str
    query: Optional[str]
    metric: str
    message: str
    role: Optional[str] = None
    baseline: Any = None
    current: Any = None

    def render(self) -> str:
        where = self.shape + (f":{self.query}" if self.query else "") + \
            (f" [{self.role}]" if self.role else "")
        return f"{self.level.upper():11s} {where} {self.metric}: " \
               f"{self.message}"

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def _rel(base: float, cur: float) -> float:
    if not base:
        return float("inf") if cur else 0.0
    return (cur - base) / abs(base)


def _cmp_number(out: List[Delta], shape: str, query: Optional[str],
                role: Optional[str], metric: str, base, cur,
                tol: float) -> None:
    base = float(base or 0)
    cur = float(cur or 0)
    if base == cur:
        return
    r = _rel(base, cur)
    pct = f"{r * 100:+.1f}%"
    msg = f"{base:,.0f} -> {cur:,.0f} ({pct}, tolerance " \
          f"±{tol * 100:.0f}%)"
    if r > tol:
        out.append(Delta("regression", shape, query, metric, msg, role,
                         base, cur))
    elif r < -tol:
        out.append(Delta("improvement", shape, query, metric, msg, role,
                         base, cur))


def _cmp_exact(out: List[Delta], shape: str, query: Optional[str],
               role: Optional[str], metric: str, base, cur) -> None:
    if base != cur:
        out.append(Delta("regression", shape, query, metric,
                         f"{base!r} -> {cur!r} (exact-match contract)",
                         role, base, cur))


def diff_fingerprints(baseline: Dict, current: Dict[str, Dict],
                      skipped: Optional[List[str]] = None,
                      tolerances: Optional[Dict[str, float]] = None
                      ) -> List[Delta]:
    """Grade `current` corpus fingerprints against a loaded baseline."""
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(baseline.get("tolerances") or {})
    tol.update(tolerances or {})
    out: List[Delta] = []
    base_corpus: Dict[str, Dict] = baseline.get("corpus", {})
    skipped = list(skipped or ())

    for shape in sorted(set(base_corpus) | set(current)):
        b, c = base_corpus.get(shape), current.get(shape)
        if c is None:
            if any(shape.startswith(f"{s}@") or shape == s
                   for s in skipped):
                out.append(Delta("note", shape, None, "devices",
                                 "skipped: environment has too few "
                                 "devices for this shape"))
            else:
                out.append(Delta("regression", shape, None, "corpus",
                                 "shape in baseline but not produced "
                                 "by this checkout"))
            continue
        if b is None:
            out.append(Delta("regression", shape, None, "corpus",
                             "unbaselined shape — run `python -m "
                             "siddhi_tpu.tools.audit update`"))
            continue
        bq, cq = b.get("queries", {}), c.get("queries", {})
        for q in sorted(set(bq) | set(cq)):
            if q not in cq:
                out.append(Delta("regression", shape, q, "corpus",
                                 "query disappeared from the plan"))
                continue
            if q not in bq:
                out.append(Delta("regression", shape, q, "corpus",
                                 "unbaselined query — run update"))
                continue
            _diff_query(out, shape, q, bq[q], cq[q], tol)
    return out


def _diff_query(out: List[Delta], shape: str, q: str, b: Dict, c: Dict,
                tol: Dict[str, float]) -> None:
    bsteps, csteps = b.get("steps", {}), c.get("steps", {})
    for role in sorted(set(bsteps) | set(csteps)):
        if role not in csteps:
            out.append(Delta("regression", shape, q, "steps",
                             "compiled step variant disappeared",
                             role))
            continue
        if role not in bsteps:
            out.append(Delta("regression", shape, q, "steps",
                             "new compiled step variant (unbaselined)",
                             role))
            continue
        bs, cs = bsteps[role], csteps[role]
        for m in _STEP_FLOAT_METRICS + _MEM_FLOAT_METRICS:
            _cmp_number(out, shape, q, role, m, bs.get(m), cs.get(m),
                        tol.get(m, 0.0))
        _cmp_exact(out, shape, q, role, "signature",
                   bs.get("signature"), cs.get("signature"))
        bcoll = bs.get("collectives", []) or []
        ccoll = cs.get("collectives", []) or []
        added = sorted(set(ccoll) - set(bcoll))
        removed = sorted(set(bcoll) - set(ccoll))
        if added:
            out.append(Delta("regression", shape, q, "collectives",
                             f"new collective op(s) {added} in the "
                             "step HLO", role, bcoll, ccoll))
        if removed:
            out.append(Delta("improvement", shape, q, "collectives",
                             f"collective op(s) {removed} no longer "
                             "emitted", role, bcoll, ccoll))
    # per-component state bytes
    bc = (b.get("state") or {}).get("components", {})
    cc = (c.get("state") or {}).get("components", {})
    for comp in sorted(set(bc) | set(cc)):
        if comp not in cc or comp not in bc:
            _cmp_exact(out, shape, q, None, "state_bytes",
                       {comp: bc.get(comp)}, {comp: cc.get(comp)})
            continue
        _cmp_number(out, shape, q, comp, "state_bytes", bc[comp],
                    cc[comp], tol.get("state_bytes", 0.0))
    # structural facts
    for metric, path in (
            ("dispatch_programs", "dispatch_programs"),
            ("recompile_signature_arity", "recompile_signature_arity"),
            ("collectives", "collective_kinds"),
            ("emission_cap", "emission"),
            ("fusion", "fusion"),
            ("equi_fastpath", "equi_fastpath"),
            ("merge", "merge"),
            ("types", "types")):
        _cmp_exact(out, shape, q, None, metric, b.get(path),
                   c.get(path))


# ---------------------------------------------------------------------------
# load / save
# ---------------------------------------------------------------------------

def baseline_path(path: Optional[str] = None) -> str:
    from .corpus import repo_root
    if path:
        return path
    return os.path.join(repo_root(), DEFAULT_BASELINE)


def load_baseline(path: Optional[str] = None) -> Dict:
    p = baseline_path(path)
    with open(p, "r") as fh:
        b = json.load(fh)
    v = b.get("version")
    if v != BASELINE_VERSION:
        raise ValueError(f"baseline {p} has version {v!r}; this build "
                         f"expects {BASELINE_VERSION} — regenerate with "
                         "`python -m siddhi_tpu.tools.audit update`")
    return b


def save_baseline(baseline: Dict, path: Optional[str] = None) -> str:
    p = baseline_path(path)
    tmp = p + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(baseline, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, p)
    return p


def has_regressions(deltas: List[Delta]) -> bool:
    return any(d.level == "regression" for d in deltas)

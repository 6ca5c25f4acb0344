"""Query EXPLAIN: planned operator tree + per-step XLA cost analysis.

Reference (what): the reference exposes per-operator runtime statistics and
an event-flow debugger (SiddhiAppRuntime.getStatistics / SiddhiDebugger),
so an operator can see which processor in a query chain owns the time.
TPU design (how): our "operators" compile into a handful of jitted XLA
programs (query step, per-stream pattern steps, join side steps, fused
scan steps), so the right introspection unit is the *compiled step*:
`explain()` renders the syntactic operator chain (filter / window /
stream-fn / join / NFA stages from the query AST) next to the compiled
facts — carry/state dtypes and shapes, emission caps, fusion eligibility
— and annotates each jitted step with XLA `cost_analysis()` (flops, bytes
accessed) plus `memory_analysis()` (argument/output/temp bytes = the
estimated device peak) from a re-lowering of the step at the signature it
last actually ran (steputil.jit_step captures the argument
ShapeDtypeStructs at trace time).

The diagnostic re-trace runs under `RECOMPILES.suppress()` so EXPLAIN can
never inflate the recompile counters it sits next to, and lowered cost
reports are memoized per (step, signature) on the runtime, so a repeated
`GET /explain` costs one dict lookup.  EXPLAIN may compile (deep=True);
it is an on-demand diagnostic, NOT scrape-path — `/metrics` and
`/healthz` never call it.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from .recompile import RECOMPILES

# cost_analysis keys worth surfacing (the raw dict carries per-operand
# utilization entries too noisy for a report)
_COST_KEYS = ("flops", "transcendentals", "bytes accessed")


# ---------------------------------------------------------------------------
# expression / AST rendering (SiddhiQL-ish, for the operator tree)
# ---------------------------------------------------------------------------

_BINOPS = {"Add": "+", "Subtract": "-", "Multiply": "*", "Divide": "/",
           "Mod": "%", "And": "and", "Or": "or"}


def render_expr(e) -> str:
    """Compact one-line rendering of a query_api expression tree."""
    from ..query_api import expression as ex
    if e is None:
        return ""
    if isinstance(e, ex.Constant):
        return repr(e.value)
    if isinstance(e, ex.Variable):
        pre = f"{e.stream_id}." if e.stream_id else ""
        if e.stream_index is not None:
            pre = f"{e.stream_id}[{e.stream_index}]."
        return pre + e.attribute_name
    if isinstance(e, ex.Compare):
        return (f"{render_expr(e.left)} {e.operator} "
                f"{render_expr(e.right)}")
    if isinstance(e, ex.Not):
        return f"not ({render_expr(e.expression)})"
    if isinstance(e, ex.IsNull):
        if e.expression is not None:
            return f"{render_expr(e.expression)} is null"
        return f"{e.stream_id} is null"
    if isinstance(e, ex.In):
        return f"{render_expr(e.expression)} in {e.source_id}"
    if isinstance(e, ex.AttributeFunction):
        ns = f"{e.namespace}:" if e.namespace else ""
        args = ", ".join(render_expr(p) for p in e.parameters)
        return f"{ns}{e.name}({args})"
    op = _BINOPS.get(type(e).__name__)
    if op is not None:
        return f"({render_expr(e.left)} {op} {render_expr(e.right)})"
    return type(e).__name__


def _handler_nodes(sis) -> List[Dict]:
    """filter/window/stream-fn chain of a SingleInputStream, in order."""
    from ..query_api.query import Filter, StreamFunction, Window
    out: List[Dict] = []
    for h in getattr(sis, "stream_handlers", ()):
        if isinstance(h, Filter):
            out.append({"op": "filter",
                        "expression": render_expr(h.expression)})
        elif isinstance(h, Window):
            name = (h.namespace + ":" if h.namespace else "") + h.name
            out.append({"op": "window", "name": name,
                        "parameters": [render_expr(p)
                                       for p in h.parameters]})
        elif isinstance(h, StreamFunction):
            name = (h.namespace + ":" if h.namespace else "") + h.name
            out.append({"op": "function", "name": name,
                        "parameters": [render_expr(p)
                                       for p in h.parameters]})
    return out


def _state_node(el) -> Dict:
    """Recursive rendering of a pattern/sequence state-element tree."""
    from ..query_api import query as q
    if isinstance(el, q.StreamStateElement):
        sis = el.basic_single_input_stream
        return {"op": "stream", "stream": sis.stream_id,
                "handlers": _handler_nodes(sis)}
    if isinstance(el, q.AbsentStreamStateElement):
        sis = el.basic_single_input_stream
        return {"op": "absent", "stream": sis.stream_id,
                "waiting_time_ms": el.waiting_time,
                "handlers": _handler_nodes(sis)}
    if isinstance(el, q.CountStateElement):
        return {"op": "count", "min": el.min_count, "max": el.max_count,
                "of": _state_node(el.stream_state_element)}
    if isinstance(el, q.LogicalStateElement):
        return {"op": el.type.lower(),
                "left": _state_node(el.stream_state_element_1),
                "right": _state_node(el.stream_state_element_2)}
    if isinstance(el, q.NextStateElement):
        return {"op": "next", "first": _state_node(el.state_element),
                "then": _state_node(el.next_state_element)}
    if isinstance(el, q.EveryStateElement):
        return {"op": "every", "of": _state_node(el.state_element)}
    return {"op": type(el).__name__}


def _selector_node(sel, planned) -> Dict:
    node: Dict[str, Any] = {"op": "select"}
    if sel is not None:
        if sel.selection_list:
            node["projection"] = [
                {"as": a.name, "expression": render_expr(a.expression)}
                for a in sel.selection_list]
        else:
            node["projection"] = "*"
        if sel.group_by_list:
            node["group_by"] = [render_expr(v) for v in sel.group_by_list]
        if sel.having_expression is not None:
            node["having"] = render_expr(sel.having_expression)
        if sel.order_by_list:
            node["order_by"] = [f"{render_expr(o.variable)} {o.order}"
                                for o in sel.order_by_list]
        if sel.limit is not None:
            node["limit"] = sel.limit
    node["out_columns"] = list(planned.out_schema.names)
    return node


# ---------------------------------------------------------------------------
# state / carry description
# ---------------------------------------------------------------------------

def describe_state(state) -> List[Dict]:
    """One entry per state-pytree leaf: path, dtype, shape, nbytes —
    computed from shape/dtype metadata only (never fetches device data)."""
    import jax
    from .memory import leaf_nbytes
    out: List[Dict] = []
    try:
        flat = jax.tree_util.tree_flatten_with_path(state)[0]
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return out
    for path, leaf in flat:
        keys = "".join(str(p) for p in path) or "/"
        out.append({
            "path": keys,
            "dtype": str(getattr(leaf, "dtype", type(leaf).__name__)),
            "shape": list(getattr(leaf, "shape", ())),
            "nbytes": leaf_nbytes(leaf),
        })
    return out


# ---------------------------------------------------------------------------
# XLA cost analysis of jitted steps
# ---------------------------------------------------------------------------

def _spec_sig(specs) -> str:
    import jax
    try:
        return " ".join(f"{s.dtype}{list(s.shape)}"
                        for s in jax.tree_util.tree_leaves(specs))
    except Exception:  # noqa: BLE001
        return repr(specs)


def step_cost(fn, cache: Optional[Dict] = None,
              deep: bool = True, specs=None,
              collectives: bool = False) -> Dict:
    """XLA cost analysis of one jitted step at its last-traced signature.

    Returns {available, flops, bytes_accessed, peak_bytes, ...} or
    {available: False, reason} when the step has not run yet (no captured
    signature) or the backend rejects the analysis.  `deep=True` also
    compiles the lowering for memory_analysis (argument/output/temp
    bytes); the result is memoized in `cache` keyed by (owner, signature)
    so repeated EXPLAINs never re-lower.

    `specs` supplies synthesized argument ShapeDtypeStructs for steps
    that have never traced (analysis/signatures.py) — the plan auditor's
    no-traffic path; a captured (traced) signature always wins so
    EXPLAIN keeps reporting what actually ran.  `collectives=True` also
    scans the compiled HLO for collective ops (implies compiling)."""
    holder = getattr(fn, "_siddhi_argspec", None)
    traced = holder.get("argspecs") if holder else None
    origin = "traced" if traced is not None else "synthesized"
    if traced is not None:
        specs = traced
    if specs is None:
        return {"available": False,
                "reason": "step has not executed yet — send traffic, "
                          "then re-run explain"}
    owner = getattr(fn, "_siddhi_owner", "step")
    sig = _spec_sig(specs)
    key = (owner, id(fn), sig, bool(deep), bool(collectives))
    if cache is not None:
        hit = cache.get(key)
        if hit is not None:
            return hit
    out: Dict[str, Any] = {"available": True, "signature": sig,
                           "signature_origin": origin}
    try:
        with RECOMPILES.suppress():
            lowered = fn.lower(*specs)
        ca = lowered.cost_analysis()
        compiled = None
        if ca is None or deep or collectives:
            with RECOMPILES.suppress():
                compiled = lowered.compile()
        if ca is None:
            # XLA:TPU analyses compiled executables only (the lowering's
            # cost_analysis() is None there), so on the TPU even a shallow
            # EXPLAIN compiles — through the persistent cache when the
            # step has already run
            ca = compiled.cost_analysis()
        for k in _COST_KEYS:
            if k in ca:
                out[k.replace(" ", "_")] = float(ca[k])
        if deep or collectives:
            ma = compiled.memory_analysis()
            arg = int(getattr(ma, "argument_size_in_bytes", 0))
            outb = int(getattr(ma, "output_size_in_bytes", 0))
            tmp = int(getattr(ma, "temp_size_in_bytes", 0))
            alias = int(getattr(ma, "alias_size_in_bytes", 0))
            out["memory"] = {
                "argument_bytes": arg, "output_bytes": outb,
                "temp_bytes": tmp, "alias_bytes": alias,
                # live-at-once estimate while the step executes
                "peak_bytes": arg + outb + tmp - alias,
            }
            if collectives:
                from ..sharding.metrics import hlo_collectives
                out["collectives"] = hlo_collectives(compiled)
    except Exception as exc:  # noqa: BLE001 — diagnostics must not throw
        return {"available": False, "signature": sig,
                "reason": f"cost analysis failed: {exc!r}"}
    if cache is not None:
        if len(cache) >= 64:
            cache.clear()
        cache[key] = out
    return out


def _steps_of(qr, kind: str) -> List[Tuple[str, Any]]:
    """(role, jitted fn) pairs for a query runtime — every compiled XLA
    program that can run on the query's hot path."""
    p = qr.planned
    steps: List[Tuple[str, Any]] = []
    if kind == "pattern":
        # each variant is its own XLA program: the per-stream step
        # (gather/scatter, block or sharded), the contiguous-slot dense
        # specialization, and the mesh's @fuse step
        for role, d in (("step", p.steps), ("dense_step", p.dense_steps),
                        ("shard_fused_step", p.shard_fused_steps)):
            for sid, fn in (d or {}).items():
                steps.append((f"{role}[{sid}]", fn))
        if p.timer_step is not None:
            steps.append(("timer_step", p.timer_step))
    elif kind == "join":
        if p.step_left is not None:
            steps.append(("step[left]", p.step_left))
        if p.step_right is not None:
            steps.append(("step[right]", p.step_right))
        # a ring side's other programs, as they were asked for: a deeper
        # walk (`step[left]@16`), stamps out of order (`slow_step[...]`)
        for (is_left, depth, slow), fn in sorted(p.side_steps.items()):
            steps.append((
                f"{'slow_' if slow else ''}step"
                f"[{'left' if is_left else 'right'}]"
                f"{'' if depth == 1 else '@%d' % depth}", fn))
    else:
        steps.append(("step", p.step))
    for (fkind, _), (body, fn) in qr._fused_cache.items():
        steps.append((f"fused_step[{fkind}]", fn))
    mg = qr._merged
    if mg is not None:
        # the program a merged member ACTUALLY dispatches through
        # (optimizer/mqo.py); costs appear once it has traced — the
        # audit gate pins merging via the `merge` fact instead, so this
        # traced-only entry can never make fingerprints nondeterministic
        steps.append(("merged_step", mg._step))
        for (fkind, _), (body, fn) in mg._fused_cache.items():
            steps.append((f"merged_fused_step[{fkind}]", fn))
    return steps


def compiled_steps(qr) -> List[Tuple[str, Any, Any]]:
    """(role, jitted fn, argspecs) for every XLA program on the query's
    hot path, the serving ring's append/read pair included.  `argspecs`
    is the signature the program last traced at (`fn.lower(*argspecs)`
    reproduces what ran) or None when it has not run yet.  Served as
    `SiddhiAppRuntime.compiled_steps` — what a script outside the
    package walks instead of the planner's attributes."""
    steps = _steps_of(qr, qr._kind)
    ring = qr._serve_ring
    if ring is not None:
        steps = steps + ring.programs()
    return [(role, fn,
             (getattr(fn, "_siddhi_argspec", None) or {}).get("argspecs"))
            for role, fn in steps]


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

def _fusion_node(qr, kind: str) -> Dict:
    from ..core import fusion as _fusion
    return _fusion.eligibility(qr, kind)


def _merge_node(qr) -> Dict:
    """Multi-query-optimizer fact for this query (core/plan_facts.
    merge_facts): group/owner/mode/members when merged, the planner's
    exact ineligibility reason otherwise — the same single source lint
    MQO001 prints."""
    from ..core.plan_facts import merge_facts
    try:
        return merge_facts(qr)
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return {"merged": False}


def _sharding_entry(qr, kind: str, deep: bool) -> Dict:
    """{'sharding': node} for mesh-sharded queries (shard layout,
    per-shard residency, and — deep — the collectives in the compiled
    HLO), {} for single-device plans."""
    try:
        from ..sharding import explain_node
        node = explain_node(qr, kind, deep=deep)
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        node = None
    return {"sharding": node} if node is not None else {}


def _emission_node(qr, kind: str) -> Dict:
    from ..core.plan_facts import render_cap
    p = qr.planned
    node: Dict[str, Any] = {}
    if p.compact_rows is not None:
        node["cap_rows"] = render_cap(p.compact_rows)
        node["cap_explicit"] = bool(p.emit_explicit)
    if kind == "pattern":
        node["per_key"] = True
    else:
        node["batch_capacity"] = int(p.batch_capacity)
    return node


def _serving_node(rt, qr) -> Dict:
    """Device-resident serving facts (serving/ring.py): whether @serve
    routes this query's emissions through an on-device ring, the live
    ring occupancy/overflow counters once traffic has flowed, and the
    exclusion reason when the planner keeps delivery inline."""
    enabled = bool(qr.serve_emit)
    node: Dict[str, Any] = {"enabled": enabled}
    if not enabled:
        return node
    try:
        from ..serving import serving_config
        node["drain_interval_ms"] = \
            serving_config(rt)["drain_interval_ms"]
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        pass
    if qr.planned.needs_timer:
        # same exclusion as @pipeline: timer-bearing queries deliver
        # inline so wake scheduling stays synchronous
        node["active"] = False
        node["excluded"] = "needs_timer"
        return node
    node["active"] = True
    ring = qr._serve_ring
    if ring is not None:
        try:
            node["ring"] = ring.facts()
        except Exception:  # noqa: BLE001 — diagnostics must not throw
            pass
    return node


def _phases_node(rt, qr) -> Dict:
    """Live phase budget for this query (observability/phases.py): the
    per-phase seconds/share entry from phase_report, or a hint to send
    traffic when nothing has accumulated yet.  Host counters only."""
    try:
        rep = rt.phase_report()
        node = rep.get("queries", {}).get(qr.name)
        if node is None:
            return {"available": False,
                    "reason": "no phase samples yet — send traffic, "
                              "then re-run explain"}
        return {"available": True,
                "sample_every": rep.get("sample_every", 0), **node}
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return {"available": False, "reason": "phase report failed"}


def _utilization_node(rt, qr) -> Dict:
    """Live state-observatory view for this query (observability/
    stateobs.py): per-structure occupancy/capacity/high-water plus key
    hotness, or a hint to send traffic.  Host mirrors only — this node
    never touches the device."""
    try:
        from .stateobs import collect, obs_enabled
        if not obs_enabled(rt):
            return {"available": False,
                    "reason": "state observatory disabled "
                              "(state.obs.enabled=false)"}
        collect(rt)
        snap = rt.stats.stateobs.snapshot()
        structures = snap["structures"].get(qr.name)
        hotness = snap["hotness"].get(qr.name)
        if not structures and not hotness:
            return {"available": False,
                    "reason": "no sized structures observed yet — send "
                              "traffic, then re-run explain"}
        return {"available": True,
                "structures": structures or {},
                **({"hotness": hotness} if hotness else {})}
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return {"available": False, "reason": "state report failed"}


def _tree_for(qr, kind: str) -> Dict:
    """Planned operator tree from the query AST + compiled plan facts."""
    from ..query_api.query import (JoinInputStream, SingleInputStream,
                                   StateInputStream)
    p = qr.planned
    ast = qr._query_ast
    tree: Dict[str, Any] = {"kind": kind}
    ist = getattr(ast, "input_stream", None) if ast is not None else None
    if isinstance(ist, StateInputStream):
        tree["pattern"] = {
            "type": ist.state_type.lower(),
            "within_ms": ist.within_time,
            "states": _state_node(ist.state_element),
        }
        tree["key_capacity"] = p.key_capacity
        tree["nfa_slots"] = p.slots
    elif isinstance(ist, JoinInputStream):
        sides = {}
        for label, sis in (("left", ist.left_input_stream),
                           ("right", ist.right_input_stream)):
            sides[label] = {"stream": sis.stream_id,
                            "handlers": _handler_nodes(sis)}
        tree["join"] = {
            "type": ist.type,
            "on": render_expr(ist.on_compare),
            "trigger": ist.trigger,
            **sides,
        }
    elif isinstance(ist, SingleInputStream):
        tree["input"] = {"stream": ist.unique_stream_id,
                         "handlers": _handler_nodes(ist)}
    else:
        tree["input"] = {"stream": getattr(p, "input_stream_id", "?")}
    if kind == "plain":
        tree["window_processor"] = {
            "class": type(p.window).__name__,
            "needs_timer": bool(p.window.needs_timer),
            "keyed": bool(p.keyed_window),
        }
    sel = getattr(ast, "selector", None) if ast is not None else None
    tree["select"] = _selector_node(sel, p)
    tree["output"] = {
        "target": p.output_target or "(return)",
        "event_type": p.output_event_type,
    }
    return tree


def explain_query(rt, query_name: str, deep: bool = True) -> Dict:
    """Full EXPLAIN report for one query of a SiddhiAppRuntime: operator
    tree, per-step XLA cost analysis, state shapes + bytes, emission caps,
    fusion eligibility, and recompile history."""
    qr = rt.query_runtimes.get(query_name)
    if qr is None:
        raise KeyError(f"no query named {query_name!r} "
                       f"(queries: {sorted(rt.query_runtimes)})")
    kind = qr._kind
    cache = rt.__dict__.setdefault("_explain_cost_cache", {})
    # canonical no-traffic signatures (analysis/signatures.py): steps
    # that have never traced still get cost analysis, marked
    # signature_origin='synthesized'
    try:
        from ..analysis.signatures import synthesize
        synth = synthesize(qr, kind)
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        synth = {}
    steps = {}
    for role, fn in _steps_of(qr, kind):
        steps[role] = step_cost(fn, cache, deep=deep,
                                specs=synth.get(role))
    from .memory import query_component_bytes
    try:
        plan = qr.planned.describe()     # compiled facts from the planner
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        plan = {}
    leaves = describe_state(qr.state)
    report = {
        "app": rt.name,
        "query": query_name,
        "kind": kind,
        "operator_tree": _tree_for(qr, kind),
        "plan": plan,
        "steps": steps,
        "state": {
            "leaves": leaves,
            "component_bytes": query_component_bytes(qr),
            "total_bytes": sum(d["nbytes"] for d in leaves),
        },
        "emission": _emission_node(qr, kind),
        "fusion": _fusion_node(qr, kind),
        "merge": _merge_node(qr),
        "serving": _serving_node(rt, qr),
        "phases": _phases_node(rt, qr),
        "utilization": _utilization_node(rt, qr),
        **_sharding_entry(qr, kind, deep),
        "recompiles": RECOMPILES.snapshot(
            [query_name, f"fused:{query_name}"]),
        "findings": _lint_findings(rt, query_name),
    }
    return report


def _lint_findings(rt, query_name: Optional[str]) -> List[Dict]:
    """Static-analyzer findings echoed into the EXPLAIN report: app-wide
    findings plus the named query's (attribute/metadata reads only — no
    compile, safe even for shallow explain)."""
    try:
        from ..analysis import analyze
        return [f.to_dict() for f in analyze(rt)
                if query_name is None or f.query in (None, query_name)]
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return []


def _admission_entry(rt) -> Dict:
    """{'admission': report} — the app's quota/ladder state rendered
    into EXPLAIN so capacity questions and plan questions are answered
    in one place (core/admission.py; attribute reads only)."""
    adm = getattr(rt, "admission", None)
    if adm is None:
        return {}
    try:
        return {"admission": adm.report()}
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return {}


def explain_app(rt, deep: bool = False) -> Dict:
    """EXPLAIN for every query of an app (shallow by default: skips the
    per-step compile for memory analysis)."""
    return {"app": rt.name,
            **_admission_entry(rt),
            "queries": {q: explain_query(rt, q, deep=deep)
                        for q in sorted(rt.query_runtimes)}}

"""Plan auditor: fingerprints, baseline diff gate, exit-code contract,
and the never-execute/never-fetch guard.

The load-bearing promises:
- `audit check` NEVER dispatches a step, sends traffic, or fetches
  device memory, and its diagnostic lowering leaves the recompile
  counters untouched (test_audit_never_executes_or_fetches);
- the canonical synthesized signature equals the signature real
  traffic traces, so the gate grades the program production runs
  (test_synthesized_signature_matches_traced);
- an injected flops/bytes/collectives regression exits 1; clean exits
  0; errors exit 2 (test_exit_code_contract, test_injected_*).
"""
import json
import os

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.analysis import audit as audit_mod
from siddhi_tpu.tools import audit as audit_cli

PLAIN_QL = """
@app:name('AuditPlain')
define stream S (sym string, price float, volume long);
@info(name='flt')
from S[volume > 100]
select sym, price
insert into Out;
"""

PATTERN_QL = """
@app:name('AuditPattern')
define stream P (sym long, price float, volume int);
@capacity(keys='1', slots='8')
@emit(rows='64')
@info(name='seq')
from every e1=P[volume == 1], e2=P[volume == 2 and price > e1.price]
  within 1 sec
select e1.price as p1, e2.price as p2
insert into M;
"""


@pytest.fixture(scope="module")
def tiny_corpus(tmp_path_factory):
    """A two-app corpus directory (plain + pattern) — enough kinds to
    exercise the gate without fingerprinting the full shipped corpus."""
    d = tmp_path_factory.mktemp("audit_corpus")
    (d / "plain.siddhi").write_text(PLAIN_QL)
    (d / "pattern.siddhi").write_text(PATTERN_QL)
    return str(d)


def _fingerprints(samples_dir):
    fps, skipped = audit_mod.corpus_fingerprints(
        samples_dir=samples_dir, include_bench=False)
    assert not skipped
    return fps


@pytest.fixture(scope="module")
def tiny_current(tiny_corpus):
    """One shared extraction of the tiny corpus — the diff tests mutate
    COPIES of the baseline, never this."""
    return _fingerprints(tiny_corpus)


def _baseline_for(cur):
    return {
        "version": audit_mod.BASELINE_VERSION,
        "tolerances": dict(audit_mod.DEFAULT_TOLERANCES),
        "corpus": json.loads(json.dumps(cur)),
    }


# ---------------------------------------------------------------------------
# the guard: audit is static — plans, lowers, never runs
# ---------------------------------------------------------------------------

def test_audit_never_executes_or_fetches(tiny_corpus, monkeypatch):
    import jax

    from siddhi_tpu.core import runtime as rt_mod
    from siddhi_tpu.observability.recompile import RECOMPILES

    def boom(*a, **k):
        raise AssertionError("plan audit touched the device / traffic "
                             "path")

    monkeypatch.setattr(jax, "device_get", boom)
    for cls in (rt_mod.QueryRuntime, rt_mod.PatternQueryRuntime,
                rt_mod.JoinQueryRuntime):
        monkeypatch.setattr(cls, "process_staged", boom)
    before = RECOMPILES.snapshot()
    fps = _fingerprints(tiny_corpus)
    after = RECOMPILES.snapshot()
    # diagnostic lowering runs under RECOMPILES.suppress(): the audit
    # must not inflate the very counters its arity metric sits next to
    assert after == before
    got = {(shape, q) for shape, e in fps.items()
           for q in e["queries"]}
    assert got == {("samples/plain", "flt"), ("samples/pattern", "seq")}
    for e in fps.values():
        for fp in e["queries"].values():
            assert fp["totals"]["flops"] > 0
            assert fp["totals"]["bytes_accessed"] > 0


# ---------------------------------------------------------------------------
# synthesized signatures == traced signatures
# ---------------------------------------------------------------------------

def test_synthesized_signature_matches_traced(manager):
    from siddhi_tpu.analysis.signatures import synthesize
    from siddhi_tpu.observability.explain import _spec_sig

    rt = manager.create_siddhi_app_runtime(PLAIN_QL)
    qr = rt.query_runtimes["flt"]
    synth = synthesize(qr, "plain")["step"]
    rt.start()
    h = rt.get_input_handler("S")
    B = qr.planned.batch_capacity
    h.send_columns([np.arange(B, dtype=np.int32),
                    np.ones(B, np.float32),
                    np.full(B, 200, np.int64)],
                   timestamps=np.arange(B, dtype=np.int64))
    rt.flush()
    traced = qr.planned.step._siddhi_argspec["argspecs"]
    assert traced is not None, "full batch should have traced the step"
    assert _spec_sig(synth) == _spec_sig(traced)


def test_explain_reports_synthesized_costs_before_traffic(manager):
    """EXPLAIN on a never-run runtime now carries cost analysis with
    signature_origin='synthesized' instead of 'send traffic first'."""
    rt = manager.create_siddhi_app_runtime(PLAIN_QL)
    rep = rt.explain("flt")
    step = rep["steps"]["step"]
    assert step["available"]
    assert step["signature_origin"] == "synthesized"
    assert step["flops"] > 0
    assert step["memory"]["peak_bytes"] > 0


def test_traced_signature_wins_over_synthesized(manager):
    rt = manager.create_siddhi_app_runtime(PLAIN_QL)
    rt.start()
    h = rt.get_input_handler("S")
    h.send_columns([np.zeros(4, np.int32), np.ones(4, np.float32),
                    np.full(4, 200, np.int64)],
                   timestamps=np.arange(4, dtype=np.int64))
    rt.flush()
    rep = rt.explain("flt")
    assert rep["steps"]["step"]["signature_origin"] == "traced"


# ---------------------------------------------------------------------------
# fingerprint content
# ---------------------------------------------------------------------------

def test_fingerprint_shape(tiny_current):
    fp = tiny_current["samples/pattern"]["queries"]["seq"]
    assert fp["kind"] == "pattern"
    assert fp["dispatch_programs"] == 1
    # one program a role: a non-partitioned chain has the block step alone
    assert fp["recompile_signature_arity"] == 1
    assert set(fp["steps"]) == {"step[P]"}
    assert fp["emission"] == {"cap_rows": 64, "cap_explicit": True}
    assert fp["fusion"]["eligible"] is True
    assert fp["state"]["total_bytes"] > 0
    assert "pattern_slots" in fp["state"]["components"]
    # typeflow summary rides the fingerprint
    names = [c["name"] for c in fp["types"]["out_types"]]
    assert names == ["p1", "p2"]
    for s in fp["steps"].values():
        assert s["signature"]
        assert s["peak_bytes"] > 0


def test_sharded_fingerprint_reports_collectives():
    import jax
    from jax.sharding import Mesh
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    ql = """
    @app:playback
    define stream T (key long, price float, volume int);
    partition with (key of T)
    begin
      @capacity(keys='16', slots='4')
      @emit(rows='2')
      @info(name='pq')
      from every e1=T[volume == 1] -> e2=T[volume == 2]
      select e1.key as k, e2.price as p
      insert into M;
    end;
    """
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            ql, mesh=Mesh(np.array(jax.devices()[:2]), ("shard",)))
        fps = audit_mod.app_fingerprint(rt, collectives=True)
        fp = fps["pq"]
        assert fp["collective_kinds"], \
            "sharded NFA step HLO should carry collectives"
        assert fp["collective_steps"] >= 1
    finally:
        m.shutdown()


# ---------------------------------------------------------------------------
# baseline diff gate
# ---------------------------------------------------------------------------

def test_clean_diff_and_injected_regressions(tiny_current):
    base = _baseline_for(tiny_current)
    deltas = audit_mod.diff_fingerprints(base, tiny_current, [])
    assert not audit_mod.has_regressions(deltas)

    # cost regression: pinned flops halved => current reads +100%
    doctored = _baseline_for(tiny_current)
    step = doctored["corpus"]["samples/plain"]["queries"]["flt"][
        "steps"]["step"]
    step["flops"] *= 0.5
    deltas = audit_mod.diff_fingerprints(doctored, tiny_current, [])
    hit = [d for d in deltas if d.level == "regression"]
    assert hit and hit[0].metric == "flops"

    # structural regression: emission cap changed
    doctored = _baseline_for(tiny_current)
    doctored["corpus"]["samples/pattern"]["queries"]["seq"][
        "emission"]["cap_rows"] = 8
    deltas = audit_mod.diff_fingerprints(doctored, tiny_current, [])
    assert any(d.level == "regression" and d.metric == "emission_cap"
               for d in deltas)

    # collective appearing counts as a regression
    doctored = _baseline_for(tiny_current)
    for s in doctored["corpus"]["samples/pattern"]["queries"]["seq"][
            "steps"].values():
        s["collectives"] = []
    cur2 = json.loads(json.dumps(tiny_current))
    for s in cur2["samples/pattern"]["queries"]["seq"][
            "steps"].values():
        s["collectives"] = ["all-reduce"]
    deltas = audit_mod.diff_fingerprints(doctored, cur2, [])
    assert any(d.metric == "collectives" and d.level == "regression"
               for d in deltas)


def test_improvement_is_not_a_regression(tiny_current):
    doctored = _baseline_for(tiny_current)
    step = doctored["corpus"]["samples/plain"]["queries"]["flt"][
        "steps"]["step"]
    step["bytes_accessed"] *= 2.0          # pinned higher => current improved
    deltas = audit_mod.diff_fingerprints(doctored, tiny_current, [])
    assert not audit_mod.has_regressions(deltas)
    assert any(d.level == "improvement" and d.metric == "bytes_accessed"
               for d in deltas)


def test_unbaselined_and_missing_shapes(tiny_current):
    missing = _baseline_for(tiny_current)
    ghost = missing["corpus"].pop("samples/plain")
    deltas = audit_mod.diff_fingerprints(missing, tiny_current, [])
    assert any(d.level == "regression" and "unbaselined" in d.message
               for d in deltas)
    extra = _baseline_for(tiny_current)
    extra["corpus"]["samples/ghost"] = ghost
    deltas = audit_mod.diff_fingerprints(extra, tiny_current, [])
    assert any(d.level == "regression" and d.shape == "samples/ghost"
               for d in deltas)


# ---------------------------------------------------------------------------
# CLI exit-code contract (0 clean / 1 regression / 2 error)
# ---------------------------------------------------------------------------

def test_exit_code_contract(tiny_corpus, tmp_path, capsys):
    bl = str(tmp_path / "baseline.json")
    args = ["--baseline", bl, "--corpus", tiny_corpus, "--no-bench"]
    assert audit_cli.main(["check", *args]) == 2      # no baseline yet
    assert audit_cli.main(["update", *args]) == 0
    capsys.readouterr()
    assert audit_cli.main(["check", "--format", "json", *args]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["regressions"] == 0 and rep["command"] == "check"
    with open(bl) as fh:
        doctored = json.load(fh)
    doctored["corpus"]["samples/plain"]["queries"]["flt"]["steps"][
        "step"]["bytes_accessed"] *= 0.5
    with open(bl, "w") as fh:
        json.dump(doctored, fh)
    capsys.readouterr()
    assert audit_cli.main(["check", *args]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "bytes_accessed" in out
    assert audit_cli.main(["diff", *args]) == 0       # informational
    assert audit_cli.main(
        ["check", *args, "--tolerance", "nope=0.5"]) == 2
    # a huge tolerance swallows the injected regression
    assert audit_cli.main(
        ["check", *args, "--tolerance", "bytes_accessed=3.0"]) == 0


def test_baseline_version_guard(tiny_corpus, tmp_path):
    bl = str(tmp_path / "baseline.json")
    with open(bl, "w") as fh:
        json.dump({"version": 999, "corpus": {}}, fh)
    with pytest.raises(ValueError):
        audit_mod.load_baseline(bl)
    assert audit_cli.main(["check", "--baseline", bl, "--corpus",
                           tiny_corpus, "--no-bench"]) == 2


# ---------------------------------------------------------------------------
# committed baseline hygiene + docgen
# ---------------------------------------------------------------------------

def test_committed_baseline_covers_corpus():
    """PLAN_BASELINE.json must exist, parse, and cover the shipped
    samples + the three bench serving shapes the ROADMAP gates on."""
    b = audit_mod.load_baseline()
    shapes = set(b["corpus"])
    from siddhi_tpu.analysis.corpus import sample_apps
    for key in sample_apps():
        assert key in shapes, f"{key} missing from PLAN_BASELINE.json"
    for key in ("bench/flagship", "bench/windowed_join",
                "bench/block_nfa"):
        assert key in shapes
    assert any(s.startswith("bench/flagship_sharded@")
               for s in shapes), "sharded shape must be baselined"


def test_docgen_audit_metrics_page(tmp_path):
    from siddhi_tpu.tools import docgen
    docgen.write(str(tmp_path))
    page = (tmp_path / "audit-metrics.md").read_text()
    for m in audit_mod.METRICS:
        assert f"## {m.name}" in page
    assert "tolerance" in page


def test_committed_docgen_pages_match_registries():
    """The committed docs/extensions pages regenerate byte-identically
    (the CI drift gate, runnable locally via `make docgen-check`)."""
    from siddhi_tpu.tools import docgen
    pages = docgen.render(docgen.collect())
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "extensions")
    for name in ("lint-rules.md", "audit-metrics.md"):
        with open(os.path.join(root, name)) as fh:
            assert fh.read() == pages[name], \
                f"{name} drifted — run `python -m siddhi_tpu.tools." \
                f"docgen` and commit the page"

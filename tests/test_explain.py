"""Observability v2: query EXPLAIN (operator tree + XLA cost analysis),
state-memory gauges, a profiler capture over REST, /healthz readiness vs
liveness, and the no-device-touch scrape invariant (see ISSUE 3)."""
import glob
import json
import os
import re
import urllib.error
import urllib.request

import pytest

import jax

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import RECOMPILES, render_prometheus
from siddhi_tpu.observability.health import SlidingRate, app_health


@pytest.fixture()
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


def _boot(manager, ql, sends):
    rt = manager.create_siddhi_app_runtime(ql)
    rt.start()
    for sid, rows in sends:
        rt.get_input_handler(sid).send(rows)
    rt.flush()
    return rt


def _assert_cost(report):
    """At least one compiled step of the query carries a full cost
    analysis: flops, bytes accessed, and the memory estimate."""
    avail = [c for c in report["steps"].values() if c.get("available")]
    assert avail, f"no analyzable step in {list(report['steps'])}"
    c = avail[0]
    assert c["flops"] >= 0
    assert c["bytes_accessed"] > 0
    assert c["memory"]["peak_bytes"] > 0
    assert c["memory"]["argument_bytes"] >= 0
    assert "signature" in c


# -- explain(): all four query kinds ------------------------------------------

def test_explain_filter_query(manager):
    rt = _boot(manager, """
    define stream S (sym string, v int);
    @info(name='fq') from S[v > 3] select sym, v insert into Out;
    """, [("S", [["a", i] for i in range(8)])])
    rep = rt.explain("fq")
    assert rep["kind"] == "plain"
    tree = rep["operator_tree"]
    ops = [h["op"] for h in tree["input"]["handlers"]]
    assert "filter" in ops
    f = next(h for h in tree["input"]["handlers"] if h["op"] == "filter")
    assert "v > 3" in f["expression"]
    assert tree["output"]["target"] == "Out"
    _assert_cost(rep)
    # state leaves carry dtype/shape/nbytes and the totals agree
    leaves = rep["state"]["leaves"]
    assert all({"path", "dtype", "shape", "nbytes"} <= set(d)
               for d in leaves)
    assert rep["state"]["total_bytes"] == sum(d["nbytes"] for d in leaves)


def test_explain_window_query(manager):
    rt = _boot(manager, """
    define stream S (sym string, v int);
    @info(name='wq') from S#window.lengthBatch(8)
    select sym, sum(v) as t group by sym insert into W;
    """, [("S", [["a", i] for i in range(16)])])
    rep = rt.explain("wq")
    tree = rep["operator_tree"]
    w = next(h for h in tree["input"]["handlers"] if h["op"] == "window")
    assert w["name"] == "lengthBatch" and w["parameters"] == ["8"]
    assert tree["select"]["group_by"] == ["sym"]
    assert tree["window_processor"]["needs_timer"] is False
    _assert_cost(rep)
    # window buffer state is non-trivial and split per component
    comp = rep["state"]["component_bytes"]
    assert comp.get("window", 0) > 0
    # compiled-plan facts from the planner ride along
    assert rep["plan"]["window_processor"] and \
        rep["plan"]["group_slot_capacity"] > 0
    assert rep["plan"]["out_columns"] == ["sym", "t"]


def test_explain_join_query(manager):
    rt = _boot(manager, """
    define stream L (k string, x int);
    define stream R (k string, y int);
    @info(name='jq') from L#window.length(8) join R#window.length(8)
      on L.k == R.k select L.k as k, x, y insert into J;
    """, [("L", [["a", i] for i in range(4)]),
          ("R", [["a", i] for i in range(4)])])
    rep = rt.explain("jq")
    assert rep["kind"] == "join"
    j = rep["operator_tree"]["join"]
    assert j["type"] == "JOIN" and "L.k == R.k" in j["on"]
    assert j["left"]["stream"] == "L" and j["right"]["stream"] == "R"
    # both side steps ran and analyze independently
    assert rep["steps"]["step[left]"]["available"]
    assert rep["steps"]["step[right]"]["available"]
    _assert_cost(rep)
    assert rep["plan"]["left"]["kind"] == "stream"
    assert rep["plan"]["left"]["window_processor"]
    assert rep["plan"]["emission_cap_rows"] is None  # per-trace default
    assert rep["plan"]["join_type"] == "JOIN"
    # a projection: the cap's order goes first, columns exist at the cap
    assert rep["plan"]["pair_rows_materialised"] == "cap"
    # ... and `insert into` a stream: CURRENT trigger rows alone
    assert rep["plan"]["expired_rows_joined"] is False


def test_explain_pattern_query(manager):
    rt = _boot(manager, """
    define stream S (sym string, v int);
    @info(name='pq') from every s1=S[v > 1] -> s2=S[v > s1.v]
    select s1.v as a, s2.v as b insert into P;
    """, [("S", [["a", i] for i in range(8)])])
    rep = rt.explain("pq")
    assert rep["kind"] == "pattern"
    pat = rep["operator_tree"]["pattern"]
    assert pat["type"] == "pattern"
    assert pat["states"]["op"] == "next"
    assert pat["states"]["first"]["op"] == "every"
    _assert_cost(rep)
    assert rep["state"]["component_bytes"].get("pattern_slots", 0) > 0
    assert rep["emission"]["per_key"] is True
    # the 1<<30 "uncapped" sentinel renders as None, not a giant int
    assert rep["emission"]["cap_rows"] is None
    assert rep["plan"]["nfa_states"] >= 2
    assert rep["plan"]["partitioned"] is False
    # one program a role: the (block) step that ran, no twin beside it
    assert set(rep["steps"]) == {"step[S]"}
    assert rep["steps"]["step[S]"]["available"]


def test_explain_fusion_exclusion_reason(manager):
    """A timer-bearing query asked to @fuse reports the concrete
    exclusion reason, not just a log line."""
    rt = _boot(manager, """
    define stream S (sym string, v int);
    @fuse(batches='4') @info(name='tw') from S#window.time(100)
    select sym, v insert into TW;
    """, [("S", [["a", 1]])])
    fz = rt.explain("tw")["fusion"]
    assert fz["eligible"] is False
    assert fz["active"] is False
    assert fz["requested_batches"] == 4
    assert "wake" in fz["exclusion_reason"] or \
        "timer" in fz["exclusion_reason"]


def test_explain_fused_query_reports_fused_step(manager):
    rt = _boot(manager, """
    define stream S (sym string, v int);
    @fuse(batches='2') @info(name='fz') from S[v >= 0]
    select sym, v insert into Out;
    """, [("S", [["a", 0], ["a", 1]]),       # two same-signature sends
          ("S", [["a", 2], ["a", 3]])])      # fill the K=2 stack
    rep = rt.explain("fz")
    assert rep["fusion"] == {"eligible": True, "active": True,
                             "batches": 2}
    fused = [r for r in rep["steps"] if r.startswith("fused_step")]
    assert fused and rep["steps"][fused[0]]["available"]


def test_explain_unknown_query_raises(manager):
    rt = _boot(manager, """
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """, [])
    with pytest.raises(KeyError):
        rt.explain("nope")


def test_explain_does_not_inflate_recompile_counters(manager):
    """EXPLAIN re-lowers steps for cost analysis; those diagnostic traces
    must not count as recompiles (RECOMPILES.suppress)."""
    rt = _boot(manager, """
    define stream S (v int);
    @info(name='rq') from S select v insert into Out;
    """, [("S", [[1], [2]])])
    before = RECOMPILES.count("rq")
    rt.explain("rq")
    rt.explain("rq")            # second call also exercises the memo
    assert RECOMPILES.count("rq") == before


def test_explain_app_covers_all_queries(manager):
    rt = _boot(manager, """
    define stream S (v int);
    @info(name='a') from S select v insert into O1;
    @info(name='b') from S[v > 1] select v insert into O2;
    """, [("S", [[1], [2]])])
    rep = rt.explain()
    assert set(rep["queries"]) == {"a", "b"}


# -- state-memory gauges in /metrics ------------------------------------------

def test_state_bytes_family_in_exposition(manager):
    rt = _boot(manager, """
    @app:name('MemApp')
    @app:statistics('BASIC')
    define stream S (sym string, v int);
    define table T (sym string, v int);
    @info(name='wq') from S#window.length(16) select sym, v insert into W;
    @info(name='ins') from S select sym, v insert into T;
    """, [("S", [["a", i] for i in range(8)])])
    text = render_prometheus(manager.runtimes)
    assert "# TYPE siddhi_state_bytes gauge" in text
    m = re.search(r'siddhi_state_bytes\{app="MemApp",query="wq",'
                  r'component="window"\} (\d+)', text)
    assert m and int(m.group(1)) > 0
    assert re.search(r'siddhi_state_bytes\{app="MemApp",'
                     r'query="table:T",component="rows"\} [1-9]', text)
    # the gauge agrees with the runtime accessor
    assert rt.state_memory()["wq"]["window"] == \
        int(m.group(1))


def test_state_memory_covers_shared_objects(manager):
    """Named windows and aggregation duration slabs are accounted under
    the owner-label convention (window:<id>, agg:<id>)."""
    rt = _boot(manager, """
    define stream S (sym string, v double);
    define window W (sym string, v double) lengthBatch(8);
    define aggregation AggV from S select sym, sum(v) as t
      group by sym aggregate every sec...min;
    @info(name='ins') from S select sym, v insert into W;
    """, [("S", [["a", 1.0], ["b", 2.0]])])
    mem = rt.state_memory()
    assert mem["window:W"]["buffer"] > 0
    assert mem["agg:AggV"]["SECONDS"] > 0
    assert mem["agg:AggV"]["MINUTES"] > 0


# -- no-device-touch invariant for scrape + probe -----------------------------

def test_scrape_and_probe_never_touch_device(manager, monkeypatch):
    """The exposition docstring promises a Prometheus scrape never pays a
    device sync; /healthz makes the same promise, and the new memory
    gauges must read cached shape/dtype metadata, not fetch arrays.
    Monkeypatching every device->host entry point to raise proves it."""
    rt = _boot(manager, """
    @app:name('GuardApp')
    @app:statistics('DETAIL')
    define stream S (sym string, v int);
    @info(name='wq') from S#window.lengthBatch(8)
    select sym, sum(v) as t group by sym insert into W;
    """, [("S", [["a", i] for i in range(16)])])

    def boom(*a, **k):
        raise AssertionError("device sync on the scrape/probe path")

    monkeypatch.setattr(jax, "device_get", boom)
    monkeypatch.setattr(jax, "block_until_ready", boom, raising=False)
    text = render_prometheus(manager.runtimes)          # /metrics
    assert 'siddhi_state_bytes{app="GuardApp",query="wq"' in text
    rep = app_health(rt)                                # /healthz
    assert rep["ready"] and rep["live"]
    assert rep["streams"]["S"]["status"] in ("ok", "idle")
    # statistics report is allowed to walk state, but must also stay
    # fetch-free (nbytes is metadata)
    assert rt.state_memory()["wq"]["window"] > 0


# -- profiler capture + explain over REST ---------------------------------------

def test_trace_json_endpoint(manager, tmp_path):
    from siddhi_tpu.service import SiddhiRestService
    svc = SiddhiRestService().start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        ql = """@app:name('TJ')
        @app:statistics('DETAIL')
        define stream S (v int);
        @info(name='q') from S select v insert into Out;
        """
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/siddhi-apps", data=ql.encode(), method="POST"))
        body = json.dumps({"events": [[i] for i in range(4)]}).encode()
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/siddhi-apps/TJ/streams/S", data=body, method="POST"))
        svc.manager.runtimes["TJ"].flush()
        # /trace.json went with the DETAIL ring's Chrome export: a profiler
        # capture holds the runtime's own spans and the device's ops on
        # one clock
        with pytest.raises(urllib.error.HTTPError) as gone:
            urllib.request.urlopen(f"{base}/trace.json")
        assert gone.value.code == 404
        log_dir = str(tmp_path / "prof")
        started = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"{base}/profiler/start", method="POST",
            data=json.dumps({"log_dir": log_dir}).encode())).read())
        assert started == {"started": True, "log_dir": log_dir}
        with pytest.raises(urllib.error.HTTPError) as twice:
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/profiler/start", method="POST", data=b"{}"))
        assert twice.value.code == 409
        for _ in range(2):
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/siddhi-apps/TJ/streams/S", data=body,
                method="POST"))
        svc.manager.runtimes["TJ"].flush()
        stopped = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"{base}/profiler/stop", method="POST", data=b"")).read())
        assert stopped == {"stopped": True, "log_dir": log_dir}
        (capture,) = glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        names = [e.name for plane in
                 jax.profiler.ProfileData.from_file(capture).planes
                 for line in plane.lines for e in line.events
                 if e.name.startswith("siddhi:")]
        # the REST stream endpoint sends event by event: 2 posts x 4 events
        assert names.count("siddhi:send") == 8
        assert {"siddhi:stage", "siddhi:dispatch"} <= set(names)
        # explain endpoint returns the same report as the API
        rep = json.loads(urllib.request.urlopen(
            f"{base}/siddhi-apps/TJ/explain/q").read().decode())
        assert rep["query"] == "q" and rep["steps"]["step"]["available"]
        err = None
        try:
            urllib.request.urlopen(
                f"{base}/siddhi-apps/TJ/explain/nope")
        except urllib.error.HTTPError as exc:
            err = exc.code
        assert err == 404
    finally:
        svc.stop()


# -- /healthz: readiness vs liveness ------------------------------------------

def test_healthz_ready_vs_live(manager):
    from siddhi_tpu.service import SiddhiRestService
    svc = SiddhiRestService(manager=None).start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        ql = """@app:name('HZ')
        @app:statistics('BASIC')
        define stream S (v int);
        @info(name='q') from S select v insert into Out;
        """
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/siddhi-apps", data=ql.encode(), method="POST"))
        body = json.dumps({"events": [[1]]}).encode()
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/siddhi-apps/HZ/streams/S", data=body, method="POST"))
        svc.manager.runtimes["HZ"].flush()
        hz = json.loads(urllib.request.urlopen(
            f"{base}/healthz").read().decode())
        assert hz["live"] is True and hz["ready"] is True
        app = hz["apps"]["HZ"]
        assert app["streams"]["S"]["last_event_age_s"] is not None
        assert app["streams"]["S"]["backlog"] == 0
        assert "recompiles_per_s" in app and "dropped_per_s" in app
        assert urllib.request.urlopen(
            f"{base}/healthz/live").status == 200
        assert urllib.request.urlopen(
            f"{base}/healthz/ready").status == 200
        # a deployed-but-stopped app: alive (nothing should run) but NOT
        # ready (it can't accept traffic) — the verdicts must diverge
        svc.manager.runtimes["HZ"].shutdown()
        assert urllib.request.urlopen(
            f"{base}/healthz/live").status == 200
        code = None
        try:
            urllib.request.urlopen(f"{base}/healthz/ready")
        except urllib.error.HTTPError as exc:
            code = exc.code
        assert code == 503
        hz = json.loads(urllib.request.urlopen(
            f"{base}/healthz").read().decode())
        assert hz["live"] is True and hz["ready"] is False
    finally:
        svc.stop()


def test_sliding_rate_window():
    r = SlidingRate(window_s=10.0)
    assert r.observe(0, now=0.0) == 0.0
    assert r.observe(50, now=5.0) == pytest.approx(10.0)
    # old samples age out of the window: the rate follows the recent slope
    assert r.observe(50, now=20.0) == pytest.approx(0.0, abs=2.6)
    assert r.observe(50, now=40.0) == 0.0


def test_stream_status_classification(manager):
    """Backlog > 0 reads 'backlogged' (engine behind a live source) even
    when events flow; a drained-but-quiet stream reads idle/ok."""
    rt = _boot(manager, """
    @app:statistics('BASIC')
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """, [("S", [[1]])])
    rep = app_health(rt)
    assert rep["streams"]["S"]["status"] == "ok"
    # fake an ingress backlog (host-side queue depth only)
    import types
    rt.buffered_ingress_orig = rt.buffered_ingress
    rt.buffered_ingress = types.MethodType(
        lambda self: {"S": 7}, rt)
    rep = app_health(rt)
    assert rep["streams"]["S"]["status"] == "backlogged"
    assert rep["streams"]["S"]["backlog"] == 7
    rt.buffered_ingress = rt.buffered_ingress_orig


# -- span meta caps + consistent dumps ----------------------------------------

def test_span_meta_clamped():
    from siddhi_tpu.observability.tracing import (
        _MAX_META_CHARS, _MAX_SPANS, BatchTrace)
    tr = BatchTrace("S", 1)
    huge = "x" * 100_000
    tr.add_span("step", 0, 10, {"blob": huge, "n": 3})
    meta = tr.spans[0].meta
    assert len(meta["blob"]) < _MAX_META_CHARS + 32
    assert meta["n"] == 3
    # pathological meta key counts truncate with a marker
    tr.add_span("step", 0, 10, {f"k{i}": i for i in range(64)})
    assert tr.spans[1].meta.get("meta_truncated", 0) > 0
    # span count per trace is bounded
    for i in range(2 * _MAX_SPANS):
        tr.add_span("s", 0, 1, {})
    assert len(tr.spans) == _MAX_SPANS


def test_tracer_dump_consistent_under_churn():
    """dump() must return a consistent snapshot while other threads keep
    finishing traces into the ring."""
    import threading
    from siddhi_tpu.observability.tracing import PipelineTracer
    tracer = PipelineTracer(capacity=32)
    stop = threading.Event()

    def churn():
        while not stop.is_set():
            tr = tracer.start("S", 1)
            if tr is not None:
                tr.add_span("step", 0, 5, {"query": "q"})
                tracer.finish(tr)

    threads = [threading.Thread(target=churn) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            for d in tracer.dump():
                assert d["stream"] == "S"
                for s in d["spans"]:
                    assert "stage" in s and "duration_us" in s
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=2.0)


# -- ConsoleReporter quantile lines -------------------------------------------

def test_console_reporter_quantile_lines(manager):
    import time
    from siddhi_tpu.utils.statistics import ConsoleReporter
    rt = _boot(manager, """
    @app:statistics('BASIC')
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """, [("S", [[i] for i in range(8)])])
    lines = []
    rep = ConsoleReporter(rt, interval_s=0.05, out=lines.append)
    rep.start()
    deadline = time.time() + 5
    while len(lines) < 2 and time.time() < deadline:
        time.sleep(0.02)
    rep.stop()
    assert lines, "reporter emitted nothing"
    # first line stays machine-parseable JSON (scrapers rely on it)
    parsed = json.loads(lines[0])
    assert parsed["queries"]["q"]["events"] == 8
    # the human quantile summary follows, with drop/cap-growth counters
    qline = next(ln for ln in lines if ln.startswith("query q:"))
    for token in ("p50=", "p95=", "p99=", "max=", "drops=",
                  "cap_growths="):
        assert token in qline, (token, qline)


# -- the selector's row layout, a fact of the plan ---------------------------

_LAYOUT_STREAM = "define stream S (sym string, price float, v int);\n"
_LAYOUT_QUERY = ("@info(name='lq') from S#window.lengthBatch(4) "
                 "select avg(price) as ap{group_by} insert into Out;")
LAYOUT_CASES = {
    # no slot is ever allocated: rows are scanned where they stand
    "ungrouped": (_LAYOUT_QUERY.format(group_by=""), "in_order"),
    "no_window": ("@info(name='lq') from S select sum(v) as t, "
                  "count() as n insert into Out;", "in_order"),
    "session_key_ungrouped": (
        "@info(name='lq') from S#window.session(1 sec, sym) "
        "select max(v) as m insert into Out;", "in_order"),
    "join_ungrouped": (
        "@info(name='lq') from S#window.length(4) as a join "
        "S#window.length(4) as b on a.sym == b.sym "
        "select sum(a.v) as t insert into Out;", "in_order"),
    # a slot column: the sort by (slot, reset epoch) and the way back
    "group_by": (_LAYOUT_QUERY.format(group_by=" group by sym"), "sorted"),
    "partition_with": (
        "partition with (sym of S) begin "
        + _LAYOUT_QUERY.format(group_by="") + " end;", "sorted"),
    "keyed_window_range_partition": (
        "partition with (v < 4 as 'low' or v >= 4 as 'high' of S) begin "
        "@info(name='lq') from S#window.length(4) "
        "select sum(v) as t insert into Out; end;", "sorted"),
    "distinct_count": ("@info(name='lq') from S "
                       "select distinctCount(sym) as dc insert into Out;",
                       "sorted"),
    "join_group_by": (
        "@info(name='lq') from S#window.length(4) as a join "
        "S#window.length(4) as b on a.sym == b.sym "
        "select a.sym as sym, sum(a.v) as t group by a.sym "
        "insert into Out;", "sorted"),
    # no aggregator, no layout to report
    "no_aggregation": ("@info(name='lq') from S[v > 1] select sym, v "
                       "insert into Out;", None),
}


@pytest.mark.parametrize("case", sorted(LAYOUT_CASES))
def test_explain_says_the_selector_layout(manager, case):
    """`selector_layout` is chosen from the plan alone and EXPLAIN says it
    for every plain or join query with an aggregation: `in_order` exactly
    where no group slot is ever allocated."""
    query, want = LAYOUT_CASES[case]
    rt = _boot(manager, _LAYOUT_STREAM + query,
               [("S", [["ab"[i % 2], float(i), i] for i in range(8)])])
    qr = rt.query_runtimes["lq"]
    plan = rt.explain("lq")["plan"]
    assert plan.get("selector_layout") == want
    assert plan == qr.planned.describe()
    # a join with an aggregator keeps every candidate pair row
    assert plan.get("pair_rows_materialised") == (
        "all" if case.startswith("join_") else None)
    # ... and joins the window's EXPIRED rows: the aggregator retracts them
    assert plan.get("expired_rows_joined") == (
        True if case.startswith("join_") else None)
    if want == "in_order":
        assert getattr(qr.planned, "slot_allocator", None) is None
        assert "group_slot_capacity" not in plan

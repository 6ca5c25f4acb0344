"""Observability layer: histogram quantiles vs numpy, recompile accounting,
pipeline tracing, Prometheus exposition, OFF-level zero-overhead (see
ISSUE: observability tentpole; reference roles: Dropwizard metrics +
log4j TRACE in the reference engine)."""
import json
import re
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import LogHistogram, RECOMPILES
from siddhi_tpu.observability.exposition import render_prometheus


@pytest.fixture()
def manager():
    m = SiddhiManager()
    yield m
    m.shutdown()


# -- histogram unit behavior ---------------------------------------------------

def test_histogram_quantiles_vs_numpy():
    """Log2 buckets bound the quantile error at one octave: every reported
    quantile must lie within [q/2, 2q] of the numpy reference."""
    rng = np.random.default_rng(7)
    # lognormal latencies: heavy tail, like real dispatch times
    vals = (rng.lognormal(mean=10.0, sigma=1.5, size=20_000)).astype(np.int64)
    h = LogHistogram()
    for v in vals.tolist():
        h.record(v)
    assert h.total == vals.size
    assert h.max_ns == int(vals.max())
    for q in (0.50, 0.95, 0.99):
        ref = float(np.quantile(vals, q))
        got = h.quantile(q)
        assert ref / 2 <= got <= ref * 2, (q, ref, got)
    # quantiles are monotone and bounded by the observed max
    assert h.quantile(0.5) <= h.quantile(0.95) <= h.quantile(0.99) \
        <= h.max_ns


def test_histogram_empty_and_edge():
    h = LogHistogram()
    assert h.quantile(0.99) == 0.0
    assert h.snapshot()["count"] == 0
    h.record(0)
    h.record(-5)        # clamped, never throws
    assert h.total == 2
    assert h.quantile(1.0) == 0.0


def test_histogram_prometheus_buckets_cumulative():
    h = LogHistogram()
    for v in (10, 100, 1000, 10_000):
        h.record(v)
    buckets = h.buckets_seconds()
    cums = [c for _, c in buckets]
    assert cums == sorted(cums)
    assert cums[-1] == h.total
    les = [le for le, _ in buckets]
    assert les == sorted(les)


# -- report(): histogram quantiles replace the scalar era ---------------------

def test_report_has_latency_quantiles(manager):
    rt = manager.create_siddhi_app_runtime("""
    @app:statistics('BASIC')
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(20):
        h.send([i])
    rt.flush()
    rep = rt.statistics()
    q = rep["queries"]["q"]
    assert q["events"] == 20
    assert 0 < q["p50_us"] <= q["p95_us"] <= q["p99_us"]
    # tiny epsilon: p99 can equal max exactly, and max_ns/1e6*1000
    # rounds differently than max_ns/1e3 at the last float ulp
    assert q["p99_us"] <= q["max_latency_ms"] * 1000 * (1 + 1e-9)
    assert q["avg_latency_us"] > 0
    # junction-hop histogram rides along at BASIC
    assert rep["junctions"]["S"]["count"] == 20


def test_off_level_records_nothing(manager):
    """OFF must stay allocation-free: no registry keys appear from traffic."""
    rt = manager.create_siddhi_app_runtime("""
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(5):
        h.send([i])
    rt.flush()
    st = rt.stats
    assert st._stream_in == {}
    assert st._query_events == {}
    assert st._query_hist == {}
    assert st._junction_hist == {}
    assert st._sink_hist == {}
    assert st._counters == {}
    rep = rt.statistics()
    assert rep["streams"] == {} and rep["queries"] == {}


def test_report_safe_after_shutdown(manager):
    rt = manager.create_siddhi_app_runtime("""
    @app:statistics('BASIC')
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """)
    rt.start()
    rt.get_input_handler("S").send([1])
    rt.flush()
    rt.shutdown()
    rep = rt.statistics()        # must not raise on a stopped app
    assert rep["buffered_emissions"] == 0
    assert rep["buffered_ingress"] == {}


# -- recompile accounting ------------------------------------------------------

def test_recompile_counter_shape_change_and_steady_state(manager):
    rt = manager.create_siddhi_app_runtime("""
    define stream S (v int);
    @info(name='rq') from S select v insert into Out;
    """)
    rt.add_callback("rq", lambda ts, i, o: None)
    rt.start()
    h = rt.get_input_handler("S")
    h.send([[1], [2]])                      # bucket 8 -> first compile
    rt.flush()
    base = RECOMPILES.count("rq")
    assert base >= 1
    for i in range(5):                      # steady state: same signature
        h.send([[i], [i + 1]])
    rt.flush()
    assert RECOMPILES.count("rq") == base   # stays flat
    h.send([[i] for i in range(100)])       # bucket 128 -> re-trace
    rt.flush()
    after = RECOMPILES.count("rq")
    assert after == base + 1
    # the triggering abstract shapes are recorded
    snap = RECOMPILES.snapshot(["rq"])["rq"]
    assert snap["count"] == after
    assert any("128" in s for s in snap["signatures"])
    # report() projects the app's owners
    rt.set_statistics_level("BASIC")
    rep = rt.statistics()
    assert rep["recompiles"]["rq"]["count"] == after


# -- pipeline tracing ----------------------------------------------------------

def test_detail_trace_spans(manager):
    rt = manager.create_siddhi_app_runtime("""
    @app:statistics('DETAIL')
    define stream S (v int);
    @info(name='tq') from S select v insert into Out;
    """)
    rt.add_callback("tq", lambda ts, i, o: None)
    rt.start()
    rt.get_input_handler("S").send([[1], [2]])
    rt.flush()
    traces = rt.trace_dump("tq")
    assert traces, "DETAIL dispatch must record a batch trace"
    tr = traces[0]
    assert tr["stream"] == "S" and tr["events"] == 2
    stages = [s["stage"] for s in tr["spans"]]
    assert "query" in stages and "dispatch" in stages
    qspan = next(s for s in tr["spans"] if s["stage"] == "query")
    assert qspan["query"] == "tq"
    assert qspan["duration_us"] >= 0
    # filtering by an unknown query returns nothing
    assert rt.trace_dump("nope") == []


def test_basic_level_no_traces(manager):
    rt = manager.create_siddhi_app_runtime("""
    @app:statistics('BASIC')
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """)
    rt.start()
    rt.get_input_handler("S").send([[1]])
    rt.flush()
    assert rt.trace_dump() == []


# -- Prometheus exposition -----------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? '
    r'[-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|[0-9]+)$')


def _assert_valid_exposition(text):
    seen_types = {}
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            seen_types[name] = kind
            continue
        assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
    return seen_types


def test_render_prometheus_families(manager):
    rt = manager.create_siddhi_app_runtime("""
    @app:name('PromApp')
    @app:statistics('BASIC')
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """)
    rt.add_callback("q", lambda ts, i, o: None)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(10):
        h.send([i])
    rt.flush()
    text = render_prometheus(manager.runtimes)
    types = _assert_valid_exposition(text)
    assert types["siddhi_stream_events_total"] == "counter"
    assert types["siddhi_query_latency_seconds"] == "histogram"
    assert types["siddhi_query_recompiles_total"] == "counter"
    assert 'siddhi_stream_events_total{app="PromApp",stream="S"} 10' in text
    # histogram contract: +Inf bucket equals _count
    m = re.search(r'siddhi_query_latency_seconds_bucket\{app="PromApp",'
                  r'query="q",le="\+Inf"\} (\d+)', text)
    c = re.search(r'siddhi_query_latency_seconds_count\{app="PromApp",'
                  r'query="q"\} (\d+)', text)
    assert m and c and m.group(1) == c.group(1) == "10"
    assert re.search(r'siddhi_query_recompiles_total\{app="PromApp",'
                     r'query="q"\} \d+', text)


def test_metrics_endpoint_scrape():
    """End to end through a running SiddhiAppRuntime + REST service: the
    scrape parses, carries per-query histogram buckets, per-stream
    throughput counters, per-query recompile counts — and the histogram's
    p99 answer is consistent with its own bucket data."""
    from siddhi_tpu.service import SiddhiRestService
    svc = SiddhiRestService().start()
    try:
        base = f"http://127.0.0.1:{svc.port}"
        ql = """@app:name('ScrapeApp')
        @app:statistics('DETAIL')
        define stream S (v int);
        @info(name='q') from S select v insert into Out;
        """
        req = urllib.request.Request(f"{base}/siddhi-apps",
                                     data=ql.encode(), method="POST")
        assert urllib.request.urlopen(req).status == 201
        for i in range(30):
            body = json.dumps({"events": [[i]]}).encode()
            urllib.request.urlopen(urllib.request.Request(
                f"{base}/siddhi-apps/ScrapeApp/streams/S", data=body,
                method="POST"))
        rt = svc.manager.runtimes["ScrapeApp"]
        rt.flush()
        resp = urllib.request.urlopen(f"{base}/metrics")
        assert resp.status == 200
        assert "text/plain" in resp.headers["Content-Type"]
        text = resp.read().decode()
        types = _assert_valid_exposition(text)
        for fam in ("siddhi_stream_events_total",
                    "siddhi_query_latency_seconds",
                    "siddhi_query_recompiles_total",
                    "siddhi_uptime_seconds"):
            assert fam in types, f"{fam} missing from scrape"
        assert 'siddhi_stream_events_total{app="ScrapeApp",stream="S"} 30' \
            in text
        # p99 consistency: the p99 the report computes must fall at or
        # below the first bucket edge whose cumulative count covers 99%
        h = rt.stats._query_hist["q"]
        p99 = h.quantile(0.99)
        buckets = h.buckets_seconds()
        edge = next(le for le, cum in buckets if cum >= 0.99 * h.total)
        assert p99 / 1e9 <= edge
        # recompile counts are non-zero for the compiled query step
        assert re.search(r'siddhi_query_recompiles_total\{app="ScrapeApp",'
                         r'query="q"\} [1-9]', text)
        # the trace endpoint serves DETAIL traces for the query
        tr = json.loads(urllib.request.urlopen(
            f"{base}/trace/q").read().decode())
        assert tr["query"] == "q" and tr["traces"]
    finally:
        svc.stop()


# -- capped-emission counters --------------------------------------------------

def test_emission_cap_growth_counter(manager):
    """Implicit-cap overflow growth shows up in the stats counters (the
    old failure mode: cap churn was invisible to operators)."""
    rt = manager.create_siddhi_app_runtime("""
    @app:statistics('BASIC')
    define stream L (k string, x int);
    define stream R (k string, y int);
    @info(name='jq')
    from L#window.length(64) join R#window.length(64)
      on L.k == R.k
    select L.k as k, x, y insert into J;
    """)
    got = []
    rt.add_batch_callback("jq", lambda ts, b: got.append(b["n_valid"]))
    rt.start()
    hl = rt.get_input_handler("L")
    hr = rt.get_input_handler("R")
    hl.send([["a", i] for i in range(64)])
    hr.send([["a", i] for i in range(64)])   # 64x64 fan-out over the cap
    rt.flush()
    rep = rt.statistics()
    ctr = rep.get("counters", {})
    assert ctr.get("jq.cap_growths", 0) >= 1, ctr
    assert ctr.get("jq.dropped", 0) >= 1, ctr


# -- ConsoleReporter hygiene ---------------------------------------------------

def test_console_reporter_stop_idempotent(manager):
    from siddhi_tpu.utils.statistics import ConsoleReporter
    rt = manager.create_siddhi_app_runtime("""
    define stream S (v int);
    @info(name='q') from S select v insert into Out;
    """)
    rep = ConsoleReporter(rt, interval_s=0.05, out=lambda line: None)
    rep.stop()                 # before start: no-op, no raise
    rep.start()
    assert rep.start() is rep  # double start: no second thread
    rep.stop()
    rep.stop()                 # double stop: no raise
    # restartable after stop
    lines = []
    rep.out = lines.append
    rep.start()
    import time
    deadline = time.time() + 2.0
    while not lines and time.time() < deadline:
        time.sleep(0.01)
    rep.stop()
    assert lines


def test_console_reporter_warns_instead_of_dying(capsys):
    from siddhi_tpu.utils.statistics import ConsoleReporter

    class Boom:
        def statistics(self):
            raise RuntimeError("boom")

    rep = ConsoleReporter(Boom(), interval_s=0.02)
    rep._WARN_INTERVAL_S = 0.0
    rep.start()
    import time
    deadline = time.time() + 2.0
    while time.time() < deadline:
        if "report failed" in capsys.readouterr().err:
            break
        time.sleep(0.02)
    else:
        rep.stop()
        raise AssertionError("no rate-limited warning on stderr")
    assert rep._thread is not None and rep._thread.is_alive()
    rep.stop()

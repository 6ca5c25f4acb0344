"""`fetch_bytes_per_send` (layer_metrics/): the `bytes` of the runtime's
`siddhi:fetch` spans over the traced slice, per send — on a small trace
recorded here with spans shaped like the runtime's, on the recorded TPU
trace of PR 24 (whose fetches say no bytes), and on a run without a trace."""
import os
import shutil
import time

import pytest

from benchmarks.harness import loader
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import fetch_bytes_per_send as fb

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny_spans.xplane.pb")
CELLS = {".sat": "pattern_1m.saturated", ".paced": "pattern_1m.paced",
         ".mesh4": "pattern_32m.mesh4_saturated",
         ".zipf": "pattern_16m_zipf.paced"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three sends as the blocking path spans them: a header fetch (24 B),
    two `rows` fetches of a banded emission (1 of 2 ranks) and one fetch
    that says no bytes; one more fetch BEFORE the slice."""
    import jax
    from jax.profiler import TraceAnnotation as span
    out = str(tmp_path_factory.mktemp("fetch_trace"))
    jax.profiler.start_trace(out)
    try:
        with span("siddhi:fetch", q="q", what="rows", bytes=999999):
            time.sleep(0.001)                      # warm-up: not in the slice
        for i in range(3):
            with span("bench:send_columns", sid=i):
                with span("siddhi:send", stream="S", batch=i + 1, events=8):
                    with span("siddhi:fetch", q="q", what="header",
                              bytes=24):
                        time.sleep(0.001)
                    with span("siddhi:demux", q="q"):
                        for nbytes in (1200, 2000 + i):
                            with span("siddhi:fetch", q="q", what="rows",
                                      bytes=nbytes, ranks=1, ranks_cap=2):
                                time.sleep(0.001)
                        with span("siddhi:fetch", q="q", what="rows"):
                            pass                   # says nothing: not counted
    finally:
        jax.profiler.stop_trace()
    return out


def test_the_reader_sums_the_bytes_of_the_fetches_in_the_slice(traced, capsys):
    got = fb.read_fetches(tr.newest_xplane(traced))
    assert got == {"sends": 3, "fetches": 9,
                   "bytes": 3 * (24 + 1200 + 2000) + 3,
                   "ranks": 6, "ranks_cap": 12}
    run = {"trace_dir": traced, "trace_reduced": {"sends_in_slice": 3}}
    assert fb.read(run) == pytest.approx((3 * 3224 + 3) / 3)
    assert fb.read(run) == pytest.approx(3225.0)          # kept on the run
    printed = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("fetches over the slice")]
    assert len(printed) == 1                              # printed once


def test_fetches_that_say_no_bytes_read_as_nothing(tmp_path):
    """The recorded TPU trace of PR 24: its `siddhi:fetch` spans carry
    `what` alone.  The reader finds nothing and does not raise."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "t.xplane.pb")
    assert fb.read_fetches(RECORDED) is None
    assert fb.read({"trace_dir": str(tmp_path),
                    "trace_reduced": {"sends_in_slice": 3}}) is None


def test_a_run_without_a_trace_reads_as_nothing():
    assert fb.read({"trace_dir": None, "trace_reduced": None}) is None
    assert fb.read({"trace_dir": "/nowhere",
                    "trace_reduced": {"sends_in_slice": 0}}) is None


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_benchmark_json_lists_the_metric_beside_fetch_ms(suffix):
    entries = {e["name"]: e for e in loader.load_benchmark()["per_layer"]}
    new, old = (entries["fetch_bytes_per_send" + suffix],
                entries["fetch_ms_per_send" + suffix])
    assert new["workloads"] == old["workloads"] == [CELLS[suffix]]
    assert new["moves"] == old["moves"] and new["layer"] == old["layer"]
    assert (new["source"], new["better"], new["unit"]) == \
        ("program_span", "lower", "bytes")
    names = [e["name"] for e, _ in loader.resolve(CELLS[suffix]).per_layer]
    assert "fetch_bytes_per_send" + suffix in names

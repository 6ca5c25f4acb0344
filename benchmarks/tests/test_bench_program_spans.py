"""The reduction of the runtime's own spans (harness/program_spans.py): its
interval arithmetic by hand, the small TPU v5e trace recorded by
`record_spans.py`, the readers in layer_metrics/ on top of it, and what a
trace without the spans gives."""
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import loader
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny_spans.xplane.pb")
NEW = ("stage_ms_per_send", "route_keys_ms_per_send", "obs_feed_ms_per_send",
       "h2d_ms_per_send", "dispatch_ms_per_send", "fetch_ms_per_send",
       "demux_ms_per_send", "sink_ms_per_send", "send_unspanned_ms_per_send",
       "dispatches_per_send", "fetches_per_send",
       "idle_pre_dispatch_ms_per_send", "idle_post_step_ms_per_send")
CELLS = {"pattern_1m.saturated": (".sat", "events_per_s"),
         "pattern_1m.paced": (".paced", "latency_p50_ms")}


def S(name, s, e):
    return ("siddhi:" + name, float(s), float(e))


# -- the arithmetic, by hand -----------------------------------------------------

def test_self_time_and_idle_under_a_span_with_two_children():
    spans = {"A": [S("send", 0, 100), S("stage", 10, 30),
                   S("fetch", 40, 90)]}
    red = ps.reduce_intervals(spans, [[[50, 60]]], 0, 100)
    assert red["sends"] == 1
    assert red["slice_s"] == pytest.approx(100e-9)
    # the device is idle [0, 50) and [60, 100)
    assert red["idle_s"] == pytest.approx(90e-9)
    assert red["idle_in_send_s"] == pytest.approx(90e-9)
    sp = red["spans"]
    assert sp["send"] == {"count": 1, "wall_s": pytest.approx(100e-9),
                          # [0,10) + [30,40) + [90,100)
                          "self_s": pytest.approx(30e-9),
                          "idle_s": pytest.approx(30e-9)}
    assert sp["stage"]["self_s"] == pytest.approx(20e-9)
    assert sp["stage"]["idle_s"] == pytest.approx(20e-9)
    # fetch [40, 90): the device works [50, 60) under it
    assert sp["fetch"]["self_s"] == pytest.approx(50e-9)
    assert sp["fetch"]["idle_s"] == pytest.approx(40e-9)
    # self times tile the send; idle under them tiles the idle in the send
    assert sum(v["self_s"] for v in sp.values()) == pytest.approx(100e-9)
    assert sum(v["idle_s"] for v in sp.values()) == pytest.approx(
        red["idle_in_send_s"])


def test_a_gap_straddling_two_spans_is_split_between_them():
    spans = {"A": [S("send", 0, 100), S("stage", 0, 40), S("h2d", 40, 70)]}
    red = ps.reduce_intervals(spans, [[[0, 20], [60, 100]]], 0, 100)
    assert red["idle_s"] == pytest.approx(40e-9)          # the gap [20, 60)
    assert red["spans"]["stage"]["idle_s"] == pytest.approx(20e-9)
    assert red["spans"]["h2d"]["idle_s"] == pytest.approx(20e-9)
    assert red["spans"]["send"]["idle_s"] == 0.0          # self: [70, 100)


def test_a_delivery_on_a_second_thread_counts_like_the_first():
    spans = {"A": [S("send", 0, 100), S("dispatch", 10, 20)],
             "B": [S("fetch", 30, 60), S("demux", 60, 80),
                   S("sink", 65, 75)]}
    red = ps.reduce_intervals(spans, [[[20, 50]]], 0, 100)
    sp = red["spans"]
    # nesting is per thread: the drainer's spans are not the send's children
    assert sp["send"]["self_s"] == pytest.approx(90e-9)
    assert sp["fetch"]["self_s"] == pytest.approx(30e-9)
    assert sp["demux"]["self_s"] == pytest.approx(10e-9)   # [60,65)+[75,80)
    assert sp["sink"]["self_s"] == pytest.approx(10e-9)
    # idle: [0, 20) and [50, 100)
    assert sp["dispatch"]["idle_s"] == pytest.approx(10e-9)
    assert sp["fetch"]["idle_s"] == pytest.approx(10e-9)   # [50, 60)
    assert sp["demux"]["idle_s"] == pytest.approx(10e-9)
    assert sp["sink"]["idle_s"] == pytest.approx(10e-9)
    # two threads at once: an idle instant can lie under a span of each
    # (the send's self time and the drainer's fetch both cover [50, 60))
    assert sp["send"]["idle_s"] == pytest.approx(60e-9)
    assert red["idle_in_send_s"] == pytest.approx(70e-9)


def test_device_planes_are_averaged_and_the_slice_clips():
    spans = {"A": [S("send", -20, 40), S("send", 50, 120),
                   S("timer", 45, 48)]}
    red = ps.reduce_intervals(spans, [[[0, 50]], [[0, 100]]], 0, 100)
    assert red["idle_s"] == pytest.approx(25e-9)           # (50 + 0) / 2
    # a span that started before the slice is clipped and not counted
    assert red["sends"] == 1
    assert red["spans"]["send"]["wall_s"] == pytest.approx(90e-9)
    assert red["spans"]["timer"] == {
        "count": 1, "wall_s": pytest.approx(3e-9),
        "self_s": pytest.approx(3e-9), "idle_s": 0.0}


def test_no_send_in_the_slice_reduces_to_nothing():
    assert ps.reduce_intervals({}, [[[0, 10]]], 0, 100) is None
    assert ps.reduce_intervals({"A": [S("stage", 5, 9)]}, [], 0, 100) is None
    assert ps.reduce_intervals({"A": [S("send", 200, 300)]}, [], 0,
                               100) is None


# -- the recorded trace ----------------------------------------------------------

def recorded_run(tmp_path):
    """A run record whose trace is the recorded file, as run.py leaves it."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(RECORDED, d / "t.xplane.pb")
    return {"trace_dir": str(tmp_path),
            "trace_reduced": tr.reduce_trace(RECORDED)}


def test_recorded_tpu_trace_reduces_to_known_numbers(tmp_path, capsys):
    run = recorded_run(tmp_path)
    red = ps.program_spans(run)
    assert ps.program_spans(run) is red                   # kept on the run
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("program spans: ")]
    assert len(lines) == 1                                # printed once
    assert json.loads(lines[0][len("program spans: "):]) == red
    assert red["sends"] == run["trace_reduced"]["sends_in_slice"] == 3
    assert red["slice_s"] == pytest.approx(
        run["trace_reduced"]["window_s"], abs=1e-12)
    sp = red["spans"]
    assert {n: v["count"] for n, v in sp.items()} == {
        "send": 3, "stage": 3, "h2d": 3, "dispatch": 3, "fetch": 3,
        "demux": 3, "sink": 3}
    # the sleeps: 2 ms of stage, 1 ms of h2d, 1 ms of demux's own and 3 ms
    # of sink (the subscriber inside it) a send, each a little over
    for name, ms in (("stage", 2.0), ("h2d", 1.0), ("demux", 1.0),
                     ("sink", 3.0)):
        per_send = sp[name]["self_s"] * 1e3 / 3
        assert ms <= per_send < ms + 0.6, (name, per_send)
    # demux's wall holds the sink; its self time does not
    assert sp["demux"]["wall_s"] == pytest.approx(
        sp["demux"]["self_s"] + sp["sink"]["wall_s"], abs=1e-9)
    # the second send's delivery ran on another thread: its fetch and
    # demux are not that send's children, so the sends' self time is what
    # lies between their child spans PLUS that delivery's wall — a third
    # of all the delivery there was
    between = sp["send"]["wall_s"] - sum(
        sp[n]["wall_s"] for n in ("stage", "h2d", "dispatch", "fetch",
                                  "demux"))
    off_thread = sp["send"]["self_s"] - between
    assert off_thread == pytest.approx(
        (sp["fetch"]["wall_s"] + sp["demux"]["wall_s"]) / 3, rel=0.3)
    # busy + idle is the slice, and the device idles under every host span
    busy = run["trace_reduced"]["busy_s"]
    assert red["idle_s"] + busy == pytest.approx(red["slice_s"], abs=1e-9)
    for name in ("stage", "dispatch", "fetch", "demux", "sink"):
        assert sp[name]["idle_s"] == sp[name]["self_s"]
    # the three 0.2 ms steps: the device stamps an op about a millisecond
    # BEFORE the host span that launched it (trace_reduce's docstring; its
    # skew correction applies only where the slice starts with an op, and
    # this one starts with host work: skew 0), so they fall under the span
    # before `dispatch` — the idle-under-span split is good to that lead
    assert run["trace_reduced"]["skew_s"] == 0.0
    assert sp["h2d"]["self_s"] - sp["h2d"]["idle_s"] == pytest.approx(
        busy, abs=1e-9)
    assert {n: v["self_s"] for n, v in sp.items()} == {
        n: pytest.approx(v, abs=1e-9) for n, v in RECORDED_SELF_S.items()}
    assert red["idle_in_send_s"] == pytest.approx(0.027611697, abs=1e-9)


# self_s of each span in the recorded file, as record_spans.py printed them
# when it recorded it on the chip (TPU v5 lite, PR 24)
RECORDED_SELF_S = {
    "send": 0.006678248, "stage": 0.00703256, "h2d": 0.00327043,
    "dispatch": 0.00075054, "fetch": 0.00184491, "demux": 0.00441638,
    "sink": 0.00983852}


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_reads_the_recorded_trace(name, tmp_path):
    run = recorded_run(tmp_path)
    read = importlib.import_module(f"benchmarks.layer_metrics.{name}").read
    value = read(run)
    assert value is not None and value >= 0.0
    red = ps.program_spans(run)
    if name == "dispatches_per_send":
        assert value == 1.0
    if name == "fetches_per_send":
        assert value == 1.0
    if name == "route_keys_ms_per_send":
        assert value == 0.0           # the recorded path opens no such span
    if name == "idle_pre_dispatch_ms_per_send":
        assert value == pytest.approx(sum(
            red["spans"][n]["idle_s"] for n in ("stage", "h2d", "dispatch"))
            * 1e3 / 3)
    if name == "idle_post_step_ms_per_send":
        assert value == pytest.approx(sum(
            red["spans"][n]["idle_s"] for n in ("fetch", "demux", "sink"))
            * 1e3 / 3)


@pytest.mark.parametrize("name", NEW)
def test_a_trace_without_the_program_spans_reads_as_nothing(
        name, tmp_path, monkeypatch):
    """The parent of the PR that added the spans: same trace, no `siddhi:`
    event in it.  Every new reader returns None and none raises."""
    monkeypatch.setattr(ps, "read_program_spans", lambda path: {})
    run = recorded_run(tmp_path)
    read = importlib.import_module(f"benchmarks.layer_metrics.{name}").read
    assert read(run) is None
    # and so does a run that was not traced at all
    assert read({"trace_dir": None, "trace_reduced": None}) is None


# -- BENCHMARK.json and the rehearsal ----------------------------------------------

def test_benchmark_json_lists_each_new_metric_once_per_cell():
    entries = {e["name"]: e for e in loader.load_benchmark()["per_layer"]}
    for cell, (suffix, moves) in CELLS.items():
        for name in NEW:
            e = entries[name + suffix]
            assert e["workloads"] == [cell] and e["moves"] == moves
            assert e["source"] == "program_span" and e["better"] == "lower"
        got = {e["name"] for e, _ in loader.resolve(cell).per_layer}
        assert {n + suffix for n in NEW} <= got


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal_computes_every_new_entry(cell):
    cmd = [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
           str(2 ** 31 + 24), "--seconds", "1.5", "--trace", "1",
           "--rehearse"]
    done = subprocess.run(cmd, cwd=loader.ROOT, text=True, timeout=600,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    spans = [ln for ln in lines if ln.startswith("program spans: ")]
    assert len(spans) == 1
    red = json.loads(spans[0][len("program spans: "):])
    assert red["sends"] >= 1 and red["spans"]["dispatch"]["count"] == \
        red["sends"]
    withheld = next(ln for ln in lines if "withheld" in ln)
    for name in NEW:
        assert name + CELLS[cell][0] in withheld, name
    assert json.loads(lines[-1])["metrics"] == {}

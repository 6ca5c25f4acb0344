"""The trace reduction: its interval arithmetic by hand, and the small TPU
v5e trace recorded by `record_trace.py` (three sends of one elementwise op,
20 ms of `bench:wait_due` before the second and third)."""
import gzip
import os
import shutil

import pytest

from benchmarks.harness import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny_tpu.xplane.pb")


def test_union_clip_complement_and_the_idle_clock():
    merged = tr.union([[5, 7], [0, 2], [1, 3], [7, 8], [9, 9]])
    assert merged == [[0, 3], [5, 8]]
    assert tr.total(merged) == 6
    assert tr.clip(merged, 2, 6) == [[2, 3], [5, 6]]
    assert tr.complement(merged, -1, 10) == [[-1, 0], [3, 5], [8, 10]]
    assert tr.complement([], 0, 4) == [[0, 4]]
    # idle before t, mean over two planes: one idle in [0, 4) and [6, 10),
    # the other never
    before = tr.idle_before([[[0, 4], [6, 10]], []])
    assert before([-1, 0, 2, 4, 5, 8, 10, 99]).tolist() == \
        [0, 0, 1, 2, 2, 3, 4, 4]
    assert tr.idle_before([])(5.0) == 0.0


def test_self_time_is_the_span_minus_what_is_nested_in_it():
    spans = [("bench:send_columns", 0, 100), ("bench:subscriber", 60, 90),
             ("bench:wait_due", 100, 150), ("bench:send_columns", 150, 200)]
    selfs = tr.self_intervals(spans)
    assert selfs["bench:send_columns"] == [[0, 60], [90, 100], [150, 200]]
    assert selfs["bench:subscriber"] == [[60, 90]]
    assert selfs["bench:wait_due"] == [[100, 150]]


def test_module_name_drops_the_run_id():
    assert tr._module_name("jit_step_w(6555239562323361334)") == "jit_step_w"
    assert tr._module_name("jit_f(x)") == "jit_f(x)"


def test_recorded_tpu_trace_reduces_to_known_numbers():
    red = tr.reduce_trace(RECORDED)
    assert red["devices"] == 1
    assert red["sends_in_slice"] == 3
    assert red["ops"] == 3
    # the device stamps the first op 1.08 ms before the host span that
    # launched it: without the skew correction that op falls outside
    assert red["skew_s"] == pytest.approx(0.001083472, abs=1e-9)
    assert red["window_s"] == pytest.approx(0.044703207, abs=1e-9)
    assert red["busy_s"] == pytest.approx(0.000609718, abs=1e-9)
    assert red["by_module"] == [["jit__lambda",
                                 pytest.approx(0.000609718, abs=1e-9)]]
    gaps = dict(red["idle_gaps"])
    assert list(gaps) == ["bench:wait_due", "bench:send_columns",
                          "bench:subscriber", "no_span"]
    assert gaps["bench:wait_due"] == pytest.approx(0.041291289, abs=1e-9)
    assert gaps["bench:send_columns"] == pytest.approx(0.00207357, abs=1e-9)
    assert gaps["bench:subscriber"] == pytest.approx(0.00069957, abs=1e-9)
    # busy + the split is the slice; two sleeps of 20 ms are the longest gaps
    assert red["busy_s"] + sum(gaps.values()) == pytest.approx(
        red["window_s"], abs=1e-9)
    assert 0.020 < red["longest_gap_s"] < 0.023
    idle_pct = 100 * (1 - red["busy_s"] / red["window_s"])
    assert 98.5 < idle_pct < 98.7


# -- the split of idle time over what the host was doing -------------------------

def split(spans, busy, lo, hi):
    gaps = [tr.complement(tr.clip(tr.union(b), lo, hi), lo, hi)
            for b in busy]
    return tr.split_idle(spans, tr.idle_before(gaps), lo, hi)


def test_a_gap_goes_to_the_innermost_program_span_under_it():
    spans = {"A": [("bench:send_columns", 0, 100), ("siddhi:send", 10, 90),
                   ("siddhi:h2d", 20, 50), ("siddhi:sink", 60, 80),
                   ("bench:subscriber", 65, 75),
                   ("bench:wait_due", 100, 150)]}
    got = split(spans, [[[50, 60]]], 0, 150)
    assert got == {
        "bench:send_columns": 20.0,       # [0, 10) + [90, 100): outside send
        "siddhi:send": 20.0,              # its unspanned self time, idle part
        "siddhi:h2d": 30.0,               # not send_columns, not send
        "siddhi:sink": 20.0,              # the subscriber stays in it
        "bench:wait_due": 50.0}
    assert sum(got.values()) == 150 - 10  # the slice's idle time


def test_two_threads_share_a_gap_and_never_count_it_twice():
    spans = {"sender": [("bench:send_columns", 0, 40),
                        ("siddhi:send", 0, 40), ("siddhi:h2d", 10, 30),
                        ("bench:wait_due", 40, 100)],
             "drainer": [("siddhi:fetch", 20, 60),
                         ("bench:subscriber", 70, 80)]}
    got = split(spans, [[[0, 10]], [[0, 20]]], 0, 100)
    # two planes: idle is the mean, 85 of 100
    assert sum(got.values()) == pytest.approx(85.0)
    assert got["siddhi:send"] == pytest.approx(5.0)       # [30, 40) halved
    # [10, 20) is h2d's alone, one plane idle; [20, 30) shared with fetch
    assert got["siddhi:h2d"] == pytest.approx(5.0 + 5.0)
    assert got["siddhi:fetch"] == pytest.approx(5.0 + 5.0 + 20.0)
    # a program span on ANY thread comes before a harness span: wait_due
    # gets [60, 100) less the subscriber's [70, 80), which it shares
    assert got["bench:wait_due"] == pytest.approx(30.0 + 5.0)
    assert got["bench:subscriber"] == pytest.approx(5.0)
    assert "bench:send_columns" not in got


def test_idle_under_no_span_is_named_so():
    got = split({"A": [("bench:send_columns", 10, 20)]}, [[[12, 14]]], 0, 30)
    assert got == {"no_span": 20.0, "bench:send_columns": 8.0}


def test_recorded_program_spans_name_the_gaps_one_level_down():
    """PR 24's recorded blocking trace: the gaps inside the call go to the
    program's spans, the call's own name keeps only what lies outside
    `siddhi:send`, and the list still adds up."""
    red = tr.reduce_trace(os.path.join(os.path.dirname(RECORDED),
                                       "tiny_spans.xplane.pb"))
    gaps = dict(red["idle_gaps"])
    assert len(red["idle_gaps"]) == 10
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)
    assert list(gaps)[:3] == ["bench:wait_due", "siddhi:sink",
                              "siddhi:stage"]
    assert gaps["siddhi:sink"] == pytest.approx(0.008205, abs=1e-9)
    assert gaps["bench:send_columns"] < 1e-4 < gaps["siddhi:send"]
    assert "bench:subscriber" not in gaps


# -- `breakdown.device_ops` adds up ---------------------------------------------------

def test_a_modules_time_is_its_ops_self_time():
    # a loop [0, 100) that runs two body ops: the whole lengths are 170,
    # the plane was busy 100
    events = [(0, 0.0, 100.0), (1, 10.0, 40.0), (2, 50.0, 90.0)]
    assert sum(ns for _, ns, _ in tr.self_times(events, 0.0, 100.0)) == 100.0
    # two ops that only overlap (host ops of two threads in a rehearsal):
    # still the union, never the sum
    events = [(0, 0.0, 60.0), (1, 40.0, 100.0)]
    assert [ns for _, ns, _ in tr.self_times(events, 0.0, 100.0)] == \
        [40.0, 60.0]


@pytest.mark.parametrize("name", [
    "tiny_tpu.xplane.pb", "tiny_spans.xplane.pb", "tiny_served.xplane.pb.gz",
    "tiny_sections.xplane.pb.gz", "tiny_plain.xplane.pb.gz"])
def test_device_ops_sum_to_no_more_than_the_planes_busy_time(name, tmp_path):
    """Every v5e recording, the tiered (Zipf-shaped) send among them: each
    has one device plane, so the list's sum is that plane's."""
    path = os.path.join(os.path.dirname(RECORDED), name)
    if name.endswith(".gz"):
        with gzip.open(path) as src, \
                open(tmp_path / "t.xplane.pb", "wb") as dst:
            shutil.copyfileobj(src, dst)
        path = str(tmp_path / "t.xplane.pb")
    red = tr.reduce_trace(path)
    assert red["devices"] == 1 and red["busy_s"] > 0
    booked = sum(s for _, s in red["by_module"])
    assert booked <= red["busy_s"] * (1 + 1e-9)
    # ten modules or fewer ran: the list is whole and closes on busy_s
    assert len(red["by_module"]) < 10
    assert booked == pytest.approx(red["busy_s"], rel=1e-9)
    # the whole lengths the list used to sum: more than the plane was busy
    # wherever a program runs a loop
    devices, spans = tr.read_planes(path)
    lo, hi, _ = tr.slice_of(spans)
    (rec,) = devices.values()
    whole = sum(min(e + red["skew_s"] * 1e9, hi) -
                max(s + red["skew_s"] * 1e9, lo)
                for _, s, e in rec["ops"]
                if e + red["skew_s"] * 1e9 > lo
                and s + red["skew_s"] * 1e9 < hi) / 1e9
    assert whole >= booked * (1 - 1e-9)
    if "sections" in name:
        assert whole > 1.2 * red["busy_s"]


def test_a_trace_without_sends_reduces_to_nothing(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.newest_xplane(str(tmp_path))

"""The trace reduction: its interval arithmetic by hand, and the small TPU
v5e trace recorded by `record_trace.py` (three sends of one elementwise op,
20 ms of `bench:wait_due` before the second and third)."""
import os

import pytest

from benchmarks.harness import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "tiny_tpu.xplane.pb")


def test_union_clip_complement_overlap():
    merged = tr.union([[5, 7], [0, 2], [1, 3], [7, 8], [9, 9]])
    assert merged == [[0, 3], [5, 8]]
    assert tr.total(merged) == 6
    assert tr.clip(merged, 2, 6) == [[2, 3], [5, 6]]
    assert tr.complement(merged, -1, 10) == [[-1, 0], [3, 5], [8, 10]]
    assert tr.complement([], 0, 4) == [[0, 4]]
    assert tr.overlap(merged, 2, 6) == 2
    assert tr.overlap(merged, 3, 5) == 0


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
    assert list(gaps)[0] == "bench:wait_due"
    assert gaps["bench:wait_due"] == pytest.approx(0.043238108, abs=1e-9)
    assert gaps["bench:send_columns"] == pytest.approx(0.000855381, abs=1e-9)
    # busy + gaps is the slice; two sleeps of 20 ms are the longest gaps
    assert red["busy_s"] + sum(gaps.values()) == pytest.approx(
        red["window_s"], abs=1e-9)
    assert 0.020 < red["longest_gap_s"] < 0.023
    idle_pct = 100 * (1 - red["busy_s"] / red["window_s"])
    assert 98.5 < idle_pct < 98.7


def test_a_trace_without_sends_reduces_to_nothing(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.newest_xplane(str(tmp_path))

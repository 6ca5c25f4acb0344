"""`obs_feed_idle_ms_per_send` (layer_metrics/): the device-idle time under
`siddhi:obs_feed` alone, on hand-made intervals — a feed that runs after the
dispatch lies under the step and reads ~0, a parent-shaped trace (the feed
before the dispatch) reads the span's whole self time — and its entries in
BENCHMARK.json."""
import pytest

from benchmarks.harness import loader
from benchmarks.harness import program_spans as ps
from benchmarks.layer_metrics import (idle_pre_dispatch_ms_per_send,
                                      obs_feed_idle_ms_per_send,
                                      obs_feed_ms_per_send)

MS = 1e6          # the intervals are in ns
# the two throughput cells: `per_layer` holds at most 128 entries and had 126
CELLS = {"pattern_1m.saturated": (".sat", "events_per_s"),
         "pattern_32m.mesh4_saturated": (".mesh4", "events_per_s")}


def S(name, s, e):
    return ("siddhi:" + name, s * MS, e * MS)


def run_of(spans, busy, hi):
    """A run record that already holds its reduction, as `program_spans`
    leaves it after the first reader."""
    return {"program_spans": ps.reduce_intervals(
        {"A": spans}, [[[s * MS, e * MS] for s, e in busy]], 0.0, hi * MS)}


# two sends of 20 ms each: route_keys 2, h2d 2, dispatch 1, the feed 6, a
# 4 ms step that starts when its dispatch ends, the fetch to the send's end
FEED_FIRST = [S("send", 0, 20), S("route_keys", 0, 2), S("h2d", 2, 4),
              S("obs_feed", 4, 10), S("dispatch", 10, 11), S("fetch", 11, 20),
              S("send", 20, 40), S("route_keys", 20, 22), S("h2d", 22, 24),
              S("obs_feed", 24, 30), S("dispatch", 30, 31),
              S("fetch", 31, 40)]
FEED_FIRST_BUSY = [(11, 15), (31, 35)]
DISPATCH_FIRST = [S("send", 0, 20), S("route_keys", 0, 2), S("h2d", 2, 4),
                  S("dispatch", 4, 5), S("obs_feed", 5, 11),
                  S("fetch", 11, 20),
                  S("send", 20, 40), S("route_keys", 20, 22),
                  S("h2d", 22, 24), S("dispatch", 24, 25),
                  S("obs_feed", 25, 31), S("fetch", 31, 40)]
DISPATCH_FIRST_BUSY = [(5, 9), (25, 29)]


def test_a_feed_before_the_dispatch_reads_its_whole_self_time():
    run = run_of(FEED_FIRST, FEED_FIRST_BUSY, 40)
    assert obs_feed_ms_per_send.read(run) == pytest.approx(6.0)
    assert obs_feed_idle_ms_per_send.read(run) == pytest.approx(6.0)
    # the chip is idle under all of the prep: 2 + 2 + 6 + 1
    assert idle_pre_dispatch_ms_per_send.read(run) == pytest.approx(11.0)


def test_a_feed_under_the_step_reads_only_what_outlasts_the_step():
    run = run_of(DISPATCH_FIRST, DISPATCH_FIRST_BUSY, 40)
    assert obs_feed_ms_per_send.read(run) == pytest.approx(6.0)   # same work
    # the 4 ms step covers [5, 9) of the feed's [5, 11)
    assert obs_feed_idle_ms_per_send.read(run) == pytest.approx(2.0)
    # PRE_DISPATCH still names `obs_feed`: 2 + 2 + 1 + the feed's tail 2
    assert idle_pre_dispatch_ms_per_send.read(run) == pytest.approx(7.0)


def test_a_step_longer_than_the_feed_covers_it():
    run = run_of(DISPATCH_FIRST, [(5, 18), (25, 38)], 40)
    assert obs_feed_idle_ms_per_send.read(run) == 0.0
    assert obs_feed_ms_per_send.read(run) == pytest.approx(6.0)


def test_it_reads_obs_feed_alone_and_every_thread_of_it():
    # a drainer thread's demux holds the emission side's feed: idle there
    # counts, the idle under the other spans does not
    spans = {"A": [S("send", 0, 20), S("dispatch", 4, 5),
                   S("obs_feed", 5, 11)],
             "B": [S("demux", 12, 16), S("obs_feed", 13, 14)]}
    red = ps.reduce_intervals(spans, [[[5 * MS, 9 * MS]]], 0.0, 20 * MS)
    run = {"program_spans": red}
    assert obs_feed_idle_ms_per_send.read(run) == pytest.approx(2.0 + 1.0)
    assert red["spans"]["demux"]["idle_s"] == pytest.approx(3e-3)


def test_no_spans_no_number_and_a_path_without_the_feed_reads_zero():
    assert obs_feed_idle_ms_per_send.read(
        {"trace_dir": None, "trace_reduced": None}) is None
    run = run_of([S("send", 0, 20), S("dispatch", 4, 5)], [(5, 9)], 20)
    assert obs_feed_idle_ms_per_send.read(run) == 0.0


def test_benchmark_json_lists_the_metric_once_per_cell_beside_its_twin():
    entries = {e["name"]: e for e in loader.load_benchmark()["per_layer"]}
    for cell, (suffix, moves) in CELLS.items():
        e = entries["obs_feed_idle_ms_per_send" + suffix]
        twin = entries["obs_feed_ms_per_send" + suffix]
        assert {k: v for k, v in e.items() if k != "name"} == \
            {k: v for k, v in twin.items() if k != "name"}
        assert e["workloads"] == [cell] and e["moves"] == moves
        assert e["layer"] == "host staging"
        got = {en["name"] for en, _ in loader.resolve(cell).per_layer}
        assert e["name"] in got

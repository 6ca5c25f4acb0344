"""The span primitive (observability/phases.py `phase`) at the runtime's
hot-path boundaries: what a jax.profiler capture holds (a CPU session
writes TraceAnnotations to the host plane too), what the phase profiler
gets from the same call sites, that neither changes what the program does,
that every jitted step's XLA module carries its role — and the counters
that ride along (adopted uploads, pending timers, full window slabs)."""
import contextlib
import glob
import os
import time
import types

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import event as ev
from siddhi_tpu.observability import phases as ph

PATTERN_QL = """
@app:name('SpanApp')
@app:playback
{annotations}
define stream T (key long, price float, stage int);
partition with (key of T)
begin
  @capacity(keys='4096', slots='4') @emit(rows='2') {query_annotations}
  @info(name='q')
  from every e1=T[stage == 1] -> e2=T[stage == 2 and price >= e1.price]
  select e1.key as k, e1.price as p1, e2.price as p2 insert into Matches;
end;
"""
N_KEYS = 1024       # >= 1024 contiguous keys: block memo and dense step


def pattern_ql(annotations="", query_annotations=""):
    return PATTERN_QL.format(annotations=annotations,
                             query_annotations=query_annotations)


def send_pattern(rt, i, n_keys=N_KEYS):
    """One send completing one match per key: both stages of every key."""
    keys = np.repeat(np.arange(n_keys, dtype=np.int64), 2)
    stage = np.tile(np.array([1, 2], np.int32), n_keys)
    price = np.full(2 * n_keys, 1.0 + i, np.float32)
    ts = np.full(2 * n_keys, 1000 + 10 * i, np.int64) + stage
    rt.get_input_handler("T").send_columns([keys, price, stage],
                                           timestamps=ts)


@contextlib.contextmanager
def profiler_session(tmp_path):
    """Everything inside is captured; yields a function that, called after
    the block, returns the capture's `siddhi:*` events as dicts."""
    log_dir = str(tmp_path / "capture")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)

    def events():
        (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        out = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for t, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("siddhi:"):
                        out.append(dict(
                            dict(e.stats), name=e.name[len("siddhi:"):],
                            thread=t, start=e.start_ns,
                            end=e.start_ns + e.duration_ns))
        return out
    try:
        yield events
    finally:
        jax.profiler.stop_trace()


def capture(tmp_path, ql, n_sends=3, batch_cb=True, await_delivery=False):
    """Deploy, warm one send outside the capture, run `n_sends` inside it;
    returns (events, rows delivered per send).  `await_delivery` waits
    (bounded) for each send's rows before the next: under `@serve` that
    gives the drainer thread its turn — on a loaded machine the final
    flush() otherwise sometimes delivers all three itself."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(ql)
        rows = []
        if batch_cb:
            rt.add_batch_callback(
                "q", lambda ts, b: rows.append(int(np.sum(b["valid"]))))
        rt.start()
        send_pattern(rt, 0)
        rt.flush()
        del rows[:]
        with profiler_session(tmp_path) as events:
            for i in range(1, n_sends + 1):
                send_pattern(rt, i)
                deadline = time.monotonic() + 10.0
                while await_delivery and len(rows) < i and \
                        time.monotonic() < deadline:
                    time.sleep(0.002)
            rt.flush()
    finally:
        m.shutdown()
    return events(), rows


def _inside(evs, s):
    """The spans of one send's thread that lie inside it, in start order."""
    return sorted((e for e in evs if e is not s and e["thread"] == s["thread"]
                   and s["start"] <= e["start"] and e["end"] <= s["end"]),
                  key=lambda e: e["start"])


def _header_fetch(inside):
    """The one `fetch` of a blocking send that holds the wait for its step."""
    (header,) = [e for e in inside
                 if e["name"] == "fetch" and e["what"] == "header"]
    return header


def _prep_feeds(inside):
    """The `obs_feed` spans of a send's prep (the emission side's, inside
    demux, says no `after`)."""
    return [e for e in inside if e["name"] == "obs_feed" and "after" in e]


# -- (a) what a capture of the blocking path holds ----------------------------

def test_off_capture_holds_every_span_nested_in_its_send(tmp_path):
    evs, rows = capture(tmp_path, pattern_ql())
    assert rows == [N_KEYS] * 3
    sends = [e for e in evs if e["name"] == "send"]
    assert len(sends) == 3
    assert [s["events"] for s in sends] == [2 * N_KEYS] * 3
    assert len({s["batch"] for s in sends}) == 3 and \
        all(s["batch"] > 0 for s in sends)
    taken = ("stage", "route_keys", "obs_feed", "h2d", "dispatch", "fetch",
             "demux", "sink")
    for s in sends:
        inside = _inside(evs, s)
        names = [e["name"] for e in inside]
        for name in taken:
            assert name in names, (name, names)
        # one send, one identifier, on every span it caused
        assert {e["batch"] for e in inside} == {s["batch"]}
        # query-level spans name their query
        assert {e["q"] for e in inside if e["name"] != "stage"} == {"q"}
        by = {n: [e for e in inside if e["name"] == n] for n in taken}
        assert len(by["dispatch"]) == 1
        assert by["dispatch"][0]["step"] == "pattern_dense"
        assert by["route_keys"][0]["keys"] == N_KEYS
        assert by["route_keys"][0]["memo_hit"] == 1
        assert by["obs_feed"][0]["keys"] == N_KEYS
        # 1,024 keys take the 4,096-key bucket: the host groups the
        # columns into its [4096, 2] cells by a take (an identity `sel`
        # would read "view": tests/test_grouped_columns.py)
        assert by["route_keys"][0]["grouped"] == "take"
        # the grouped columns go up after routing, then what else prep
        # produced, then the step; the observatory feed runs once the step
        # is submitted (under it, on a chip) and before the header fetch,
        # which holds the wait for the step
        assert len(by["h2d"]) == 2
        assert by["h2d"][0]["bytes"] == 2 * 4096 * (8 + 4 + 4)
        assert by["route_keys"][0]["end"] <= by["h2d"][0]["start"]
        assert by["h2d"][0]["end"] <= by["h2d"][1]["start"]
        assert by["h2d"][1]["end"] <= by["dispatch"][0]["start"]
        # (the span says which order it ran in; demux holds a second
        # `obs_feed`, the emission side's, which says nothing)
        assert [e.get("after") for e in by["obs_feed"]] == ["dispatch", None]
        assert by["dispatch"][0]["end"] <= by["obs_feed"][0]["start"]
        assert by["obs_feed"][0]["end"] <= _header_fetch(inside)["start"]
        kinds = sorted(e["what"] for e in by["fetch"])
        assert kinds == ["header", "rows"]        # payload: `valid` only
        assert all(e["bytes"] > 0 for e in by["fetch"])
        assert by["demux"][0]["rows"] == N_KEYS
        # pipeline order on the thread
        order = [by[n][0]["start"] for n in
                 ("stage", "route_keys", "h2d", "dispatch", "obs_feed",
                  "fetch", "demux")]
        assert order == sorted(order)
        # the emission side's observatory feed and the subscriber sit
        # inside demux
        d = by["demux"][0]
        assert d["start"] <= by["sink"][0]["start"] and \
            by["sink"][0]["end"] <= d["end"]
    # nothing of the runtime ran outside a send
    assert all(any(s["start"] <= e["start"] and e["end"] <= s["end"]
                   for s in sends) for e in evs)


# -- (a') the observatory feed runs under the step, on every pattern path --------

def test_sharded_send_feeds_the_observatory_after_its_dispatch(tmp_path):
    """The mesh path: `shard_group` -> `h2d` -> `dispatch` -> `obs_feed` ->
    the header `fetch` that waits for the step."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    evs, rows = capture(tmp_path, pattern_ql("@app:mesh(shards='4')"))
    assert rows == [N_KEYS] * 3
    sends = [e for e in evs if e["name"] == "send"]
    assert len(sends) == 3
    for s in sends:
        inside = _inside(evs, s)
        (feed,) = _prep_feeds(inside)
        assert feed["after"] == "dispatch" and feed["keys"] == N_KEYS
        (group,) = [e for e in inside if e["name"] == "shard_group"]
        # one grouping a send: its keys, and n x Kb rows of layout (256
        # keys a shard: Kb 512)
        assert (group["passes"], group["keys"], group["rows"]) == \
            (1, N_KEYS, 4 * 512)
        (h2d,) = [e for e in inside if e["name"] == "h2d"]
        (disp,) = [e for e in inside if e["name"] == "dispatch"]
        assert disp["step"] == "pattern_step_sharded" and h2d["shards"] == 4
        order = [group["end"], h2d["start"], h2d["end"], disp["start"],
                 disp["end"], feed["start"], feed["end"],
                 _header_fetch(inside)["start"]]
        assert order == sorted(order)


def test_tiered_send_feeds_once_after_the_last_of_its_dispatches(
        tmp_path, monkeypatch):
    """A send whose keys' counts are far apart: three tiers, three uploads
    and dispatches of the same step, ONE feed of all the tiers' keys —
    after the last dispatch, before the one header fetch."""
    from siddhi_tpu.core import keyslots
    monkeypatch.setattr(keyslots, "_TIER_MIN_CELLS", 0)
    counts = np.concatenate([np.full(200, 2), np.full(10, 16), [100]])
    keys = np.repeat(np.arange(counts.size, dtype=np.int64) * 7 + 3, counts)
    n = keys.size

    def send(rt, i):
        # all openers: partials pile up per key, nothing matches
        rt.get_input_handler("T").send_columns(
            [keys.copy(), np.full(n, 1.0 + i, np.float32),
             np.ones(n, np.int32)],
            timestamps=np.full(n, 1000 + 10 * i, np.int64))

    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(pattern_ql())
        rt.add_batch_callback("q", lambda ts, b: None)
        rt.start()
        send(rt, 0)
        with profiler_session(tmp_path) as events:
            send(rt, 1)
            send(rt, 2)
            rt.flush()
    finally:
        m.shutdown()
    evs = events()
    sends = [e for e in evs if e["name"] == "send"]
    assert len(sends) == 2
    for s in sends:
        inside = _inside(evs, s)
        (route,) = [e for e in inside if e["name"] == "route_keys"]
        assert route["tiers"] == 3 and route["keys"] == counts.size
        disp = [e for e in inside if e["name"] == "dispatch"]
        # (slots are bound in arrival order, so a tier's may be contiguous)
        assert {d["step"] for d in disp} <= {"pattern_step", "pattern_dense"}
        # three tiers, three dispatches; no span says which tier is whose
        # (the device side does: each program's `rect_<Kb>x<E>`)
        assert len(disp) == 3 and not any("tier" in d for d in disp)
        (feed,) = _prep_feeds(inside)
        assert feed["keys"] == counts.size and "tier" not in feed
        assert disp[-1]["end"] <= feed["start"] and \
            feed["end"] <= _header_fetch(inside)["start"]   # the ONE header
        assert all(e["end"] <= disp[-1]["start"] for e in inside
                   if e["name"] == "h2d")


def test_served_send_feeds_on_the_senders_thread_before_the_ring_append(
        tmp_path):
    """Under `@serve` the feed stays in the send call, on the sender's
    thread: after the step's dispatch, before the emission is handed to
    the ring (`ring_append`)."""
    evs, rows = capture(tmp_path, pattern_ql(query_annotations="@serve"),
                        await_delivery=True)
    assert rows == [N_KEYS] * 3
    sends = [e for e in evs if e["name"] == "send"]
    assert len(sends) == 3
    feeds = [e for e in evs if e["name"] == "obs_feed" and "after" in e]
    assert sorted(e["batch"] for e in feeds) == \
        sorted(s["batch"] for s in sends)
    for s in sends:
        inside = _inside(evs, s)
        (feed,) = _prep_feeds(inside)
        assert feed["keys"] == N_KEYS
        by_step = {e["step"]: e for e in inside if e["name"] == "dispatch"}
        assert by_step["pattern_dense"]["end"] <= feed["start"]
        assert feed["end"] <= by_step["ring_append"]["start"]


# -- (b) the served path: delivery on the drainer thread ------------------------

def test_serve_drainer_spans_carry_the_batch_of_their_send(tmp_path):
    evs, rows = capture(tmp_path, pattern_ql(query_annotations="@serve"),
                        await_delivery=True)
    assert rows == [N_KEYS] * 3
    sends = {s["batch"]: s for s in evs if s["name"] == "send"}
    assert len(sends) == 3
    sender = {s["thread"] for s in sends.values()}
    assert len(sender) == 1
    drained = [e for e in evs if e["thread"] not in sender]
    assert {"fetch", "demux", "sink"} <= {e["name"] for e in drained}
    ring = [e for e in evs if e["name"] == "fetch" and e["what"] == "ring"]
    assert ring and all(e["batch"] in sends for e in ring)
    # every send is delivered once, under its own batch, whichever thread
    # drains it (the drainer's, or the flush's for what is left)
    for name in ("demux", "sink"):
        got = sorted(e["batch"] for e in evs if e["name"] == name)
        assert got == sorted(sends), (name, got)
    # inside a send nothing is fetched: the ring append is dispatch-only
    assert not [e for e in evs if e["name"] == "fetch" and any(
        e["thread"] == s["thread"] and s["start"] <= e["start"] <= s["end"]
        for s in sends.values())]
    # the columns go up once a send, as in blocking delivery: the
    # pattern path's own two uploads — the accept-edge stager has no
    # subscriber that would adopt its copy, and stages nothing
    def h2d_bytes(events):
        out = {}
        for e in events:
            if e["name"] == "h2d":
                out.setdefault(e["batch"], []).append(e["bytes"])
        return list(out.values())

    blocking, _ = capture(tmp_path / "blocking", pattern_ql())
    want = h2d_bytes(blocking)
    assert len(want) == 3 and len(want[0]) == 2
    assert h2d_bytes(evs) == want
    # the ring's two programs are dispatches like the step: the append on
    # the sender's thread, inside its send, saying the ring's occupancy
    # once it is in; the read on whichever thread drains
    by_step = {}
    for e in evs:
        if e["name"] == "dispatch":
            by_step.setdefault(e["step"], []).append(e)
    assert sorted(by_step) == ["pattern_dense", "ring_append", "ring_read"]
    for step in ("ring_append", "ring_read"):
        assert sorted(e["batch"] for e in by_step[step]) == sorted(sends)
    for e in by_step["ring_append"]:
        s = sends[e["batch"]]
        assert e["thread"] == s["thread"] and \
            s["start"] <= e["start"] and e["end"] <= s["end"]
        assert 1 <= e["occupancy"] <= 3
    assert not [e for e in by_step["ring_read"] if any(
        e["thread"] == s["thread"] and s["start"] <= e["start"] <= s["end"]
        for s in sends.values())]
    # a drain cycle's fetch says how many sends it serves and how long
    # they sat in the ring: every send is served by exactly one cycle
    assert sum(e["items"] for e in ring) == 3
    assert all(e["items"] >= 1 and e["ring_wait_us"] >= 0 for e in ring)


def test_ring_fetch_span_and_phase_report_agree_on_ring_wait(tmp_path):
    """`what=ring`'s `ring_wait_us` and the scrape's `ring_wait` are the
    same append -> take stamps: the report's adds, per item, the wait
    behind the items delivered before it in its cycle, nothing else."""
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(
            pattern_ql("@app:statistics('BASIC')", "@serve"))
        rt.add_batch_callback("q", lambda ts, b: None)
        rt.start()
        with profiler_session(tmp_path) as events:
            for i in range(4):
                send_pattern(rt, i)
            rt.flush()
        node = rt.phase_report()["queries"]["q"]["phases"]
    finally:
        m.shutdown()
    ring = [e for e in events()
            if e["name"] == "fetch" and e["what"] == "ring"]
    assert sum(e["items"] for e in ring) == 4
    assert node["ring_wait"]["count"] == 4
    span_s = sum(e["ring_wait_us"] for e in ring) / 1e6
    behind = sum(node[p]["seconds"] for p in ("d2h_drain", "demux", "sink"))
    assert span_s <= node["ring_wait"]["seconds"] + 1e-4
    assert node["ring_wait"]["seconds"] - span_s <= behind + 1e-3
    # the ring's programs are booked with the step's, under their query
    assert node["dispatch_submit"]["count"] == 3 * 4


# -- (c) the spans add no sync and, at OFF, feed nothing ------------------------

class NullSpan:
    """What the primitive is without jax.profiler.TraceAnnotation."""

    def __init__(self, *a, **k):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **k):
        pass

    @staticmethod
    def is_enabled():
        return False


def count_syncs(monkeypatch, ql, n=3, batch_cb=True, event_cb=False,
                null_spans=False):
    """(device_get calls, block_until_ready calls, phase snapshot,
    statistics report, rows delivered) over n sends after one warm send."""
    gets, blocks, rows = [0], [0], []
    real_get, real_block = jax.device_get, jax.block_until_ready

    def g(*a, **k):
        gets[0] += 1
        return real_get(*a, **k)

    def b(*a, **k):
        blocks[0] += 1
        return real_block(*a, **k)

    m = SiddhiManager()
    try:
        if null_spans:
            monkeypatch.setattr(ph, "TraceAnnotation", NullSpan)
        rt = m.create_siddhi_app_runtime(ql)
        if batch_cb:
            rt.add_batch_callback(
                "q", lambda ts, bt: rows.append(int(bt["n_valid"])))
        if event_cb:
            rt.add_callback("q", lambda ts, cur, exp: None)
        rt.start()
        send_pattern(rt, 0)
        rt.flush()
        del rows[:]
        monkeypatch.setattr(jax, "device_get", g)
        monkeypatch.setattr(jax, "block_until_ready", b)
        for i in range(1, n + 1):
            send_pattern(rt, i)
        rt.flush()
        monkeypatch.setattr(jax, "device_get", real_get)
        monkeypatch.setattr(jax, "block_until_ready", real_block)
        snap = rt.stats.phases.snapshot()
        report = rt.statistics()
        phase_report = rt.phase_report()
    finally:
        monkeypatch.undo()
        m.shutdown()
    return gets[0], blocks[0], snap, report, rows, phase_report


def test_off_spans_add_no_sync_and_feed_no_profiler(monkeypatch):
    g, b, snap, _, rows, _ = count_syncs(monkeypatch, pattern_ql())
    g0, b0, snap0, _, rows0, _ = count_syncs(monkeypatch, pattern_ql(),
                                             null_spans=True)
    assert rows == rows0 == [N_KEYS] * 3
    assert (g, b) == (g0, b0)
    # one header fetch a send, no fence — and the drain's ONE read of the
    # slab's scalars (`note_nfa_facts`, PR 55)
    assert g == 3 + 1 and b == 0
    assert snap == snap0 == {"queries": {}, "sampled": {}}


# -- (d) BASIC does what OFF does -----------------------------------------------

def test_basic_with_a_batch_callback_fetches_and_builds_what_off_does(
        monkeypatch):
    """Statistics are not a reader.  On the parent an output stream nobody
    subscribed to counted as live once statistics were on: every send
    fetched the whole compacted payload a second time, sorted it and
    unpacked it into one Event a row, for a junction without a reader."""
    g_off, b_off, _, _, rows_off, _ = count_syncs(monkeypatch, pattern_ql())

    def boom(*a, **k):
        raise AssertionError("an Event was built for a stream nobody reads")

    monkeypatch.setattr(ev, "unpack", boom)
    g_on, b_on, _, report, rows_on, _ = count_syncs(
        monkeypatch, pattern_ql("@app:statistics('BASIC')"))
    assert rows_on == rows_off == [N_KEYS] * 3
    assert (g_on, b_on) == (g_off, b_off)
    # the unread output stream's throughput still counts, off the header
    assert report["streams"]["Matches"]["events"] == 4 * N_KEYS
    assert report["streams"]["T"]["events"] == 4 * 2 * N_KEYS


def test_basic_counts_a_routed_output_stream_once(monkeypatch):
    """With an event callback the rows are routed into the junction, which
    counts them in publish: the header path must not count them too."""
    _, _, _, report, _, _ = count_syncs(
        monkeypatch, pattern_ql("@app:statistics('BASIC')"),
        batch_cb=False, event_cb=True)
    assert report["streams"]["Matches"]["events"] == 4 * N_KEYS


# -- (e) the scrape's phases, from the same call sites --------------------------

def test_basic_phase_report_counts_each_phase_once_per_send(monkeypatch):
    *_, rep = count_syncs(monkeypatch,
                          pattern_ql("@app:statistics('BASIC')"), n=3)
    node = rep["queries"]["q"]["phases"]
    sends = 4                               # the warm send counts too
    for name in ("stage_host", "dispatch_submit", "demux", "sink"):
        assert node[name]["count"] == sends, (name, node[name])
        assert node[name]["seconds"] > 0
    # A2: the uploads are booked as h2d, not as staging — the grouped
    # columns after routing, what else prep produced after the feeds
    assert node["h2d"]["count"] == 2 * sends and node["h2d"]["seconds"] > 0
    # a counting subscriber costs the header fetch and nothing else
    assert node["d2h_drain"]["count"] == sends
    parts = node["stage_host"]["parts"]
    assert list(parts) == ["stage", "route_keys", "obs_feed"]
    assert sum(p["seconds"] for p in parts.values()) == pytest.approx(
        node["stage_host"]["seconds"], abs=1e-5)
    assert parts["stage"]["count"] == parts["route_keys"]["count"] == sends
    # how each send's columns were put in the per-key order
    assert parts["route_keys"]["grouped"] == {"take": sends}
    # the observatory feeds twice a send — key hotness before the step,
    # emission-cap demand at delivery — and stage_host still counts sends
    assert parts["obs_feed"]["count"] == 2 * sends
    # nothing books device time by guessing any more
    assert "device_compute" not in node
    assert rep["queries"]["q"]["accounted"] > 0.5


class _DrivenClock:
    """`phases.time`, its clock driven by the test: a loaded machine
    cannot stretch a span."""

    def __init__(self):
        self.ns = 1_000_000_000

    def perf_counter_ns(self):
        return self.ns

    def pass_ms(self, ms):
        self.ns += int(ms * 1e6)


def _stats():
    class Stats:
        enabled = True
        phases = ph.PhaseProfiler()
    return Stats()


def test_self_time_is_the_span_minus_its_children_on_the_thread(monkeypatch):
    clock = _DrivenClock()
    monkeypatch.setattr(ph, "time", clock)
    st = _stats()
    with ph.phase(st, "q", "demux"):
        clock.pass_ms(2)
        with ph.phase(st, "q", "fetch", what="rows"):
            clock.pass_ms(6)
        with ph.phase(st, ("q", "r"), "sink", mult=2):
            clock.pass_ms(4)
    snap = st.phases.snapshot()["queries"]
    q = snap["q"]
    # fetch's wall is its own; sink's counts `mult` times, for each of
    # the queries it is charged to
    assert q["d2h_drain"]["ns"] == 6e6 and q["sink"]["ns"] == 2 * 4e6
    # demux's own: its wall (12 ms) minus fetch and sink (once each)
    assert q["demux"]["ns"] == 2e6
    assert snap["r"]["sink"]["ns"] == q["sink"]["ns"]
    assert [v["count"] for v in q.values()] == [1, 1, 1]


def test_route_keys_layout_stats_are_summed_and_tiers_are_named(
        monkeypatch):
    """`route_keys`' `tiers` / `cells` / `max_e` / `ticks` are summed under
    stage_host's `route_keys` part (a span without them adds nothing); no
    span carries a `tier` stat (PR 53: `tier_scope` had no reader — the
    device side names a tier by its `rect_<Kb>x<E>`)."""
    clock = _DrivenClock()
    monkeypatch.setattr(ph, "time", clock)
    st = _stats()
    for tiers, cells, max_e, ticks in ((1, 8192, 4, 4),
                                       (3, 163840, 1514, 2084)):
        with ph.phase(st, "q", "route_keys") as sp:
            clock.pass_ms(1)
            sp.set_metadata(keys=7, tiers=tiers, cells=cells, max_e=max_e,
                            ticks=ticks, grouped="take")
    with ph.phase(st, "q", "route_keys") as sp:      # the sharded path's
        clock.pass_ms(1)
        sp.set_metadata(keys=7)
    part = st.phases.snapshot()["queries"]["q"]["stage_host"]["parts"][
        "route_keys"]
    assert part["count"] == 3 and part["ns"] == 3e6
    assert part["layout"] == {"tiers": 4, "cells": 172032, "max_e": 1518,
                              "ticks": 2088}
    assert part["grouped"] == {"take": 2}
    assert set(part["layout"]) == set(ph.LAYOUT_STATS)
    assert not hasattr(ph, "tier_scope")
    with ph.phase(st, "q", "dispatch", step="pattern_step") as sp:
        assert sp.meta == {"step": "pattern_step"}


# -- (f) every jitted step's XLA module is jit_<role> ---------------------------

PLANNER_APPS = {
    "plain": ("""define stream S (k long, v float);
        @info(name='q') from S[v > 0.0] select k, v insert into Out;""",
              {"plain_step"}),
    "keyed": ("""define stream S (k long, v float);
        partition with (k of S) begin
        @info(name='q') from S#window.length(4)
        select k, sum(v) as s insert into Out; end;""", {"keyed_step"}),
    "join": ("""define stream S (k long, v float);
        define stream R (k long, w float);
        @info(name='q') from S#window.length(8) join R#window.length(8)
        on S.k == R.k select S.k as k, v, w insert into Out;""",
             {"join_left", "join_right"}),
    "pattern": ("""define stream S (k long, v float);
        partition with (k of S) begin
        @capacity(keys='64', slots='4') @info(name='q')
        from every e1=S[v == 1.0] -> e2=S[v == 2.0]
        select e1.k as k insert into Out; end;""",
                {"pattern_step", "pattern_dense"}),
    "pattern_timer": ("""define stream S (k long, v float);
        partition with (k of S) begin
        @capacity(keys='64', slots='4') @info(name='q')
        from every e1=S[v == 1.0] -> not S[v == 2.0] for 1 sec
        select e1.k as k insert into Out; end;""", {"pattern_timer"}),
    "block": ("""define stream S (k long, v float);
        @info(name='q') from every e1=S[v == 1.0] -> e2=S[v == 2.0]
        select e1.k as k insert into Out;""", {"pattern_block"}),
    "fused": ("""define stream S (k long, v float);
        @fuse(batches='2') @info(name='q')
        from S[v > 0.0] select k, v insert into Out;""", {"fused_plain"}),
    "merged": ("""define stream S (k long, v float);
        @info(name='q') from S[v > 0.0] select k, v insert into Out;
        @info(name='q2') from S[v > 1.0] select k, v insert into Out2;""",
               {"merged_step"}),
    "served": ("""define stream S (k long, v float);
        @serve @info(name='q')
        from S[v > 0.0] select k, v insert into Out;""",
               {"plain_step", "ring_append", "ring_read"}),
}


@pytest.mark.parametrize("planner", sorted(PLANNER_APPS))
def test_every_jitted_step_lowers_to_its_role(planner, manager):
    ql, want = PLANNER_APPS[planner]
    rt = manager.create_siddhi_app_runtime(
        "@app:name('Roles')\n@app:playback\n" + ql)
    for q in rt.query_runtimes:
        rt.add_callback(q, lambda ts, cur, exp: None)
    rt.start()
    for stream in ("S", "R") if planner == "join" else ("S",):
        h = rt.get_input_handler(stream)
        # contiguous keys (dense step), then gappy ones (gather/scatter)
        for i, keys in enumerate((np.arange(8), np.array([1, 5, 9, 40]))):
            keys = keys.astype(np.int64)
            n = keys.shape[0]
            h.send_columns([keys, np.full(n, 1.0, np.float32)],
                           timestamps=np.full(n, 1000 + i, np.int64))
            h.send_columns([keys, np.full(n, 2.0, np.float32)],
                           timestamps=np.full(n, 1010 + i, np.int64))
    h.send_columns([np.arange(8, dtype=np.int64),
                    np.full(8, 3.0, np.float32)],
                   timestamps=np.full(8, 9000, np.int64))   # fires timers
    rt.flush()
    modules = set()
    for q in rt.query_runtimes:
        for _role, fn, argspecs in rt.compiled_steps(q):
            if argspecs is None:
                continue                      # never ran: nothing to name
            text = fn.lower(*argspecs).as_text()
            name = text.split("module @", 1)[1].split(" ", 1)[0]
            assert name == "jit_" + fn._siddhi_role, (name, _role)
            modules.add(fn._siddhi_role)
    assert want <= modules, (want, modules)
    assert "wrapped" not in modules


SECTION_SCOPES = ("event_load", "state_load", "nfa_advance", "state_store",
                  "emission_compaction", "emission_bands")


@pytest.mark.parametrize("role", [
    "pattern_dense", "pattern_step", "pattern_step_sharded"])
def test_named_scopes_leave_the_compiled_pattern_step_as_it_was(role,
                                                                monkeypatch):
    """jax.named_scope is op-name metadata: the lowered program without its
    debug info, and XLA's cost analysis of the compiled one, are the same
    with the sections and the `rect_*` scope and with `jax.named_scope`
    patched out — for each of the three programs the benchmark's cells
    run."""
    mesh = None
    if role == "pattern_step_sharded":
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("shard",))

    def facts(scoped):
        if not scoped:
            monkeypatch.setattr(jax, "named_scope",
                                lambda name: contextlib.nullcontext())
        m = SiddhiManager()
        try:
            rt = m.create_siddhi_app_runtime(pattern_ql(), mesh=mesh) \
                if mesh is not None else \
                m.create_siddhi_app_runtime(pattern_ql())
            rt.add_batch_callback("q", lambda ts, b: None)
            rt.start()
            send_pattern(rt, 0)               # contiguous keys: dense
            h = rt.get_input_handler("T")     # gappy keys: gather / scatter
            h.send_columns([np.array([3, 40, 700], np.int64),
                            np.full(3, 1.0, np.float32),
                            np.full(3, 1, np.int32)],
                           timestamps=np.full(3, 5000, np.int64))
            rt.flush()
            out = {}
            for _role, fn, argspecs in rt.compiled_steps("q"):
                if argspecs is not None:
                    lowered = fn.lower(*argspecs)
                    ca = lowered.compile().cost_analysis()
                    named = lowered.as_text(debug_info=True)
                    out[fn._siddhi_role] = (
                        ca.get("flops"), ca.get("bytes accessed"),
                        lowered.as_text(),
                        {n for n in SECTION_SCOPES + ("rect_", "mesh_reduce")
                         if n in named})
        finally:
            m.shutdown()
            monkeypatch.undo()
        return out

    with_scopes, without = facts(True), facts(False)
    assert role in with_scopes and set(with_scopes) == set(without)
    flops, nbytes, text, named = with_scopes[role]
    want = set(SECTION_SCOPES) | {"rect_"}
    if mesh is not None:
        want.add("mesh_reduce")
    assert want <= named and not without[role][3]
    assert (flops, nbytes) == without[role][:2]
    assert text == without[role][2]


def test_send_span_says_the_threads_page_faults_while_recorded(
        tmp_path, monkeypatch):
    """`siddhi:send` carries `minflt`, the sending thread's minor page
    faults inside the call (a `getrusage(RUSAGE_THREAD)` pair), only while
    a session records it; where the platform has no RUSAGE_THREAD the span
    says nothing."""
    calls, readings = [], iter([1100, 1207, 1300, 1414])

    def getrusage(who):
        calls.append(who)
        return types.SimpleNamespace(ru_minflt=next(readings))

    monkeypatch.setattr(ph.resource, "getrusage", getrusage)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(pattern_ql())
        rt.add_batch_callback("q", lambda ts, b: None)
        rt.start()
        send_pattern(rt, 0)
        rt.flush()
        assert calls == []                    # nobody records: not asked
        with profiler_session(tmp_path) as events:
            send_pattern(rt, 1)
            send_pattern(rt, 2)
            rt.flush()
        assert calls == [ph._RUSAGE_THREAD] * 4
        faults = [e["minflt"] for e in events() if e["name"] == "send"]
        assert faults == [107, 114]           # exit's reading - entry's
        monkeypatch.setattr(ph, "_RUSAGE_THREAD", None)
        del calls[:]
        with profiler_session(tmp_path / "none") as events:
            send_pattern(rt, 3)
            rt.flush()
        assert calls == []
        (sent,) = [e for e in events() if e["name"] == "send"]
        assert "minflt" not in sent and sent["events"] == 2 * N_KEYS
    finally:
        m.shutdown()


# -- counters that count, and two that were missing -----------------------------

def test_adopted_total_counts_the_uploads_a_step_took(manager):
    rt = manager.create_siddhi_app_runtime("""
    @app:name('Adopt')
    define stream S (k long, v float);
    @serve @info(name='q') from S[v > 0.0] select k, v insert into Out;
    """)
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(4):
        h.send_columns([np.arange(64, dtype=np.int64),
                        np.full(64, 2.0, np.float32)],
                       timestamps=np.full(64, 1000 + i, np.int64))
    rt.flush()
    facts = rt.serve_staging_facts()
    assert facts["staged_total"] == 4
    assert facts["adopted_total"] == 4       # read 0 whatever happened
    assert facts["fallback_total"] == 0


def test_pattern_path_is_not_staged_for_under_serve(manager):
    """One upload a batch (ROADMAP A4): the pattern path uploads columns
    of its own and never read the stager's copy, so a junction whose
    subscribers are pattern runtimes stages nothing under @serve."""
    rt = manager.create_siddhi_app_runtime(
        pattern_ql(query_annotations="@serve"))
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    for i in range(3):
        send_pattern(rt, i)
    rt.flush()
    facts = rt.serve_staging_facts()
    assert facts["staged_total"] == facts["adopted_total"] == 0
    assert facts["fallback_total"] == 0
    assert rt.serve_rings()["q"].facts()["appends_total"] == 3


def test_a_plain_subscriber_beside_a_pattern_still_gets_its_staged_upload(
        manager):
    """The stager serves whoever adopts: a served filter on the stream a
    pattern also reads takes the accept-edge upload, once a batch."""
    rt = manager.create_siddhi_app_runtime(
        pattern_ql(query_annotations="@serve").replace(
            "partition with", """@serve @info(name='f')
        from T[price > 0.0] select key, price insert into Seen;
        partition with"""))
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.add_batch_callback("f", lambda ts, b: None)
    rt.start()
    for i in range(3):
        send_pattern(rt, i)
    rt.flush()
    facts = rt.serve_staging_facts()
    assert facts["staged_total"] == facts["adopted_total"] == 3
    assert facts["fallback_total"] == 0


TIMEWINDOW_QL = """
@app:name('Timers')
@app:playback
define stream S (sym long, v float);
@capacity(window='{window}') @info(name='q')
from S#window.time(1 sec) select sym, sum(v) as s, count() as n
group by sym insert into Out;
"""


def dispatches_per_send(rt, n_sends, events=64):
    """`dispatch` spans of query q per send, from the DETAIL-free side:
    the recompile-free dispatch counter the profiler keeps at BASIC."""
    h = rt.get_input_handler("S")
    per_send, pending = [], []
    for i in range(n_sends):
        before = rt.stats.phases.snapshot()["queries"].get("q", {}).get(
            "dispatch_submit", {"count": 0})["count"]
        h.send_columns([np.arange(events, dtype=np.int64) % 8,
                        np.full(events, 1.0, np.float32)],
                       timestamps=np.full(events, 1000 + 600 * i, np.int64))
        after = rt.stats.phases.snapshot()["queries"]["q"][
            "dispatch_submit"]["count"]
        per_send.append(after - before)
        pending.append(rt.timers_pending())
    return per_send, pending


def test_a_time_window_holds_one_wake_up_under_playback(manager):
    """PR 51 (A12): a query runtime holds ONE pending wake-up, which a
    step's wake replaces — until then `notify_at` pushed one for every step
    that left live rows and every timer that fired pushed the next, so each
    send ran one more timer step than the last (1, 1, 3, 4, 5, ... was
    pinned here).  Sends 600 ms apart: from the third on, one timer step
    (the batch two sends back expires) and the send's own."""
    rt = manager.create_siddhi_app_runtime(
        "@app:statistics('BASIC')\n" + TIMEWINDOW_QL.format(window=4096))
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    per_send, pending = dispatches_per_send(rt, 8)
    assert per_send == [1, 1, 2, 2, 2, 2, 2, 2]
    assert pending == [1] * 8
    assert rt.timer_facts() == {"pending": 1, "timer_steps": 6,
                                "wakeups_armed": 7}
    from siddhi_tpu.observability import render_prometheus
    from siddhi_tpu.observability.health import app_health
    text = render_prometheus(manager.runtimes)
    assert 'siddhi_timers_pending{app="Timers"} 1' in text
    assert 'siddhi_timer_steps_total{app="Timers"} 6' in text
    assert 'siddhi_wakeups_armed_total{app="Timers"} 7' in text
    assert app_health(rt)["timers_pending"] == 1


def test_timer_span_carries_the_heap_depth(manager, tmp_path):
    rt = manager.create_siddhi_app_runtime(TIMEWINDOW_QL.format(window=4096))
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    with profiler_session(tmp_path) as events:
        h = rt.get_input_handler("S")
        for i in range(4):
            h.send_columns([np.arange(16, dtype=np.int64) % 8,
                            np.full(16, 1.0, np.float32)],
                           timestamps=np.full(16, 1000 + 600 * i, np.int64))
    timers = [e for e in events() if e["name"] == "timer"]
    assert len(timers) == 2 and all(e["q"] == "q" for e in timers)
    # the heap's depth while it fires: the query's one wake-up is off it
    assert [e["pending"] for e in timers] == [0, 0]
    # each inside the `timer_drain` of the send whose clock made it due
    drains = [e for e in events() if e["name"] == "timer_drain"]
    assert [(d["fired"], d["clock"]) for d in drains] == [(1, 2200),
                                                          (1, 2800)]
    for t, d in zip(timers, drains):
        assert d["start"] <= t["start"] and t["end"] <= d["end"]
    # a timer step is a dispatch like any other, nested in its timer span
    for t in timers:
        assert [e for e in events() if e["name"] == "dispatch"
                and t["start"] <= e["start"] and e["end"] <= t["end"]]


def test_full_window_slab_is_counted(manager):
    """A `window.time` slab that fills up drops its oldest rows unexpired
    (verify skill, round 5): the sampled fill probe now counts each time
    it finds one full, beside the emission drop counters."""
    from siddhi_tpu.observability import render_prometheus
    from siddhi_tpu.utils.config import InMemoryConfigManager
    manager.set_config_manager(InMemoryConfigManager(
        {"state.obs.sample.every": "1"}))
    rt = manager.create_siddhi_app_runtime(TIMEWINDOW_QL.format(window=1024))
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    h = rt.get_input_handler("S")
    for i in range(4):          # 2,048 rows alive at once, a 1,024-row slab
        h.send_columns([np.arange(512, dtype=np.int64) % 8,
                        np.full(512, 1.0, np.float32)],
                       timestamps=np.full(512, 1000 + i, np.int64))
    rt.flush()
    counters = rt.stats.exposition_snapshot()["counters"]
    assert counters.get("q.window_full", 0) >= 1
    assert 'siddhi_window_slab_full_total{app="Timers",query="q"}' in \
        render_prometheus(manager.runtimes)

"""`timewindow_256sym`: `model.py` alone — its reference against a vectorised
formula written HERE (per symbol: a cumulative sum of the arrivals less a
cumulative sum of what has expired by each arrival), `expected_rows` against
the reference send by send (the gap, the send that owes nothing), `compare`
on each fault and the bfloat16 control, the exactness its tolerances rest on,
`least_bytes` from shapes, what its configuration and its traffic file state —
the table's entries, held ONE-SIDED (`check_*(bench)`, which
`test_bench_adding_pr.py` runs on the scratch adding PR) — and the whole of a
run, sound and doctored underneath: a runtime that forgets the window it
carried, one that fires its timers ahead of the send's rows (the parent's
`_route_columns`), a row delivered for a send that owes none."""
import json
import os

import jax
import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader, numeric
from siddhi_tpu.core import runtime as _runtime
from test_bench_doctored import load_run_module

CELL = "timewindow_256sym.paced"
BENCH = loader.load_benchmark()
WARM = 10                 # the traffic's rehearsal `warmup_sends`
# the entries PR 51 appended, in their order
APPENDED = [
    "plain_window_ms_per_send.paced", "plain_order_ms_per_send.paced",
    "plain_aggregate_ms_per_send.paced", "plain_unscoped_ms_per_send.paced",
    "agg_layout_ms_per_send.paced", "project_ms_per_send.paced",
    "timer_steps_per_send.paced", "zero_row_sends_pct.paced",
    "step_roofline.timewindow"]
# the lists the cell joined
JOINED = [
    "send_to_delivery_ms_per_send.paced", "subscriber_ms_per_send.paced",
    "after_delivery_ms_per_send.paced", "compiles_in_window",
    "device_busy_ms_per_send.paced", "device_idle_pct.paced", "state_bytes",
    "peak_hbm_bytes", "compile_s", "stage_ms_per_send.paced",
    "route_keys_ms_per_send.paced", "obs_feed_ms_per_send.paced",
    "h2d_ms_per_send.paced", "dispatch_ms_per_send.paced",
    "fetch_ms_per_send.paced", "demux_ms_per_send.paced",
    "sink_ms_per_send.paced", "send_unspanned_ms_per_send.paced",
    "dispatches_per_send.paced", "fetches_per_send.paced",
    "idle_pre_dispatch_ms_per_send.paced",
    "idle_post_step_ms_per_send.paced", "fetch_bytes_per_send.paced",
    "obs_feed_idle_ms_per_send.paced", "page_faults_per_send.paced"]


def zero(model):
    return dict.fromkeys(model.LIMITS, 0)


def sends_of(cell, seed, n, **traffic):
    """The first `n` sends of the cell's traffic, as the harness makes
    them, and the plan they were made with."""
    m, t = cell.model, dict(cell.traffic, **traffic)
    plan, clock, sends = m.plan(seed, t, cell.sizes), 1000, []
    for i in range(n):
        clock += m.clock_step_ms(t)
        sends.append(m.make_send(np.random.default_rng([seed, i]), i, t,
                                 plan, clock))
    return sends, plan


def by_formula(sends, symbols, having, window_ms=1000):
    """The query over the whole stream at once: an arrival at time t sees,
    of its symbol, every arrival up to itself less every arrival with
    timestamp <= t - window — two cumulative sums a symbol, float64.  The
    rows (symbol, total, n) of each send."""
    ts = np.concatenate([s["ts"] for s in sends])
    sym = np.concatenate([s["cols"][0] for s in sends])
    price = np.concatenate([s["cols"][1] for s in sends]).astype(np.float64)
    total = np.zeros(ts.shape[0])
    count = np.zeros(ts.shape[0], np.int64)
    for k in range(symbols):
        at = np.nonzero(sym == k)[0]
        if not at.size:
            continue
        t_k, cum = ts[at], np.concatenate([[0.0], np.cumsum(price[at])])
        gone = np.searchsorted(t_k, t_k - window_ms, side="right")
        here = np.arange(1, at.size + 1)
        total[at] = cum[here] - cum[gone]
        count[at] = here - gone
    emit = total > having
    out, lo = [], 0
    for s in sends:
        m = emit[lo:lo + s["events"]]
        out.append((sym[lo:lo + s["events"]][m], total[lo:lo + s["events"]][m],
                    count[lo:lo + s["events"]][m]))
        lo += s["events"]
    return out


# -- the model alone ------------------------------------------------------------

@pytest.mark.parametrize("events,rate", [(1024, 4000), (300, 1500),
                                         (8192, 40000), (1, 8)])
def test_the_reference_is_the_two_cumulative_sums(events, rate):
    cell = loader.resolve(CELL, rehearse=events != 8192)
    sends, plan = sends_of(cell, 5, 20, events_per_send=events,
                           rate_events_per_s=rate)
    refs = cell.model.reference(sends, plan)
    want = by_formula(sends, plan["symbols"], plan["having_total"])
    assert events == 1 or sum(w[0].shape[0] for w in want) > 0
    for send, ref, (sym, total, n) in zip(sends, refs, want):
        assert np.array_equal(ref["symbol"], sym)
        assert np.array_equal(ref["n"], n)
        assert np.array_equal(ref["total"], total.astype(np.float32))
        assert np.array_equal(ref["ap"], (total / n).astype(np.float32))
        assert cell.model.expected_rows(send) == sym.shape[0]
        assert (ref["symbol"].dtype, ref["total"].dtype, ref["n"].dtype,
                ref["ap"].dtype) == (np.int64, np.float32, np.int64,
                                     np.float32)


def test_a_send_is_new_in_every_column_on_the_grid_and_on_the_schedule():
    cell = loader.resolve(CELL)
    m, t = cell.model, cell.traffic
    assert m.events_per_send(t) == 8192
    assert m.clock_step_ms(t) == round(8192e3 / t["rate_events_per_s"])
    assert m.clock_step_ms(dict(t, rate_events_per_s=20000)) == 410
    assert (t["symbols"], t["zipf_s"], t["gap_every_sends"], t["gap_ms"],
            t["price_lo"], t["price_ticks"]) == (256, 1.0, 32, 1500, 10.0, 400)
    sends, plan = sends_of(cell, 9, 40)
    step = m.clock_step_ms(t)
    for i, s in enumerate(sends):
        sym, price, vol = s["cols"]
        assert (sym.dtype, price.dtype, vol.dtype, s["ts"].dtype) == \
            (np.int64, np.float32, np.int64, np.int64)
        assert 0 <= sym.min() and sym.max() <= 255
        assert 10 <= price.min() and price.max() < 60
        assert np.array_equal(price * 8, np.round(price * 8))   # the grid
        assert 1 <= vol.min() and vol.max() <= 1000
        # spread evenly over the send's interval of the schedule
        assert np.array_equal(s["ts"] - s["ts"][0],
                              np.arange(8192) * step // 8192)
        if i:
            gap = int(s["ts"][0]) - int(sends[i - 1]["ts"][-1])
            assert gap == (1500 if i % 32 == 0 else 1), i
            assert (m.expected_rows(s) == 0) == (i % 32 == 0), i
    for a, b in zip(sends, sends[1:]):
        for ca, cb in zip(a["cols"], b["cols"]):
            assert not np.array_equal(ca, cb)
    # a minority passes `having`: about the share the traffic file states
    full = [m.expected_rows(s) for i, s in enumerate(sends) if i % 32 >= 5]
    assert 0.8 * t["having_share"] < np.mean(full) / 8192 < \
        1.2 * t["having_share"]
    # Zipf(1.0) over 256: the top rank takes 16.3 %; the hot set moves
    sym = np.concatenate([s["cols"][0] for s in sends])
    assert abs(np.mean(sym == 0) - 0.1633) < 0.01
    later, _ = sends_of(cell, 9, 1)
    shifted = m.make_send(np.random.default_rng([9, 0]), 128, t,
                          m.plan(9, t, cell.sizes), 2000)
    assert np.array_equal(shifted["cols"][0],
                          (later[0]["cols"][0] + 1) % 256)


def in_float32(sends, symbols, having, nudge=0.0):
    """The reference's loop with every sum kept in float32 — adds and
    removes in the order they fall; `nudge` moves every price off the
    grid.  The totals of the rows each send emits."""
    import collections
    held, acc, out = collections.deque(), np.zeros(symbols, np.float32), []
    for send in sends:
        price = send["cols"][1] + np.float32(nudge)
        rows = []
        for t, s, p in zip(send["ts"].tolist(), send["cols"][0].tolist(),
                           price):
            while held and held[0][0] + 1000 <= t:
                _, s0, p0 = held.popleft()
                acc[s0] -= p0
            held.append((t, s, p))
            acc[s] += p
            if acc[s] > having:
                rows.append(acc[s])
        out.append(np.asarray(rows, np.float32))
    return out


def test_the_tolerances_rest_on_sums_that_float32_holds_exactly():
    """On the 1/8 grid float32 accumulation of the window's terms is the
    float64 reference bit for bit; a cent off the grid and it is not — the
    residue of add and remove shows within a few sends."""
    cell = loader.resolve(CELL)
    m = cell.model
    sends, plan = sends_of(cell, 4, 12)
    want = m.reference(sends, plan)
    got = in_float32(sends, plan["symbols"], plan["having_total"])
    assert sum(w["total"].shape[0] for w in want) > 10000
    for g, w in zip(got, want):
        assert np.array_equal(g, w["total"])
    off = in_float32(sends, plan["symbols"], plan["having_total"], 0.01)
    window = m.SlidingWindow(plan["symbols"], plan["having_total"])
    exact = [
        (window.feed(s["ts"], s["cols"][0],
                     (s["cols"][1] + np.float32(0.01)).astype(np.float64)
                     )["total"]) for s in sends]
    assert any(g.shape != w.shape or not np.array_equal(g, w)
               for g, w in zip(off, exact))
    assert m.TOTAL_RTOL * plan["having_total"] < m.TICK / 2
    # a threshold ON the grid, a price range past 64: refused
    with pytest.raises(AssertionError):
        m.plan(1, cell.traffic, dict(cell.sizes, having_total="60000.0"))
    with pytest.raises(AssertionError):
        sends_of(cell, 4, 1, price_ticks=1000)


def test_compare_catches_each_fault_and_the_control_fails():
    cell = loader.resolve(CELL)
    m = cell.model
    ZERO = zero(m)
    assert list(m.LIMITS) == ["rows_missing", "rows_unexpected",
                              "rows_differing", "values_over_tolerance"]
    assert set(m.LIMITS.values()) == {0}
    sends, plan = sends_of(cell, 2, 6)
    want = m.canonical(m.reference(sends, plan)[5])
    n = want["n"].shape[0]
    assert n > 2000 and m.compare(want, want) == ZERO
    keep = np.arange(n) != 17
    withheld = {k: a[keep] for k, a in want.items()}
    assert m.compare(withheld, want) == dict(ZERO, rows_missing=1)
    twice = {k: np.concatenate([a, a[17:18]]) for k, a in want.items()}
    assert m.compare(twice, want) == dict(ZERO, rows_unexpected=1)
    # arrival order is the guarantee: two rows of two symbols swapped
    i = next(i for i in range(n - 1)
             if want["symbol"][i] != want["symbol"][i + 1])
    swapped = {k: a.copy() for k, a in want.items()}
    for a in swapped.values():
        a[[i, i + 1]] = a[[i + 1, i]]
    got = m.compare(swapped, want)
    assert got["rows_differing"] == 2 and got["rows_missing"] == 0
    # one tick on one total, one count off, a NaN
    tick = dict(want, total=want["total"].copy())
    tick["total"][5] -= np.float32(m.TICK)
    assert m.compare(tick, want) == dict(ZERO, values_over_tolerance=1)
    count = dict(want, n=want["n"].copy())
    count["n"][5] += 1
    assert m.compare(count, want) == dict(ZERO, rows_differing=1)
    nan = dict(want, ap=want["ap"].copy())
    nan["ap"][5] = np.nan
    assert m.compare(nan, want) == dict(ZERO, values_over_tolerance=1)
    # a send that owes none and gets one
    none = {k: a[:0] for k, a in want.items()}
    one = {k: a[:1] for k, a in want.items()}
    assert m.compare(one, none) == dict(ZERO, rows_unexpected=1)
    assert m.compare(none, none) == ZERO
    # the control: total and ap through bfloat16, the exact columns as
    # they are — over the tolerance on nearly every row, and by it alone
    ctl = m.compare(m.canonical(m.control_rows(want)), want)
    assert ctl == dict(ZERO, values_over_tolerance=ctl[
        "values_over_tolerance"]) and ctl["values_over_tolerance"] > 0.95 * n
    assert np.array_equal(m.control_rows(want)["total"],
                          numeric.to_bf16(want["total"]))


def test_least_bytes_from_shapes():
    cell = loader.resolve(CELL)
    # events of 28 B in, each held (36 B) written once and read once, 30 %
    # of them rows of 32 B out, 256 slots of 12 B read and written
    assert cell.model.least_bytes(cell.traffic, cell.sizes, cell.config) \
        == 8192 * 28 + 2 * 8192 * 36 + 2457 * 32 + 2 * 256 * 12 == 903968


def test_config_and_traffic_state_what_the_contract_asks():
    cell = loader.resolve(CELL)
    cfg, t = cell.config, cell.traffic
    for key in ("source", "deployment", "assumed", "guarantees",
                "tolerance", "reduced_why", "scale_from"):
        assert cfg[key]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert "configs`[1]" in cfg["source"] and \
        "GroupByWindowSingleQueryPerformance" in cfg["source"]
    assert cfg["sizes"] == {"window_rows": 131072,
                            "having_total": "60000.0625"}
    assert cfg["rehearse_sizes"] == {"window_rows": 8192,
                                     "having_total": "8000.0625"}
    assert (cfg["stream"], cfg["query"], cfg["columns"]) == \
        ("StockStream", "q", ["symbol", "total", "n", "ap"])
    for text in (cfg["deployment"], cfg["scale_from"]):
        assert "by nature" in text and "stream" in text
    assert any("256 symbols" in a and "no cardinality" in a
               for a in cfg["assumed"])
    assert any("Zipf(1.0)" in a for a in cfg["assumed"])
    assert any(a.startswith("the `having` threshold") for a in cfg["assumed"])
    assert any(a.startswith("the event-time spacing") for a in cfg["assumed"])
    assert any(a.startswith("the feed gap") for a in cfg["assumed"])
    assert any("arrival order, none lost, none twice" in g
               for g in cfg["guarantees"])
    assert any("emits nothing" in g for g in cfg["guarantees"])
    app = cell.app_text
    assert "@app:playback" in app and "window.time(1 sec)" in app
    assert "(symbol long, price float, volume long)" in app
    assert "sum(price) as total, count() as n, avg(price) as ap" in app
    assert "group by symbol" in app and "having total > 60000.0625" in app
    assert "@capacity(window='131072')" in app and "insert into Out" in app
    assert (cell.chips, t["loop"], t["events_per_send"]) == (1, "open", 8192)
    assert "prefill" not in t and t["drain_limit_s"] == 60
    assert t["who"] and t["what"] and t["rate_why"] and t["having_why"]
    assert loader.resolve(CELL, rehearse=True).traffic["warmup_sends"] == WARM
    check_the_tables_configuration_and_what_its_cell_reports(BENCH)
    # the model imports nothing of the program
    with open(os.path.join(loader.BENCH_DIR, "configs", cfg["name"],
                           "model.py")) as fh:
        assert "siddhi_tpu" not in fh.read().split('"""', 2)[2]


# -- the table, one-sided --------------------------------------------------------

def check_the_tables_configuration_and_what_its_cell_reports(bench):
    cell = loader.resolve(CELL)
    cfg = cell.config
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == \
        "benchmarks/configs/timewindow_256sym/config.json"
    w = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("timewindow_256sym", "tw_paced_8k", 1)
    assert "assumed" in w["why"] and len(w["why"]) <= 200
    # an open loop: it reports no `events_per_s`
    assert {e["name"] for e in cell.end_to_end} == {
        "latency_p50_ms", "setup_s"}


def check_the_appended_entries_and_the_lists_the_cell_joined(bench):
    """The nine entries stay, together and in order, behind PR 48's 89; the
    cell stands FIRST in their lists (a later cell may join behind it) and
    is a member of the lists it joined (a later PR may lengthen them)."""
    names = [e["name"] for e in bench["per_layer"]]
    at = names.index(APPENDED[0])
    assert names[at:at + len(APPENDED)] == APPENDED
    assert 89 <= at and len(names) <= 128
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for n in APPENDED:
        e = by_name[n]
        assert e["workloads"][:1] == [CELL] and e["moves"] == \
            "latency_p50_ms"
    assert [by_name[n]["source"] for n in APPENDED] == \
        ["device_trace"] * 6 + ["program_span", "host_clock", "device_trace"]
    assert {by_name[n]["layer"] for n in APPENDED} == {
        "device step", "dispatch", "served path"}
    for n in JOINED:
        assert CELL in by_name[n]["workloads"], n
    # what the cell reads, among the entries that stood when it was added
    mine = {n for n in names[:at + len(APPENDED)]
            if CELL in by_name[n]["workloads"]}
    assert mine == set(APPENDED) | set(JOINED)
    # a plain step: no pattern section, no rectangle, no ring, no join
    assert not any(n.startswith(("step_event_load", "step_state", "step_scan",
                                 "step_compact", "step_unscoped", "join_",
                                 "ring_", "hot_tier", "scan_ticks"))
                   for n in mine)
    # every reader the loader resolves for it is a file
    assert [e["name"] for e, _ in loader.resolve(CELL).per_layer
            if e["name"] in mine] == [n for n in names if n in mine]


def test_the_appended_entries_and_the_lists_the_cell_joined():
    check_the_appended_entries_and_the_lists_the_cell_joined(BENCH)


# -- the whole of a run, sound and doctored underneath --------------------------

class BrokenHandler:
    def __init__(self, owner, handler):
        self.owner, self.handler = owner, handler

    def send_columns(self, cols, timestamps=None):
        o = self.owner
        o.calls += 1
        timed = o.calls > WARM
        before = o.deliveries
        AHEAD["on"] = timed and o.fault == "timers_ahead_of_the_send"
        if o.fault == "forgets_the_carried_window" and timed \
                and o.calls == WARM + 4:
            # a program that loses the state it carried: the window's rows
            # and the symbols' sums, as they were at deploy
            qr = o._rt.query_runtimes["q"]
            qr.state = jax.tree.map(lambda x: jax.numpy.array(x, copy=True),
                                    qr.planned.init_state())
            o.forgot = True
        self.handler.send_columns(cols, timestamps=timestamps)
        if o.fault == "row_for_a_zero_row_send" and timed \
                and o.deliveries == before and not o.invented:
            o.invented = True        # nothing came of this send: make a row
            o.callback(timestamps, {
                "valid": np.ones(1, bool), "kind": np.zeros(1, np.int32),
                "cols": {"symbol": np.array([3], np.int64),
                         "total": np.array([70000.0], np.float32),
                         "n": np.array([2000], np.int64),
                         "ap": np.array([35.0], np.float32)}})


class BrokenRuntime:
    """The real runtime with one fault between it and its user."""

    def __init__(self, rt, fault):
        self._rt, self.fault = rt, fault
        self.calls = self.deliveries = 0
        self.invented = self.forgot = False
        self.callback = None

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def get_input_handler(self, stream):
        return BrokenHandler(self, self._rt.get_input_handler(stream))

    def add_batch_callback(self, query, cb):
        def counted(ts, b):
            if (b["valid"] & (b["kind"] == 0)).any():
                self.deliveries += 1
            cb(ts, b)
        self.callback = cb
        self._rt.add_batch_callback(query, counted)


AHEAD = {"on": False}
_advance = _runtime.SiddhiAppRuntime._playback_advance


def timers_ahead_of_the_send(self, first, last):
    """The parent's `_route_columns`, once the warm-up is through: the
    clock at the send's LAST timestamp and the timers drained to it BEFORE
    its rows are dispatched."""
    if not AHEAD["on"]:
        return _advance(self, first, last)
    with self._lock:
        if last is not None and last > self._playback_time:
            self._playback_time = last
        self._scheduler.drain_playback(self._playback_time)
        return self._playback_time


def run_with(monkeypatch, capsys, fault, seed=11):
    real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime
    made = []

    def create(self, *a, **kw):
        rt = real(self, *a, **kw)
        made.append(BrokenRuntime(rt, fault) if fault else rt)
        return made[-1]
    monkeypatch.setattr(siddhi_tpu.SiddhiManager,
                        "create_siddhi_app_runtime", create)
    if fault == "timers_ahead_of_the_send":
        monkeypatch.setattr(_runtime.SiddhiAppRuntime, "_playback_advance",
                            timers_ahead_of_the_send)
    try:
        rc = load_run_module().main([
            "--workload", CELL, "--seed", str(seed), "--seconds", "4.0",
            "--trace", "0", "--rehearse"])
    finally:
        AHEAD["on"] = False
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out, made[-1]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_the_rehearsal_of_the_cell_is_correct(monkeypatch, capsys, seed):
    rc, last, out, _rt = run_with(monkeypatch, capsys, None, seed)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0, \
        out[-1500:]
    assert last["attempted"] >= 15
    assert list(last["compared"]) == [
        "rows_missing", "rows_unexpected", "rows_differing",
        "values_over_tolerance", "stray_rows", "listener_errors",
        "sends_undelivered"]
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values())


def test_a_runtime_that_forgets_the_carried_window_is_not_correct(
        monkeypatch, capsys):
    rc, last, out, rt = run_with(monkeypatch, capsys,
                                 "forgets_the_carried_window")
    assert rc == 0 and rt.forgot
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    # its symbols start from nothing: rows that pass `having` are missing
    assert last["compared"]["rows_missing"]["value"] >= 1
    assert "OVER" in out


def test_timers_fired_ahead_of_the_sends_rows_are_not_correct(
        monkeypatch, capsys):
    """A6 (iii) put back: rows that arrive before an expiry see a window
    the expiry has already left — totals too small, and fewer rows over
    `having`."""
    rc, last, out, _rt = run_with(monkeypatch, capsys,
                                  "timers_ahead_of_the_send")
    assert rc == 0
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    c = last["compared"]
    assert c["rows_missing"]["value"] + c["rows_differing"]["value"] + \
        c["values_over_tolerance"]["value"] >= 1


def test_a_row_for_a_send_that_owes_none_is_not_correct(monkeypatch, capsys):
    rc, last, out, rt = run_with(monkeypatch, capsys,
                                 "row_for_a_zero_row_send")
    assert rc == 0 and rt.invented
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    assert last["compared"]["rows_unexpected"] == {"value": 1, "limit": 0}
    assert last["compared"]["sends_undelivered"]["value"] == 0

"""`timewindow_256sym` through its own app text: the sliding
`window.time(1 sec)` with `sum` / `count` / `avg` by symbol and a `having`
that bites, fed by `send_columns` under `@app:playback`, read by a batch
callback, against the plain per-event reference of
`benchmarks/configs/timewindow_256sym/model.py` (a deque and a float64 sum a
symbol, carried across sends), send by send in delivery order: sends across
the window's expiry, a gap longer than the window, sends that owe no rows,
Zipf keys.

And the scheduler under `@app:playback` (ROADMAP A12), one case a defect, each
failing on the parent of PR 51: a query holds ONE pending wake-up, a clock
that jumped runs ONE timer step and not one for every distinct expiry time,
and no timer fires ahead of the rows of its own send."""
import collections
import importlib.util
import json
import os

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import RECOMPILES

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CFG_DIR = os.path.join(ROOT, "benchmarks", "configs", "timewindow_256sym")
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "tw_paced_8k.json")) as _fh:
    TRAFFIC = json.load(_fh)
with open(os.path.join(CFG_DIR, "config.json")) as _fh:
    CONFIG = json.load(_fh)
SECTIONS = ("plain_chain", "window_fill", "window_state", "window_order",
            "agg_layout", "agg_scan", "project")
N_SENDS = 20            # two gaps behind the first send
EVERY = 8               # a gap every 8th send, as the traffic's rehearsal
# (sizes, traffic overrides): the configuration's own rehearsal sizes, and a
# ragged width (every send has invalid rows) at a rate that puts five sends
# in the window
SHAPES = {
    "rehearse": (CONFIG["rehearse_sizes"], TRAFFIC["rehearse"]),
    "e300": ({"window_rows": 8192, "having_total": "4000.0625"},
             {"events_per_send": 300, "rate_events_per_s": 1500,
              "gap_every_sends": EVERY}),
}
SEEDS = (11, 2 ** 31 + 7)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL = _load(os.path.join(CFG_DIR, "model.py"), "bench_model_timewindow_t1")
_SS = _load(os.path.join(HERE, "test_step_sections.py"), "_step_sections_tw")
# `profiler_session`: a capture's `siddhi:*` spans as dicts
_SPANS = _load(os.path.join(HERE, "test_spans.py"), "_spans_tw")


def app_text(sizes, statistics=False):
    with open(os.path.join(CFG_DIR, "app.siddhi")) as fh:
        text = fh.read().format(**sizes)
    return ("@app:statistics('BASIC')\n" if statistics else "") + text


class Driven:
    """The app deployed and subscribed; `send` returns the CURRENT rows the
    call delivered (blocking delivery: they are here when it returns)."""

    def __init__(self, sizes, statistics=False):
        self.manager = SiddhiManager()
        self.rt = self.manager.create_siddhi_app_runtime(
            app_text(sizes, statistics))
        self.errors, self.batches = [], []
        self.rt.set_exception_listener(self.errors.append)
        self.rt.add_batch_callback(CONFIG["query"], self._on_batch)
        self.rt.start()
        self.handler = self.rt.get_input_handler(CONFIG["stream"])

    def _on_batch(self, _ts, b):
        sel = b["valid"] & (b["kind"] == 0)
        self.batches.append({n: np.asarray(b["cols"][n])[sel]
                             for n in CONFIG["columns"]})

    def send(self, cols, ts):
        self.batches = []
        self.handler.send_columns([c.copy() for c in cols],
                                  timestamps=ts.copy())
        assert not self.errors, self.errors[:1]
        return {n: np.concatenate([b[n] for b in self.batches])
                if self.batches else np.zeros(0, MODEL_DTYPES[n])
                for n in CONFIG["columns"]}

    def dispatches(self):
        """Device steps of the query so far (statistics BASIC)."""
        return self.rt.stats.phases.snapshot()["queries"].get(
            CONFIG["query"], {}).get("dispatch_submit", {"count": 0})["count"]

    def close(self):
        self.manager.shutdown()


MODEL_DTYPES = {"symbol": np.int64, "total": np.float32, "n": np.int64,
                "ap": np.float32}


def make_sends(shape, seed, n_sends=N_SENDS):
    sizes, over = SHAPES[shape]
    traffic = dict(TRAFFIC, **over)
    plan = MODEL.plan(seed, traffic, sizes)
    clock, sends = 1000, []
    for i in range(n_sends):
        clock += MODEL.clock_step_ms(traffic)
        sends.append(MODEL.make_send(np.random.default_rng([seed, i]), i,
                                     traffic, plan, clock))
    return sizes, plan, sends


def drive(shape, seed, n_sends=N_SENDS):
    sizes, plan, sends = make_sends(shape, seed, n_sends)
    d = Driven(sizes)
    try:
        traces, rows, pending, facts = [], [], [], []
        for s in sends:
            rows.append(d.send(s["cols"], s["ts"]))
            traces.append(sum(o["count"]
                              for o in RECOMPILES.snapshot().values()))
            pending.append(d.rt.timers_pending())
            facts.append(d.rt.timer_facts())
    finally:
        d.close()
    return {"sends": sends, "rows": rows, "traces": traces,
            "pending": pending, "facts": facts,
            "refs": MODEL.reference(sends, plan)}


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(shape, seed):
        if (shape, seed) not in cache:
            cache[shape, seed] = drive(shape, seed)
        return cache[shape, seed]
    return get


ZERO = dict.fromkeys(MODEL.LIMITS, 0)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_send_delivers_the_reference_rows_in_arrival_order(
        shape, seed, runs):
    run = runs(shape, seed)
    for i, (got, want) in enumerate(zip(run["rows"], run["refs"])):
        assert MODEL.compare(got, want) == ZERO, (i, want["n"].shape)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_traffic_has_gaps_sends_that_owe_nothing_and_skewed_keys(
        shape, seed, runs):
    run = runs(shape, seed)
    owed = [MODEL.expected_rows(s) for s in run["sends"]]
    assert owed == [w["n"].shape[0] for w in run["refs"]]
    every = EVERY
    assert TRAFFIC["rehearse"]["gap_every_sends"] == EVERY
    for i, s in enumerate(run["sends"]):
        if i and i % every == 0:
            # a gap longer than the window: everything held expires by the
            # clock alone, and the send starts on an empty window
            assert s["ts"][0] - run["sends"][i - 1]["ts"][-1] == \
                TRAFFIC["gap_ms"] > MODEL.WINDOW_MS
            assert owed[i] == 0
        elif i:
            assert s["ts"][0] - run["sends"][i - 1]["ts"][-1] <= 2
    assert 0 < sum(n == 0 for n in owed) < len(owed)
    assert max(owed) < run["sends"][0]["events"] // 2      # a minority
    # rows expire INSIDE sends: a window's worth of sends lies between gaps
    span = run["sends"][every - 1]["ts"][-1] - run["sends"][1]["ts"][0]
    assert span > MODEL.WINDOW_MS
    # Zipf keys: the top rank takes several times an even share
    top = collections.Counter(
        np.concatenate([s["cols"][0] for s in run["sends"]]).tolist()
    ).most_common(1)[0][1]
    events = sum(s["events"] for s in run["sends"])
    assert top > 20 * events / int(TRAFFIC["symbols"])


@pytest.mark.parametrize("seed", SEEDS)
def test_nothing_compiles_after_the_first_gap(seed, runs):
    """Two shapes of the one step: the send's, and the timer's 8-row
    batch, first run at the first gap."""
    traces = runs("rehearse", seed)["traces"]
    first_gap = EVERY
    assert traces[first_gap] == traces[-1] > traces[0] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_at_bfloat16_fails_by_the_tolerance_alone(seed, runs):
    run = runs("rehearse", seed)
    worst = dict(ZERO)
    for want in run["refs"]:
        ctl = MODEL.compare(MODEL.control_rows(want), want)
        worst = {n: max(worst[n], ctl[n]) for n in worst}
    assert worst["values_over_tolerance"] > 0
    assert {n: v for n, v in worst.items()
            if n != "values_over_tolerance"} == {
        "rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def test_a_carried_window_forgotten_and_a_flipped_having_each_fail(runs):
    run = runs("rehearse", SEEDS[0])
    i = next(i for i, w in enumerate(run["refs"]) if w["n"].shape[0] > 8)
    want = run["refs"][i]
    assert MODEL.compare(want, want) == ZERO
    fewer = {n: a[:-1] for n, a in want.items()}
    assert MODEL.compare(fewer, want)["rows_missing"] == 1
    assert MODEL.compare(want, fewer)["rows_unexpected"] == 1
    off = dict(want, n=want["n"] + 1)
    assert MODEL.compare(off, want)["rows_differing"] == want["n"].shape[0]
    # one tick (1/8) on one total is over the tolerance; one ulp is not
    tick = dict(want, total=want["total"].copy())
    tick["total"][3] += np.float32(MODEL.TICK)
    assert MODEL.compare(tick, want)["values_over_tolerance"] == 1
    ulp = dict(want, total=np.nextafter(want["total"], np.float32(np.inf)))
    assert MODEL.compare(ulp, want) == ZERO


# -- the scheduler under @app:playback: A12's three defects -----------------------

def defect_one_wake_up_a_query(runs):
    """(i) `notify_at` pushed a wake-up for every step that left live rows:
    the heap grew by one a send, and every entry ran a device step."""
    run = runs("rehearse", SEEDS[0])
    assert max(run["pending"]) == 1
    armed = [f["wakeups_armed"] for f in run["facts"]]
    assert armed[-1] <= 2 * len(armed)


def defect_one_step_a_clock_advance(runs):
    """(ii) `drain_playback` ran one device step for every distinct expiry
    millisecond that was due: a gap of 1,500 ms over a window holding ~1,000
    distinct milliseconds cost as many steps."""
    sizes, _plan, sends = make_sends("rehearse", SEEDS[0],
                                     n_sends=EVERY
                                     + 1)
    d = Driven(sizes, statistics=True)
    try:
        per_send = []
        for s in sends:
            before = d.dispatches()
            d.send(s["cols"], s["ts"])
            per_send.append(d.dispatches() - before)
        facts = d.rt.timer_facts()
    finally:
        d.close()
    # a send is its own step and at most the one timer step its first
    # row's clock made due; the send after the gap is exactly those two
    assert max(per_send) == 2 and per_send[0] == 1 and per_send[-1] == 2
    assert facts["timer_steps"] == sum(per_send) - len(per_send)


def defect_no_timer_ahead_of_its_sends_rows(runs):
    """(iii) `_route_columns` drained the timers to the send's LAST
    timestamp before its rows were dispatched: rows that arrive before an
    expiry saw a window the expiry had already left (436 of 576 rows wrong
    after the first expiry, builder, PR 49).  Two sends of one symbol: the
    first at 1,000 ... 1,099 ms, the second across 1,900 ... 2,199 ms, so
    the first's rows expire between the second's own."""
    sizes = {"window_rows": 2048, "having_total": "0.0625"}
    n = 576
    one = (np.zeros(n, np.int64), np.full(n, 10.0, np.float32),
           np.ones(n, np.int64))
    ts1 = 1000 + np.arange(n, dtype=np.int64) * 100 // n
    ts2 = 1900 + np.arange(n, dtype=np.int64) * 300 // n
    window = MODEL.SlidingWindow(1, 0.0625)
    window.feed(ts1, one[0], one[1])
    want = window.feed(ts2, one[0], one[1])
    assert want["n"].min() < n < want["n"].max()    # it bites mid-send
    d = Driven(sizes)
    try:
        d.send(one, ts1)
        got = d.send(one, ts2)
    finally:
        d.close()
    assert MODEL.compare(got, want) == ZERO


DEFECTS = {"i_one_wake_up_a_query": defect_one_wake_up_a_query,
           "ii_one_step_a_clock_advance": defect_one_step_a_clock_advance,
           "iii_no_timer_ahead_of_its_sends_rows":
               defect_no_timer_ahead_of_its_sends_rows}


@pytest.mark.parametrize("defect", sorted(DEFECTS))
def test_a12s_scheduler_defect_is_repaired(defect, runs):
    DEFECTS[defect](runs)


def test_a_gap_expires_the_whole_window_by_one_timer_step_at_the_first_row(
        tmp_path):
    """The send after a gap: one `timer_drain` at its FIRST row's clock,
    one timer step in it, before the send's own dispatch; no row delivered
    by the timer (EXPIRED rows emit nothing under `insert into`)."""
    sizes, _plan, sends = make_sends("rehearse", SEEDS[1], n_sends=EVERY + 1)
    d = Driven(sizes)
    try:
        for s in sends[:-1]:
            d.send(s["cols"], s["ts"])
        before = d.rt.timer_facts()
        with _SPANS.profiler_session(tmp_path) as events:
            got = d.send(sends[-1]["cols"], sends[-1]["ts"])
        after = d.rt.timer_facts()
    finally:
        d.close()
    assert got["n"].shape[0] == 0
    assert after["timer_steps"] - before["timer_steps"] == 1
    assert after["pending"] == 1
    spans = events()
    (drain,) = [e for e in spans if e["name"] == "timer_drain"]
    assert (drain["clock"], drain["fired"]) == (int(sends[-1]["ts"][0]), 1)
    dispatches = sorted(e["start"] for e in spans if e["name"] == "dispatch")
    assert len(dispatches) == 2
    assert drain["start"] <= dispatches[0] <= drain["end"] < dispatches[1]
    # the group-slot feed says how many slots the allocator has bound
    bound = [e["bound"] for e in spans
             if e["name"] == "route_keys" and "bound" in e]
    assert bound and 0 < bound[0] <= int(TRAFFIC["symbols"])


# -- the step's sections -------------------------------------------------------------

def test_every_op_of_both_shapes_of_the_step_names_one_section():
    """The send's step and the timer's (the same `jit_plain_step` at the
    TIMER batch's 8 rows): every instruction that runs as an op and carries
    an `op_name` of the program names exactly one of PR 39's sections — the
    time window's under `window_fill` / `window_state` (since PR 52 it lays
    its emission out in order itself: `sort_rows`' `window_order` has left
    this program), the grouped aggregate's `sorted` layout under
    `agg_layout`, `having` under `project`."""
    sizes, _plan, sends = make_sends(
        "rehearse", SEEDS[0], n_sends=EVERY + 1)
    d = Driven(sizes)
    try:
        for s in sends:
            d.send(s["cols"], s["ts"])
        assert d.rt.explain(CONFIG["query"])["plan"]["selector_layout"] == \
            "sorted"
        texts = [fn.lower(*specs).compile().as_text()
                 for _role, fn, specs in d.rt.compiled_steps(CONFIG["query"])
                 if specs is not None and fn._siddhi_role == "plain_step"]
    finally:
        d.close()
    assert texts
    for text in texts:
        named, short = collections.Counter(), []
        for opcode, op_name in _SS.executed(text):
            parts = op_name.split(";")[0].split("/")
            if parts[0] != "jit(plain_step)":
                continue
            sections = [p for p in parts if p in SECTIONS]
            assert len(sections) <= 1, op_name
            if sections:
                named[sections[0]] += 1
            else:
                short.append((opcode, op_name))
        assert not short, short
        assert set(SECTIONS[1:]) - {"window_order"} == set(named), named

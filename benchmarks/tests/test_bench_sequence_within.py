"""`sequence_within`: `model.py` alone — its reference against a per-event
Python loop (one pending thread), `expected_rows` against the reference send
by send (the carried event, the feed's pause), `compare` on each fault and the
bfloat16 control, `least_bytes` from shapes, what its configuration and its
two traffic files state — and the whole of a run, sound and doctored
underneath: a row withheld, a row delivered for a send that owes none, a
program that ignores `within` across sends."""
import json
import os

import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader, numeric
from test_bench_doctored import load_run_module

PACED, SAT = "sequence_within.paced", "sequence_within.saturated"
BENCH = loader.load_benchmark()
ZERO = dict.fromkeys(("rows_missing", "rows_unexpected", "rows_differing"), 0)
WARM = 8                  # both traffics' rehearsal `warmup_sends`


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


def by_hand(sends, within=1000):
    """The query, an event at a time in plain Python: the pending thread is
    completed or killed by the next event, whatever send brings it; an
    event of volume 1 then seeds a new one."""
    pending, out = None, []
    for send in sends:
        _symbol, price, volume = send["cols"]
        rows = []
        for p, v, t in zip(price.tolist(), volume.tolist(),
                           send["ts"].tolist()):
            if pending is not None:
                if v == 2 and p > pending[0] and t - pending[1] <= within:
                    rows.append((pending[0], p))
                pending = None
            if v == 1:
                pending = (p, t)
        out.append(rows)
    return out


def pairs(rows):
    return list(zip(rows["p1"].tolist(), rows["p2"].tolist()))


# -- the model alone ------------------------------------------------------------

@pytest.mark.parametrize("cell,events", [(PACED, 64), (PACED, 1000),
                                         (SAT, 4096), (SAT, 1)])
def test_the_reference_is_the_per_event_loop(cell, events):
    cell = loader.resolve(cell)
    sends, plan = sends_of(cell, 5, 40, events_per_send=events)
    refs = cell.model.reference(sends, plan)
    hand = by_hand(sends)
    assert sum(map(len, hand)) > 0
    for send, ref, want in zip(sends, refs, hand):
        assert pairs(ref) == want
        assert cell.model.expected_rows(send) == len(want)
        assert ref["p1"].dtype == ref["p2"].dtype == np.float32


def test_a_send_is_new_in_every_column_and_knows_the_carried_event():
    cell = loader.resolve(PACED)
    m, t = cell.model, cell.traffic
    assert m.events_per_send(t) == 8192 and m.clock_step_ms(t) == 64
    assert (t["volume_mix"], t["pause_every_sends"], t["pause_ms"]) == \
        ([0.4, 0.4, 0.2], 8, 2000)
    sends, plan = sends_of(cell, 9, 40)
    volumes = np.concatenate([s["cols"][2] for s in sends])
    share = np.bincount(volumes, minlength=4)[1:] / volumes.size
    assert np.allclose(share, [0.4, 0.4, 0.2], atol=0.005)
    for i, s in enumerate(sends):
        sym, price, vol = s["cols"]
        assert (sym.dtype, price.dtype, vol.dtype, s["ts"].dtype) == \
            (np.int64, np.float32, np.int32, np.int64)
        assert 0 <= sym.min() and sym.max() <= 999
        assert 0 <= price.min() and price.max() < 1
        assert set(np.unique(vol).tolist()) == {1, 2, 3}
        # 1 ms per 128 events, non-decreasing
        assert np.array_equal(s["ts"] - s["ts"][0],
                              np.arange(8192) // 128)
        if i:
            gap = int(s["ts"][0]) - int(sends[i - 1]["ts"][-1])
            assert gap == (2000 if i % 8 == 0 else 1), i
            before = sends[i - 1]
            assert s["carried"] == (before["cols"][2][-1],
                                    before["cols"][1][-1],
                                    before["ts"][-1])
        else:
            assert s["carried"] is None
        # about 8 % of adjacent pairs: 655 a send
        assert 560 < m.expected_rows(s) < 750, (i, m.expected_rows(s))
    for a, b in zip(sends, sends[1:]):
        for ca, cb in zip(a["cols"], b["cols"]):
            assert not np.array_equal(ca, cb)


def test_a_thread_crosses_a_send_and_a_pause_expires_it():
    """Over seeded sends of ONE event each every pair is a crossing: one
    in eight crosses a pause and is owed nothing; a reference that forgets
    `within` there owes more."""
    cell = loader.resolve(PACED)
    sends, plan = sends_of(cell, 3, 400, events_per_send=1)
    refs = cell.model.reference(sends, plan)
    hand, forgetful = by_hand(sends), by_hand(sends, within=10 ** 9)
    assert [pairs(r) for r in refs] == hand
    crossed = sum(map(len, hand))
    assert crossed > 10
    assert sum(map(len, forgetful)) > crossed
    for i, (owed, loose) in enumerate(zip(hand, forgetful)):
        assert owed == loose or (i % 8 == 0 and owed == [])


def test_compare_catches_each_fault_and_the_control_fails():
    cell = loader.resolve(PACED)
    m = cell.model
    assert m.LIMITS == ZERO
    sends, plan = sends_of(cell, 2, 3)
    want = m.canonical(m.reference(sends, plan)[2])
    n = want["p1"].shape[0]
    assert n > 500 and m.compare(want, want) == ZERO
    keep = np.arange(n) != 17
    withheld = {k: a[keep] for k, a in want.items()}
    assert m.compare(withheld, want) == dict(ZERO, rows_missing=1)
    twice = {k: np.concatenate([a, a[17:18]]) for k, a in want.items()}
    assert m.compare(twice, want) == dict(ZERO, rows_unexpected=1)
    # delivery order is the guarantee: two rows swapped differ
    swapped = {k: a.copy() for k, a in want.items()}
    for a in swapped.values():
        a[[3, 4]] = a[[4, 3]]
    assert m.compare(swapped, want) == dict(ZERO, rows_differing=2)
    ulp = dict(want, p2=want["p2"].copy())
    ulp["p2"][5] = np.nextafter(ulp["p2"][5], np.float32(2))
    assert m.compare(ulp, want) == dict(ZERO, rows_differing=1)
    # a send that owes none and gets one
    none = {k: a[:0] for k, a in want.items()}
    one = {k: a[:1] for k, a in want.items()}
    assert m.compare(one, none) == dict(ZERO, rows_unexpected=1)
    # the control: both prices through bfloat16 differ on nearly every row
    ctl = m.compare(m.canonical(m.control_rows(want)), want)
    assert ctl["rows_missing"] == ctl["rows_unexpected"] == 0
    assert ctl["rows_differing"] > 0.95 * n
    assert np.array_equal(m.control_rows(want)["p1"],
                          numeric.to_bf16(want["p1"]))


def test_least_bytes_from_shapes():
    paced, sat = loader.resolve(PACED), loader.resolve(SAT)
    # events of 24 B in, 8 % of them rows of 16 B out, the 8-slot slab of
    # 69 B slots read and written
    assert paced.model.least_bytes(paced.traffic, paced.sizes, paced.config) \
        == 8192 * 24 + 655 * 16 + 2 * 8 * 69 == 208192
    assert sat.model.least_bytes(sat.traffic, sat.sizes, sat.config) \
        == 131072 * 24 + 10485 * 16 + 2 * 8 * 69 == 3314592


def test_config_and_traffic_state_what_the_contract_asks():
    paced, sat = loader.resolve(PACED), loader.resolve(SAT)
    cfg = paced.config
    assert sat.config == cfg
    for key in ("source", "deployment", "assumed", "guarantees",
                "tolerance", "reduced_why", "scale_from"):
        assert cfg[key]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert "configs`[3]" in cfg["source"] and "bench.py" in cfg["source"] \
        and "SEQUENCE_QL" in cfg["source"]
    assert cfg["sizes"] == {"emit_rows": 65536}
    assert cfg["rehearse_sizes"] == {"emit_rows": 4096}
    assert (cfg["stream"], cfg["query"], cfg["columns"]) == \
        ("S", "q", ["p1", "p2"])
    for text in (cfg["deployment"], cfg["reduced_why"], cfg["scale_from"]):
        assert "by nature" in text and "stream" in text
    assert any("n_dropped" in g for g in cfg["guarantees"])
    assert any("carried" in g for g in cfg["guarantees"])
    assert any("0.4, 0.4, 0.2" in a for a in cfg["assumed"])
    assert any(a.startswith("the feed pause") for a in cfg["assumed"])
    # the corpus text, but for the emission cap
    from siddhi_tpu.analysis import corpus
    want = corpus.SEQUENCE_QL.format(ann="").strip() \
        .replace("rows='4096'", "rows='65536'")
    assert paced.app_text.strip() == want
    assert (paced.chips, sat.chips) == (1, 1)
    assert (paced.traffic["loop"], sat.traffic["loop"]) == ("open", "closed")
    assert (paced.traffic["events_per_send"],
            sat.traffic["events_per_send"]) == (8192, 131072)
    for t in (paced.traffic, sat.traffic):
        assert (t["volume_mix"], t["pause_every_sends"], t["pause_ms"],
                t["symbols"]) == ([0.4, 0.4, 0.2], 8, 2000, 1000)
        assert "prefill" not in t and t["drain_limit_s"] == 60
        assert t["who"] and t["what"] and t["rehearse"]
    assert (paced.traffic["warmup_sends"], paced.traffic["trace_sends"]) == \
        (64, 60)
    assert (sat.traffic["warmup_sends"], sat.traffic["trace_sends"]) == \
        (16, 8)
    assert paced.traffic["rate_why"] and sat.traffic["prepare_why"]
    check_the_tables_configuration_and_what_its_cells_report(BENCH)
    # the model imports nothing of the program
    with open(os.path.join(loader.BENCH_DIR, "configs", cfg["name"],
                           "model.py")) as fh:
        assert "siddhi_tpu" not in fh.read().split('"""', 2)[2]


def check_the_tables_configuration_and_what_its_cells_report(bench):
    paced, sat = loader.resolve(PACED), loader.resolve(SAT)
    cfg = paced.config
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert entry["file"] == "benchmarks/configs/sequence_within/config.json"
    assert {e["name"] for e in paced.end_to_end} == {
        "latency_p50_ms", "setup_s"}
    assert {e["name"] for e in sat.end_to_end} == {
        "events_per_s", "latency_p50_ms", "setup_s"}


def check_the_three_entries_and_the_lists_the_cells_joined(bench):
    """One-sided (PR 50): the three entries stay, together and in order,
    behind the join's; their lists are held as PREFIXES — a later PR may
    add a cell behind these two, or an entry behind these three."""
    names = [e["name"] for e in bench["per_layer"]]
    three = ["step_roofline.seq", "scan_ticks_per_send.seq",
             "layout_cells_per_event.seq"]
    at = names.index(three[0])
    assert names[at:at + 3] == three
    assert 86 <= at and len(names) <= 128
    by_name = {e["name"]: e for e in bench["per_layer"]}
    assert by_name["step_roofline.seq"]["workloads"][:1] == [PACED]
    for n in three[1:]:
        assert by_name[n]["workloads"][:2] == [PACED, SAT]
        assert by_name[n]["moves"] == "latency_p50_ms"
    # what the two cells read, among the entries that stood with them
    mine = {cell: {n for n in names[:at + 3]
                   if cell in by_name[n]["workloads"]}
            for cell in (PACED, SAT)}
    # the branch opens no `obs_feed` span; `_jit_sequential` does put the
    # block program under a `rect_1x<B>`, so the rectangle's reader reads it
    for n in ("obs_feed_ms_per_send", "obs_feed_idle_ms_per_send"):
        assert n + ".paced" not in mine[PACED]
    assert "hot_tier_busy_ms_per_send.paced" in mine[PACED]
    assert not any(n.startswith(("plain_", "join_", "obs_feed"))
                   for n in mine[SAT])
    for base in ("step_event_load", "step_state_load", "step_scan",
                 "step_state_store", "step_compact", "step_unscoped"):
        assert base + "_ms_per_send.paced" in mine[PACED]
        assert base + "_ms_per_send.sat" in mine[SAT]
    assert "step_roofline" in mine[SAT] and \
        "route_keys_ms_per_send.sat" in mine[SAT]
    for cell in (PACED, SAT):
        assert {"state_bytes", "peak_hbm_bytes", "compile_s"} <= mine[cell]
    assert "compiles_in_window" in mine[PACED]
    assert SAT in next(e for e in bench["end_to_end"]
                       if e["name"] == "events_per_s")["workloads"]


def test_the_three_entries_and_the_lists_the_cells_joined():
    check_the_three_entries_and_the_lists_the_cells_joined(BENCH)


# -- the whole of a run, sound and doctored underneath --------------------------

class BrokenHandler:
    def __init__(self, owner, handler):
        self.owner, self.handler = owner, handler

    def send_columns(self, cols, timestamps=None):
        o = self.owner
        o.calls += 1
        timed = o.calls > WARM
        before = o.deliveries
        if o.fault == "ignores_within_across_sends" and o.last is not None:
            # a program that keeps its pending thread through a feed pause:
            # where the event before the pause and the first one after it
            # would match but for `within`, it delivers that row too
            v1, p1, t1 = o.last
            if timed and v1 == 1 and cols[2][0] == 2 and cols[1][0] > p1 \
                    and timestamps[0] - t1 > 1000:
                o.forgot += 1
                o.callback(timestamps, {
                    "valid": np.ones(1, bool),
                    "kind": np.zeros(1, np.int32),
                    "cols": {"p1": np.array([p1], np.float32),
                             "p2": cols[1][:1].copy()}})
        o.last = (cols[2][-1], cols[1][-1], timestamps[-1])
        self.handler.send_columns(cols, timestamps=timestamps)
        if o.fault == "row_for_a_zero_row_send" and timed \
                and o.deliveries == before and not o.invented:
            o.invented = True        # nothing came of this send: make a row
            o.callback(timestamps, {
                "valid": np.ones(1, bool), "kind": np.zeros(1, np.int32),
                "cols": {"p1": np.array([0.25], np.float32),
                         "p2": np.array([0.5], np.float32)}})


class BrokenRuntime:
    """The real runtime with one fault between it and its user."""

    def __init__(self, rt, fault):
        self._rt, self.fault = rt, fault
        self.calls = self.deliveries = self.forgot = 0
        self.invented = self.withheld = False
        self.callback = self.last = None

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def get_input_handler(self, stream):
        return BrokenHandler(self, self._rt.get_input_handler(stream))

    def add_batch_callback(self, query, cb):
        def doctored(ts, b):
            current = np.nonzero(b["valid"] & (b["kind"] == 0))[0]
            if current.size:
                self.deliveries += 1
            if self.fault == "withhold_a_row" and self.calls > WARM + 3 \
                    and current.size and not self.withheld:
                self.withheld = True
                valid = np.array(b["valid"])
                valid[current[current.size // 2]] = False
                b = dict(b, valid=valid)
            cb(ts, b)
        self.callback = cb
        self._rt.add_batch_callback(query, doctored)


def run_with(monkeypatch, capsys, cell, fault, seed=11, **traffic):
    real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime
    assert loader.resolve(cell, rehearse=True).traffic["warmup_sends"] \
        == WARM                      # BrokenRuntime spares the warm-up
    made = []

    def create(self, *a, **kw):
        rt = real(self, *a, **kw)
        made.append(BrokenRuntime(rt, fault) if fault else rt)
        return made[-1]
    monkeypatch.setattr(siddhi_tpu.SiddhiManager,
                        "create_siddhi_app_runtime", create)
    if traffic:
        real_resolve = loader.resolve

        def resolve(name, rehearse=False):
            c = real_resolve(name, rehearse)
            c.traffic.update(traffic)
            return c
        monkeypatch.setattr(loader, "resolve", resolve)
    rc = load_run_module().main(["--workload", cell, "--seed", str(seed),
                                 "--seconds", "1.0", "--trace", "0",
                                 "--rehearse"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out, made[-1]


@pytest.mark.parametrize("cell", [PACED, SAT])
def test_the_rehearsal_of_the_cell_is_correct(monkeypatch, capsys, cell):
    rc, last, out, _rt = run_with(monkeypatch, capsys, cell, None)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0, \
        out[-1500:]
    assert last["attempted"] >= 15
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values())


@pytest.mark.parametrize("cell", [PACED, SAT])
def test_a_withheld_row_is_not_correct(monkeypatch, capsys, cell):
    rc, last, out, rt = run_with(monkeypatch, capsys, cell, "withhold_a_row")
    assert rc == 0 and rt.withheld
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    assert last["compared"]["rows_missing"] == {"value": 1, "limit": 0}
    assert last["compared"]["sends_undelivered"]["value"] >= 1
    assert "OVER" in out


def test_a_row_for_a_send_that_owes_none_is_not_correct(monkeypatch, capsys):
    """One event a send: most sends owe nothing and are done when their
    call returns; a row that then comes for one is unexpected there."""
    rc, last, out, rt = run_with(monkeypatch, capsys, SAT,
                                 "row_for_a_zero_row_send",
                                 events_per_send=1)
    assert rc == 0 and rt.invented
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    assert last["compared"]["rows_unexpected"] == {"value": 1, "limit": 0}
    assert last["compared"]["sends_undelivered"]["value"] == 0


def test_a_program_that_ignores_within_across_sends_is_not_correct(
        monkeypatch, capsys):
    """One event a send, a pause every second send: a program that keeps a
    thread through the pause delivers a row the reference does not owe."""
    rc, last, out, rt = run_with(monkeypatch, capsys, SAT,
                                 "ignores_within_across_sends",
                                 events_per_send=1, pause_every_sends=2)
    assert rc == 0 and rt.forgot >= 1
    assert last["correct"] is False and last["failed"] >= rt.forgot, \
        out[-1500:]
    unexpected = last["compared"]["rows_unexpected"]
    assert unexpected["value"] >= 1 and unexpected["limit"] == 0


def test_the_same_one_event_sends_undoctored_are_correct(monkeypatch, capsys):
    rc, last, out, _rt = run_with(monkeypatch, capsys, SAT, None,
                                  events_per_send=1, pause_every_sends=2)
    assert rc == 0 and last["correct"] is True, out[-1500:]
    assert last["attempted"] >= 100

"""`nexmark_q8`: `model.py` alone — its reference against a brute-force
O(n^2) join on 2,000 rows, `expected_rows` (the generator's own arithmetic)
against the reference send by send, what the generator makes, `compare` and
the float32 control, `least_bytes` against the bytes the lowered step
programs say at rehearse size, what its configuration and its traffic file
state — the table's entries, held ONE-SIDED (`check_*(bench)` / `check_*(cell,
done)`, which `test_bench_adding_pr.py` runs on the scratch adding PR) — and
the whole of a run, sound and doctored underneath: a row withheld."""
import json
import os

import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader
from test_bench_doctored import load_run_module

CELL = "nexmark_q8.saturated"
BENCH = loader.load_benchmark()
WARM = 20 + 4             # the traffic's rehearsal prefill and warm-up
# the entries PR 57 appended, in their order
APPENDED = [
    "step_roofline.q8", "join_index_ms_per_send.q8",
    "window_rows_resident.q8", "window_dropped_rows.q8",
    "join_probe_depth.q8", "rows_per_event.q8"]
# the lists the cell joined
JOINED = [
    "send_to_delivery_ms_per_send.sat", "subscriber_ms_per_send.sat",
    "after_delivery_ms_per_send.sat", "stage_ms_per_send.sat",
    "route_keys_ms_per_send.sat", "obs_feed_ms_per_send.sat",
    "h2d_ms_per_send.sat", "dispatch_ms_per_send.sat",
    "fetch_ms_per_send.sat", "demux_ms_per_send.sat", "sink_ms_per_send.sat",
    "device_busy_ms_per_send.sat", "device_idle_pct.sat",
    "dispatches_per_send.sat", "fetches_per_send.sat",
    "fetch_bytes_per_send.sat", "idle_pre_dispatch_ms_per_send.sat",
    "idle_post_step_ms_per_send.sat", "join_window_ms_per_send.sat",
    "join_probe_ms_per_send.sat", "join_pairs_ms_per_send.sat",
    "join_compact_ms_per_send.sat", "join_unscoped_ms_per_send.sat",
    "ops_sort_ms_per_send.sat", "ops_gather_ms_per_send.sat",
    "ops_scatter_ms_per_send.sat", "send_unspanned_ms_per_send.sat",
    "obs_feed_idle_ms_per_send.sat", "page_faults_per_send.sat",
    "state_bytes", "peak_hbm_bytes", "compile_s"]


def sends_of(cell, seed, n, sizes=None, **traffic):
    """The first `n` sends of the cell's traffic, as the harness makes
    them, and the plan they were made with."""
    m, t = cell.model, dict(cell.traffic, **traffic)
    plan, sends = m.plan(seed, t, sizes or cell.sizes), []
    for i in range(n):
        sends.append(m.make_send(np.random.default_rng([seed, i]), i, t,
                                 plan, 1000 + i))
    return sends, plan


def zero(model):
    return dict.fromkeys(model.LIMITS, 0)


# -- the model alone ------------------------------------------------------------

# (active people, rows a send, sends, ms a row): a window of ~250 persons with
# 600 active — resident, expired and never-seen sellers; and 40 active people
# in 16-row sends — ids named ahead of time (the lead) a real share
SHAPES = {"expiry": (600, 64, 40, 3600), "lead": (40, 16, 130, 14000)}


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 5])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_reference_is_the_brute_force_join(shape, seed):
    """2,000 rows and more through both, send by send, and the generator's
    own arithmetic (`expected_rows`) beside them."""
    active, rows, n, ms = SHAPES[shape]
    cell = loader.resolve(CELL, rehearse=True)
    m = cell.model
    sends, plan = sends_of(
        cell, seed, n, sizes=dict(cell.sizes, active_people=active),
        rows_per_send=rows, first_event=active * 50, stamp_us=ms * 1000)
    assert sum(s["events"] for s in sends) >= 2000
    refs, brute = m.reference(sends, plan), \
        m.brute_force(sends, plan["window_ms"])
    total = at_person = 0
    for send, ref, want in zip(sends, refs, brute):
        assert m.compare(m.canonical(ref), m.canonical(want)) == zero(m)
        assert m.expected_rows(send) == want["id"].shape[0]
        assert [ref[n].dtype for n in ("id", "name", "reserve")] == \
            [np.int64, np.int32, np.int64]
        total += want["id"].shape[0]
        at_person += want["id"].shape[0] * (send["stream"] == "Person")
    auctions = sum(s["events"] for s in sends if s["stream"] == "Auction")
    if shape == "expiry":
        # some sellers resident, some expired or never seen
        assert 0.3 * auctions < total < 0.95 * auctions
    else:
        assert at_person >= 5, at_person


def test_the_generator_is_the_sources_as_recalled():
    cell = loader.resolve(CELL)
    m, t = cell.model, cell.traffic
    assert m.events_per_send(t) == 32768 and t["stamp_us"] == 1250
    assert (t["loop"], t["first_event"], t["warmup_sends"],
            t["trace_sends"]) == ("closed", 540000000, 8, 8)
    assert t["prefill"]["sends"] * t["prefill"]["rows_per_send"] >= 34560000
    cell = loader.resolve(CELL, rehearse=True)
    sends, plan = sends_of(cell, 9, 16)
    assert [s["stream"] for s in sends[:8]] == \
        ["Person", "Auction", "Auction", "Auction"] * 2
    p, a = sends[0], sends[1]
    assert [c.dtype for c in p["cols"]] == \
        [np.int64] + [np.int32] * 5 + [np.int64]
    assert [c.dtype for c in a["cols"]] == \
        [np.int64, np.int32, np.int32] + [np.int64] * 6
    # person ids run on from the generator's first person; stamps 0.9 s a
    # row at rehearse size, never stepping back, across sends too
    first = 62500 // 50
    assert np.array_equal(p["cols"][0], 1000 + first + np.arange(256))
    ts = np.concatenate([s["ts"] for s in sends])
    assert (np.diff(ts) >= 0).all() and ts[1] - ts[0] == 900
    # three auctions an epoch, ids in order; 3 of 4 name the hot seller
    ids = np.concatenate([s["cols"][0] for s in sends[1:4]])
    assert np.array_equal(ids, 1000 + first * 3 + np.arange(768))
    seller = np.concatenate([s["cols"][7] for s in sends if
                             s["stream"] == "Auction"]) - 1000
    epoch = np.concatenate([
        plan["first_epoch"] + (k // 4) * 256 + ((k % 4 - 1) * 256 +
                                                np.arange(256)) // 3
        for k in range(16) if k % 4])
    hot = seller == epoch // 100 * 100
    assert 0.72 < hot.mean() < 0.79
    cold = seller[~hot]
    assert cold.min() >= epoch.min() + 1 - 1250 and \
        (cold <= epoch[~hot] + 10).all()
    # reserve = initialBid + a price; prices are 10 ** (6 u) dollars
    assert (a["cols"][4] > a["cols"][3]).all() and a["cols"][3].min() >= 100
    assert a["cols"][3].max() <= 100_000_000


def test_compare_catches_each_fault_and_the_control_fails():
    cell = loader.resolve(CELL, rehearse=True)
    m = cell.model
    sends, plan = sends_of(cell, 3, 12)
    want = m.canonical(m.reference(sends, plan)[-1])
    n = want["id"].shape[0]
    assert n > 150 and m.compare(want, want) == zero(m)
    less = {k: a[1:] for k, a in want.items()}
    assert m.compare(less, want) == dict(zero(m), rows_missing=1)
    assert m.compare(want, less) == dict(zero(m), rows_unexpected=1)
    other = dict(want, name=want["name"].copy())
    other["name"][0] += 1
    assert m.compare(m.canonical(other), want)["rows_differing"] >= 1
    ctl = m.compare(m.canonical(m.control_rows(want)), want)
    assert ctl["rows_differing"] > 0.1 * n and ctl["rows_missing"] == 0


def test_least_bytes_is_below_what_the_lowered_programs_say():
    """From shapes at full size; and at rehearse size below the bytes XLA's
    cost analysis books for the two side programs (a true lower bound: the
    roofline share cannot pass 100 %)."""
    cell = loader.resolve(CELL)
    m = cell.model
    n = 32768
    # a person row 44 B, an auction row 72 B: 65 B a row over a round, in
    # and once more into its ring; a head word a row; 0.71 rows owed a row:
    # 24 B read for each (key, stamp, payload), 28 B out
    assert (m.PERSON_BYTES, m.AUCTION_BYTES, m.ROW_BYTES) == (44, 72, 28)
    assert m.least_bytes(cell.traffic, cell.sizes, cell.config) == \
        int(2 * 65 * n + 4 * n + 0.71 * n * (24 + 28)) == 5600706
    small = loader.resolve(CELL, rehearse=True)
    from siddhi_tpu import SiddhiManager
    mgr = SiddhiManager()
    rt = mgr.create_siddhi_app_runtime(small.app_text)
    rt.add_batch_callback("q8", lambda ts, b: None)
    rt.start()
    try:
        sends, _plan = sends_of(small, 1, 8)
        for s in sends:
            rt.get_input_handler(s["stream"]).send_columns(
                s["cols"], timestamps=s["ts"])
        booked = []
        for role, fn, spec in rt.compiled_steps("q8"):
            if spec is not None:
                cost = fn.lower(*spec).compile().cost_analysis()
                cost = cost[0] if isinstance(cost, list) else cost
                booked.append(cost["bytes accessed"])
        assert len(booked) >= 2
        # a round is one person program and three auction programs
        per_send = (max(booked) + 3 * min(booked)) / 4
        assert small.model.least_bytes(
            small.traffic, small.sizes, small.config) <= per_send
    finally:
        mgr.shutdown()


def test_config_and_traffic_state_what_the_contract_asks():
    cell = loader.resolve(CELL)
    cfg, t = cell.config, cell.traffic
    for key in ("source", "deployment", "assumed", "guarantees",
                "sizes_why", "reduced_why", "scale_from", "tolerance"):
        assert cfg[key]
    assert cfg["reduced"] in ([], ["window_hours"])
    assert len(cfg["source"]) <= 200 and "NEXmark" in cfg["source"] and \
        "Beam" in cfg["source"]
    assert (cfg["stream"], cfg["streams"], cfg["query"], cfg["columns"]) == \
        ("Person", ["Person", "Auction"], "q8", ["id", "name", "reserve"])
    said = " ".join(cfg["assumed"])
    for word in ("numActivePeople", "PERSON_ID_LEAD", "HOT_SELLER_RATIO",
                 "first_event", "`extra`", "1.25 ms", "32,768"):
        assert word in said, word
    assert any("no row dropped for capacity" in g for g in cfg["guarantees"])
    app = cell.app_text
    assert "@app:playback" in app and "window.time(12 hours)" in app
    assert "on P.id == A.seller" in app and \
        "select P.id as id, P.name as name, A.reserve as reserve" in app
    assert cell.chips == 1 and t["drain_limit_s"] <= 30
    assert t["who"] and t["what"] and t["warmup_why"] and t["prepare_why"]
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
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == "benchmarks/configs/nexmark_q8/config.json"
    w = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("nexmark_q8", "q8_saturated_replay", 1)
    assert len(w["why"]) <= 200
    assert {e["name"] for e in cell.end_to_end} == {
        "events_per_s", "latency_p50_ms", "setup_s"}


def check_the_appended_entries_and_the_lists_the_cell_joined(bench):
    """The six entries stay, together and in order, behind PR 55's 113; the
    cell stands FIRST in their lists (a later cell may join behind it) and
    is a member of the lists it joined (a later PR may lengthen them)."""
    names = [e["name"] for e in bench["per_layer"]]
    at = names.index(APPENDED[0])
    assert names[at:at + len(APPENDED)] == APPENDED
    assert 113 <= at and len(names) <= 128
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for n in APPENDED:
        e = by_name[n]
        assert e["workloads"][:1] == [CELL] and e["moves"] == "events_per_s"
    assert [by_name[n]["source"] for n in APPENDED] == \
        ["device_trace"] * 2 + ["program_span"] * 3 + ["host_clock"]
    assert [by_name[n]["layer"] for n in APPENDED] == \
        ["device step"] * 2 + ["state"] * 2 + ["device step", "emission"]
    for n in JOINED:
        assert CELL in by_name[n]["workloads"], n
    # a lane table's fill says nothing of a chain: the cell is not there
    assert CELL not in by_name["join_lane_fill_pct.sat"]["workloads"]
    e2e = {e["name"]: e for e in bench["end_to_end"]}
    assert CELL in e2e["events_per_s"]["workloads"]
    # what the cell reads, among the entries that stood when it was added
    mine = {n for n in names[:at + len(APPENDED)]
            if CELL in by_name[n]["workloads"]}
    assert mine == set(APPENDED) | set(JOINED)
    # every reader the loader resolves for it is a file
    assert [e["name"] for e, _ in loader.resolve(CELL).per_layer
            if e["name"] in mine] == [n for n in names if n in mine]


def test_the_appended_entries_and_the_lists_the_cell_joined():
    check_the_appended_entries_and_the_lists_the_cell_joined(BENCH)


def check_a_rehearsal_of_the_cell_reads_its_windows(cell, done):
    """What a RUN of this cell gives, held of this cell alone: traced, the
    readers of the windows' span args find them."""
    if cell != CELL:
        return
    if done.trace:
        withheld = done.out.split("REHEARSAL metrics computed and "
                                  "withheld: ")[1].splitlines()[0]
        for name in ("window_rows_resident.q8", "window_dropped_rows.q8",
                     "join_probe_depth.q8", "rows_per_event.q8",
                     "join_index_ms_per_send.q8"):
            assert name in withheld, name
        assert "join windows over the slice:" in done.out


# -- the whole of a run, sound and doctored underneath --------------------------

class BrokenRuntime:
    """The real runtime with one fault between it and its user."""

    def __init__(self, rt, fault):
        self._rt, self.fault = rt, fault
        self.deliveries = 0
        self.withheld = False

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def add_batch_callback(self, query, cb):
        def doctored(ts, b):
            self.deliveries += 1
            if self.fault == "a_row_withheld" and self.deliveries > WARM \
                    and not self.withheld and b["valid"].any():
                b = dict(b, valid=b["valid"].copy())
                b["valid"][np.nonzero(b["valid"])[0][0]] = False
                self.withheld = True
            cb(ts, b)
        self._rt.add_batch_callback(query, doctored)


def run_with(monkeypatch, capsys, fault, seed=11, trace=0):
    real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime
    made = []

    def create(self, *a, **kw):
        rt = real(self, *a, **kw)
        made.append(BrokenRuntime(rt, fault) if fault else rt)
        return made[-1]
    monkeypatch.setattr(siddhi_tpu.SiddhiManager,
                        "create_siddhi_app_runtime", create)
    rc = load_run_module().main([
        "--workload", CELL, "--seed", str(seed), "--seconds", "1.5",
        "--trace", str(trace), "--rehearse", "--control", "1"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out, made[-1]


@pytest.mark.parametrize("seed,trace", [(11, 1), (2 ** 31 + 7, 0)])
def test_the_rehearsal_of_the_cell_is_correct(monkeypatch, capsys, seed,
                                              trace):
    rc, last, out, _rt = run_with(monkeypatch, capsys, None, seed, trace)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0, \
        out[-1500:]
    assert last["attempted"] >= 4
    assert list(last["compared"]) == [
        "rows_missing", "rows_unexpected", "rows_differing", "stray_rows",
        "listener_errors", "sends_undelivered"]
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values())
    assert "control rows_differing" in out and "FAILS" in out
    assert "compiles in window 0" in out
    if trace:
        # every new reader returned a number (withheld in a rehearsal)
        withheld = out.split("REHEARSAL metrics computed and withheld: ")[
            1].splitlines()[0]
        for name in APPENDED:
            if name != "step_roofline.q8":     # no peaks in a rehearsal: None
                assert name in withheld, name


def test_a_withheld_row_is_not_correct(monkeypatch, capsys):
    rc, last, out, rt = run_with(monkeypatch, capsys, "a_row_withheld")
    assert rc == 0 and rt.withheld
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    assert last["compared"]["rows_missing"] == {"value": 1, "limit": 0}

"""`kleene_1m`: `model.py` alone — its reference against a per-event Python
loop written HERE (a key's threads as two plain lists), `expected_rows` against
the reference send by send, the generator's episodes and its valve, `compare`
on each fault and the bfloat16 control, `least_bytes` from shapes, what its
configuration and its traffic file state — the table's entries, held ONE-SIDED
(`check_*(bench)` / `check_*(cell, done)`, which `test_bench_adding_pr.py` runs
on the scratch adding PR) — and the whole of a run, sound and doctored
underneath: a row withheld, a runtime that forgets the threads it carried."""
import json
import os

import jax
import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader, numeric
from test_bench_doctored import load_run_module

CELL = "kleene_1m.saturated"
BENCH = loader.load_benchmark()
WARM = 16                 # the traffic's rehearsal `warmup_sends`
# the entries PR 55 appended, in their order
APPENDED = [
    "step_roofline.kleene", "nfa_fork_ms_per_send.kleene",
    "nfa_capture_ms_per_send.kleene", "rows_per_event.kleene",
    "emit_fill_pct.kleene"]
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
    "idle_post_step_ms_per_send.sat", "step_event_load_ms_per_send.sat",
    "step_state_load_ms_per_send.sat", "step_scan_ms_per_send.sat",
    "step_state_store_ms_per_send.sat", "step_compact_ms_per_send.sat",
    "step_unscoped_ms_per_send.sat", "ops_sort_ms_per_send.sat",
    "ops_gather_ms_per_send.sat", "ops_scatter_ms_per_send.sat",
    "send_unspanned_ms_per_send.sat", "obs_feed_idle_ms_per_send.sat",
    "page_faults_per_send.sat", "state_bytes", "peak_hbm_bytes", "compile_s",
    "compiles_in_window"]


def zero(model):
    return dict.fromkeys(model.LIMITS, 0)


def sends_of(cell, seed, n, sizes=None, **traffic):
    """The first `n` sends of the cell's traffic, as the harness makes
    them, and the plan they were made with."""
    m, t = cell.model, dict(cell.traffic, **traffic)
    plan, clock, sends = m.plan(seed, t, sizes or cell.sizes), 1000, []
    for i in range(n):
        clock += m.clock_step_ms(t)
        sends.append(m.make_send(np.random.default_rng([seed, i]), i, t,
                                 plan, clock))
    return sends, plan


def by_hand(sends):
    """The query as `config.json`'s `assumed` states it, an event at a time
    in plain Python: per key a list of collectors [A, Bs] and a list of
    waiting prefixes (A, first B, last B).  The rows of each send."""
    collectors, waiting, out = {}, {}, []
    for send in sends:
        rows = []
        for k, p, v in zip(*(c.tolist() for c in send["cols"])):
            p = np.float32(p)
            mine, wait = collectors.setdefault(k, []), waiting.setdefault(k, [])
            if v == 1:
                mine.append([p, []])
            elif v == 2:
                for c in list(mine):
                    if p >= c[0]:
                        c[1].append(p)
                        wait.append((c[0], c[1][0], p))
                        if len(c[1]) == 5:
                            mine.remove(c)
            elif v == 3:
                rows += [(k, a, b0, bl, p) for a, b0, bl in wait]
                wait.clear()
        out.append(sorted(rows))
    return out


def tuples(rows):
    return sorted(zip(*(rows[n].tolist() for n in ("k", "p1", "b0", "bl",
                                                   "p3"))))


# -- the model alone ------------------------------------------------------------

@pytest.mark.parametrize("seed", [5, 2 ** 31 + 5])
def test_the_reference_is_the_per_event_loop(seed):
    cell = loader.resolve(CELL, rehearse=True)
    sends, plan = sends_of(cell, seed, 48)
    refs = cell.model.reference(sends, plan)
    hand = by_hand(sends)
    assert sum(map(len, hand)) > 10000
    for send, ref, want in zip(sends, refs, hand):
        assert tuples(ref) == want
        assert cell.model.expected_rows(send) == len(want)
        assert [ref[n].dtype for n in ("k", "p1", "b0", "bl", "p3")] == \
            [np.int64] + [np.float32] * 4
    assert plan["peaks"]["threads"] >= 16 and plan["peaks"]["rows"] >= 12


def test_a_key_walks_its_episodes_four_events_a_visit():
    cell = loader.resolve(CELL)
    m, t = cell.model, cell.traffic
    assert m.events_per_send(t) == 524288 and m.clock_step_ms(t) == 10
    assert (t["keys_per_send"], t["b_max"], t["pass_probability"],
            t["warmup_sends"], t["loop"], t["trace_sends"]) == \
        (131072, 7, 0.75, 64, "closed", 8)
    cell = loader.resolve(CELL, rehearse=True)
    sends, _plan = sends_of(cell, 9, 64)
    first = sends[0]
    keys, price, vol = first["cols"]
    assert (keys.dtype, price.dtype, vol.dtype, first["ts"].dtype) == \
        (np.int64, np.float32, np.int32, np.int64)
    assert np.array_equal(keys, np.repeat(np.arange(128), 4))
    assert np.array_equal(first["ts"], 1010 + np.tile(np.arange(4), 128))
    assert np.array_equal(sends[9]["cols"][0], np.repeat(
        np.arange(128, 256), 4))
    # key 0's events over its eight visits: whole episodes A, n x B, C
    v0 = np.concatenate([s["cols"][2][:4] for s in sends[::8]])
    p0 = np.concatenate([s["cols"][1][:4] for s in sends[::8]])
    assert v0[0] == 1
    text = "".join("ABC"[v - 1] for v in v0)
    episodes = text.split("C")[:-1]
    assert episodes and all(e[0] == "A" and set(e[1:]) == {"B"} and
                            1 <= len(e) - 1 <= 7 for e in episodes)
    # a B is its own A's price plus u, or minus u and a cent
    a = None
    for v, p in zip(v0, p0):
        if v == 1:
            a = p
            assert 0 <= p < 1
        elif v == 2:
            assert a <= p < a + 1 or a - 1.01 < p <= a - np.float32(0.01)
    # over every key: n ~ U{1 .. 7}, three Bs in four pass their own A
    vol = np.concatenate([s["cols"][2].reshape(-1, 4) for s in sends], 0)
    share_b = np.mean(vol == 2)
    assert abs(share_b - 4 / 6) < 0.02          # A, 4 Bs on average, C


def test_the_valve_keeps_every_seed_inside_the_sizes():
    """At sizes the traffic overruns (12 threads, 12 rows) the generator's
    valve draws the Bs that could overrun below every A: its own walk and
    the reference never need a thirteenth thread or row; without it they do."""
    cell = loader.resolve(CELL, rehearse=True)
    m = cell.model
    small = dict(cell.sizes, slots=12, emit_rows=12)
    sends, plan = sends_of(cell, 3, 40, sizes=small)
    t = plan["threads"]
    assert t.shy_events > 0 and t.peak_threads <= 12 and t.peak_rows <= 12
    # a shy B lies in (-2, -1], under every A and every miss but a sliver
    price = np.concatenate([s["cols"][1] for s in sends])
    assert 0 < (price < -1.01).sum() <= t.shy_events
    refs = m.reference(sends, dict(plan))
    assert sum(r["k"].shape[0] for r in refs) == sum(
        m.expected_rows(s) for s in sends)
    open_sends, _ = sends_of(cell, 3, 40)
    with pytest.raises(ValueError, match="more than 12 live threads|owed"):
        m.reference(open_sends, dict(plan, slots=12, emit_rows=12))
    # at the deployment's sizes the valve sleeps through a rehearsal
    _s, plan32 = sends_of(cell, 3, 40)
    assert plan32["threads"].shy_events == 0


def test_compare_catches_each_fault_and_the_control_fails():
    cell = loader.resolve(CELL, rehearse=True)
    m = cell.model
    ZERO = zero(m)
    assert list(m.LIMITS) == ["rows_missing", "rows_unexpected",
                              "rows_differing"]
    assert set(m.LIMITS.values()) == {0}
    sends, plan = sends_of(cell, 2, 48)
    want = m.canonical(m.reference(sends, plan)[47])
    n = want["k"].shape[0]
    assert n > 300 and m.compare(want, want) == ZERO
    keep = np.arange(n) != 17
    withheld = {k: a[keep] for k, a in want.items()}
    assert m.compare(withheld, want) == dict(ZERO, rows_missing=1)
    twice = {k: np.concatenate([a, a[17:18]]) for k, a in want.items()}
    assert m.compare(m.canonical(twice), want) == dict(
        ZERO, rows_unexpected=1)
    off = dict(want, bl=want["bl"].copy())
    off["bl"][5] = np.nextafter(off["bl"][5], np.float32(9))
    assert m.compare(off, want) == dict(ZERO, rows_differing=1)
    none = {k: a[:0] for k, a in want.items()}
    assert m.compare(none, none) == ZERO
    assert m.compare(none, want) == dict(ZERO, rows_missing=n)
    ctl = m.compare(m.canonical(m.control_rows(want)), want)
    assert ctl == dict(ZERO, rows_differing=ctl["rows_differing"]) and \
        ctl["rows_differing"] > 0.9 * n
    assert np.array_equal(m.control_rows(want)["p1"],
                          numeric.to_bf16(want["p1"]))
    assert np.array_equal(m.control_rows(want)["k"], want["k"])


def test_least_bytes_from_shapes():
    cell = loader.resolve(CELL)
    # 131,072 keys' state rows (6,408 B) read and written, 524,288 events
    # of 24 B in, 0.83 rows an event of 32 B out
    assert cell.model.least_bytes(cell.traffic, cell.sizes, cell.config) \
        == 2 * 131072 * 6408 + 524288 * 24 + 435159 * 32 == 1706326752
    assert cell.model.SLOT_BYTES == 36


def test_config_and_traffic_state_what_the_contract_asks():
    cell = loader.resolve(CELL)
    cfg, t = cell.config, cell.traffic
    for key in ("source", "deployment", "assumed", "guarantees",
                "sizes_why", "reduced_why", "scale_from",
                "reference_peaks"):
        assert cfg[key]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert "configs`[4]" in cfg["source"] and "B<1:5>" in cfg["source"]
    assert cfg["sizes"] == {"n_keys": 1048576, "slots": 32, "emit_rows": 32}
    assert cfg["rehearse_sizes"] == {"n_keys": 1024, "slots": 32,
                                     "emit_rows": 32}
    assert (cfg["stream"], cfg["query"], cfg["columns"]) == \
        ("TradeStream", "kleene", ["k", "p1", "b0", "bl", "p3"])
    assert any(a.startswith("THE COUNT ATOM'S SEMANTICS") and
               "CountPatternTestCase" in a for a in cfg["assumed"])
    assert any(a.startswith("the generator's valve") for a in cfg["assumed"])
    assert any(a.startswith("the episode mix") for a in cfg["assumed"])
    assert any("no fork lost" in g and "no row lost" in g
               for g in cfg["guarantees"])
    app = cell.app_text
    with open(os.path.join(loader.BENCH_DIR, "configs", "pattern_1m",
                           "app.siddhi")) as fh:
        flagship = fh.read()
    assert app.split("@info")[0] == flagship.format(
        n_keys=1048576, slots=32, emit_rows=32).split("@info")[0]
    assert "-> e2=TradeStream[volume == 2 and price >= e1.price]<1:5>" in app
    assert ("select e1.key as k, e1.price as p1, e2[0].price as b0, "
            "e2[last].price as bl, e3.price as p3") in app
    assert cell.chips == 1 and "prefill" not in t and \
        t["drain_limit_s"] == 60
    assert t["who"] and t["what"] and t["warmup_why"] and t["prepare_why"]
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
    assert entry["file"] == "benchmarks/configs/kleene_1m/config.json"
    w = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("kleene_1m", "kleene_saturated_sweep", 1)
    assert len(w["why"]) <= 200
    assert {e["name"] for e in cell.end_to_end} == {
        "events_per_s", "latency_p50_ms", "setup_s"}


def check_the_appended_entries_and_the_lists_the_cell_joined(bench):
    """The five entries stay, together and in order, behind PR 54's 108; the
    cell stands FIRST in their lists (a later cell may join behind it) and
    is a member of the lists it joined (a later PR may lengthen them)."""
    names = [e["name"] for e in bench["per_layer"]]
    at = names.index(APPENDED[0])
    assert names[at:at + len(APPENDED)] == APPENDED
    assert 108 <= at and len(names) <= 128
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for n in APPENDED:
        e = by_name[n]
        assert e["workloads"][:1] == [CELL] and e["moves"] == "events_per_s"
    assert [by_name[n]["source"] for n in APPENDED] == \
        ["device_trace"] * 3 + ["host_clock", "program_span"]
    assert [by_name[n]["layer"] for n in APPENDED] == \
        ["device step"] * 3 + ["emission"] * 2
    for n in JOINED:
        assert CELL in by_name[n]["workloads"], n
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


def check_a_rehearsal_of_the_cell_says_its_rows_and_its_fill(cell, done):
    """What a RUN of this cell gives, held of this cell alone: the
    reference's own peaks line, and — traced — the two readers the CPU can
    feed (`rows_per_event`, `emit_fill_pct`)."""
    if cell != CELL:
        return
    assert "kleene_1m reference over" in done.out
    if done.trace:
        withheld = done.out.split("REHEARSAL metrics computed and "
                                  "withheld: ")[1].splitlines()[0]
        assert "rows_per_event.kleene" in withheld and \
            "emit_fill_pct.kleene" in withheld
        assert "emission fill over the slice:" in done.out


# -- the whole of a run, sound and doctored underneath --------------------------

class BrokenHandler:
    def __init__(self, owner, handler):
        self.owner, self.handler = owner, handler

    def send_columns(self, cols, timestamps=None):
        o = self.owner
        o.calls += 1
        if o.fault == "forgets_the_carried_threads" and o.calls == WARM + 4:
            # a program that loses the state it carried: every key's
            # collectors and waiting prefixes, as they were at deploy
            qr = o._rt.query_runtimes["kleene"]
            qr.state = jax.tree.map(
                lambda x: jax.numpy.array(x, copy=True),
                qr.planned.init_state(qr.planned.key_capacity))
            o.forgot = True
        self.handler.send_columns(cols, timestamps=timestamps)


class BrokenRuntime:
    """The real runtime with one fault between it and its user."""

    def __init__(self, rt, fault):
        self._rt, self.fault = rt, fault
        self.calls = 0
        self.withheld = self.forgot = False

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def get_input_handler(self, stream):
        return BrokenHandler(self, self._rt.get_input_handler(stream))

    def add_batch_callback(self, query, cb):
        def doctored(ts, b):
            if self.fault == "a_row_withheld" and self.calls > WARM + 2 \
                    and not self.withheld and b["valid"].any():
                b = dict(b, valid=b["valid"].copy(), kind=b["kind"],
                         cols=b["cols"])
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


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 7])
def test_the_rehearsal_of_the_cell_is_correct(monkeypatch, capsys, seed):
    rc, last, out, _rt = run_with(monkeypatch, capsys, None, seed)
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


def test_a_withheld_row_is_not_correct(monkeypatch, capsys):
    rc, last, out, rt = run_with(monkeypatch, capsys, "a_row_withheld")
    assert rc == 0 and rt.withheld
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    assert last["compared"]["rows_missing"] == {"value": 1, "limit": 0}


def test_a_runtime_that_forgets_the_carried_threads_is_not_correct(
        monkeypatch, capsys):
    rc, last, out, rt = run_with(monkeypatch, capsys,
                                 "forgets_the_carried_threads")
    assert rc == 0 and rt.forgot
    assert last["correct"] is False and last["failed"] >= 1, out[-1500:]
    # its keys start from nothing: the rows their old As were owed are gone
    assert last["compared"]["rows_missing"]["value"] >= 100
    assert "OVER" in out

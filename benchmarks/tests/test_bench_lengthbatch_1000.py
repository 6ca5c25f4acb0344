"""`lengthbatch_1000`: its plain reference against a per-event Python loop,
its generator's bookkeeping, `least_bytes` from shapes, the whole of a run
with a batch dropped / two rows swapped / a bfloat16 payload underneath it,
and the plain step's sections (`harness/plain_sections.py`) — on hand-made
events, on `data/tiny_plain.xplane.pb.gz` (`record_plain.py`: two sends of
this tree's `jit_plain_step` on the v5e: the sections add up to the slice's
busy time), and on a trace with no plain program (None from every reader)."""
import json
import os

import numpy as np
import pytest

import siddhi_tpu
from benchmarks.harness import loader, numeric, plain_sections as ps
from benchmarks.harness import trace_reduce as tr
from adding_pr import NEW_CELLS, NEW_CLOSED
from test_bench_doctored import load_run_module
from test_bench_step_sections import reader, recorded_run

CELL = "lengthbatch_1000.saturated"
BENCH = loader.load_benchmark()
ENTRIES = {e["name"]: e for e in BENCH["per_layer"]}
QUANTITIES = {
    "plain_window_ms_per_send": ("plain_chain", "window_fill",
                                 "window_state"),
    "plain_order_ms_per_send": ("window_order", "agg_layout"),
    "plain_aggregate_ms_per_send": ("agg_scan", "project"),
    "plain_unscoped_ms_per_send": (ps.UNSCOPED,),
}


# -- the model alone ----------------------------------------------------------------

def by_hand(prices, w):
    """The query, an event at a time in plain Python: the running float64
    mean of the batch being filled; a full batch's means come out at its
    last event."""
    out, batch = [], []
    for p in prices:
        batch.append(float(p))
        if len(batch) == w:
            acc = 0.0
            for j, v in enumerate(batch, 1):
                acc += v
                out.append(np.float32(acc / j))
            batch = []
    return out


@pytest.mark.parametrize("w,sizes", [(7, (10, 3, 25, 1, 6, 4, 30)),
                                     (1000, (384, 4096, 384, 131))])
def test_the_reference_is_the_per_event_loop(w, sizes):
    m = loader.resolve(CELL).model
    rng = np.random.default_rng(5)
    sends = [{"cols": [None, (10 + 990 * rng.random(n)).astype(np.float32),
                       None], "events": n} for n in sizes]
    refs = m.reference(sends, {"window_length": w})
    want = by_hand(np.concatenate([s["cols"][1] for s in sends]), w)
    got = np.concatenate([r["ap"] for r in refs])
    assert got.dtype == np.float32 and got.shape[0] == len(want)
    np.testing.assert_allclose(got, np.array(want, np.float32), rtol=2e-7)
    # a batch's rows go to the send that holds its last event
    done, at = 0, 0
    for n, r in zip(sizes, refs):
        at += n
        assert r["ap"].shape[0] == at // w * w - done
        done = at // w * w


def test_a_send_is_new_in_every_column_and_keeps_the_fill():
    cell = loader.resolve(CELL)
    m, t = cell.model, cell.traffic
    assert m.events_per_send(t) == 131072 and m.clock_step_ms(t) == 1024
    plan = m.plan(9, t, cell.sizes)
    clock, sends = 1000, []
    for i in range(4):
        clock += m.clock_step_ms(t)
        sends.append(m.make_send(np.random.default_rng([9, i]), i, t, plan,
                                 clock))
    for i, s in enumerate(sends):
        sym, price, vol = s["cols"]
        assert (sym.dtype, price.dtype, vol.dtype, s["ts"].dtype) == \
            (np.int64, np.float32, np.int32, np.int64)
        assert 0 <= sym.min() and sym.max() < 1000
        assert 10 <= price.min() and price.max() < 1000
        assert 1 <= vol.min() and vol.max() <= 1000
        assert s["fill"] == 72 * i % 1000 and s["events"] == 131072
        assert m.expected_rows(s) == (131000, 131000, 131000, 131000)[i]
        assert bool((np.diff(s["ts"]) >= 0).all())
        assert s["ts"][-1] - s["ts"][0] == 1023
    for a, b in zip(sends, sends[1:]):
        assert a["ts"][-1] < b["ts"][0]
        for ca, cb in zip(a["cols"], b["cols"]):
            assert not np.array_equal(ca, cb)
    # fill = 72 i mod 1000 comes round after 125 sends; a send that starts
    # at fill 928 or more completes 132 batches
    rows = [(72 * i % 1000 + 131072) // 1000 for i in range(125)]
    assert set(rows) == {131, 132} and sum(rows) == 131072 * 125 // 1000


def test_compare_catches_each_fault_and_the_control_fails():
    m = loader.resolve(CELL).model
    assert all(v == 0 for v in m.LIMITS.values())
    assert 1e-4 <= m.AP_RTOL <= 5e-4
    rng = np.random.default_rng(2)
    prices = (10 + 990 * rng.random(4000)).astype(np.float32)
    want = m.reference([{"cols": [None, prices, None], "events": 4000}],
                       {"window_length": 1000})[0]
    assert m.canonical(want) is want
    assert m.compare(want, want) == dict.fromkeys(m.LIMITS, 0)
    # float32 accumulation, in order: inside the tolerance
    f32 = (np.cumsum(prices.reshape(4, 1000), axis=1, dtype=np.float32) /
           np.arange(1, 1001, dtype=np.float32)).reshape(-1)
    assert m.compare({"ap": f32}, want) == dict.fromkeys(m.LIMITS, 0)
    short = {"ap": want["ap"][1000:]}
    assert m.compare(short, want)["rows_missing"] == 1000
    twice = {"ap": np.concatenate([want["ap"], want["ap"][:1000]])}
    assert m.compare(twice, want)["rows_unexpected"] == 1000
    swapped = want["ap"].copy()
    swapped[[2000, 2001]] = swapped[[2001, 2000]]
    assert m.compare({"ap": swapped}, want)["rows_differing"] == 2
    off = want["ap"].copy()
    off[7] *= np.float32(1 + 3 * m.AP_RTOL)
    off[8] = np.nan
    assert m.compare({"ap": off}, want)["rows_differing"] == 2
    # the control: bfloat16 anywhere is outside it on most rows — prices
    # rounded before the mean, or the mean itself
    ctl = m.compare(m.control_rows(want), want)
    assert ctl["rows_differing"] > 3000
    coarse = m.reference([{"cols": [None, numeric.to_bf16(prices), None],
                           "events": 4000}], {"window_length": 1000})[0]
    assert m.compare(coarse, want)["rows_differing"] > 0


def test_least_bytes_from_shapes():
    cell = loader.resolve(CELL)
    # 131,072 events of 24 B in, as many rows of 12 B out, two 1,000-row
    # buffers of 24 B rows read and written
    assert cell.model.least_bytes(cell.traffic, cell.sizes, cell.config) \
        == 131072 * (24 + 12) + 2 * 2 * 1000 * 24 == 4814592
    r = loader.resolve(CELL, rehearse=True)
    assert r.model.least_bytes(r.traffic, r.sizes, r.config) \
        == 1024 * 36 + 4 * 10 * 24


def test_config_states_what_the_contract_asks():
    cell = loader.resolve(CELL)
    cfg = cell.config
    for key in ("source", "deployment", "assumed", "guarantees",
                "tolerance"):
        assert cfg[key]
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert "configs`[0]" in cfg["source"] and "bench.py" in cfg["source"]
    assert cfg["sizes"] == {"window_length": 1000}
    assert (cfg["stream"], cfg["query"], cfg["columns"]) == \
        ("StockStream", "q", ["ap"])
    assert "lengthBatch(1000)" in cell.app_text
    assert cell.chips == 1 and cell.traffic["loop"] == "closed"
    check_the_tables_configuration_and_what_its_cell_reports(BENCH)
    # the model imports nothing of the program
    with open(os.path.join(loader.BENCH_DIR, "configs", cfg["name"],
                           "model.py")) as fh:
        assert "siddhi_tpu" not in fh.read().split('"""', 2)[2]


# -- the whole of a run with the timed path broken underneath it -------------------

class Doctored:
    """The real runtime with one fault between it and its subscriber."""

    def __init__(self, rt, fault, window_length):
        self._rt, self._fault, self._w = rt, fault, window_length
        self._deliveries, self._done = 0, False

    def __getattr__(self, name):
        return getattr(self._rt, name)

    def add_batch_callback(self, query, cb):
        def doctored(ts, b):
            self._deliveries += 1
            out = {k: np.array(b[k]) for k in ("ts", "kind", "valid")}
            ap = np.array(b["cols"]["ap"])
            current = np.nonzero(out["valid"] & (out["kind"] == 0))[0]
            hit = self._deliveries >= 20 and current.size and not self._done
            if hit and self._fault == "drop_batch":
                out["valid"][current[:self._w]] = False
            if hit and self._fault == "swap_rows":
                a, b2 = current[0], current[1]   # rows 1 and 2 of a batch
                ap[[a, b2]] = ap[[b2, a]]
            if self._fault == "bf16_payload":
                ap = numeric.to_bf16(ap)
            self._done = self._done or bool(hit)
            out["cols"] = {"ap": ap}
            cb(ts, out)
        self._rt.add_batch_callback(query, doctored)


def run_with(monkeypatch, capsys, fault):
    real = siddhi_tpu.SiddhiManager.create_siddhi_app_runtime
    w = loader.resolve(CELL, rehearse=True).sizes["window_length"]

    def create(self, *a, **kw):
        rt = real(self, *a, **kw)
        return Doctored(rt, fault, w) if fault else rt
    monkeypatch.setattr(siddhi_tpu.SiddhiManager,
                        "create_siddhi_app_runtime", create)
    rc = load_run_module().main(["--workload", CELL, "--seed", "11",
                                 "--seconds", "1.0", "--trace", "0",
                                 "--rehearse"])
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("fault,number", [
    ("drop_batch", "rows_missing"), ("swap_rows", "rows_differing"),
    ("bf16_payload", "rows_differing")])
def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, fault,
                                            number):
    rc, last, out = run_with(monkeypatch, capsys, fault)
    assert rc == 0
    assert last["correct"] is False, out[-1500:]
    assert last["failed"] >= 1 and "OVER" in out
    over = {n for n, c in last["compared"].items() if c["value"] > c["limit"]}
    # a send short of a batch is also never complete
    assert number in over and over <= {number, "sends_undelivered"}, \
        last["compared"]


def test_the_same_run_unbroken_is_correct(monkeypatch, capsys):
    rc, last, _ = run_with(monkeypatch, capsys, None)
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert all(c == {"value": 0, "limit": 0}
               for c in last["compared"].values())


# -- the plain step's sections: the arithmetic, on hand-made planes --------------------

def test_a_tf_op_names_its_outermost_plain_section():
    assert ps.named("jit(plain_step)/window_order/sort:") == "window_order"
    assert ps.named("jit(plain_step)/agg_scan/jit(_where)/select_n:") == \
        "agg_scan"
    # merged by the compiler: the first name decides
    assert ps.named("jit(plain_step)/agg_layout/gather;jit(plain_step)/"
                    "agg_scan/add:") == "agg_layout"
    assert ps.named("state[0][0].ts:") is ps.named("") is ps.named(None) \
        is None
    # a scope's name inside another word is not the scope, nor is a
    # pattern program's section one of these
    assert ps.named("jit(f)/my_project/add:") is None
    assert ps.named("jit(pattern_step)/rect_8x4/selector/add:") is None
    assert not set(ps.SECTIONS) & set(ps.ss.SECTIONS)


class FakeLine:
    def __init__(self, name, events):
        self.name, self._events = name, events

    def events(self):
        return iter(self._events)


class FakePlane:
    def __init__(self, metadata, ops, modules):
        self.name = "/device:TPU:0"
        self.metadata = metadata
        self.lines = [FakeLine(tr.OPS_LINE, ops),
                      FakeLine(tr.MODULES_LINE, modules)]


def test_a_planes_time_goes_to_sections_unscoped_and_other_modules():
    def op(tf_op, pid, cat="x"):
        return {"tf_op": tf_op, "program_id": pid, "hlo_category": cat}
    meta = {
        1: ("sort.1", op("jit(plain_step)/window_order/sort:", 7)),
        2: ("while.1", op("jit(plain_step)/agg_scan/while:", 7)),
        3: ("fusion.1", op("", 7, "data formatting")),      # inside the loop
        4: ("copy.1", op("state[0][0].ts:", 7, "data formatting")),
        5: ("convert.1", op("jit(convert_element_type)/convert:", 9)),
        6: ("fusion.2", op("jit(plain_step)/window_fill/concatenate:", 7)),
        20: ("jit_plain_step(7)", {}), 21: ("jit_convert(9)", {}),
    }
    ops = [(1, 0.0, 40.0), (2, 50.0, 150.0), (3, 60.0, 90.0),
           (4, 160.0, 170.0), (5, 200.0, 205.0), (6, 210.0, 260.0),
           (1, 1000.0, 1040.0)]                             # past the slice
    modules = [(20, 0.0, 260.0), (21, 200.0, 205.0)]
    sections, unscoped, others = ps.reduce_plane(
        FakePlane(meta, ops, modules), 0.0, 300.0, 0.0)
    # the loop's own 70 and the 30 of the op inside it that names nothing
    assert sections == {"window_order": 40.0, "agg_scan": 100.0,
                        "window_fill": 50.0, ps.UNSCOPED: 10.0}
    assert unscoped == {"data formatting": 10.0}
    assert others == {"jit_convert": 5.0}
    assert sum(sections.values()) + sum(others.values()) == 205.0
    # a plane whose programs name no plain section: all other modules
    bare = {k: (n, dict(s, tf_op="jit(pattern_step)/rect_8x4/selector/a:")
                if s else s) for k, (n, s) in meta.items()}
    sections, _, others = ps.reduce_plane(
        FakePlane(bare, ops, modules), 0.0, 300.0, 0.0)
    assert sections == {} and others == {"jit_plain_step": 200.0,
                                        "jit_convert": 5.0}


# -- this tree's scopes, as recorded on the v5e ------------------------------------------

def test_the_recorded_plain_steps_sections_add_up_to_the_busy_time(
        tmp_path, capsys):
    run = recorded_run(tmp_path, "tiny_plain.xplane.pb.gz")
    out = ps.plain_sections(run)
    assert "plain step sections: {" in capsys.readouterr().out
    assert ps.plain_sections(run) is out             # computed once
    assert capsys.readouterr().out == ""
    red = run["trace_reduced"]
    assert (out["sends"], out["devices"]) == (red["sends_in_slice"], 1) \
        == (2, 1)
    # every section the deployed text's step keeps ops of (no filter: the
    # chain holds nothing), each with time in it
    for section in ps.SECTIONS[1:]:
        assert out["sections_s"][section] > 0, section
    assert set(out["sections_s"]) <= set(ps.SECTIONS) | {ps.UNSCOPED}
    assert sum(out["unscoped_by_category_s"].values()) == \
        pytest.approx(out["sections_s"].get(ps.UNSCOPED, 0.0))
    assert out["unscoped_share"] < 0.25
    assert "jit_plain_step" not in out["other_modules_s"]
    # closure: to the last digit, both sides sum whole nanoseconds
    assert out["plain_s"] + sum(out["other_modules_s"].values()) == \
        pytest.approx(out["total_s"])
    assert out["total_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert out["closure"]["ratio"] == pytest.approx(1.0, rel=1e-9)
    # the four readers split the plain step's time between them
    per_send = sum(reader(q)(run) for q in QUANTITIES)
    assert per_send == pytest.approx(out["plain_s"] * 1e3 / 2)
    for q, sections in QUANTITIES.items():
        assert reader(q)(run) == pytest.approx(sum(
            out["sections_s"].get(s, 0.0) for s in sections) * 1e3 / 2), q
    # and the pattern programs' reader sees the plain step as another module
    assert ps.ss.step_sections(run) is None


@pytest.mark.parametrize("name", ["tiny_sections.xplane.pb.gz",
                                  "tiny_served.xplane.pb.gz"])
def test_a_trace_with_no_plain_program_reads_none(tmp_path, name):
    """The pattern cells' recordings (and so a tree older than the scopes,
    and the CPU rehearsal, which has no device plane): None, no line."""
    run = recorded_run(tmp_path, name)
    assert ps.plain_sections(run) is None
    for q in QUANTITIES:
        assert reader(q)(run) is None, q
        assert reader(q)({"trace_dir": None, "trace_reduced": None}) is None


# -- the four entries and the lists the cell joined ---------------------------------------

def check_the_tables_configuration_and_what_its_cell_reports(bench):
    cell = loader.resolve(CELL)
    cfg = cell.config
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and entry["reduced"] == []
    assert {e["name"] for e in cell.end_to_end} == {
        "events_per_s", "latency_p50_ms", "setup_s"}


def check_the_four_entries_and_the_lists_the_cell_joined(bench):
    """One-sided (PR 41): the four entries stay, together and in order,
    after the pattern programs' entries; a later PR may add a cell to a
    list behind this one, or an entry behind these — whatever cells it
    lists (PR 50: what the cell resolves is held among the entries that
    stood with it)."""
    names = [e["name"] for e in bench["per_layer"]]
    entries = {e["name"]: e for e in bench["per_layer"]}
    at = names.index(next(iter(QUANTITIES)) + ".sat")
    assert names[at:at + 4] == [q + ".sat" for q in QUANTITIES]
    assert 76 <= at and len(names) <= 128
    stood = set(names[:at + 4])
    for q in QUANTITIES:
        e = dict(entries[q + ".sat"])
        assert e.pop("workloads")[0] == CELL
        assert e == {"name": q + ".sat", "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "device step",
                     "moves": "events_per_s"}
    got = {e["name"]: read.__module__
           for e, read in loader.resolve(CELL).per_layer
           if e["name"] in stood}
    for q in QUANTITIES:
        assert got[q + ".sat"] == "bench_layer_" + q
    # joined: every `.sat` span / clock / idle quantity, the un-suffixed
    # four; left out: the pattern programs' sections, key routing and the
    # observatory's feed (an ungrouped query opens neither span)
    assert {n for n in got if n.startswith("step_") and
            n != "step_roofline"} == set()
    assert not {n for n in got if n.split("_ms")[0] in (
        "route_keys", "obs_feed", "obs_feed_idle")}
    assert {"state_bytes", "peak_hbm_bytes", "compile_s", "step_roofline",
            "device_busy_ms_per_send.sat", "device_idle_pct.sat",
            "fetch_bytes_per_send.sat", "page_faults_per_send.sat"} <= \
        set(got)
    assert len(got) >= 26
    # it joined each list behind the five pattern cells
    cells = [w["name"] for w in bench["workloads"]]
    older = cells[:cells.index(CELL)]
    for e in bench["end_to_end"] + bench["per_layer"]:
        if CELL in e.get("workloads", ()):
            assert e["workloads"].index(CELL) == \
                len([c for c in e["workloads"] if c in older]), e["name"]


def test_the_four_entries_and_the_lists_the_cell_joined():
    check_the_four_entries_and_the_lists_the_cell_joined(BENCH)


def test_a_seventh_cell_behind_it_trips_no_pin(adding_pr):
    assert [w["name"] for w in adding_pr["workloads"]][-4:] == \
        list(NEW_CELLS)
    events = next(e for e in adding_pr["end_to_end"]
                  if e["name"] == "events_per_s")
    assert NEW_CLOSED in events["workloads"][-2:]
    check_the_four_entries_and_the_lists_the_cell_joined(adding_pr)
    check_the_tables_configuration_and_what_its_cell_reports(adding_pr)

"""`sequence_within` through its own app text: the un-partitioned sequence
`every e1=S[volume == 1], e2=S[volume == 2 and price > e1.price] within 1 sec`
fed by `send_columns`, read by a batch callback, against an independent
per-event NFA loop written HERE (one pending thread, plain Python — not the
vectorised pair formula of `benchmarks/configs/sequence_within/model.py`,
which `benchmarks/tests/test_bench_sequence_within.py` holds to a loop of its
own): exact rows in order, a thread carried
across a send and across a chunk boundary, `within` at exactly 1,000 ms and at
1,001, sends with invalid rows (the masked `sel` branch), nothing dropped,
nothing compiled after the first send of a width; and the block step's
device-trace sections (`jax.named_scope`), which name every part of the
program, leave what it lowers to as it was, and whose `route_keys` span says
the chunks the step scans."""
import contextlib
import importlib.util
import json
import os
import re

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import pattern_block
from siddhi_tpu.observability import RECOMPILES
from siddhi_tpu.observability import phases as ph

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CFG_DIR = os.path.join(ROOT, "benchmarks", "configs", "sequence_within")
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "seq_paced_8k.json")) as _fh:
    TRAFFIC = json.load(_fh)
with open(os.path.join(CFG_DIR, "config.json")) as _fh:
    CONFIG = json.load(_fh)
SECTIONS = ("event_load", "state_load", "nfa_advance", "state_store",
            "match_rows", "selector", "emission_compaction")
N_SENDS = 18            # two feed pauses
SLOTS = 8               # app.siddhi's `@capacity(slots='8')`
# (sizes, events a send): the configuration's own rehearsal sizes (half a
# 2,048 bucket: every send has invalid rows), the source's sizes under a
# full bucket (the identity `sel`), and a ragged width
SHAPES = {"rehearse": (CONFIG["rehearse_sizes"],
                       TRAFFIC["rehearse"]["events_per_send"]),
          "source_e2048": (CONFIG["sizes"], 2048),
          "source_e300": (CONFIG["sizes"], 300)}
SEEDS = (11, 2 ** 31 + 7)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL = _load(os.path.join(CFG_DIR, "model.py"),
              "bench_model_sequence_within_t1")


def app_text(sizes, statistics=False, chain="sequence"):
    """The configuration's app; `chain="pattern"`: the same app with `->`
    for the `,` — a PATTERN chain, which keeps the block step's grid."""
    with open(os.path.join(CFG_DIR, "app.siddhi")) as fh:
        text = fh.read().format(**sizes)
    if chain == "pattern":
        assert text.count("[volume == 1], e2=") == 1
        text = text.replace("[volume == 1], e2=", "[volume == 1] -> e2=")
    return ("@app:statistics('BASIC')\n" if statistics else "") + text


def nfa_by_hand(sends):
    """The query, an event at a time: one pending thread.  An event either
    completes the pending thread or kills it (strict continuity), then
    seeds a new one where its volume is 1.  A list of (p1, p2) a send."""
    pending, out = None, []
    for cols, ts in sends:
        _symbol, price, volume = cols
        rows = []
        for p, v, t in zip(price.tolist(), volume.tolist(), ts.tolist()):
            if pending is not None:
                p1, t1 = pending
                if v == 2 and p > p1 and t - t1 <= 1000:
                    rows.append((p1, p))
                pending = None
            if v == 1:
                pending = (p, t)
        out.append(rows)
    return out


class Driven:
    """The app deployed and subscribed; `send` returns what the call
    delivered (blocking delivery: the rows are here when it returns)."""

    def __init__(self, sizes, statistics=False, chain="sequence"):
        self.manager = SiddhiManager()
        self.rt = self.manager.create_siddhi_app_runtime(
            app_text(sizes, statistics, chain))
        self.errors, self.batches = [], []
        self.rt.set_exception_listener(self.errors.append)
        self.rt.add_batch_callback(CONFIG["query"], self._on_batch)
        self.rt.start()
        self.handler = self.rt.get_input_handler(CONFIG["stream"])

    def _on_batch(self, _ts, b):
        sel = b["valid"] & (b["kind"] == 0)
        self.batches.append(({n: np.asarray(b["cols"][n])[sel]
                              for n in CONFIG["columns"]}, b["n_dropped"]))

    def send(self, cols, ts):
        before = len(self.batches)
        self.handler.send_columns([c.copy() for c in cols],
                                  timestamps=ts.copy())
        got = self.batches[before:]
        rows = {n: np.concatenate([r[n] for r, _ in got]) if got else
                np.zeros(0, np.float32) for n in CONFIG["columns"]}
        return rows, sum(int(d) for _, d in got)

    def compiles(self):
        return RECOMPILES.snapshot([CONFIG["query"]])[
            CONFIG["query"]]["count"]

    def close(self):
        self.manager.shutdown()


def drive(shape, seed, n_sends=N_SENDS, statistics=False, keep=None,
          chain="sequence"):
    sizes, events = SHAPES[shape]
    traffic = dict(TRAFFIC, events_per_send=events)
    d = Driven(sizes, statistics, chain)
    try:
        plan = MODEL.plan(seed, traffic, sizes)
        out = {"sends": [], "rows": [], "dropped": [], "compiles": []}
        clock = 1000
        for sid in range(n_sends):
            clock += MODEL.clock_step_ms(traffic)
            send = MODEL.make_send(np.random.default_rng([seed, sid]), sid,
                                   traffic, plan, clock)
            out["sends"].append(send)
            rows, dropped = d.send(send["cols"], send["ts"])
            out["rows"].append(rows)
            out["dropped"].append(dropped)
            out["compiles"].append(d.compiles())
        assert not d.errors, d.errors[:1]
        if keep is not None:
            out["kept"] = keep(d.rt)
        return out
    finally:
        d.close()


@pytest.fixture(scope="module")
def runs():
    cache = {}

    def get(shape, seed):
        if (shape, seed) not in cache:
            cache[shape, seed] = drive(shape, seed)
        return cache[shape, seed]
    return get


CASES = pytest.mark.parametrize(
    "shape,seed", [(shape, seed) for shape in sorted(SHAPES)
                   for seed in SEEDS])


@CASES
def test_every_send_delivers_the_per_event_loops_rows_in_order(shape, seed,
                                                               runs):
    run = runs(shape, seed)
    by_hand = nfa_by_hand([(s["cols"], s["ts"]) for s in run["sends"]])
    for i, (send, got, want) in enumerate(zip(run["sends"], run["rows"],
                                              by_hand)):
        assert list(zip(got["p1"].tolist(), got["p2"].tolist())) == want, i
        assert got["p1"].dtype == got["p2"].dtype == np.float32
        assert len(want) == MODEL.expected_rows(send), i
    # about 8 % of adjacent pairs match
    events = run["sends"][0]["events"] * len(run["sends"])
    assert 0.06 * events < sum(map(len, by_hand)) < 0.10 * events


@CASES
def test_threads_cross_sends_and_the_pause_expires_them(shape, seed, runs):
    """A send whose first event completes the thread the send before left
    pending is owed that row; over a feed pause the thread is owed
    nothing, whatever the first event is."""
    run = runs(shape, seed)
    every = TRAFFIC["pause_every_sends"]
    for i, send in enumerate(run["sends"][1:], 1):
        before = run["sends"][i - 1]
        gap = int(send["ts"][0]) - int(before["ts"][-1])
        if i % every == 0:
            assert gap == TRAFFIC["pause_ms"], i
        else:
            assert 0 <= gap <= 1, i
        v1, p1 = before["cols"][2][-1], before["cols"][1][-1]
        would = v1 == 1 and send["cols"][2][0] == 2 and \
            send["cols"][1][0] > p1
        first = run["rows"][i]["p1"][:1].tolist() == [float(p1)] and \
            run["rows"][i]["p2"][:1].tolist() == [float(send["cols"][1][0])]
        if would:
            assert first == (gap <= 1000), i


def test_some_thread_was_carried_across_a_send(runs):
    """Over the seeded runs at least one thread completed across a send
    boundary; the pause's expiry is driven by hand below, where a seed
    need not happen on one."""
    carried = 0
    for shape in sorted(SHAPES):
        for seed in SEEDS:
            run = runs(shape, seed)
            before = [s["cols"] for s in run["sends"][:-1]]
            after = [s["cols"] for s in run["sends"][1:]]
            carried += sum(
                b[2][-1] == 1 and a[2][0] == 2 and a[1][0] > b[1][-1]
                for b, a in zip(before, after))
    assert carried >= 1


@CASES
def test_nothing_is_dropped_and_nothing_compiles_after_the_first_send(
        shape, seed, runs):
    run = runs(shape, seed)
    assert run["dropped"] == [0] * len(run["sends"])
    assert run["compiles"][-1] == run["compiles"][0], run["compiles"]


# -- edges, by hand ---------------------------------------------------------

def hand_send(n, t0, events):
    """A send of `n` events, all volume 3 at `t0`, but `events`:
    {row: (volume, price, ms after t0)}."""
    volume = np.full(n, 3, np.int32)
    price = np.zeros(n, np.float32)
    ts = np.full(n, t0, np.int64)
    for row, (v, p, dt) in events.items():
        volume[row], price[row] = v, p
        ts[row:] = t0 + dt          # timestamps stay non-decreasing
    return [np.zeros(n, np.int64), price, volume], ts


# each case: the sends (n, t0, events) and the rows each is owed
EDGES = {
    "a_thread_carried_across_a_send_completes": (
        [(256, 1000, {255: (1, 0.25, 0)}), (256, 1001, {0: (2, 0.5, 0)})],
        [[], [(0.25, 0.5)]]),
    "a_carried_thread_dies_at_an_event_that_does_not_match": (
        [(256, 1000, {255: (1, 0.25, 0)}),
         (256, 1001, {0: (3, 0.9, 0), 1: (2, 0.5, 0)})],
        [[], []]),
    "across_a_chunk_boundary": (
        [(256, 1000, {127: (1, 0.25, 0), 128: (2, 0.5, 0)})],
        [[(0.25, 0.5)]]),
    "a_price_that_is_not_greater_does_not_match": (
        [(256, 1000, {10: (1, 0.5, 0), 11: (2, 0.5, 0),
                      20: (1, 0.5, 0), 21: (2, 0.75, 0)})],
        [[(0.5, 0.75)]]),
    "within_at_1000_ms_inside_a_send": (
        [(256, 1000, {10: (1, 0.25, 0), 11: (2, 0.5, 1000)})],
        [[(0.25, 0.5)]]),
    "within_at_1001_ms_inside_a_send": (
        [(256, 1000, {10: (1, 0.25, 0), 11: (2, 0.5, 1001)})],
        [[]]),
    "within_at_1000_ms_across_sends": (
        [(256, 1000, {255: (1, 0.25, 0)}), (256, 2000, {0: (2, 0.5, 0)})],
        [[], [(0.25, 0.5)]]),
    "within_at_1001_ms_across_sends": (
        [(256, 1000, {255: (1, 0.25, 0)}), (256, 2001, {0: (2, 0.5, 0)})],
        [[], []]),
    "an_expired_thread_does_not_hold_the_next_seed_back": (
        [(256, 1000, {255: (1, 0.25, 0)}),
         (256, 3000, {0: (2, 0.5, 0), 1: (1, 0.1, 0), 2: (2, 0.2, 0)})],
        [[], [(0.1, 0.2)]]),
    "a_full_bucket_carries_too": (
        [(512, 1000, {511: (1, 0.25, 0)}), (512, 1001, {0: (2, 0.5, 0)})],
        [[], [(0.25, 0.5)]]),
}


@pytest.fixture(scope="module")
def edge_app():
    """One deployment for every edge: between two cases an event that
    matches nothing clears whatever thread the case before left."""
    d = Driven(CONFIG["rehearse_sizes"])
    yield d
    d.close()


@pytest.mark.parametrize("case", sorted(EDGES))
def test_an_edge(case, edge_app):
    sends, owed = EDGES[case]
    assert [[(np.float32(a), np.float32(b)) for a, b in rows]
            for rows in owed] == nfa_by_hand(
                [hand_send(*s) for s in sends]), "the case's own arithmetic"
    era = 10 ** 6 * (1 + sorted(EDGES).index(case))   # each case its own
    clear = hand_send(8, era, {})
    assert edge_app.send(*clear)[0]["p1"].shape[0] == 0
    for i, (send, want) in enumerate(zip(sends, owed)):
        n, t0, events = send
        t0 += era + 10
        rows, dropped = edge_app.send(*hand_send(n, t0, events))
        assert list(zip(rows["p1"].tolist(), rows["p2"].tolist())) == \
            [(float(np.float32(a)), float(np.float32(b)))
             for a, b in want], (case, i)
        assert dropped == 0
    assert not edge_app.errors, edge_app.errors[:1]


# -- the block step's sections ------------------------------------------------

def step_texts(rt):
    """{role: (lowered text, lowered text with debug info)} of the programs
    the query ran."""
    out = {}
    for role, fn, specs in rt.compiled_steps(CONFIG["query"]):
        if specs is not None:
            lowered = fn.lower(*specs)
            out[role] = (lowered.as_text(),
                         lowered.as_text(debug_info=True))
    return out


@pytest.fixture(scope="module")
def with_scopes():
    run = drive("rehearse", 3, n_sends=3, keep=step_texts)
    assert sum(r["p1"].shape[0] for r in run["rows"]) > 0
    return run["kept"]


@pytest.fixture(scope="module")
def grid_scopes():
    """The same app as a PATTERN chain (`->`): the grid form."""
    return drive("rehearse", 3, n_sends=3, keep=step_texts,
                 chain="pattern")["kept"]


def ops_under(named, op):
    """The scope paths of the `stablehlo.<op>` lines of a lowered text
    with debug info."""
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', named, re.M))
    return [locs.get(ref, ref) for ref in re.findall(
        r'stablehlo\.%s\b.*?loc\((#loc\d+)\)\s*$' % op, named, re.M)]


@pytest.mark.parametrize("chain", ["sequence", "pattern"])
def test_the_block_step_names_the_seven_sections(chain, request):
    texts = request.getfixturevalue(
        {"sequence": "with_scopes", "pattern": "grid_scopes"}[chain])
    assert list(texts) == ["step[S]"]
    text, named = texts["step[S]"]
    assert "jit(pattern_block)" in named
    for section in SECTIONS:
        assert f"/{section}/" in named, section
    if chain == "pattern":
        # the chunk scan, whole, is the advance's; its completions are
        # sorted back into arrival order
        assert "nfa_advance/while" in named
        assert "match_rows/jit(argsort)" in named
        assert "stablehlo.sort" in text
        return
    # the linear form: one pass — no loop, no sort, and no gather but
    # `event_load`'s four (three columns and the timestamp by `csel`)
    assert "stablehlo.while" not in text and "/while" not in named
    assert "stablehlo.sort" not in text and "argsort" not in named
    gathers = ops_under(named, "gather")
    assert len(gathers) == 4 == text.count('"stablehlo.gather"')
    assert all("/event_load/" in g for g in gathers), gathers


def test_named_scopes_leave_the_lowered_block_step_as_it_was(with_scopes,
                                                             monkeypatch):
    """`jax.named_scope` is op-name metadata: the lowered program without
    its debug info is the same with `jax.named_scope` patched out."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    try:
        without = drive("rehearse", 3, n_sends=3, keep=step_texts)["kept"]
    finally:
        monkeypatch.undo()
    text, named = without["step[S]"]
    assert not any(f"/{s}/" in named for s in SECTIONS)
    assert text == with_scopes["step[S]"][0]


@pytest.mark.parametrize("chain", ["sequence", "pattern"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_route_keys_span_says_the_chunks_the_step_scans(shape, chain):
    """`phase_report()` lists the layout under stage_host's parts, and the
    layout says which form ran: a tier a send, `max_e` the bucket; a
    SEQUENCE one tick and the (S - 1) x (P + bucket) slots its stages read,
    a PATTERN `ticks` the chunks of the send's bucket and `cells` the
    [P + W, W] grids over them."""
    n = 3
    run = drive(shape, 5, n_sends=n, statistics=True, chain=chain,
                keep=lambda rt: (
                    ph.phase_report(rt)["queries"][CONFIG["query"]],
                    rt.query_runtimes[CONFIG["query"]].planned.spec))
    report, spec = run["kept"]
    lay = report["phases"]["stage_host"]["parts"]["route_keys"]["layout"]
    events = SHAPES[shape][1]
    bucket = next(b for b in (512, 2048) if events <= b)
    assert lay["tiers"] == n and lay["max_e"] == n * bucket
    assert spec.state_type == chain.upper() and spec.n_states == 2
    if chain == "sequence":
        assert lay["ticks"] == n
        assert lay["cells"] == n * (SLOTS + bucket)
        assert pattern_block.block_layout(8192, SLOTS, spec) == {
            "tiers": 1, "cells": 8200, "ticks": 1, "max_e": 8192}
        assert pattern_block.block_layout(131072, SLOTS, spec) == {
            "tiers": 1, "cells": 131080, "ticks": 1, "max_e": 131072}
        return
    chunks = bucket // pattern_block.CHUNK
    assert lay["ticks"] == n * chunks
    assert lay["cells"] == n * chunks * (SLOTS + 128) * 128
    assert pattern_block.block_layout(8192, SLOTS, spec)["ticks"] == 64
    assert pattern_block.block_layout(131072, SLOTS, spec) == {
        "tiers": 1, "cells": 1024 * 136 * 128, "ticks": 1024,
        "max_e": 131072}
    assert pattern_block.block_layout(100, SLOTS, spec) == {
        "tiers": 1, "cells": 108 * 100, "ticks": 1, "max_e": 100}

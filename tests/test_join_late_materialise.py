"""Late materialisation of a join's pair rows (`core/join.py` `make_step`,
`late_pairs`): where the selector is a projection, the step takes the emission
cap's order straight from the probe's flags and gathers, selects and emits
`cap` rows, never the N = R x Q candidate pair rows.  Every case here is held
to a nested-loop join written below — no line of it from the package: the
rows in DELIVERY order (arriving rows in arrival order, each against the other
side's held rows in arrival order, an outer side's unmatched rows last), the
cut at an explicit `@emit(rows=...)` and its `n_dropped`, an implicit cap that
grows; and three joins that must NOT take the path (an aggregator, a `having`,
an `order by ... limit`), whose answers read every candidate row whatever the
cap.  `describe()` says which path a plan got, and the lowered `join_len128`
programs carry no gather whose result has N rows.

The same file holds the rule of which window output rows are join TRIGGERS
(`expired_rows_joined`): a join that says `insert into` a stream and selects
a projection takes CURRENT trigger rows alone — no EXPIRED joined row is
made, counted against the cap or handed to a callback, both windows' state
is what the `insert all events` twin's is — and every other join keeps its
EXPIRED rows, held to the same nested loop."""
import logging
import re

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import join as joinmod

import test_join_len128_config as cfg
from test_lengthbatch_state import _LOC, _REF    # an op's `loc` and its name

HEAD = """
@app:playback
define stream L (symbol long, price float);
define stream R (symbol long, qty int);
"""
PROJ = "select L.symbol as s, L.price as p, R.qty as v"
ON = "L.symbol == R.symbol"
WINDOW = 8


def app(join="join", on=ON, sel=PROJ, ann="", frm=None):
    frm = frm or (f"L#window.length({WINDOW}) {join} "
                  f"R#window.length({WINDOW})\n  on {on}")
    return f"{HEAD}{ann}\n@info(name='q')\nfrom {frm}\n{sel}\ninsert into Out;"


TABLE_APP = HEAD + """
@Index('symbol')
define table T (symbol long, qty int);
@info(name='fill') from R select symbol, qty insert into T;
@emit(rows='{cap}')
@info(name='q')
from L join T on L.symbol == T.symbol
select L.symbol as s, L.price as p, T.qty as v
insert into Out;"""


def traffic(seed, n_sends=6, events=24, symbols=4):
    """Sends to R and L in turn, wider than the window: [(side, rows)]."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_sends):
        sym = rng.integers(0, symbols, events).astype(np.int64)
        if i % 2 == 0:
            out.append(("R", list(zip(
                sym.tolist(), rng.integers(1, 9, events).tolist()))))
        else:
            out.append(("L", list(zip(
                sym.tolist(),
                rng.random(events).astype(np.float32).tolist()))))
    return out


# -- the reference: a nested loop over plain lists ------------------------

class NestedLoop:
    """Each arriving row against every held row of the OTHER side, as that
    side stood before the send; an unmatched arriving row of an outer side
    pairs with None, after the send's matched pairs (this system's delivery
    order).  A side holds its last `window` rows (None: all)."""

    def __init__(self, on, outer=(), window=WINDOW, triggers=("L", "R"),
                 keeps=("L", "R")):
        self.on, self.outer, self.window = on, outer, window
        self.triggers, self.keeps = triggers, keeps
        self.held = {"L": [], "R": []}

    def send(self, side, rows):
        other = "R" if side == "L" else "L"
        pairs, lone = [], []
        # what the send pushes out of its own side's window — rows of this
        # send among them, where it is wider than the window — joined, in
        # eviction order, as the arriving rows are (inner joins)
        own, self.expired = list(self.held[side]), []
        if side in self.triggers and side in self.keeps \
                and self.window is not None:
            for e in rows:
                if len(own) == self.window:
                    gone = own.pop(0)
                    self.expired += [
                        lr for h in self.held[other]
                        if self.on(*(lr := (gone, h) if side == "L"
                                     else (h, gone)))]
                own.append(e)
        if side in self.triggers:
            for e in rows:
                hits = 0
                for h in self.held[other]:
                    lr = (e, h) if side == "L" else (h, e)
                    if self.on(*lr):
                        pairs.append(lr)
                        hits += 1
                if not hits and side in self.outer:
                    lone.append((e, None) if side == "L" else (None, e))
        if side in self.keeps:
            held = self.held[side] + list(rows)
            self.held[side] = held if self.window is None \
                else held[-self.window:]
        return pairs + lone


def project(pairs):
    """`select L.symbol as s, L.price as p, R.qty as v`, None for a null."""
    return [(l[0] if l else None, l[1] if l else None, r[1] if r else None)
            for l, r in pairs]


def equi(l, r):
    return l[0] == r[0]


# -- the program under the same sends -------------------------------------

def drive(ql, sends, fastpath=True, monkeypatch=None):
    """{rows: a list a send of the CURRENT rows delivered while the send's
    call ran, in order; dropped: a count a send; slots: the payload widths
    seen (the cap, where the step compacted); plan: describe()}."""
    if monkeypatch is not None:
        monkeypatch.setattr(joinmod, "FASTPATH_ENABLED", fastpath)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(ql)
        errors, rows, dropped, removed, counts = [], [], [], [], []
        rt.set_exception_listener(errors.append)

        def on_events(_ts, cur, exp):
            rows.extend(tuple(e.data) for e in (cur or []))
            removed.extend(tuple(e.data) for e in (exp or []))
        rt.add_callback("q", on_events)
        slots = set()

        def on_batch(_ts, b):
            dropped.append(int(b["n_dropped"]))
            slots.add(int(np.asarray(b["valid"]).shape[0]))
            counts.append({k: int(b[k]) for k in (
                "n_valid", "n_current", "n_expired")})
        rt.add_batch_callback("q", on_batch)
        rt.start()
        # removed: the EXPIRED rows handed to the query callback, over the
        # whole run; counts: every emission's header
        out = {"rows": [], "dropped": [], "slots": slots,
               "removed": removed, "counts": counts}
        for i, (side, data) in enumerate(sends):
            r0, d0 = len(rows), len(dropped)
            cols = [np.asarray(c, dt) for c, dt in zip(
                zip(*data), (np.int64, np.float32 if side == "L"
                             else np.int32))]
            rt.get_input_handler(side).send_columns(
                cols, timestamps=np.full(len(data), 1000 + i, np.int64))
            out["rows"].append(rows[r0:])
            out["dropped"].append(sum(dropped[d0:]))
        rt.flush()
        out["flushed"] = rows[sum(map(len, out["rows"])):]
        qr = rt.query_runtimes["q"]
        out["plan"] = qr.planned.describe()
        out["fastpath"] = qr.planned.fastpath
        # both sides' window state after the last send, leaf by leaf
        out["windows"] = [np.asarray(x) for x in
                          jax.tree.leaves((qr.state[0], qr.state[1]))]
        # and the rows each side's window holds, oldest first: (ts, columns)
        out["resident"] = [resident_rows(side.window, st) for side, st in
                           ((qr.planned.left, qr.state[0]),
                            (qr.planned.right, qr.state[1]))
                           if side.window is not None and st]
        assert rt.explain("q")["plan"]["pair_rows_materialised"] == \
            out["plan"]["pair_rows_materialised"]
        assert not errors, errors[:1]
        return out
    finally:
        m.shutdown()


def resident_rows(window, state):
    """A window's rows, oldest first, whatever form its slab takes (a
    compacted buffer's alive rows by `add_seq`, a ring's from its tail)."""
    buf = window.current_buffer(state)
    if buf is None:
        return None
    alive = np.asarray(buf.alive)
    order = np.argsort(np.asarray(buf.add_seq)[alive], kind="stable")
    return [np.asarray(a)[alive][order].tolist()
            for a in (buf.ts,) + tuple(buf.cols)]


def f32(x):
    return float(np.float32(x))


# (app text at a cap, the cap, reference, fast path on at plan time, the
# probe the plan takes).  A cap UNDER the candidate rows (48 window rows a
# send x 8 lanes, or x the 8-row grid) and over the valid ones: the step
# compacts, and drops nothing.
CAP = 160
LATE_CASES = {
    "inner_bucket": (app(ann=f"@emit(rows='{CAP}')"), CAP,
                     lambda: NestedLoop(equi), True, "bucket"),
    "inner_grid": (app(ann=f"@emit(rows='{CAP}')"), CAP,
                   lambda: NestedLoop(equi), False, None),
    "table_probe": (TABLE_APP.format(cap=448), 448, lambda: NestedLoop(
        equi, window=None, triggers=("L",), keeps=("R",)), True, "table"),
    "left_outer": (app("left outer join", ann=f"@emit(rows='{CAP}')"), CAP,
                   lambda: NestedLoop(equi, outer=("L",)), True, "bucket"),
    "right_outer": (app("right outer join", ann=f"@emit(rows='{CAP}')"), CAP,
                    lambda: NestedLoop(equi, outer=("R",)), True, "bucket"),
    "full_outer": (app("full outer join", ann=f"@emit(rows='{CAP}')"), CAP,
                   lambda: NestedLoop(equi, outer=("L", "R")), True,
                   "bucket"),
    "full_outer_grid": (app("full outer join", ann=f"@emit(rows='{CAP}')"),
                        CAP, lambda: NestedLoop(equi, outer=("L", "R")),
                        False, None),
    "residual_conjunct": (
        app(on=f"{ON} and L.price > 0.4", ann=f"@emit(rows='{CAP}')"), CAP,
        lambda: NestedLoop(lambda l, r: l[0] == r[0] and l[1] > f32(0.4)),
        True, "bucket"),
}


@pytest.mark.parametrize("case", sorted(LATE_CASES))
def test_a_projection_join_delivers_the_nested_loops_rows_in_order(
        case, monkeypatch):
    ql, cap, make_ref, fastpath, mode = LATE_CASES[case]
    sends = traffic(seed=43)
    run = drive(ql, sends, fastpath, monkeypatch)
    assert run["fastpath"] == mode
    assert run["plan"]["pair_rows_materialised"] == "cap"
    # a payload `cap` slots wide: there were more candidate rows (the
    # table's first probe meets 12 candidates a row: 288 rows, under it)
    assert max(run["slots"]) == cap
    assert run["slots"] == {cap} or case == "table_probe"
    ref = make_ref()
    owed = 0
    for i, (side, data) in enumerate(sends):
        want = project(ref.send(side, data))
        assert run["rows"][i] == want, (case, i)
        owed += len(want)
    assert owed > 100 and run["dropped"] == [0] * len(sends)
    assert run["flushed"] == []


def test_a_fused_projection_join_delivers_the_same_rows(monkeypatch):
    """`@fuse(batches=2)` scans the raw step: the same rows in the same
    order, handed over when a stack drains."""
    sends = traffic(seed=44)
    run = drive(app(ann=f"@fuse(batches='2') @emit(rows='{CAP}')"), sends)
    assert run["plan"]["pair_rows_materialised"] == "cap"
    assert run["slots"] == {CAP}
    ref = NestedLoop(equi)
    want = [row for side, data in sends
            for row in project(ref.send(side, data))]
    got = [row for rows in run["rows"] for row in rows] + run["flushed"]
    assert got == want and len(want) > 100
    assert sum(run["dropped"]) == 0


@pytest.mark.parametrize("case,join,outer", [
    ("inner", "join", ()), ("full_outer", "full outer join", ("L", "R"))])
@pytest.mark.parametrize("fastpath", [True, False], ids=["bucket", "grid"])
def test_an_explicit_cap_delivers_the_first_rows_and_counts_the_rest(
        case, join, outer, fastpath, monkeypatch):
    """`@emit(rows='40')` under sends that make more: only CURRENT rows
    join (`expired_rows_joined` false), so the cut is exact — a send
    delivers the first `cap` of the nested loop's rows and `n_dropped` is
    the rest, whatever its windows expired meanwhile."""
    cap = 40
    sends = traffic(seed=45)
    run = drive(app(join, ann=f"@emit(rows='{cap}')"), sends, fastpath,
                monkeypatch)
    assert run["plan"]["pair_rows_materialised"] == "cap"
    assert run["plan"]["expired_rows_joined"] is False
    assert run["plan"]["emission_cap_rows"] == cap
    ref = NestedLoop(equi, outer=outer)
    cut = 0
    for i, (side, data) in enumerate(sends):
        want = project(ref.send(side, data))
        assert run["rows"][i] == want[:cap], (case, i)
        assert run["dropped"][i] == max(0, len(want) - cap), (case, i)
        cut += len(want) > cap
    assert cut >= 3


def test_an_explicit_cap_before_any_row_expires_is_an_exact_cut():
    """Windows that never fill expire nothing, so every valid joined row is
    a CURRENT one: delivered = the first `cap`, dropped = the rest."""
    cap = 24
    sends = [("R", [(i % 2, i + 1) for i in range(4)]),
             ("L", [(i % 2, f32(i / 10)) for i in range(8)]),
             ("R", [(0, 9)] * 2), ("L", [(1, f32(0.5))] * 6)]
    ql = app(ann=f"@emit(rows='{cap}')").replace(
        f"length({WINDOW})", "length(64)")
    run = drive(ql, sends)
    ref = NestedLoop(equi, window=64)
    for i, (side, data) in enumerate(sends):
        want = project(ref.send(side, data))
        assert run["rows"][i] == want[:cap], i
        assert run["dropped"][i] == max(0, len(want) - cap), i
    assert run["dropped"] == [0, 0, 0, 0] and len(run["rows"][1]) == 16
    # and with the cap under the rows of one send
    run = drive(ql.replace(f"rows='{cap}'", "rows='10'"), sends)
    assert [len(r) for r in run["rows"]] == [0, 10, 8, 10]
    assert run["dropped"] == [0, 6, 0, 2] and run["slots"] == {10}


def test_an_implicit_cap_grows_and_then_delivers_every_row(caplog):
    """No `@emit`: the cap is max(2 R, 1024) of N = R x 16 candidate rows
    (R = the send's 256 rows: CURRENT triggers alone); one symbol makes
    256 x 16 pairs a send, the overflow grows the cap once — to the rows
    the send made, no EXPIRED ones among them — and the next send of the
    same shape is delivered whole."""
    events, window = 256, 16
    ql = app().replace(f"length({WINDOW})", f"length({window})")
    sends = [("R", [(0, i % 8 + 1) for i in range(events)])] + [
        ("L", [(0, f32(i / events)) for i in range(events)])] * 3
    with caplog.at_level(logging.WARNING, logger="siddhi_tpu"):
        run = drive(ql, sends)
    assert run["plan"]["pair_rows_materialised"] == "cap"
    assert any("growing the cap" in r.getMessage() for r in caplog.records)
    ref = NestedLoop(equi, window=window)
    wants = [project(ref.send(side, data)) for side, data in sends]
    assert len(wants[1]) == events * window
    # the send that overflowed delivered a prefix and said what it dropped
    first = run["rows"][1]
    assert 0 < len(first) < len(wants[1]) and first == wants[1][:len(first)]
    assert run["dropped"][1] == len(wants[1]) - len(first)
    assert run["plan"]["emission_cap_rows"] == events * window
    assert run["rows"][3] == wants[3] and run["dropped"][3] == 0


# -- joins that keep today's order of operations --------------------------

def _no_expiry_sends():
    return [("R", [(i % 2, i + 1) for i in range(6)]),
            ("L", [(i % 2, f32((7 * i % 10) / 10)) for i in range(10)]),
            ("L", [(i % 2, f32((3 * i % 10) / 10)) for i in range(6)])]


def _wide(ql):
    return ql.replace(f"length({WINDOW})", "length(64)")


def test_an_aggregator_still_reads_every_row_past_the_cap():
    """A running count and sum over the joined rows: a row past the cap is
    not delivered and still counts — the next send's values start where ALL
    of this send's rows left them."""
    cap = 8
    sends = _no_expiry_sends()
    run = drive(_wide(app(sel="select count() as c, sum(R.qty) as t",
                          ann=f"@emit(rows='{cap}')")), sends)
    assert run["plan"]["pair_rows_materialised"] == "all"
    assert run["plan"]["selector_layout"] == "in_order"
    ref = NestedLoop(equi, window=64)
    count = total = 0
    for i, (side, data) in enumerate(sends):
        want = []
        for _l, r in ref.send(side, data):
            count, total = count + 1, total + r[1]
            want.append((count, total))
        assert run["rows"][i] == want[:cap], i
        assert run["dropped"][i] == max(0, len(want) - cap), i
    assert count == 48 and run["dropped"] == [0, 22, 10]


def test_a_having_decides_which_rows_count_against_the_cap():
    cap = 6
    sends = _no_expiry_sends()
    run = drive(_wide(app(sel=PROJ + " having p > 0.35",
                          ann=f"@emit(rows='{cap}')")), sends)
    assert run["plan"]["pair_rows_materialised"] == "all"
    ref = NestedLoop(equi, window=64)
    for i, (side, data) in enumerate(sends):
        want = [row for row in project(ref.send(side, data))
                if row[1] > f32(0.35)]
        assert run["rows"][i] == want[:cap], i
        assert run["dropped"][i] == max(0, len(want) - cap), i
    assert run["dropped"][1] > 0 and run["dropped"][2] > 0


def test_an_order_by_limit_ranks_the_whole_chunk():
    """`order by p desc limit 5` under `@emit(rows='8')` of 30 joined rows:
    the five best of ALL of them, not of the first eight."""
    sends = _no_expiry_sends()
    run = drive(_wide(app(sel=PROJ + " order by p desc limit 5",
                          ann="@emit(rows='8')")), sends)
    assert run["plan"]["pair_rows_materialised"] == "all"
    ref = NestedLoop(equi, window=64)
    ranked_past_the_cap = 0
    for i, (side, data) in enumerate(sends):
        want = project(ref.send(side, data))
        best = sorted(want, key=lambda row: -row[1])[:5]   # stable
        assert run["rows"][i] == best, i
        assert run["dropped"][i] == 0
        ranked_past_the_cap += best != sorted(
            want[:8], key=lambda row: -row[1])[:5]
    assert ranked_past_the_cap


@pytest.mark.parametrize("sel,want", [
    (PROJ, "cap"),
    (PROJ + " offset 2", "all"),
    (PROJ + " limit 3", "all"),
    ("select L.symbol as s, max(L.price) as p group by L.symbol", "all"),
])
def test_describe_says_which_path_a_plan_got(sel, want):
    run = drive(app(sel=sel), [("R", [(1, 2)]), ("L", [(1, f32(0.5))])])
    assert run["plan"]["pair_rows_materialised"] == want


# -- which window output rows are join triggers ---------------------------

def twin(ql, kinds="all"):
    """The same app with `insert <kinds> events into Out`."""
    assert ql.count("insert into Out;") == 1
    return ql.replace("insert into Out;", f"insert {kinds} events into Out;")


CURRENT_ONLY = dict(LATE_CASES, fused=(
    app(ann=f"@fuse(batches='2') @emit(rows='{CAP}')"), CAP,
    lambda: NestedLoop(equi), True, "bucket"))


@pytest.mark.parametrize("case", sorted(CURRENT_ONLY))
def test_a_current_only_projection_join_joins_its_current_rows_alone(
        case, monkeypatch):
    """`insert into` + a projection: the nested loop's rows in delivery
    order, no EXPIRED row in any emission's header or at the callback, and
    both windows left as the `insert all events` twin leaves them — which
    does make EXPIRED rows out of the same sends."""
    ql, _cap, make_ref, fastpath, mode = CURRENT_ONLY[case]
    sends = traffic(seed=46)
    run = drive(ql, sends, fastpath, monkeypatch)
    assert run["fastpath"] == mode
    assert run["plan"]["expired_rows_joined"] is False
    ref = make_ref()
    want = [project(ref.send(side, data)) for side, data in sends]
    if case == "fused":
        assert sum(run["rows"], []) + run["flushed"] == sum(want, [])
    else:
        assert run["rows"] == want and run["flushed"] == []
    assert sum(map(len, want)) > 100 and sum(run["dropped"]) == 0
    assert run["counts"] and run["removed"] == []
    for header in run["counts"]:
        assert header["n_expired"] == 0, header
        assert header["n_valid"] == header["n_current"], header
    both = drive(twin(ql), sends, fastpath, monkeypatch)
    assert both["plan"]["expired_rows_joined"] is True
    if mode != "table":          # a windowless stream side expires nothing
        assert sum(h["n_expired"] for h in both["counts"]) > 50
        assert both["removed"]
    assert len(run["windows"]) == len(both["windows"]) >= 1
    for i, (mine, theirs) in enumerate(zip(run["windows"],
                                           both["windows"])):
        np.testing.assert_array_equal(mine, theirs, err_msg=f"leaf {i}")


TABLE_OUT = app().replace(
    "define stream R", "define table Kept (s long, p float, v int);\n"
    "define stream R").replace("insert into Out;", "insert into Kept;")
# every join that keeps its EXPIRED trigger rows: (app text, whether the
# callback's EXPIRED rows are the nested loop's, row for row)
KEEPS_EXPIRED = {
    "all_events_bucket": (twin(app()), True, True),
    "all_events_grid": (twin(app()), False, True),
    "expired_events": (twin(app(), "expired"), True, True),
    "sum": (app(sel="select L.symbol as s, sum(R.qty) as v"), True, False),
    "having": (app(sel=PROJ + " having p > 0.35"), True, False),
    "order_by_limit": (app(sel=PROJ + " order by p desc limit 5"), True,
                       False),
    "output_rate": (app(sel=PROJ + "\noutput last every 3 events"), True,
                    False),
    "into_a_table": (TABLE_OUT, True, False),
}


@pytest.mark.parametrize("case", sorted(KEEPS_EXPIRED))
def test_every_other_join_keeps_its_expired_rows(case, monkeypatch):
    """`insert all events`, `insert expired events`, an aggregator (it needs
    the retraction), a `having`, an `order by ... limit`, an `output ...
    every` (it counts them) and a table as the target: the plan says the
    EXPIRED rows join, and they do — for the projections, the nested
    loop's, row for row, at the query callback."""
    ql, fastpath, exact = KEEPS_EXPIRED[case]
    sends = traffic(seed=47)
    run = drive(ql, sends, fastpath, monkeypatch)
    assert run["plan"]["expired_rows_joined"] is True
    assert sum(h["n_expired"] for h in run["counts"]) > 0
    if exact:
        ref = NestedLoop(equi)
        cur, gone = [], []
        for side, data in sends:
            cur += project(ref.send(side, data))
            gone += project(ref.expired)
        assert sum(run["rows"], []) == cur and len(gone) > 100
        assert run["removed"] == gone
        assert sum(h["n_expired"] for h in run["counts"]) == len(gone)
        assert sum(h["n_current"] for h in run["counts"]) == len(cur)
        assert sum(run["dropped"]) == 0


NAMED_BATCH = HEAD + """
define window W (symbol long, qty int) lengthBatch(4);
@info(name='fill') from R select symbol, qty insert into W;
@info(name='q')
from W unidirectional join L#window.length(8) on L.symbol == W.symbol
select L.symbol as s, L.price as p, W.qty as v
insert into Out;"""
TIME_APP = app(frm=f"L#window.time(1 sec) join R#window.time(1 sec)\n"
               f"  on {ON}")
# trigger windows whose CURRENT rows are NOT their arrivals: (app, the
# reference, the trigger side's processor, whether the twin's sends
# expire rows)
MASKED_CASES = {
    # a named `lengthBatch` window: its rows reach the join a batch late,
    # the batch before them as EXPIRED rows among them
    "named_length_batch": (NAMED_BATCH, lambda: NestedLoop(
        equi, triggers=("R",), keeps=("L",)), "PassAllWindow", True),
    # a time window under a CURRENT-only join is kept as a ring (since
    # PR 57: its arrivals are its CURRENT rows, in stamp order); nothing is
    # a second old here, so it holds every row
    "time_window": (TIME_APP, lambda: NestedLoop(equi, window=None),
                    "TimeRingWindow", False),
}


@pytest.mark.parametrize("case", sorted(MASKED_CASES))
def test_a_window_that_is_not_its_arrivals_joins_its_current_rows_masked(
        case):
    """The step keeps the window's output rows as its trigger rows and
    masks the EXPIRED ones: the nested loop's rows, no EXPIRED row made."""
    ql, make_ref, processor, expires = MASKED_CASES[case]
    sends = traffic(seed=48, n_sends=10)
    run = drive(ql, sends)
    assert run["plan"]["expired_rows_joined"] is False
    trigger = run["plan"]["left"]
    assert trigger["window_processor"] == processor
    ref = make_ref()
    want = [project(ref.send(side, data)) for side, data in sends]
    assert run["rows"] == want and sum(map(len, want)) > 100
    assert run["removed"] == [] and sum(run["dropped"]) == 0
    assert all(h["n_expired"] == 0 and h["n_valid"] == h["n_current"]
               for h in run["counts"])
    both = drive(twin(ql), sends)
    assert both["plan"]["expired_rows_joined"] is True
    assert both["rows"] == want
    assert (sum(h["n_expired"] for h in both["counts"]) > 50) == expires
    if processor == "TimeRingWindow":
        # another slab layout, the same rows in the same order
        assert run["resident"] == both["resident"] and run["resident"][0]
        return
    for mine, theirs in zip(run["windows"], both["windows"]):
        np.testing.assert_array_equal(mine, theirs)


# -- the deployed programs: no column is expanded over N rows -------------

_GATHER = re.compile(
    r'"?stablehlo\.gather"?\(.*->\s*tensor<(\d+)x(?:i|ui|f)\d+>')
_FLAGS = re.compile(r"tensor<(\d+)xi1>")


def gather_rows(text):
    """The rows of every rank-1 gather result in a lowered program's text."""
    return [int(m.group(1)) for line in text.splitlines()
            if (m := _GATHER.search(line))]


def candidate_rows(text):
    """N: the widest flag vector of a side program is its candidate pair
    rows' validity (the probe's `[R, Q]` mask, flat)."""
    return max(int(n) for n in _FLAGS.findall(text))


def deploy_w128(select=None, seed=5, kinds=None, debug_info=False):
    """`join_len128` at the source's window under sends eight windows wide
    (`w128_e1024`: a cap of 8,192 — at `rehearse_sizes` the cap IS the
    candidate rows and nothing is compacted), optionally with another select
    list or `insert <kinds> events into`: ({role: lowered text} of the two
    side programs — with their debug info, which names each op's section,
    where asked —, describe())."""
    sizes, events = cfg.SHAPES["w128_e1024"]
    ql = cfg.app_text("join_len128", sizes)
    if select is not None:
        assert PROJ in ql
        ql = ql.replace(PROJ, select)
    if kinds is not None:
        ql = twin(ql, kinds)
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(ql)
        errors = []
        rt.set_exception_listener(errors.append)
        rt.add_batch_callback(cfg.CONFIG["query"], lambda _ts, _b: None)
        rt.start()
        rng = np.random.default_rng(seed)
        for side, dtype in (("R", np.int32), ("L", np.float32)):
            rt.get_input_handler(side).send_columns(
                [rng.integers(0, 64, events).astype(np.int64),
                 rng.integers(1, 9, events).astype(dtype)],
                timestamps=np.full(events, 1000, np.int64))
        assert not errors, errors[:1]
        texts = {role: both[debug_info] for role, both
                 in cfg.side_step_texts(rt).items()}
        assert sorted(texts) == ["step[left]", "step[right]"]
        return texts, rt.query_runtimes[cfg.CONFIG["query"]].planned.describe()
    finally:
        m.shutdown()


def test_the_deployed_join_gathers_no_column_over_the_candidate_rows():
    texts, plan = deploy_w128()
    assert plan["pair_rows_materialised"] == "cap"
    cap = plan["emission_cap_rows"]
    for role, text in texts.items():
        n, sizes = candidate_rows(text), gather_rows(text)
        assert n >= 4 * cap, (role, n)
        # the columns, `ts`, `kind` and the `ri` pick, at the cap ...
        assert sizes.count(cap) >= 6, (role, sizes)
        # ... and no column over the N candidate rows (one such gather
        # would be `ri`'s pick; the step makes none), nor over any other
        # width above the cap
        assert sizes.count(n) == 0 and max(sizes) <= cap, (role, sizes)


def test_the_guard_sees_a_join_that_expands_its_columns():
    """The same count on the same deployment with an aggregator in the
    select list — a plan that keeps every candidate row — finds the N-row
    gathers: the guard above can fail."""
    texts, plan = deploy_w128(
        select="select L.symbol as s, L.price as p, sum(R.qty) as v")
    assert plan["pair_rows_materialised"] == "all"
    for role, text in texts.items():
        n = candidate_rows(text)
        assert n >= 4 * plan["emission_cap_rows"]
        assert gather_rows(text).count(n) >= 4, role


_RESULT = re.compile(r"->\s*\(?tensor<(\d+)[x>]")
_SORT = re.compile(r"stablehlo\.sort|call @argsort")
_BATCH = re.compile(r"%arg\d+: tensor<(\d+)xi64> [^%]*?loc\(\"ts\"\)")


def trigger_rows(named):
    """Of a side program's lowered text with debug info: (B, the rows the
    send stages; the sorts under `window_order`; the leading dimension
    of every result made under `join_probe`)."""
    (batch,) = set(_BATCH.findall(named))
    names = {m.group(1): m.group(2) for line in named.splitlines()
             if (m := _LOC.match(line))}
    sorts, probe_rows = 0, set()
    for line in named.splitlines():
        ref = _REF.search(line)
        scopes = names.get(ref.group(1), "").split("/") if ref else ()
        # `jnp.argsort` is a private function: the scope names its call
        if "window_order" in scopes and _SORT.search(line):
            sorts += 1
        if "join_probe" in scopes and (m := _RESULT.search(line)):
            probe_rows.add(int(m.group(1)))
    return int(batch), sorts, probe_rows


def test_the_deployed_join_takes_its_arrivals_as_trigger_rows():
    """The cell's app: the trigger rows are the B staged rows — no
    `window_order` sort, nothing with 2 B rows under `join_probe`, B x 16
    candidate flags."""
    texts, plan = deploy_w128(debug_info=True)
    assert plan["expired_rows_joined"] is False
    for role, named in texts.items():
        batch, sorts, probe_rows = trigger_rows(named)
        assert sorts == 0, role
        assert batch in probe_rows and max(probe_rows) == batch, (
            role, batch, sorted(probe_rows))
        assert candidate_rows(named) == batch * 16, role


def test_the_guard_sees_a_join_whose_expired_rows_are_triggers():
    """The same deployment with `insert all events into`: the window's 2 B
    output rows, sorted, are the trigger rows — the guard above can fail."""
    texts, plan = deploy_w128(kinds="all", debug_info=True)
    assert plan["expired_rows_joined"] is True
    assert plan["pair_rows_materialised"] == "cap"
    for role, named in texts.items():
        batch, sorts, probe_rows = trigger_rows(named)
        assert sorts == 1, role
        assert max(probe_rows) == 2 * batch, (role, sorted(probe_rows))
        assert candidate_rows(named) == 2 * batch * 16, role

"""The sliding time window's step (`TimeWindow.process` since PR 52: one
argsort and ONE gather of whole rows a section) held to the form it
replaced, which is kept below as the plain reference: one argsort of every
slot to merge the entries due with the arrivals and a scatter of the ranks,
`sort_rows` to order the emission (a second argsort of the same order), a
third argsort to compact the buffer, and a gather an array for both.

Both forms are handed the SAME state and rows at every step of a drive, and
everything a step returns is compared bit for bit at every slot — the
emission and its filler (an invalid row is a buffer slot or an arrival that
the sort moved last), the buffer's live AND dead slots, `nseq` and
`next_wakeup` — in time order and out of it: there is one path, so a
disordered send differs from any other in its data alone.  End to end the
same apps run on the window and on the reference patched into it: `insert
all events`, a keyed `partition with`, a join with `window.time` on both
sides, a `define window ... time`."""
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.compiler import SiddhiCompiler
from siddhi_tpu.core import event as ev
from siddhi_tpu.core import window as W
from siddhi_tpu.core.window import (
    BIG_SEQ, NO_WAKEUP, Buffer, Rows, TimeWindow, WindowOutput, concat_rows,
    gather_packed, sort_rows)
from siddhi_tpu.query_api.expression import Constant

from test_lengthbatch_state import assert_same

APP = "define stream S (symbol long, price float, volume long);"
T = 100               # the window, ms


def window(B, hint=64, t=T):
    schema = ev.Schema(SiddhiCompiler.parse(APP).stream_definition_map["S"],
                       ev.StringInterner())
    return TimeWindow(schema, [Constant(t, "INT")], B, capacity_hint=hint)


def sort_form(win, state, rows, now):
    """`TimeWindow.process` as it stood at b993d59 (PR 51), scopes and
    all."""
    buf, seq0 = state
    C = win.capacity
    B = rows.capacity
    t = win.time_ms
    with jax.named_scope("window_fill"):
        is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)
        exp_due = jnp.logical_and(buf.alive, buf.expire_ts <= now)
        em_ts = jnp.concatenate([buf.expire_ts, rows.ts])
        em_pri = jnp.concatenate([jnp.zeros((C,), jnp.int64),
                                  jnp.ones((B,), jnp.int64)])
        em_valid = jnp.concatenate([exp_due, is_cur])
        em_key = jnp.where(em_valid, em_ts * 2 + em_pri, BIG_SEQ)
        order = jnp.argsort(em_key, stable=True)      # [C+B]
        rank = jnp.zeros((C + B,), jnp.int64).at[order].set(
            jnp.arange(C + B, dtype=jnp.int64))
        seqs = seq0 + rank
        exp_rows = Rows(
            ts=buf.expire_ts,
            kind=jnp.full((C,), ev.EXPIRED, jnp.int32),
            valid=exp_due,
            seq=seqs[:C],
            gslot=buf.gslot,
            cols=buf.cols,
        )
        cur_rows = Rows(
            ts=rows.ts, kind=jnp.full((B,), ev.CURRENT, jnp.int32),
            valid=is_cur, seq=seqs[C:], gslot=rows.gslot,
            cols=rows.cols,
        )
        both = concat_rows(exp_rows, cur_rows)
    out = sort_rows(both)
    with jax.named_scope("window_state"):
        keep_old = jnp.logical_and(buf.alive, jnp.logical_not(exp_due))
        cand_ts = jnp.concatenate([buf.ts, rows.ts])
        cand_add = jnp.concatenate([buf.add_seq, seqs[C:]])
        cand_expts = jnp.concatenate([buf.expire_ts, rows.ts + t])
        cand_gslot = jnp.concatenate([buf.gslot, rows.gslot])
        cand_cols = tuple(jnp.concatenate([bc, rc])
                          for bc, rc in zip(buf.cols, rows.cols))
        cand_valid = jnp.concatenate([keep_old, is_cur])
        cand_key = jnp.where(cand_valid, cand_add, BIG_SEQ)
        corder = jnp.argsort(cand_key)                # oldest first
        total = jnp.sum(cand_valid.astype(jnp.int64))
        drop = jnp.maximum(total - C, 0)
        sel = jnp.clip(jnp.arange(C, dtype=jnp.int64) + drop, 0,
                       C + B - 1)
        pos = corder[sel.astype(jnp.int32)]
        svalid = (jnp.arange(C, dtype=jnp.int64) + drop) < total
        nbuf = Buffer(
            ts=cand_ts[pos],
            add_seq=jnp.where(svalid, cand_add[pos], BIG_SEQ),
            expire_seq=jnp.full((C,), BIG_SEQ, jnp.int64),
            expire_ts=jnp.where(svalid, cand_expts[pos], BIG_SEQ),
            alive=svalid, gslot=cand_gslot[pos],
            cols=tuple(c[pos] for c in cand_cols),
        )
        nseq = seq0 + rank.max() + 1
        nseq = jnp.where(jnp.any(em_valid), nseq, seq0)
        wake = jnp.min(jnp.where(nbuf.alive, nbuf.expire_ts, NO_WAKEUP))
    return ((nbuf, nseq), WindowOutput(out, nbuf, wake))


# -- a step's rows -----------------------------------------------------------------

def rows_of(ts, rng, valid=None, kind=None):
    """One step's input rows: the timestamps given, every other slot of
    every column random — the invalid ones too."""
    ts = np.asarray(ts, np.int64)
    B = ts.shape[0]
    return Rows(
        ts=jnp.asarray(ts),
        kind=jnp.asarray(np.full(B, ev.CURRENT, np.int32)
                         if kind is None else np.asarray(kind, np.int32)),
        valid=jnp.asarray(np.ones(B, bool) if valid is None
                          else np.asarray(valid, bool)),
        seq=jnp.zeros((B,), jnp.int64),
        gslot=jnp.asarray(rng.integers(-1, 6, B).astype(np.int32)),
        cols=(jnp.asarray(rng.integers(0, 256, B).astype(np.int64)),
              jnp.asarray((10 + rng.integers(0, 400, B) / 8).astype(
                  np.float32)),
              jnp.asarray(rng.integers(1, 1000, B).astype(np.int64))))


def timer(B, rng):
    """The scheduler's TIMER batch: one valid TIMER row, no arrival."""
    kind = np.full(B, ev.TIMER, np.int32)
    valid = np.zeros(B, bool)
    valid[0] = True
    return rows_of(np.zeros(B, np.int64), rng, valid, kind)


def spread(start, span, B):
    """B timestamps spread evenly over [start, start + span), in order."""
    return start + (np.arange(B, dtype=np.int64) * span) // B


# A drive is a list of steps `(rows, now)`.  Each maker returns (window,
# steps).

def steady(rng, B=16, hint=64, sends=14, span=40):
    """Sends that abut: from the fourth on every send expires about as
    many rows as it brings, between its own arrivals."""
    steps, clock = [], 1000
    for _ in range(sends):
        ts = spread(clock, span, B)
        steps.append((rows_of(ts, rng), ts[-1]))
        clock += span
    return window(B, hint), steps


def gap(rng):
    """Steady sends, then one 500 ms later: the whole window expires in
    front of its first arrival; then the window fills again."""
    win, steps = steady(rng, sends=5)
    clock = int(steps[-1][1]) + 500
    for _ in range(4):
        ts = spread(clock, 40, 16)
        steps.append((rows_of(ts, rng), ts[-1]))
        clock += 40
    return win, steps


def timer_steps(rng):
    """TIMER batches of the scheduler's 8 rows between the sends: one that
    expires a part, one nothing (`now` before every expiry), one all."""
    win, steps = steady(rng, sends=3)
    last = int(steps[-1][1])
    steps.append((timer(8, rng), last + T - 60))     # a part
    steps.append((timer(8, rng), last + T - 60))     # nothing more
    ts = spread(last + T - 50, 40, 16)
    steps.append((rows_of(ts, rng), ts[-1]))
    steps.append((timer(8, rng), int(ts[-1]) + 5 * T))   # all
    steps.append((timer(8, rng), int(ts[-1]) + 6 * T))   # empty window
    return win, steps


def holes(rng):
    """A filter before the window leaves holes in `valid`, and TIMER rows
    stand among the CURRENT ones."""
    steps, clock = [], 1000
    for i in range(12):
        ts = spread(clock, 40, 16)
        valid = rng.random(16) < 0.6
        kind = np.full(16, ev.CURRENT, np.int32)
        kind[rng.random(16) < 0.15] = ev.TIMER
        if i == 5:
            valid[:] = False            # a send the filter empties
        # the rows the filter dropped may carry any timestamp
        ts = np.where(valid, ts, rng.integers(0, 5000, 16))
        steps.append((rows_of(ts, rng, valid, kind), clock + 39))
        clock += 40
    return window(16), steps


def expiry_at_an_arrival(rng):
    """Arrivals whose timestamp EQUALS an entry's expiry time (the entry
    goes first), several at one millisecond, several expiries at one."""
    steps = []
    first = np.repeat(np.asarray([1000, 1003, 1003, 1010], np.int64), 4)
    steps.append((rows_of(first, rng), 1010))
    second = np.repeat(np.asarray([1100, 1103, 1104, 1110], np.int64), 4)
    steps.append((rows_of(second, rng), 1110))
    third = np.repeat(np.asarray([1200, 1200, 1204, 1210], np.int64), 4)
    steps.append((rows_of(third, rng), 1210))
    return window(16), steps


def overflow(rng, with_expiries):
    """A slab of 32 rows fed 16 a send, 1 ms apart: it fills in two sends
    and every later one drops the oldest rows — `with_expiries`: two sends
    far enough on that the slab's oldest rows expire in the step that
    drops the next oldest."""
    steps, clock = [], 1000
    for i in range(8):
        if with_expiries and i in (3, 6):
            clock += 57
        ts = spread(clock, 16, 16)
        steps.append((rows_of(ts, rng), ts[-1]))
        clock += 16
    return window(16, hint=32), steps


def few_live_rows(rng):
    """B larger than the live rows: sends of 32 slots that carry two or
    three rows each, far enough apart that at most a handful are alive."""
    steps, clock = [], 1000
    for _ in range(10):
        valid = np.zeros(32, bool)
        valid[rng.choice(32, int(rng.integers(2, 4)), replace=False)] = True
        ts = spread(clock, 30, 32)
        steps.append((rows_of(ts, rng, valid), ts[-1]))
        clock += 45
    return window(32), steps


def now_before_every_expiry(rng):
    """Wall-clock steps whose `now` is behind the rows' own timestamps:
    nothing is due, whatever the arrivals carry."""
    steps, clock = [], 1000
    for _ in range(4):
        ts = spread(clock, 40, 16)
        steps.append((rows_of(ts, rng), 900))
        clock += 40
    return window(16, hint=128), steps


def now_ahead_of_the_rows(rng):
    """Wall-clock steps whose `now` runs ahead of the rows' timestamps:
    entries are due that expire AFTER every arrival of the step."""
    steps, clock = [], 1000
    for _ in range(8):
        ts = spread(clock, 40, 16)
        steps.append((rows_of(ts, rng), int(ts[-1]) + 70))
        clock += 40
    return window(16), steps


def disorder_inside_a_send(rng):
    """One send's timestamps shuffled, then sends in order again."""
    win, steps = steady(rng, sends=4)
    clock = int(steps[-1][1]) + 1
    ts = spread(clock, 40, 16)
    shuffled = ts.copy()
    shuffled[[3, 9]] = shuffled[[9, 3]]
    steps.append((rows_of(shuffled, rng), ts[-1]))
    for _ in range(4):
        clock += 40
        ts = spread(clock, 40, 16)
        steps.append((rows_of(ts, rng), ts[-1]))
    return win, steps


def disorder_across_sends(rng):
    """A send 60 ms EARLIER than the one before it: its rows stand in the
    buffer behind rows that expire after them, and leave it before them;
    then sends in order until the disorder has left the window."""
    win, steps = steady(rng, sends=4)
    clock = int(steps[-1][1]) + 1
    late = spread(clock - 60, 10, 16)
    steps.append((rows_of(late, rng), clock))
    ts = spread(clock, 40, 16)
    steps.append((rows_of(ts, rng), ts[-1]))
    steps.append((timer(8, rng), int(ts[-1]) + 1))
    clock += 40 + T                      # the disorder has expired by now
    for _ in range(4):
        ts = spread(clock, 40, 16)
        steps.append((rows_of(ts, rng), ts[-1]))
        clock += 40
    return win, steps


def a_late_row_among_filtered_ones(rng):
    """One timestamp 500 ms behind the clock, first in a slot the filter
    dropped, then in a valid one: due at once, it is emitted CURRENT and
    expires in the NEXT step, ahead of every other entry."""
    win, steps = steady(rng, sends=3)
    clock = int(steps[-1][1]) + 1
    for late_is_valid in (False, True):
        ts = spread(clock, 40, 16)
        ts[7] = clock - 500
        valid = np.ones(16, bool)
        valid[7] = late_is_valid
        steps.append((rows_of(ts, rng, valid), ts[-1]))
        clock += 40
    return win, steps


def many_live_rows(rng):
    """The window of a real deployment in small: sends of 128 rows over
    8 ms each, 1,600 live in a slab of 2,048 — from the thirteenth on every
    send expires as many rows as it brings, between its own arrivals —
    then a gap, and one send out of order."""
    steps, clock = [], 10_000
    for i in range(24):
        if i == 20:
            clock += 3 * T
        ts = spread(clock, 8, 128)
        if i == 22:
            ts = ts[::-1].copy()
        steps.append((rows_of(ts, rng), ts[-1]))
        clock += 8
    return window(128, hint=2048), steps


DRIVES = {
    "steady_sends": steady,
    "sends_of_one_row": lambda rng: steady(rng, B=1, hint=8, sends=12,
                                           span=30),
    "a_gap_longer_than_the_window": gap,
    "timer_steps_with_no_arrival": timer_steps,
    "holes_in_valid": holes,
    "expiry_equal_to_an_arrivals_timestamp": expiry_at_an_arrival,
    "overflow_drops_the_oldest": lambda rng: overflow(rng, False),
    "overflow_with_expiries_in_the_step": lambda rng: overflow(rng, True),
    "b_larger_than_the_live_rows": few_live_rows,
    "now_before_every_expiry": now_before_every_expiry,
    "now_ahead_of_the_rows": now_ahead_of_the_rows,
    "disorder_inside_a_send": disorder_inside_a_send,
    "disorder_across_sends_then_order": disorder_across_sends,
    "a_late_row_the_filter_dropped": a_late_row_among_filtered_ones,
    "many_live_rows": many_live_rows,
}


# -- comparing ---------------------------------------------------------------------

def assert_step_equal(new, ref, where):
    """Everything a step returns, bit for bit: the dead slots too."""
    (nbuf, nseq), nout = new
    (rbuf, rseq), rout = ref
    assert_same(nout.rows, rout.rows, (where, "emission"))
    assert_same((nseq, nout.next_wakeup), (rseq, rout.next_wakeup),
                (where, "nseq, next_wakeup"))
    assert_same(nbuf, rbuf, (where, "state"))
    assert_same(nout.buffer, rout.buffer, (where, "exposed buffer"))
    # what every step leaves, and a join's probe reads: the live rows a
    # prefix, oldest first
    alive = np.asarray(nbuf.alive)
    n = int(alive.sum())
    assert alive[:n].all() and not alive[n:].any(), where
    assert (np.diff(np.asarray(nbuf.add_seq)[:n]) > 0).all(), where


def drive(win, steps, where):
    """The window over `steps` from `init_state`, each step's two forms on
    the same input; returns the state at the end."""
    new = jax.jit(win.process)
    ref = jax.jit(lambda s, r, t: sort_form(win, s, r, t))
    state = win.init_state()
    for i, (rows, now) in enumerate(steps):
        now = jnp.asarray(now, jnp.int64)
        done = new(state, rows, now)
        assert_step_equal(done, ref(state, rows, now), (where, i))
        state = done[0]
    return state


@pytest.mark.parametrize("name", sorted(DRIVES))
def test_rows_and_state_equal_the_sort_forms_step_by_step(name):
    rng = np.random.default_rng([52, sorted(DRIVES).index(name)])
    win, steps = DRIVES[name](rng)
    drive(win, steps, name)


def test_each_case_meets_what_its_name_says():
    """The drives are what they claim: rows expire inside sends, a slab
    overflows, an expiry stands at an arrival's timestamp, B exceeds the
    live rows ..."""
    seen = {}
    for name in sorted(DRIVES):
        rng = np.random.default_rng([52, sorted(DRIVES).index(name)])
        win, steps = DRIVES[name](rng)
        step = jax.jit(win.process)
        state, facts = win.init_state(), []
        newest = -1
        for rows, now in steps:
            before = jax.device_get(state[0])
            state, out = step(state, rows, jnp.asarray(now, jnp.int64))
            r = jax.device_get(out.rows)
            cur = np.asarray(rows.valid) & (np.asarray(rows.kind) == ev.CURRENT)
            exp = r.valid & (r.kind == ev.EXPIRED)
            arrived = set(np.asarray(rows.ts)[cur].tolist())
            facts.append({
                "live_before": int(before.alive.sum()), "ncur": int(cur.sum()),
                "expired": int(exp.sum()),
                "dropped": int(before.alive.sum()) + int(cur.sum())
                - int(exp.sum()) - int(np.asarray(state[0].alive).sum()),
                "tie": bool(arrived & set(r.ts[exp].tolist())),
                # an EXPIRED row stands between two CURRENT ones
                "interleaved": bool(
                    exp.any() and cur.any() and
                    np.flatnonzero(exp).min() <
                    np.flatnonzero(r.valid & (r.kind == ev.CURRENT)).max()
                    and np.flatnonzero(exp).max() >
                    np.flatnonzero(r.valid & (r.kind == ev.CURRENT)).min()),
                # an arrival older than one before it, in this send or an
                # earlier one
                "late": bool(cur.any() and (np.minimum.accumulate(
                    np.asarray(rows.ts)[cur][::-1])[::-1] <
                    np.maximum.accumulate(np.concatenate(
                        [[newest], np.asarray(rows.ts)[cur][:-1]]))).any()),
                # live rows that expire before a row that arrived earlier
                "buffer_out_of_expiry_order": bool((np.diff(
                    before.expire_ts[before.alive]) < 0).any()),
                "capacity": win.capacity})
            newest = max([newest] + np.asarray(rows.ts)[cur].tolist())
        seen[name] = facts
    assert sum(f["interleaved"] for f in seen["steady_sends"]) >= 8
    assert any(f["expired"] == f["live_before"] > 0 and f["ncur"] > 0
               for f in seen["a_gap_longer_than_the_window"])
    assert any(f["ncur"] == 0 and 0 < f["expired"] < f["live_before"]
               for f in seen["timer_steps_with_no_arrival"])
    assert any(f["ncur"] == 0 and f["expired"] == 0 and f["live_before"] > 0
               for f in seen["timer_steps_with_no_arrival"])
    assert seen["steady_sends"][0]["live_before"] == 0
    assert any(f["tie"] for f in
               seen["expiry_equal_to_an_arrivals_timestamp"])
    assert all(f["dropped"] == 0 for n, fs in seen.items() for f in fs
               if not n.startswith("overflow"))
    assert any(f["dropped"] > 0 and f["expired"] == 0
               for f in seen["overflow_drops_the_oldest"])
    assert any(f["dropped"] > 0 and f["expired"] > 0
               for f in seen["overflow_with_expiries_in_the_step"])
    assert all(f["live_before"] < 32 == f["capacity"] // 2
               for f in seen["b_larger_than_the_live_rows"])
    assert all(f["expired"] == 0 for f in seen["now_before_every_expiry"])
    assert any(f["ncur"] == 0 for f in seen["holes_in_valid"])
    disordered = ("disorder_inside_a_send", "disorder_across_sends_then_order",
                  "a_late_row_the_filter_dropped", "many_live_rows")
    for name, facts in seen.items():
        assert any(f["late"] for f in facts) == (name in disordered), name
    across = seen["disorder_across_sends_then_order"]
    assert any(f["buffer_out_of_expiry_order"] and f["expired"] > 0
               for f in across)
    assert not across[-1]["buffer_out_of_expiry_order"]
    many = seen["many_live_rows"]
    assert max(f["live_before"] for f in many) > 1300
    assert sum(f["expired"] > 100 and f["interleaved"] for f in many) >= 4


def test_the_keyed_window_gives_each_key_its_own_drive():
    """`planner.kstep` runs `process` under `jax.vmap` over a send's keys
    (`now` shared): every key gets the rows and state of its own drive."""
    names = ["steady_sends", "disorder_across_sends_then_order",
             "holes_in_valid", "a_gap_longer_than_the_window"]
    made = []
    for lane, name in enumerate(names):
        rng = np.random.default_rng([52, 7, lane])
        win, steps = DRIVES[name](rng)
        made.append([s for s in steps if s[0].capacity == 16][:9])
    win = window(16)
    new = jax.jit(jax.vmap(win.process, in_axes=(0, 0, 0)))
    ref = jax.jit(jax.vmap(lambda s, r, t: sort_form(win, s, r, t),
                           in_axes=(0, 0, 0)))
    state = jax.tree.map(lambda x: jnp.stack([x] * len(names)),
                         win.init_state())
    for i in range(9):
        rows = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[lane[i][0] for lane in made])
        now = jnp.asarray([int(lane[i][1]) for lane in made], jnp.int64)
        done = new(state, rows, now)
        want = ref(state, rows, now)
        for lane in range(len(names)):
            pick = lambda x: x[lane]              # noqa: E731
            assert_step_equal(jax.tree.map(pick, done),
                              jax.tree.map(pick, want), (names[lane], i))
        state = done[0]


def test_a_parent_shaped_state_goes_on_through_a_snapshot():
    """The state's shape did not change: the sort form's state, through
    what `rt.snapshot()` / `restore()` do to it, is the window's own, and
    the window goes on from it."""
    rng = np.random.default_rng(521)
    win, steps = steady(rng, sends=10)
    ref = jax.jit(lambda s, r, t: sort_form(win, s, r, t))
    state = win.init_state()
    for rows, now in steps[:5]:
        state, _ = ref(state, rows, jnp.asarray(now, jnp.int64))
    blob = pickle.dumps(jax.tree.map(np.asarray, jax.device_get(state)))
    state = jax.tree.map(jnp.asarray, pickle.loads(blob))
    assert jax.tree.structure(state) == jax.tree.structure(win.init_state())
    new = jax.jit(win.process)
    for i, (rows, now) in enumerate(steps[5:]):
        now = jnp.asarray(now, jnp.int64)
        done = new(state, rows, now)
        assert_step_equal(done, ref(state, rows, now), i)
        state = done[0]


# -- the moves: whole rows, once a section -----------------------------------------

@pytest.mark.parametrize("batched", [False, True], ids=["rows", "keys_x_rows"])
def test_gather_packed_is_the_gather_of_each_array(batched):
    """Every dtype a window's row carries — i64 (negative, above 2**32),
    i32, f32 (NaN, -0.0, inf by their bits), bool — comes back as `a[idx]`
    gives it, alone and under `vmap`."""
    rng = np.random.default_rng(522)
    shape = (3, 300) if batched else (300,)
    f = rng.standard_normal(shape).astype(np.float32)
    f[..., :4] = [np.nan, -0.0, np.inf, -np.inf]
    arrays = (
        jnp.asarray(rng.integers(-2**62, 2**62, shape)),
        jnp.asarray(rng.integers(-2**31, 2**31 - 1, shape).astype(np.int32)),
        jnp.asarray(f), jnp.asarray(rng.random(shape) < 0.5),
        jnp.asarray(np.full(shape, int(BIG_SEQ))))
    idx = jnp.asarray(rng.integers(0, 300, shape[:-1] + (70,)).astype(
        np.int32))
    fn = jax.vmap(gather_packed) if batched else gather_packed
    take = jax.vmap(lambda a, i: a[i]) if batched else (lambda a, i: a[i])
    got = jax.jit(fn)(arrays, idx)
    for g, a in zip(got, arrays):
        assert g.dtype == a.dtype
        assert_same(g, take(a, idx), str(a.dtype))


def test_a_step_is_three_sorts_two_row_gathers_and_no_scatter(monkeypatch):
    """What the v5e charged the sort form for is gone from the program: the
    scatter of the ranks, `sort_rows`' second sort of the same order, and
    the sixteen gathers an array.  What is left: the merge's argsort, its
    inverse, the compaction's, and one gather of whole rows a section (the
    compaction reads its order by a slice)."""
    win = window(16)
    rng = np.random.default_rng(3)
    rows = rows_of(spread(1000, 40, 16), rng)
    state, now = win.init_state(), jnp.asarray(1040, jnp.int64)

    def prims(fn):
        seen = {}

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name in ("sort", "gather", "scatter",
                                          "scatter-add"):
                    seen.setdefault(eqn.primitive.name, []).append(
                        eqn.outvars[0].aval.shape)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub)
        walk(jax.make_jaxpr(fn)(state, rows, now).jaxpr)
        return seen
    N = win.capacity + 16
    got = prims(win.process)
    assert sorted(got) == ["gather", "sort"]
    assert len(got["sort"]) == 3
    # i64 ts + bool + i32 gslot + (i64, f32, i64) = 9 planes emitted;
    # three i64 stamps + i32 gslot + the columns = 12 kept
    assert sorted(got["gather"]) == sorted([(9, N), (12, win.capacity)])
    was = prims(lambda s, r, n: sort_form(win, s, r, n))
    assert len(was["sort"]) == 3 and len(was["scatter"]) == 1
    assert len(was["gather"]) == 9 + 1 + 7

    def not_called(_rows):
        raise AssertionError("TimeWindow.process called sort_rows")
    monkeypatch.setattr(W, "sort_rows", not_called)
    jax.make_jaxpr(win.process)(state, rows, now)


# -- end to end: the apps that run the window, on it and on the reference ----------

class _SortFormWindow(TimeWindow):
    """`TimeWindow` with the reference for a step."""

    def process(self, state, rows, now):
        return sort_form(self, state, rows, now)


APPS = {
    "insert_all_events": ("""
        @app:playback
        define stream S (symbol long, price float, volume long);
        @info(name='q') from S#window.time(100)
        select symbol, price, sum(price) as total, count() as n
        insert all events into Out;""", "q"),
    "insert_expired_events": ("""
        @app:playback
        define stream S (symbol long, price float, volume long);
        @info(name='q') from S[price > 15.0]#window.time(100)
        select symbol, price, volume
        insert expired events into Out;""", "q"),
    "group_by_having": ("""
        @app:playback
        define stream S (symbol long, price float, volume long);
        @info(name='q') from S#window.time(100)
        select symbol, sum(price) as total, count() as n, avg(price) as ap
        group by symbol having total > 60.0
        insert into Out;""", "q"),
    "partition_with_a_keyed_window": ("""
        @app:playback
        define stream S (symbol long, price float, volume long);
        partition with (symbol of S) begin
        @info(name='q') from S#window.time(100)
        select symbol, sum(price) as total, count() as n
        insert all events into Out;
        end;""", "q"),
    "join_time_windows_on_both_sides": ("""
        @app:playback
        define stream S (symbol long, price float, volume long);
        define stream R (symbol long, price float, volume long);
        @info(name='q') from S#window.time(100) as a join
        R#window.time(60) as b on a.symbol == b.symbol
        select a.symbol as symbol, a.price as ap, b.price as bp
        insert all events into Out;""", "q"),
    "define_window_time": ("""
        @app:playback
        define stream S (symbol long, price float, volume long);
        define window W (symbol long, price float, volume long) time(100)
        output all events;
        from S insert into W;
        @info(name='q') from W
        select symbol, sum(price) as total, count() as n
        insert all events into Out;""", "q"),
}


def traffic(name, rng):
    """[(stream, columns, timestamps)]: 14 sends of 24 rows, 8 symbols,
    sends abutting at 40 ms — one a gap, one out of time order."""
    out, clock = [], 1000
    for i in range(14):
        if i == 6:
            clock += 400
        ts = spread(clock, 40, 24)
        if i == 10:
            ts = ts[::-1].copy()
        cols = [rng.integers(0, 8, 24).astype(np.int64),
                (10 + rng.integers(0, 400, 24) / 8).astype(np.float32),
                rng.integers(1, 1000, 24).astype(np.int64)]
        stream = "R" if name.startswith("join") and i % 3 == 1 else "S"
        out.append((stream, cols, ts))
        clock += 40
    return out


def run_app(name, sends):
    text, query = APPS[name]
    got = []
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime(text)
        errors = []
        rt.set_exception_listener(errors.append)
        rt.add_callback(query, lambda ts, ins, outs: got.append((
            ts, [(e.timestamp, tuple(e.data)) for e in ins or []],
            [(e.timestamp, tuple(e.data)) for e in outs or []])))
        rt.start()
        for stream, cols, ts in sends:
            rt.get_input_handler(stream).send_columns(
                [c.copy() for c in cols], timestamps=ts.copy())
        rt.flush()
        assert not errors, errors[:1]
    finally:
        m.shutdown()
    return got


@pytest.mark.parametrize("name", sorted(APPS))
def test_an_app_delivers_what_it_delivered_on_the_sort_form(name, monkeypatch):
    sends = traffic(name, np.random.default_rng([52, len(name)]))
    got = run_app(name, sends)
    monkeypatch.setitem(W.WINDOW_TYPES, "time", _SortFormWindow)
    want = run_app(name, sends)
    assert sum(len(i) + len(o) for _, i, o in want) > 100
    assert got == want

"""The buffers a `lengthBatch(n)` window keeps — the pending batch and the
previous batch — held, leaf for leaf and filler included, to the scatter form
they were built by until PR 44, which is kept below as the plain reference:
every candidate (`concat(pending, arrivals)`, n + B rows) scattered by rank
into a fresh buffer, six arrays a buffer, `mode="drop"`.  The window itself
gathers each buffer's rows by DESTINATION (one rank lookup, 2n rows an
array); both forms are carried side by side across consecutive steps from
`init_state`, state and emitted `Rows` compared after every step, under
`jax.vmap` over keys as the keyed window of a partition calls it, and
across a snapshot swapped between the two.  The lowered `jit_plain_step`
carries no scatter under `window_state` and no gather wider than 2n there."""
import pickle
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu.compiler import SiddhiCompiler
from siddhi_tpu.core import event as ev
from siddhi_tpu.core.window import (
    NO_WAKEUP, Buffer, LengthBatchWindow, Rows, WindowOutput,
    concat_rows, empty_buffer, sort_rows)
from siddhi_tpu.query_api.expression import Constant

import test_lengthbatch_config as cfg

APP = "define stream S (symbol string, price float, volume long);"
STEPS = 10
SWAP_AT = 5           # the step before which the two forms trade snapshots
KEYS = 4
# (window length n, rows a step B)
SHAPES = {"b_below_n": (10, 4), "b_equals_n": (8, 8), "b_multiple_of_n": (8, 32),
          "n10_b1024": (10, 1024), "n1000_b4096": (1000, 4096),
          "n_is_one": (1, 16)}
# what a step's rows look like, in turn: `dense` fills every slot with a
# CURRENT row, `filtered` leaves holes and TIMER rows as a filter upstream
# does, `one_batch` and `few` scatter n and n // 3 CURRENT rows among
# invalid ones, `empty` has no CURRENT row at all
DRIVE = ("dense", "filtered", "one_batch", "empty", "few", "dense",
         "filtered", "few", "one_batch", "dense")


def window(n, B):
    schema = ev.Schema(SiddhiCompiler.parse(APP).stream_definition_map["S"],
                       ev.StringInterner())
    return LengthBatchWindow(schema, [Constant(n, "INT")], B)


def scatter_form(win, state, rows, now):
    """`LengthBatchWindow.process` as it stood at 19838ab (PR 43): the
    emission's code is the window's own to this day, the state is scattered."""
    pend, prev, seq0 = state
    n = win.length
    B = rows.capacity
    is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)
    ncur = jnp.sum(is_cur.astype(jnp.int64))
    fill0 = jnp.sum(pend.alive.astype(jnp.int64))
    k = jnp.cumsum(is_cur.astype(jnp.int64)) - 1
    g = fill0 + k
    batch_idx = g // n
    nflush = (fill0 + ncur) // n
    span = 2 * n + 2
    flushed_cur = jnp.logical_and(is_cur, batch_idx < nflush)
    pos_in_batch = g % n
    cur_seq = seq0 + batch_idx * span + n + 1 + pos_in_batch
    pend_flush = jnp.logical_and(pend.alive, nflush > 0)
    pend_rank = jnp.cumsum(pend.alive.astype(jnp.int64)) - 1
    pend_seq = seq0 + 0 * span + n + 1 + pend_rank
    cur_rows = Rows(
        ts=jnp.concatenate([pend.ts, rows.ts]),
        kind=jnp.full((n + B,), ev.CURRENT, jnp.int32),
        valid=jnp.concatenate([pend_flush, flushed_cur]),
        seq=jnp.concatenate([pend_seq, cur_seq]),
        gslot=jnp.concatenate([pend.gslot, rows.gslot]),
        cols=tuple(jnp.concatenate([pc, rc])
                   for pc, rc in zip(pend.cols, rows.cols)))
    prev_rank = jnp.cumsum(prev.alive.astype(jnp.int64)) - 1
    prev_valid = jnp.logical_and(prev.alive, nflush > 0)
    prev_seq = seq0 + prev_rank
    arr_exp_valid = jnp.logical_and(is_cur, batch_idx + 1 < nflush)
    arr_exp_seq = seq0 + (batch_idx + 1) * span + pos_in_batch
    pend_exp_valid = jnp.logical_and(pend.alive, nflush > 1)
    pend_exp_seq = seq0 + 1 * span + pend_rank
    exp_rows = Rows(
        ts=jnp.concatenate([prev.ts, pend.ts, rows.ts]),
        kind=jnp.full((2 * n + B,), ev.EXPIRED, jnp.int32),
        valid=jnp.concatenate([prev_valid, pend_exp_valid, arr_exp_valid]),
        seq=jnp.concatenate([prev_seq, pend_exp_seq, arr_exp_seq]),
        gslot=jnp.concatenate([prev.gslot, pend.gslot, rows.gslot]),
        cols=tuple(jnp.concatenate([a, b, c]) for a, b, c in
                   zip(prev.cols, pend.cols, rows.cols)))
    F = B // n + 1
    f = jnp.arange(F, dtype=jnp.int64)
    reset_rows = Rows(
        ts=jnp.full((F,), 0, jnp.int64) + now,
        kind=jnp.full((F,), ev.RESET, jnp.int32),
        valid=f < nflush,
        seq=seq0 + f * span + n,
        gslot=jnp.full((F,), -1, jnp.int32),
        cols=tuple(jnp.full((F,), ev.default_value(t_), d)
                   for t_, d in zip(win.schema.types, win.schema.dtypes)))
    out = sort_rows(concat_rows(concat_rows(exp_rows, cur_rows), reset_rows))

    with jax.named_scope("window_state"):
        np_old_valid = jnp.logical_and(pend.alive, nflush == 0)
        np_arr_valid = jnp.logical_and(is_cur, batch_idx == nflush)
        cand_valid = jnp.concatenate([np_old_valid, np_arr_valid])
        cand_rank_src = jnp.concatenate([pend_rank, pos_in_batch])
        cand_ts = jnp.concatenate([pend.ts, rows.ts])
        cand_gslot = jnp.concatenate([pend.gslot, rows.gslot])
        cand_cols = tuple(jnp.concatenate([pc, rc])
                          for pc, rc in zip(pend.cols, rows.cols))
        npend = empty_buffer(win.schema, n)
        tgt = jnp.where(cand_valid, cand_rank_src, n).astype(jnp.int32)

        def scat(dst, src):
            return dst.at[tgt].set(src, mode="drop")
        npend = Buffer(
            ts=scat(npend.ts, cand_ts),
            add_seq=npend.add_seq,
            expire_seq=npend.expire_seq,
            expire_ts=npend.expire_ts,
            alive=jnp.zeros((n,), jnp.bool_).at[tgt].set(cand_valid,
                                                          mode="drop"),
            gslot=scat(npend.gslot, cand_gslot),
            cols=tuple(scat(c0, c) for c0, c in zip(npend.cols, cand_cols)))
        lb_old_valid = jnp.logical_and(pend.alive, nflush == 1)
        lb_arr_valid = jnp.logical_and(is_cur, batch_idx == nflush - 1)
        lbc_valid = jnp.concatenate([lb_old_valid, lb_arr_valid])
        nprev0 = empty_buffer(win.schema, n)
        tgt2 = jnp.where(lbc_valid, cand_rank_src, n).astype(jnp.int32)

        def scat2(dst, src):
            return dst.at[tgt2].set(src, mode="drop")
        flushed_prev = Buffer(
            ts=scat2(nprev0.ts, cand_ts),
            add_seq=nprev0.add_seq, expire_seq=nprev0.expire_seq,
            expire_ts=nprev0.expire_ts,
            alive=jnp.zeros((n,), jnp.bool_).at[tgt2].set(lbc_valid,
                                                          mode="drop"),
            gslot=scat2(nprev0.gslot, cand_gslot),
            cols=tuple(scat2(c0, c) for c0, c in zip(nprev0.cols, cand_cols)))
        nprev = jax.tree.map(
            lambda new, old: jnp.where(nflush > 0, new, old),
            flushed_prev, prev)
        nseq = seq0 + nflush * span
    return ((npend, nprev, nseq),
            WindowOutput(out, None, jnp.asarray(NO_WAKEUP, jnp.int64)))


def rows_of(how, n, B, step, rng):
    """One step's input rows — every slot of every column random, the
    invalid ones too — and how many of them are CURRENT arrivals."""
    valid = np.ones(B, bool)
    kind = np.full(B, ev.CURRENT, np.int32)
    if how == "filtered":
        valid = rng.random(B) < 0.7
        kind[rng.random(B) < 0.2] = ev.TIMER
    elif how == "empty":
        valid = rng.random(B) < 0.5
        kind[:] = ev.TIMER
    elif how != "dense":
        valid[:] = False
        some = min(B, n if how == "one_batch" else max(1, n // 3))
        valid[rng.choice(B, some, replace=False)] = True
    rows = Rows(
        ts=jnp.asarray(1000 + B * step + np.arange(B, dtype=np.int64)),
        kind=jnp.asarray(kind), valid=jnp.asarray(valid),
        seq=jnp.arange(B, dtype=jnp.int64),
        gslot=jnp.asarray(rng.integers(-1, 6, B).astype(np.int32)),
        cols=(jnp.asarray(rng.integers(1, 50, B).astype(np.int32)),
              jnp.asarray((10 + 990 * rng.random(B)).astype(np.float32)),
              jnp.asarray(rng.integers(1, 10 ** 12, B).astype(np.int64))))
    return rows, int((valid & (kind == ev.CURRENT)).sum())


def bits(a):
    """An array as its bytes' integers: NaN equals NaN, -0.0 is not 0.0."""
    a = np.ascontiguousarray(a)
    return a.view(f"u{a.dtype.itemsize}") if a.dtype != bool else a


def assert_same(got, want, where):
    got, want = jax.device_get((got, want))
    assert jax.tree.structure(got) == jax.tree.structure(want), where
    for (path, g), w in zip(jax.tree.flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        at = (where, jax.tree_util.keystr(path))
        assert g.dtype == w.dtype and g.shape == w.shape, at
        np.testing.assert_array_equal(bits(g), bits(w), err_msg=str(at))


def through_a_snapshot(state):
    """What `rt.snapshot()` / `rt.restore()` do to a query's state."""
    blob = pickle.dumps(jax.tree.map(np.asarray, jax.device_get(state)))
    return jax.tree.map(jnp.asarray, pickle.loads(blob))


def fields(buf):
    return dict(zip(Buffer._fields[:-1], buf[:-1]),
                **{f"cols[{i}]": c for i, c in enumerate(buf.cols)})


def assert_filler_is_the_empty_buffers(state, win, where):
    """Rows that are not alive hold what `empty_buffer` holds, and no row
    of a length batch has a sequence number or an expiry."""
    empty = fields(jax.device_get(empty_buffer(win.schema, win.length)))
    for name, buf in zip(("pending", "previous"), jax.device_get(state[:2])):
        for field, got in fields(buf).items():
            rows = slice(None) if field in ("add_seq", "expire_seq",
                                            "expire_ts") else ~buf.alive
            np.testing.assert_array_equal(
                bits(got[rows]), bits(empty[field][rows]),
                err_msg=str((where, name, field)))


@pytest.mark.parametrize("keyed", [False, True], ids=["one_window", "vmap_4_keys"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_state_and_emission_equal_the_scatter_forms_step_by_step(shape, keyed):
    n, B = SHAPES[shape]
    win = window(n, B)
    now = jnp.asarray(5, jnp.int64)
    new, old = win.process, lambda s, r, t: scatter_form(win, s, r, t)
    if keyed:
        # the keyed window of a partition: `planner.py` runs `process`
        # under `jax.vmap` over the keys of a send, `now` shared
        new, old = (jax.vmap(f, in_axes=(0, 0, None)) for f in (new, old))
    new, old = jax.jit(new), jax.jit(old)
    lanes = KEYS if keyed else 1
    rng = np.random.default_rng([44, sorted(SHAPES).index(shape), keyed])
    one = win.init_state()
    state = ref_state = jax.tree.map(lambda x: jnp.stack([x] * KEYS), one) \
        if keyed else one
    fill, flushes, apart = np.zeros(lanes, np.int64), set(), False
    for step in range(STEPS):
        # every key its own rows, so that the keys' fills drift apart
        made = [rows_of(DRIVE[(step + lane) % len(DRIVE)], n, B, step, rng)
                for lane in range(lanes)]
        rows = jax.tree.map(lambda *xs: jnp.stack(xs), *[r for r, _ in made]) \
            if keyed else made[0][0]
        ncur = np.asarray([c for _, c in made])
        flushes.update(((fill + ncur) // n).tolist())
        fill = (fill + ncur) % n
        apart = apart or len(set(fill.tolist())) > 1
        if step == SWAP_AT:
            # each form goes on from the OTHER's snapshot
            state, ref_state = (through_a_snapshot(ref_state),
                                through_a_snapshot(state))
        state, out = new(state, rows, now)
        ref_state, ref_out = old(ref_state, rows, now)
        assert_same(state, ref_state, (shape, step, "state"))
        assert_same(out, ref_out, (shape, step, "emission"))
        # the pending rows stand at the front of their buffer
        alive = np.asarray(state[0].alive).reshape(lanes, n)
        assert (alive == (np.arange(n) < fill[:, None])).all(), (step, fill)
        if not keyed:
            assert_filler_is_the_empty_buffers(state, win, (shape, step))
            assert out.rows.capacity == win.out_capacity
    # the drive met steps that complete no batch, one batch and, where a
    # step's rows hold two, several; and keys with different fills
    assert {0, 1} <= flushes and (B < 2 * n or max(flushes) >= 2), flushes
    assert apart == (keyed and n > 1)


def test_pending_rows_with_holes_between_them_are_kept_by_rank():
    """No step leaves a hole in the pending buffer, but nothing in the
    window relies on that: a pending row's place is its RANK among the
    alive ones, in both forms — through a step that completes no batch
    (the old rows stay), one that completes theirs, and the next."""
    n, B = SHAPES["b_below_n"]
    win = window(n, B)
    rng = np.random.default_rng(7)
    filled, _ = rows_of("dense", n, n, 0, rng)
    holes = np.isin(np.arange(n), (1, 4, 5, 8))
    pend = empty_buffer(win.schema, n)._replace(
        ts=filled.ts, gslot=filled.gslot, cols=filled.cols,
        alive=jnp.asarray(holes))
    state = ref_state = (pend, pend._replace(alive=jnp.asarray(~holes)),
                         jnp.asarray(3 * (2 * n + 2), jnp.int64))
    now, process = jnp.asarray(5, jnp.int64), jax.jit(win.process)
    for step, how in enumerate(("few", "dense", "dense")):
        rows, _ = rows_of(how, n, B, step + 1, rng)
        state, out = process(state, rows, now)
        ref_state, ref_out = scatter_form(win, ref_state, rows, now)
        assert_same(state, ref_state, (step, "state"))
        assert_same(out, ref_out, (step, "emission"))
    assert int(state[0].alive.sum()) == (4 + 3 + 4 + 4) % n


# -- the deployed program: no scatter, no wide gather under `window_state` ----

_LOC = re.compile(r'^#loc(\d+) = loc\("([^"]*)"')
_REF = re.compile(r"loc\(#loc(\d+)\)\s*$")
_ROWS = re.compile(r"->\s*tensor<(\d+)(?:x\d+)*x\w+>")
_CALL = re.compile(r"\bcall @(\w+)\(")
_FUNC = re.compile(r"func\.func (?:public |private )?@(\w+)\(")


def rows_of_result(line):
    m = _ROWS.search(line)
    return int(m.group(1)) if m else 1


def movers_under(text, scope):
    """(op, result rows) of every gather and scatter of a lowered text (with
    debug info) whose location names `scope` — and those of the private
    functions called from there (`jnp.searchsorted`'s loop and its body)."""
    names = {m.group(1): m.group(2) for line in text.splitlines()
             if (m := _LOC.match(line))}
    ops, calls, func, opened = {}, {}, None, None
    for line in text.splitlines():
        if (m := _FUNC.search(line)):
            func = m.group(1)
        ref = _REF.search(line)
        name = names.get(ref.group(1), "") if ref else ""
        if (m := _CALL.search(line)):
            calls.setdefault(func, []).append((m.group(1), name))
        if '"stablehlo.scatter"' in line:
            opened = "scatter"          # its result stands on the closing line
        elif opened and line.lstrip().startswith("})"):
            ops.setdefault(func, []).append((opened, rows_of_result(line), name))
            opened = None
        elif '"stablehlo.gather"' in line:
            ops.setdefault(func, []).append(("gather", rows_of_result(line), name))

    def named(name):
        return scope in name.split("/")
    under = [(op, n) for found in ops.values() for op, n, name in found
             if named(name)]
    todo = [callee for made in calls.values() for callee, name in made
            if named(name)]
    seen = set()
    while todo:
        callee = todo.pop()
        if callee not in seen:
            seen.add(callee)
            under += [(op, n) for op, n, _ in ops.get(callee, [])]
            todo += [c for c, _ in calls.get(callee, [])]
    return under


def test_the_deployed_step_keeps_its_buffers_by_gathers_of_2n_rows():
    n, _events = cfg.SHAPES["w1000_e4096"]
    run = cfg.drive("w1000_e4096", 3, n_sends=2,
                    keep_runtime=cfg.plain_step_facts)
    found = movers_under(run["kept"][1], "window_state")
    assert not [m for m in found if m[0] == "scatter"], found
    sizes = [rows for op, rows in found if op == "gather"]
    # `ts`, `gslot` and the three columns, once each, and the lookup's probes
    assert sizes.count(2 * n) >= 6 and max(sizes) <= 2 * n, sizes


def test_the_guard_sees_the_scatter_forms_twelve_scatters():
    n, B = SHAPES["n1000_b4096"]
    win = window(n, B)
    rows, _ = rows_of("dense", n, B, 0, np.random.default_rng(1))
    text = jax.jit(lambda s, r: scatter_form(win, s, r, jnp.int64(0))).lower(
        win.init_state(), rows).as_text(debug_info=True)
    found = movers_under(text, "window_state")
    assert [m for m in found if m[0] == "scatter"] == [("scatter", n)] * 12

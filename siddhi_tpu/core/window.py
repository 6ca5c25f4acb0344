"""Window processors as fixed-capacity columnar buffers.

Reference behavior (what): CORE/query/processor/stream/window/* — sliding and
batch retention policies emitting CURRENT + EXPIRED (+RESET) events, driven by
arrivals and scheduler TIMER ticks (e.g. TimeWindowProcessor.java:132-168,
LengthWindowProcessor.java, LengthBatchWindowProcessor.java,
TimeBatchWindowProcessor.java).

TPU-native design (how): each window keeps a struct-of-arrays buffer of
capacity C.  Every event admitted to the window gets a monotone global
sequence number `add_seq`; when it leaves it gets `expire_seq`.  One `process`
call consumes a whole micro-batch and emits an output `Rows` block where every
row carries its own sequence number, so downstream aggregation can recover the
exact per-event ordering (expired-before-current interleavings included)
without any per-event control flow.  Scan-style aggregators (min/max/
distinctCount over a sliding window) receive an `alive[i, c]` exposure mask:
entry c is visible to output row i iff add_seq[c] <= seq[i] < expire_seq[c].

Buffers are recompacted (gather) once per batch instead of ring-indexed per
event — O(C+B) vector work that XLA fuses well.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..query_api.expression import Constant
from . import event as ev
from .steputil import from_u32_planes, join64, split64, u32_planes

BIG_SEQ = jnp.iinfo(jnp.int64).max // 4  # "never expired"
NO_WAKEUP = jnp.iinfo(jnp.int64).max // 4


class Rows(NamedTuple):
    """Ordered operator rows flowing between window -> selector -> output."""

    ts: Any     # i64[B]
    kind: Any   # i32[B] CURRENT/EXPIRED/TIMER/RESET
    valid: Any  # bool[B]
    seq: Any    # i64[B] global order
    gslot: Any  # i32[B] group-by slot (-1 none)
    cols: Tuple[Any, ...]

    @property
    def capacity(self):
        return self.ts.shape[0]


class Buffer(NamedTuple):
    """Columnar window contents."""

    ts: Any          # i64[C] original event ts
    add_seq: Any     # i64[C]
    expire_seq: Any  # i64[C] BIG_SEQ if still in window
    expire_ts: Any   # i64[C] scheduled wall expiry (time windows) else BIG
    alive: Any       # bool[C]
    gslot: Any       # i32[C]
    cols: Tuple[Any, ...]

    @property
    def capacity(self):
        return self.ts.shape[0]


def empty_buffer(schema: ev.Schema, capacity: int) -> Buffer:
    cols = tuple(
        ev.typed_full((capacity,), ev.default_value(t), d)
        for t, d in zip(schema.types, schema.dtypes)
    )
    big = jnp.full((capacity,), BIG_SEQ, jnp.int64)
    return Buffer(
        ts=jnp.zeros((capacity,), jnp.int64),
        add_seq=big,
        expire_seq=big,
        expire_ts=big,
        alive=jnp.zeros((capacity,), jnp.bool_),
        gslot=jnp.full((capacity,), -1, jnp.int32),
        cols=cols,
    )


def _gather_rows(rows: Rows, idx, valid):
    return Rows(
        ts=rows.ts[idx], kind=rows.kind[idx],
        valid=jnp.logical_and(rows.valid[idx], valid),
        seq=rows.seq[idx], gslot=rows.gslot[idx],
        cols=tuple(c[idx] for c in rows.cols),
    )


def sort_rows(rows: Rows) -> Rows:
    """Stable order by (valid desc, seq asc): invalid rows pushed to the
    end.  One device-trace section, `window_order`: a caller keeps it out
    of any section of its own.  Two parts: `order` (the key and its stable
    argsort), `to_sorted` (one gather an array by it)."""
    with jax.named_scope("window_order"):
        with jax.named_scope("order"):
            key = jnp.where(rows.valid, rows.seq, BIG_SEQ)
            idx = jnp.argsort(key, stable=True)
        with jax.named_scope("to_sorted"):
            return _gather_rows(rows, idx, jnp.ones_like(rows.valid)[idx])


def concat_rows(a: Rows, b: Rows) -> Rows:
    return Rows(
        ts=jnp.concatenate([a.ts, b.ts]),
        kind=jnp.concatenate([a.kind, b.kind]),
        valid=jnp.concatenate([a.valid, b.valid]),
        seq=jnp.concatenate([a.seq, b.seq]),
        gslot=jnp.concatenate([a.gslot, b.gslot]),
        cols=tuple(jnp.concatenate([x, y]) for x, y in zip(a.cols, b.cols)),
    )


class WindowOutput(NamedTuple):
    rows: Rows
    buffer: Optional[Buffer]      # post-state buffer (exposure source)
    next_wakeup: Any              # i64 scalar, NO_WAKEUP if none


# ---------------------------------------------------------------------------


class WindowProcessor:
    """Base: subclasses are pure — state is an explicit pytree."""

    name = "?"
    needs_timer = False
    # True for batch windows that emit RESET rows (epoch flushes) — the
    # sharded keyed path excludes them: a RESET resets ALL selector slots
    # on whichever device sees it, violating the single-writer merge
    emits_reset = False
    # True for cron-style windows: their flushes are scheduled on the
    # host clock (`host_next_wakeup`), not by the step's wake scalar
    host_scheduled = False
    # session(gap, key): position of the key column the planner vmaps the
    # processor over; None = no key axis
    session_key_pos = None
    # True where the CURRENT rows a step emits ARE its CURRENT arrivals, in
    # arrival order, in the step they arrive in: such a window answers
    # `admit`, for a reader that wants no other kind of row (a CURRENT-only
    # projection join's trigger side, core/join.py `expired_joined`)
    current_is_arrivals = False
    # True where ONE timer step at a later clock does what a step at each
    # time something came due would do in turn — the same rows, with the
    # same stamps, in the same order: the scheduler then runs one step for
    # a clock that jumped, not one for every distinct time passed
    timer_coalesces = False

    def __init__(self, schema: ev.Schema, params: List[Constant],
                 batch_capacity: int, capacity_hint: int = 1024):
        self.schema = schema
        self.batch_capacity = batch_capacity
        self.capacity_hint = capacity_hint

    # -- static description ---------------------------------------------------
    @property
    def out_capacity(self) -> int:
        raise NotImplementedError

    def init_state(self):
        raise NotImplementedError

    def process(self, state, rows: Rows, now) -> Tuple[Any, WindowOutput]:
        raise NotImplementedError

    def admit(self, state, rows: Rows, now) -> Tuple[Any, WindowOutput]:
        """`process` for a reader of CURRENT rows alone (`current_is_arrivals`
        windows only): the state after is `process`' bit for bit, and the
        output rows are the arrivals WHERE THEY STAND — `valid` narrowed to
        the CURRENT ones, each with the `seq` `process` gives it — so no
        EXPIRED row is built and nothing is sorted."""
        raise NotImplementedError

    def current_buffer(self, state) -> Optional[Buffer]:
        """Current window contents for on-demand reads/joins (reference:
        FindableProcessor.find).  Works for every window whose state leads
        with its Buffer."""
        if isinstance(state, tuple) and state and isinstance(state[0], Buffer):
            return state[0]
        return None


def _param_int(params, i, default=None):
    from ..exceptions import CompileError
    if i >= len(params):
        if default is not None:
            return default
        raise CompileError("missing window parameter")
    p = params[i]
    if not isinstance(p, Constant):
        raise CompileError("window parameters must be constants")
    return int(p.value)


class NoWindow(WindowProcessor):
    """Pass-through when the query has no window handler.

    `compact` (default True) moves valid rows to the front via sort_rows;
    the mesh-sharded plain path disables it so output rows stay aligned to
    input rows on every device and merge with a psum (planner
    _shard_plain_step) — valid rows are already in input order either way.
    """

    name = "(none)"
    compact = True
    # it emits nothing else; `process` only moves the invalid rows last
    current_is_arrivals = True

    @property
    def out_capacity(self):
        return self.batch_capacity

    def init_state(self):
        return jnp.asarray(0, jnp.int64)  # seq counter

    def process(self, state, rows: Rows, now):
        nseq, wout = self.admit(state, rows, now)
        if self.compact:
            wout = wout._replace(rows=sort_rows(wout.rows))
        return nseq, wout

    def admit(self, state, rows: Rows, now):
        seq0 = state
        is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)
        ord_ = jnp.cumsum(is_cur.astype(jnp.int64)) - 1
        seq = jnp.where(is_cur, seq0 + ord_, BIG_SEQ)
        out = Rows(rows.ts, rows.kind, is_cur, seq, rows.gslot, rows.cols)
        nseq = seq0 + jnp.sum(is_cur.astype(jnp.int64))
        return nseq, WindowOutput(out, None,
                                  jnp.asarray(NO_WAKEUP, jnp.int64))


class PassAllWindow(WindowProcessor):
    """Pass-through for queries reading a named window (reference:
    CORE/window/Window.java:65 — the window publishes CURRENT+EXPIRED events
    to subscribing queries, which must not re-window them).  Both kinds are
    forwarded with fresh sequence numbers so the selector's signed
    aggregation (add on CURRENT, subtract on EXPIRED) sees them in order."""

    name = "(named-window input)"

    @property
    def out_capacity(self):
        return self.batch_capacity

    def init_state(self):
        return jnp.asarray(0, jnp.int64)  # seq counter

    def process(self, state, rows: Rows, now):
        seq0 = state
        is_data = jnp.logical_and(
            rows.valid,
            jnp.logical_or(rows.kind == ev.CURRENT, rows.kind == ev.EXPIRED))
        ord_ = jnp.cumsum(is_data.astype(jnp.int64)) - 1
        seq = jnp.where(is_data, seq0 + ord_, BIG_SEQ)
        out = Rows(rows.ts, rows.kind, is_data, seq, rows.gslot, rows.cols)
        nseq = seq0 + jnp.sum(is_data.astype(jnp.int64))
        return nseq, WindowOutput(sort_rows(out), None,
                                  jnp.asarray(NO_WAKEUP, jnp.int64))


class LengthWindow(WindowProcessor):
    """Sliding length window (reference: LengthWindowProcessor).

    On each arrival: if full, the oldest entry is emitted as EXPIRED just
    before the CURRENT event.  expired ts keeps the original event ts.
    """

    name = "length"
    # the k-th arrival is the step's k-th CURRENT row (seq0 + 2k + 1); what
    # it evicts is the EXPIRED row just before it, from the buffer
    current_is_arrivals = True

    def __init__(self, schema, params, batch_capacity, capacity_hint=1024):
        super().__init__(schema, params, batch_capacity)
        self.length = _param_int(params, 0)

    @property
    def out_capacity(self):
        return 2 * self.batch_capacity

    def init_state(self):
        return (empty_buffer(self.schema, self.length),
                jnp.asarray(0, jnp.int64))

    def process(self, state, rows: Rows, now):
        return self._slide(state, rows, expired=True)

    def admit(self, state, rows: Rows, now):
        return self._slide(state, rows, expired=False)

    def _slide(self, state, rows: Rows, expired: bool):
        buf, seq0 = state
        C = self.length
        B = rows.capacity
        is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)
        ncur = jnp.sum(is_cur.astype(jnp.int64))

        # order arrivals among themselves: k = 0..ncur-1
        k = jnp.cumsum(is_cur.astype(jnp.int64)) - 1   # [B]

        # combined virtual sequence: old alive entries (by add_seq) then
        # currents, BOTH compacted to the front of their region; virtual index
        # v maps to physical position v (old region) or C + v - count0.
        old_key = jnp.where(buf.alive, buf.add_seq, BIG_SEQ)
        old_order = jnp.argsort(old_key)               # [C] alive first by age
        count0 = jnp.sum(buf.alive.astype(jnp.int64))
        cur_order = jnp.argsort(jnp.where(is_cur, k, BIG_SEQ))  # [B]

        comb_ts = jnp.concatenate([buf.ts[old_order], rows.ts[cur_order]])
        comb_gslot = jnp.concatenate([buf.gslot[old_order],
                                      rows.gslot[cur_order]])
        comb_cols = tuple(jnp.concatenate([bc[old_order], rc[cur_order]])
                          for bc, rc in zip(buf.cols, rows.cols))
        cur_addseq = jnp.where(is_cur, seq0 + 2 * k + 1, BIG_SEQ)
        comb_addseq = jnp.concatenate([buf.add_seq[old_order],
                                       cur_addseq[cur_order]])

        def phys(v):
            return jnp.where(v < count0, v, C + v - count0)

        if expired:
            # the k-th arrival evicts virtual entry (count0 + k - length)
            # (if >= 0)
            evict_pos = (count0 + k - C)
            has_evict = jnp.logical_and(is_cur, evict_pos >= 0)
            safe_pos = jnp.clip(phys(evict_pos), 0,
                                C + B - 1).astype(jnp.int32)

            exp_rows = Rows(
                ts=comb_ts[safe_pos],
                kind=jnp.full((B,), ev.EXPIRED, jnp.int32),
                valid=has_evict,
                seq=seq0 + 2 * k,       # expired emitted just before current k
                gslot=comb_gslot[safe_pos],
                cols=tuple(c[safe_pos] for c in comb_cols),
            )
        cur_rows = Rows(
            ts=rows.ts, kind=jnp.full((B,), ev.CURRENT, jnp.int32),
            valid=is_cur, seq=seq0 + 2 * k + 1, gslot=rows.gslot,
            cols=rows.cols,
        )
        out = sort_rows(concat_rows(exp_rows, cur_rows)) if expired \
            else cur_rows

        # new buffer = last `length` of combined valid entries
        total = count0 + ncur
        start = jnp.maximum(total - C, 0)
        take = jnp.arange(C, dtype=jnp.int64) + start        # [C] virtual
        tvalid = take < total
        tpos = jnp.clip(phys(take), 0, C + B - 1).astype(jnp.int32)
        # expire_seq of evicted entries: entry at combined pos p (p < total-C
        # after the batch) was evicted by arrival k = p - count0 + C
        nbuf = Buffer(
            ts=comb_ts[tpos],
            add_seq=comb_addseq[tpos],
            expire_seq=jnp.where(tvalid, BIG_SEQ, BIG_SEQ),
            expire_ts=jnp.full((C,), BIG_SEQ, jnp.int64),
            alive=tvalid,
            gslot=comb_gslot[tpos],
            cols=tuple(c[tpos] for c in comb_cols),
        )
        nseq = seq0 + 2 * ncur
        return ((nbuf, nseq),
                WindowOutput(out, nbuf, jnp.asarray(NO_WAKEUP, jnp.int64)))


class TimeWindow(WindowProcessor):
    """Sliding time window (reference: TimeWindowProcessor.java:86).

    Entries expire `t` ms after arrival; EXPIRED rows carry ts = expiry time
    (matching the reference, which pre-stamps the cloned expired event).
    Expiry is driven both by arrivals and by TIMER rows; `next_wakeup`
    reports the earliest pending expiry for the host scheduler.
    """

    name = "time"
    needs_timer = True
    # a TIMER step expires everything due by its `now`, each EXPIRED row
    # stamped with its own expiry time, in expiry order
    timer_coalesces = True

    def __init__(self, schema, params, batch_capacity, capacity_hint=2048):
        super().__init__(schema, params, batch_capacity)
        self.time_ms = _param_int(params, 0)
        self.capacity = max(capacity_hint, 2 * batch_capacity)

    @property
    def out_capacity(self):
        return self.batch_capacity + self.capacity

    def init_state(self):
        return (empty_buffer(self.schema, self.capacity),
                jnp.asarray(0, jnp.int64))

    def process(self, state, rows: Rows, now):
        buf, seq0 = state
        C = self.capacity
        B = rows.capacity
        N = C + B
        t = self.time_ms

        # two device-trace sections (jax.named_scope: op-name metadata), as
        # the length batch's: `window_fill` builds the rows the step emits —
        # the entries due by `now` as EXPIRED, the arrivals as CURRENT,
        # merged by time and laid out in that order — and `window_state`
        # the buffer it keeps.  Each is one argsort and ONE gather of whole
        # rows (`gather_packed`): on the v5e the sorts were never the cost
        # (0.38 ms for the 139,264 keys of the benchmark's window) — a
        # scatter of the ranks (17.4 ms) and a gather an array (8.4 ms
        # against 0.47 packed) were (PERF.md, PR 52)
        with jax.named_scope("window_fill"):
            is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)

            # Candidate expiries from the old buffer
            exp_due = jnp.logical_and(buf.alive, buf.expire_ts <= now)

            # ordering: merge (existing entries' expiries <= now) and
            # arrivals by time — expired entries (key=expire_ts, pri 0) +
            # current arrivals (key=ts, pri 1); the rows that are neither
            # behind them, as they stand
            em_ts = jnp.concatenate([buf.expire_ts, rows.ts])
            em_pri = jnp.concatenate([jnp.zeros((C,), jnp.int64),
                                      jnp.ones((B,), jnp.int64)])
            em_valid = jnp.concatenate([exp_due, is_cur])
            em_key = jnp.where(em_valid, em_ts * 2 + em_pri, BIG_SEQ)
            order = jnp.argsort(em_key, stable=True).astype(
                jnp.int32)                                # [C+B]
            # a row's seq is its place in that order, so the emission in
            # seq order is the gather by `order` itself; an EXPIRED row
            # carries its expiry time (the reference stamps it)
            gslot = jnp.concatenate([buf.gslot, rows.gslot])
            cols = tuple(jnp.concatenate([bc, rc])
                         for bc, rc in zip(buf.cols, rows.cols))
            o_ts, o_valid, o_gslot, *o_cols = gather_packed(
                (em_ts, em_valid, gslot) + cols, order)
            out = Rows(
                ts=o_ts,
                kind=jnp.where(order < C, ev.EXPIRED,
                               ev.CURRENT).astype(jnp.int32),
                valid=o_valid,
                seq=seq0 + jnp.arange(N, dtype=jnp.int64),
                gslot=o_gslot, cols=tuple(o_cols),
            )
            # the arrivals' places, which the buffer keeps as `add_seq`:
            # `order` inverted
            rank = jnp.argsort(order).astype(jnp.int64)
            nseq = jnp.where(jnp.any(em_valid), seq0 + N, seq0)

        with jax.named_scope("window_state"):
            # new buffer = (old alive minus expired) + arrivals; compact by
            # age
            keep_old = jnp.logical_and(buf.alive, jnp.logical_not(exp_due))
            cand_ts = jnp.concatenate([buf.ts, rows.ts])
            cand_add = jnp.concatenate([buf.add_seq, seq0 + rank[C:]])
            cand_expts = jnp.concatenate([buf.expire_ts, rows.ts + t])
            cand_valid = jnp.concatenate([keep_old, is_cur])
            cand_key = jnp.where(cand_valid, cand_add, BIG_SEQ)
            corder = jnp.argsort(cand_key).astype(
                jnp.int32)                                # oldest first
            total = jnp.sum(cand_valid.astype(jnp.int64))
            # overflow: drop OLDEST if total > C (keep most recent C) —
            # `drop` is at most B, so the C kept are a slice of the order
            drop = jnp.maximum(total - C, 0)
            pos = jax.lax.dynamic_slice(corder, (drop,), (C,))
            svalid = (jnp.arange(C, dtype=jnp.int64) + drop) < total
            n_ts, n_add, n_expts, n_gslot, *n_cols = gather_packed(
                (cand_ts, cand_add, cand_expts, gslot) + cols, pos)
            nbuf = Buffer(
                ts=n_ts,
                add_seq=jnp.where(svalid, n_add, BIG_SEQ),
                expire_seq=jnp.full((C,), BIG_SEQ, jnp.int64),
                expire_ts=jnp.where(svalid, n_expts, BIG_SEQ),
                alive=svalid, gslot=n_gslot, cols=tuple(n_cols),
            )
            wake = jnp.min(jnp.where(nbuf.alive, nbuf.expire_ts, NO_WAKEUP))
        return ((nbuf, nseq), WindowOutput(out, nbuf, wake))


class LengthBatchWindow(WindowProcessor):
    emits_reset = True
    """Tumbling length batch (reference: LengthBatchWindowProcessor).

    Arrivals accumulate silently; when `n` have gathered the whole batch is
    emitted as CURRENT, preceded by the previous batch as EXPIRED and a RESET
    row separating them.
    """

    name = "lengthBatch"

    def __init__(self, schema, params, batch_capacity, capacity_hint=1024):
        super().__init__(schema, params, batch_capacity)
        self.length = _param_int(params, 0)

    @property
    def out_capacity(self):
        # EXPIRED slots prev + pending + arrivals, CURRENT pending + arrivals
        n = self.length
        flushes = self.batch_capacity // n + 1       # one RESET slot each
        return 2 * self.batch_capacity + 3 * n + flushes

    def init_state(self):
        # pending buffer (filling), previous batch buffer (for EXPIRED replay)
        return (empty_buffer(self.schema, self.length),
                empty_buffer(self.schema, self.length),
                jnp.asarray(0, jnp.int64))

    def process(self, state, rows: Rows, now):
        # two device-trace sections (jax.named_scope: op-name metadata):
        # `window_fill` builds the rows a step emits, `window_state` the
        # buffers it keeps; the ordering is `sort_rows`' own section
        with jax.named_scope("window_fill"):
            pend, prev, seq0 = state
            n = self.length
            B = rows.capacity
            is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)
            ncur = jnp.sum(is_cur.astype(jnp.int64))
            fill0 = jnp.sum(pend.alive.astype(jnp.int64))

            # global arrival index g = fill0 + k (k = order within batch)
            k = jnp.cumsum(is_cur.astype(jnp.int64)) - 1
            g = fill0 + k
            batch_idx = g // n           # which tumble this arrival belongs to
            nflush = (fill0 + ncur) // n  # completed batches this step

            # ---- output construction -------------------------------------------
            # seq layout per flush f (0-based among this step's flushes):
            #   expired rows of batch f-1+prev : seq = seq0 + f*(2n+2) + [0..n)
            #   reset row                      : seq0 + f*(2n+2) + n
            #   current rows of batch f        : seq0 + f*(2n+2) + n+1 + [0..n)
            span = 2 * n + 2

            # currents of flushed batches: arrival with batch_idx < nflush
            flushed_cur = jnp.logical_and(is_cur, batch_idx < nflush)
            pos_in_batch = g % n
            cur_seq = seq0 + batch_idx * span + n + 1 + pos_in_batch
            # pending entries flushed in flush 0
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
                           for pc, rc in zip(pend.cols, rows.cols)),
            )

            # expired rows: prev batch replayed at flush 0; batch f-1 replayed at
            # flush f.  prev buffer: ranks 0..n-1.
            prev_rank = jnp.cumsum(prev.alive.astype(jnp.int64)) - 1
            prev_valid = jnp.logical_and(prev.alive, nflush > 0)
            prev_seq = seq0 + prev_rank
            # arrivals replayed as expired at flush (batch_idx+1) if batch_idx+1 < nflush
            arr_exp_valid = jnp.logical_and(is_cur, batch_idx + 1 < nflush)
            arr_exp_seq = seq0 + (batch_idx + 1) * span + pos_in_batch
            # pending entries (flushed at 0) replayed as expired at flush 1
            pend_exp_valid = jnp.logical_and(pend.alive, nflush > 1)
            pend_exp_seq = seq0 + 1 * span + pend_rank

            exp_rows = Rows(
                ts=jnp.concatenate([prev.ts, pend.ts, rows.ts]),
                kind=jnp.full((2 * n + B,), ev.EXPIRED, jnp.int32),
                valid=jnp.concatenate([prev_valid, pend_exp_valid, arr_exp_valid]),
                seq=jnp.concatenate([prev_seq, pend_exp_seq, arr_exp_seq]),
                gslot=jnp.concatenate([prev.gslot, pend.gslot, rows.gslot]),
                cols=tuple(jnp.concatenate([a, b, c]) for a, b, c in
                           zip(prev.cols, pend.cols, rows.cols)),
            )

            # reset rows, one per flush
            F = B // n + 1
            f = jnp.arange(F, dtype=jnp.int64)
            reset_rows = Rows(
                ts=jnp.full((F,), 0, jnp.int64) + now,
                kind=jnp.full((F,), ev.RESET, jnp.int32),
                valid=f < nflush,
                seq=seq0 + f * span + n,
                gslot=jnp.full((F,), -1, jnp.int32),
                cols=tuple(jnp.full((F,), ev.default_value(t_), d)
                           for t_, d in zip(self.schema.types, self.schema.dtypes)),
            )
            emitted = concat_rows(concat_rows(exp_rows, cur_rows),
                                  reset_rows)
        out = sort_rows(emitted)

        with jax.named_scope("window_state"):
            # ---- new state ------------------------------------------------------
            # the rows a step keeps are one contiguous range of the global
            # arrival index (a pending row's is its rank, the k-th CURRENT
            # arrival's fill0 + k): prev' = batch nflush-1, pending' = what
            # follows it.  So the two buffers are built by DESTINATION: each
            # of their 2n slots looks its source row up in the candidates'
            # running count, and every array is gathered once at 2n rows.
            # The candidates are `cur_rows`' slots: concat(pending, arrivals)
            cand_count = jnp.concatenate([pend_rank, g]) + 1
            dest = (nflush - 1) * n + jnp.arange(2 * n, dtype=jnp.int64)
            live = jnp.logical_and(dest >= 0, dest < fill0 + ncur)
            src = jnp.searchsorted(cand_count, dest + 1, side="left")
            empty = empty_buffer(self.schema, 2 * n)

            def kept(cand, filler):
                return jnp.where(live, cand[src], filler)
            both = Buffer(
                ts=kept(cur_rows.ts, empty.ts),
                add_seq=empty.add_seq, expire_seq=empty.expire_seq,
                expire_ts=empty.expire_ts,
                alive=live,
                gslot=kept(cur_rows.gslot, empty.gslot),
                cols=tuple(kept(c, c0)
                           for c, c0 in zip(cur_rows.cols, empty.cols)),
            )
            npend = jax.tree.map(lambda x: x[n:], both)
            # prev' = last flushed batch (batch nflush-1) if any flush else prev
            nprev = jax.tree.map(
                lambda new, old: jnp.where(nflush > 0, new[:n], old), both, prev)

            nseq = seq0 + nflush * span
        return ((npend, nprev, nseq),
                WindowOutput(out, None, jnp.asarray(NO_WAKEUP, jnp.int64)))


class TimeBatchWindow(WindowProcessor):
    emits_reset = True
    """Tumbling time batch (reference: TimeBatchWindowProcessor).

    Time is divided into [start + k*t, start + (k+1)*t) slices; at each slice
    boundary the gathered events are emitted as CURRENT (preceded by the
    previous slice as EXPIRED + RESET).  Driven by arrivals and TIMER rows.
    """

    name = "timeBatch"
    needs_timer = True

    def __init__(self, schema, params, batch_capacity, capacity_hint=2048):
        super().__init__(schema, params, batch_capacity)
        self.time_ms = _param_int(params, 0)
        self.capacity = max(capacity_hint, 2 * batch_capacity)

    @property
    def out_capacity(self):
        return 2 * self.capacity + 2 * self.batch_capacity + 2

    def init_state(self):
        return (
            empty_buffer(self.schema, self.capacity),   # pending slice
            empty_buffer(self.schema, self.capacity),   # previous slice
            jnp.asarray(-1, jnp.int64),                 # slice start ts (-1 unset)
            jnp.asarray(0, jnp.int64),                  # seq counter
        )

    def process(self, state, rows: Rows, now):
        pend, prev, start0, seq0 = state
        t = self.time_ms
        C = self.capacity
        B = rows.capacity

        is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)
        any_cur = jnp.any(is_cur)
        first_ts = jnp.min(jnp.where(is_cur, rows.ts, BIG_SEQ))
        start = jnp.where(start0 >= 0, start0, first_ts)

        # how many slice boundaries passed by `now`?
        elapsed = jnp.maximum(now - start, 0)
        nflush = jnp.where(start0 >= 0,
                           elapsed // t,
                           jnp.maximum((now - first_ts), 0) // t)
        nflush = jnp.where(jnp.logical_or(start0 >= 0, any_cur), nflush, 0)
        flush = nflush > 0
        # NOTE: if multiple slice boundaries pass in one gap, intermediate
        # empty slices collapse — matching observable outputs (empty batches
        # emit nothing).
        new_start = jnp.where(flush, start + nflush * t, start)

        # arrivals belong to pending slice if ts < boundary else to the new one
        boundary = start + jnp.where(flush, nflush, 1) * t
        to_pend = jnp.logical_and(is_cur, rows.ts < boundary)
        to_next = jnp.logical_and(is_cur, jnp.logical_not(to_pend))

        # flushed slice contents = pending + arrivals with ts < boundary
        pend_rank = jnp.cumsum(pend.alive.astype(jnp.int64)) - 1
        npend_fill = jnp.sum(pend.alive.astype(jnp.int64))
        arr_rank = npend_fill + jnp.cumsum(to_pend.astype(jnp.int64)) - 1

        # seq layout: expired prev [0..C), reset C, current flushed [C+1 ...)
        exp_rows = Rows(
            ts=prev.ts, kind=jnp.full((C,), ev.EXPIRED, jnp.int32),
            valid=jnp.logical_and(prev.alive, flush),
            seq=seq0 + jnp.cumsum(prev.alive.astype(jnp.int64)) - 1,
            gslot=prev.gslot, cols=prev.cols,
        )
        reset_rows = Rows(
            ts=jnp.full((1,), 0, jnp.int64) + now,
            kind=jnp.full((1,), ev.RESET, jnp.int32),
            valid=jnp.reshape(flush, (1,)),
            seq=jnp.full((1,), seq0 + C, jnp.int64),
            gslot=jnp.full((1,), -1, jnp.int32),
            cols=tuple(jnp.full((1,), ev.default_value(t_), d)
                       for t_, d in zip(self.schema.types, self.schema.dtypes)),
        )
        cur_rows = Rows(
            ts=jnp.concatenate([pend.ts, rows.ts]),
            kind=jnp.full((C + B,), ev.CURRENT, jnp.int32),
            valid=jnp.concatenate([
                jnp.logical_and(pend.alive, flush),
                jnp.logical_and(to_pend, flush)]),
            seq=seq0 + C + 1 + jnp.concatenate([pend_rank, arr_rank]),
            gslot=jnp.concatenate([pend.gslot, rows.gslot]),
            cols=tuple(jnp.concatenate([pc, rc])
                       for pc, rc in zip(pend.cols, rows.cols)),
        )
        out = sort_rows(concat_rows(concat_rows(exp_rows, cur_rows), reset_rows))

        # new pending: if flush -> arrivals beyond boundary; else pending+arrivals
        keep_pend = jnp.logical_and(pend.alive, jnp.logical_not(flush))
        arr_keep = jnp.where(flush, to_next, to_pend)
        base_fill = jnp.sum(keep_pend.astype(jnp.int64))
        cand_valid = jnp.concatenate([keep_pend, arr_keep])
        cand_rank = jnp.concatenate([
            pend_rank,
            base_fill + jnp.cumsum(arr_keep.astype(jnp.int64)) - 1])
        cand_ts = jnp.concatenate([pend.ts, rows.ts])
        cand_gslot = jnp.concatenate([pend.gslot, rows.gslot])
        cand_cols = tuple(jnp.concatenate([pc, rc])
                          for pc, rc in zip(pend.cols, rows.cols))
        tgt = jnp.where(cand_valid, cand_rank, C).astype(jnp.int32)
        fresh = empty_buffer(self.schema, C)
        npend = Buffer(
            ts=fresh.ts.at[tgt].set(cand_ts, mode="drop"),
            add_seq=fresh.add_seq, expire_seq=fresh.expire_seq,
            expire_ts=fresh.expire_ts,
            alive=jnp.zeros((C,), jnp.bool_).at[tgt].set(cand_valid, mode="drop"),
            gslot=fresh.gslot.at[tgt].set(cand_gslot, mode="drop"),
            cols=tuple(f.at[tgt].set(c, mode="drop")
                       for f, c in zip(fresh.cols, cand_cols)),
        )

        # new prev: flushed slice if flush else old prev
        ftgt = jnp.where(
            jnp.concatenate([pend.alive, to_pend]),
            jnp.concatenate([pend_rank, arr_rank]), C).astype(jnp.int32)
        fprev = Buffer(
            ts=fresh.ts.at[ftgt].set(cand_ts, mode="drop"),
            add_seq=fresh.add_seq, expire_seq=fresh.expire_seq,
            expire_ts=fresh.expire_ts,
            alive=jnp.zeros((C,), jnp.bool_).at[ftgt].set(
                jnp.concatenate([pend.alive, to_pend]), mode="drop"),
            gslot=fresh.gslot.at[ftgt].set(cand_gslot, mode="drop"),
            cols=tuple(f.at[ftgt].set(c, mode="drop")
                       for f, c in zip(fresh.cols, cand_cols)),
        )
        nprev = jax.tree.map(lambda a, b: jnp.where(flush, a, b), fprev, prev)

        nseq = jnp.where(flush, seq0 + 2 * C + B + 2, seq0)
        nstart = jnp.where(jnp.logical_or(start0 >= 0, any_cur), new_start,
                           jnp.asarray(-1, jnp.int64))
        wake = jnp.where(nstart >= 0, nstart + t, NO_WAKEUP)
        return ((npend, nprev, nstart, nseq), WindowOutput(out, None, wake))


# ---------------------------------------------------------------------------

WINDOW_TYPES = {
    "length": LengthWindow,
    "time": TimeWindow,
    "lengthBatch": LengthBatchWindow,
    "timeBatch": TimeBatchWindow,
}

from . import window_ext as _window_ext  # noqa: E402  (registry extension)
_window_ext.register(WINDOW_TYPES)
from . import window_expr as _window_expr  # noqa: E402
_window_expr.register(WINDOW_TYPES)


def create_window(name: str, schema: ev.Schema, params, batch_capacity: int,
                  capacity_hint: int = 2048) -> WindowProcessor:
    if name not in WINDOW_TYPES:
        from ..exceptions import CompileError
        raise CompileError(f"unknown window type {name!r}; "
                           f"available: {sorted(WINDOW_TYPES)}")
    return WINDOW_TYPES[name](schema, params, batch_capacity,
                              capacity_hint=capacity_hint)


def gather_packed(arrays, idx):
    """`tuple(a[idx] for a in arrays)` over `[n]` row arrays, as ONE gather
    of whole rows: the arrays' u32 planes (`steputil.u32_planes`) stacked
    `[planes, n]` and gathered along n, as `pattern_planner._gathering`
    moves its columns.  XLA:TPU gathers by the slice, not by the element —
    the time window's planes at 139,264 rows move in ~0.5 ms together and
    in 8.4 ms an array at a time (PERF.md, PR 52).  `idx` must be in
    bounds."""
    packed = jnp.stack([p for a in arrays for p in u32_planes(a)])
    planes = iter(packed.at[:, idx].get(mode="promise_in_bounds"))
    return tuple(from_u32_planes(planes, a.dtype) for a in arrays)


# ---------------------------------------------------------------------------
# the time window as a RING, for a reader of CURRENT rows alone
# ---------------------------------------------------------------------------

class RingSlab(NamedTuple):
    """A ring's rows, `[C]` arrays by ring position.  A 64-bit array is
    kept as its two u32 planes `(low, high)` — XLA:TPU holds an s64 array
    so anyway, and converts a whole s64 argument on the way into and out of
    every program that takes it, a pass over all C rows a step; the planes
    pass through untouched (the pattern state's lesson, PR 27)."""

    ts: Any          # (lo, hi): the event's stamp
    expire_ts: Any   # (lo, hi): ts + the window's time
    gslot: Any       # i32
    cols: Tuple[Any, ...]


def _planes(x):
    """An array as the planes a ring keeps it in."""
    return split64(x) if x.dtype.itemsize == 8 else (x,)


def slab_take(col, idx):
    """`col[idx]` of a ring column (an array, or a 64-bit one's planes)."""
    if isinstance(col, tuple):
        return join64(*(p.at[idx].get(mode="promise_in_bounds")
                        for p in col))
    return col.at[idx].get(mode="promise_in_bounds")


class SlabColumn:
    """A ring column behind `column[idx]` and `.dtype`, which is all the
    join step asks of the other side's columns."""

    def __init__(self, col, dtype):
        self.col, self.dtype = col, dtype

    def __getitem__(self, idx):
        return slab_take(self.col, idx).astype(self.dtype)


def ring_age(pos, tail, C: int):
    """How far behind the tail's row a ring position stands, in arrivals:
    0 the oldest resident row, `count - 1` the newest."""
    return jnp.mod(pos - tail, C)


def ring_search(exp, tail, count, x, C: int):
    """How many of the ring's `count` rows, oldest first, carry
    `expire_ts <= x` — the rows are in expiry order, so the expired ones are
    a prefix and this is its length.  `exp` the stamps' planes, `x` a scalar
    or `[R]`; ~log2(C) reads of `exp` a value, never a pass over it."""
    x = jnp.asarray(x, jnp.int64)
    lo = jnp.zeros(x.shape, jnp.int32)
    hi = jnp.zeros(x.shape, jnp.int32) + count

    def body(_, c):
        lo, hi = c
        go = lo < hi
        mid = (lo + hi) // 2
        le = slab_take(exp, jnp.mod(tail + mid, C)) <= x
        return (jnp.where(jnp.logical_and(go, le), mid + 1, lo),
                jnp.where(jnp.logical_and(go, jnp.logical_not(le)), mid, hi))

    return jax.lax.fori_loop(0, max(1, int(C).bit_length()), body,
                             (lo, hi))[0]


def ring_write(planes, values, src1, take1, h1, src2, take2):
    """Write the arrivals into `[C]` ring planes in place: two blocks of B
    rows each — `[h1, h1 + B)`, which holds the head, and `[0, B)`, where a
    write that passes the end wraps to — read, overlaid (`take*`: which block
    rows are arrivals, `src*`: which) and written back by
    `dynamic_update_slice`.  Nothing else of the C rows is touched.
    `values`: the arrivals' `[B]` arrays, one a plane, of the plane's
    dtype."""
    B = src1.shape[0]
    v1 = gather_packed(values, src1)
    v2 = gather_packed(values, src2)
    out = []
    for p, a, b in zip(planes, v1, v2):
        blk = jax.lax.dynamic_slice(p, (h1,), (B,))
        p = jax.lax.dynamic_update_slice(p, jnp.where(take1, a, blk), (h1,))
        p = jax.lax.dynamic_update_slice(
            p, jnp.where(take2, b, p[:B]), (0,))
        out.append(p)
    return tuple(out)


class TimeRingWindow(TimeWindow):
    """`window.time` for a reader of CURRENT rows alone (a CURRENT-only
    projection join's sides, core/join.py): the slab is a RING.  A step costs
    what arrives, never what is resident —

    - the arrivals are written at the head in place (`ring_write`);
    - the rows due by `now` are a PREFIX of the ring (stamps that never step
      back make expiry order arrival order), found by `ring_search`; the
      tail moves past them and nothing is rewritten — no EXPIRED row is
      built, there is no `alive` plane;
    - a row is resident iff its `ring_age` is below `count`; a reader at
      stamp `t` sees it iff also `expire_ts > t`.

    State: `(RingSlab, seq, pos)`, `pos` = i32 `[tail, count]`.  `admit`
    needs the batch's CURRENT stamps non-decreasing and none before the
    side's last (the runtime checks them on the host and otherwise sends the
    batch through `ring_process`, today's whole-slab `process`, which leaves
    the ring in expiry order again).  A row that would overwrite a resident
    one is COUNTED (`dropped`): the oldest row goes, and the runtime reports
    it as an error — a window bound too small is a wrong answer."""

    current_is_arrivals = True

    def __init__(self, schema, params, batch_capacity, capacity_hint=2048):
        super().__init__(schema, params, batch_capacity, capacity_hint)
        # the bound as asked for: the ring's rows ARE the window's bound
        # (no `2 x batch` floor — a trace whose batch is wider than the
        # ring takes `ring_process`)
        self.capacity = max(int(capacity_hint), 8)

    @staticmethod
    def _slab_of(ts, expire_ts, gslot, cols) -> RingSlab:
        return RingSlab(ts=_planes(ts), expire_ts=_planes(expire_ts),
                        gslot=gslot,
                        cols=tuple(c if c.dtype.itemsize != 8 else _planes(c)
                                   for c in cols))

    def init_state(self):
        buf = empty_buffer(self.schema, self.capacity)
        return (self._slab_of(buf.ts, buf.expire_ts, buf.gslot, buf.cols),
                jnp.asarray(0, jnp.int64), jnp.zeros((2,), jnp.int32))

    def slab_columns(self, slab: RingSlab):
        """The slab's columns as the join step reads them."""
        return tuple(SlabColumn(c, d)
                     for c, d in zip(slab.cols, self.schema.dtypes))

    def _arrivals(self, rows: Rows):
        is_cur = jnp.logical_and(rows.valid, rows.kind == ev.CURRENT)
        k = jnp.cumsum(is_cur.astype(jnp.int32)) - 1
        return is_cur, k, jnp.sum(is_cur, dtype=jnp.int32)

    def ring_admit(self, state, rows: Rows, now):
        """-> (state, the arrivals as CURRENT rows where they stand, `pos`
        [B] the ring position each was written to (C where none), `dropped`,
        `plan`): `plan` is `ring_write`'s last five arguments, for a caller
        that keeps a plane of its own by ring position (the join's same-key
        links) and writes its arrivals' values the same way."""
        slab, seq0, rp = state
        C, B, t = self.capacity, rows.capacity, self.time_ms
        tail, count = rp[0], rp[1]
        with jax.named_scope("window_state"):
            is_cur, k, ncur = self._arrivals(rows)
            n_exp = ring_search(slab.expire_ts, tail, count, now, C)
            tail = jnp.mod(tail + n_exp, C)
            count = count - n_exp
            head = jnp.mod(tail + count, C)
            dropped = jnp.maximum(count + ncur - C, 0)
            tail = jnp.mod(tail + dropped, C)
            count = count + ncur - dropped
            # the arrivals, valid ones first (as they stand where the
            # batch's invalid rows are its tail, which is how it is staged)
            order = jnp.argsort(jnp.logical_not(is_cur),
                                stable=True).astype(jnp.int32)
            r = jnp.arange(B, dtype=jnp.int32)
            h1 = jnp.minimum(head, C - B)
            i1 = r + (h1 - head)
            i2 = r + (C - head)
            plan = (order[jnp.clip(i1, 0, B - 1)],
                    jnp.logical_and(i1 >= 0, i1 < ncur), h1,
                    order[jnp.clip(i2, 0, B - 1)], i2 < ncur)
            flat, tree = jax.tree.flatten(slab)
            values = jax.tree.leaves(self._slab_of(
                rows.ts, rows.ts + t, rows.gslot, rows.cols))
            nslab = jax.tree.unflatten(tree, ring_write(flat, values, *plan))
            pos = jnp.where(is_cur, jnp.mod(head + k, C), C)
            cur = Rows(rows.ts, jnp.full((B,), ev.CURRENT, jnp.int32),
                       is_cur, seq0 + k.astype(jnp.int64), rows.gslot,
                       rows.cols)
            nstate = (nslab, seq0 + ncur.astype(jnp.int64),
                      jnp.stack([tail, count]).astype(jnp.int32))
        return nstate, cur, pos, dropped, plan

    def admit(self, state, rows: Rows, now):
        nstate, cur, _pos, _dropped, _ = self.ring_admit(state, rows, now)
        return nstate, WindowOutput(cur, None,
                                    jnp.asarray(NO_WAKEUP, jnp.int64))

    def current_buffer(self, state):
        """The resident rows as a `Buffer` whose `alive` says so and whose
        `add_seq` is their age (an on-demand read; a pass over the slab)."""
        slab, rp = state[0], state[2]   # (a join side keeps more behind)
        C = self.capacity
        age = ring_age(jnp.arange(C, dtype=jnp.int32), rp[0], C)
        live = age < rp[1]
        big = jnp.full((C,), BIG_SEQ, jnp.int64)
        return Buffer(
            ts=join64(*slab.ts),
            add_seq=jnp.where(live, age.astype(jnp.int64) - (C + 1), big),
            expire_seq=big,
            expire_ts=jnp.where(live, join64(*slab.expire_ts), big),
            alive=live, gslot=slab.gslot,
            cols=tuple(join64(*c).astype(d) if isinstance(c, tuple) else c
                       for c, d in zip(slab.cols, self.schema.dtypes)))

    def ring_process(self, state, rows: Rows, now):
        """The batch through `TimeWindow.process` — for stamps out of order,
        a batch wider than the ring: the whole slab is sorted and rewritten,
        as before the ring.  -> (state, the arrivals as CURRENT rows where
        they stand, `dropped`); the ring then starts at 0, in expiry order
        (a row that came late stands where its expiry puts it)."""
        _slab, seq0, _rp = state
        C = self.capacity
        old = self.current_buffer(state)
        is_cur, k, ncur = self._arrivals(rows)
        kept = jnp.sum(jnp.logical_and(old.alive, old.expire_ts > now),
                       dtype=jnp.int32)
        dropped = jnp.maximum(kept + ncur - C, 0)
        (nbuf, nseq), _ = TimeWindow.process(self, (old, seq0), rows, now)
        with jax.named_scope("window_state"):
            order = jnp.argsort(
                jnp.where(nbuf.alive, nbuf.expire_ts, BIG_SEQ),
                stable=True).astype(jnp.int32)
            g = gather_packed(
                (nbuf.ts, nbuf.expire_ts, nbuf.gslot) + tuple(nbuf.cols),
                order)
            nbuf = nbuf._replace(ts=g[0], expire_ts=g[1], gslot=g[2],
                                 cols=tuple(g[3:]))
            count = jnp.sum(nbuf.alive, dtype=jnp.int32)
        cur = Rows(rows.ts, jnp.full(rows.ts.shape, ev.CURRENT, jnp.int32),
                   is_cur, seq0 + k.astype(jnp.int64), rows.gslot, rows.cols)
        nstate = (self._slab_of(nbuf.ts, nbuf.expire_ts, nbuf.gslot,
                                nbuf.cols), nseq,
                  jnp.stack([jnp.zeros_like(count), count]))
        return nstate, cur, dropped

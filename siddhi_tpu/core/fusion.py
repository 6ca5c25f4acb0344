"""Scan-fused multi-batch stepping: K device steps per dispatch, one
header fetch (`@fuse(batches='K')`).

Reference behavior (what): none — the reference processes one event at a
time; batching depth is a TPU-native concern.

TPU design (how): at small batches the engine is bound by per-send
fixed costs, not by device work — each send pays host dispatch, an H2D
submit and a blocking emission fetch whatever its size (the un-fused
shares on the v5e: PERF.md sections 5-7, `sequence_within.paced` — the
denominator a fused cell is to be judged against; none runs `@fuse` yet).
Fused stepping stacks K staged micro-batches into [K, B]
host arrays, ships them in ONE transfer, and runs the compiled query
step as a `lax.scan` over the leading axis in ONE dispatch:
partition/window/NFA state threads through the scan carry exactly as it
threads through K sequential `jit_step` calls, emissions accumulate into
a [K, cap] block, and a single combined [K, 2] header rides one
`device_get`.  Per-send fetch and dispatch overhead divide by K.

Semantics: a fused query's processing (and therefore its delivery,
table writes, and downstream routing) lags up to K-1 batches until the
stack fills or `flush()` drains it — the same relaxation `@pipeline`
makes for delivery, extended to the step itself.  Partial stacks drain
through the ORIGINAL sequential path, so a flush is byte-identical to
never having fused.  Timer-bearing queries (time/cron windows, absent
patterns) are excluded at wiring time, same rule as `@pipeline`: their
device-computed wake scalar cannot lag.

Paths fused: plain (non-keyed, non-range-partition) single-stream
queries, non-partitioned pattern/sequence queries, join sides — each
wraps the plan's un-jitted step body so fused and sequential execution
run the identical per-batch program — and MESH-SHARDED partitioned
patterns, whose stacks run a lax.scan INSIDE the shard_map
(pattern_planner._shard_fused_step) so the per-dispatch overhead divides
by K per shard.  Keyed-window and unsharded partitioned-pattern paths
fall back to sequential dispatch.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Tuple

import jax
import numpy as np

from ..observability import phases as _phases
from . import event as ev
from .keyslots import valid_first_sel
from .steputil import fuse_step

jnp = jax.numpy


def ineligible_reason(qr, kind: str):
    """Why this runtime cannot fuse (None = eligible).  Static properties
    only; per-batch variation is handled by the stack signature."""
    if kind == "merged":
        # a merge group only admits timer-free, unsharded plain members
        # (optimizer/mqo.py), so the merged body always fuses
        return None
    p = qr.planned
    if kind == "plain":
        if p.needs_timer:
            return "timer-bearing window (time/cron) — wake cannot lag"
        if p.keyed_window:
            return "keyed-window slab path is not fused yet"
        if p.partition_key_fn is not None:
            return "range-partition key derivation is not fused yet"
        if p.raw_step is None:
            return "sharded step has no fusable body"
        return None
    if kind == "pattern":
        if p.timer_step is not None:
            return "absent pattern needs timer wakeups — wake cannot lag"
        if p.mesh is not None:
            # sharded partitioned patterns fuse through the shard_map'd
            # scan step (pattern_planner._shard_fused_step)
            if p.shard_fused_steps:
                return None
            return "sharded pattern step has no fusable body"
        if p.partition_positions:
            return "partitioned pattern grouping is not fused yet"
        if p.step_bodies is None:
            return "sharded pattern step has no fusable body"
        return None
    if kind == "join":
        if p.needs_timer:
            return "timer-bearing join window — wake cannot lag"
        if (p.step_left is not None and p.raw_left is None) or \
                (p.step_right is not None and p.raw_right is None):
            return "sharded join step has no fusable body"
        return None
    return f"unknown runtime kind {kind!r}"


def eligibility(qr, kind: str) -> Dict:
    """Fusion facts for EXPLAIN (observability/explain.py): whether the
    query CAN fuse, whether it IS fusing (and at what K), and — when
    @fuse was requested but wiring skipped it — the concrete exclusion
    reason instead of a log line that scrolled away."""
    reason = ineligible_reason(qr, kind)
    node: Dict = {"eligible": reason is None}
    if reason is not None:
        node["exclusion_reason"] = reason
    fb = qr._fuse
    node["active"] = fb is not None
    if fb is not None:
        node["batches"] = fb.k
    elif qr._fuse_requested:
        node["requested_batches"] = qr._fuse_requested
    return node


class FuseBuffer:
    """Per-query accumulator of staged sends for fused dispatch.

    All entry points run under the query lock (junction dispatch holds
    it), so the buffer needs no lock of its own.  `offer` stacks
    same-signature batches (same input tag + bucket capacity); a
    signature change drains the pending stack sequentially first, so
    cross-batch order within the query is preserved exactly.
    """

    __slots__ = ("qr", "k", "kind", "items", "sig", "bypass", "ingests")

    def __init__(self, qr, k: int, kind: str):
        self.qr = qr
        self.k = max(1, int(k))
        self.kind = kind
        self.items: List[Tuple] = []
        # per-item ingest stamps (junction send-acceptance perf_counter_ns,
        # or None at OFF): a batch's `<query>:e2e` sample must include the
        # time it sat in this stack waiting for the dispatch
        self.ingests: List = []
        self.sig = None
        self.bypass = False

    def offer(self, args: Tuple, staged: ev.StagedBatch, tag) -> bool:
        """Accept a send into the stack.  Returns False when the caller
        must run the sequential path itself (drain re-entry, or an
        attached debugger that expects per-batch breakpoints)."""
        if self.bypass or self.qr.app._debugger is not None:
            return False
        # captured before a signature-change drain(), which resets the
        # runtime's stash while re-processing the OLD stack
        t_in = self.qr._ingest_ns
        sig = (tag, staged.ts.shape[0])
        if self.items and sig != self.sig:
            self.drain()
        self.sig = sig
        self.items.append(args)
        self.ingests.append(t_in)
        if len(self.items) >= self.k:
            self.dispatch()
        return True

    def drain(self) -> None:
        """Deliver a partial stack through the ORIGINAL sequential path
        (flush()/quiesce/signature change): byte-identical to never
        having fused, at sequential cost — partial stacks are rare and a
        scan re-trace per partial length would be a recompile per size."""
        if not self.items:
            return
        items, self.items = self.items, []
        ingests, self.ingests = self.ingests, []
        qr = self.qr
        self.bypass = True
        try:
            for args, t_in in zip(items, ingests):
                qr._ingest_ns = t_in
                qr.process_staged(*args)
                # consume the inline-delivery flag HERE (a drain may run
                # from flush()/quiesce with no junction dispatch around
                # it to close e2e) — stack wait is inside the sample
                owed, qr._e2e_owed = qr._e2e_owed, False
                if owed and t_in is not None and qr.app.stats.enabled:
                    qr.app.stats.e2e_latency(
                        qr.name, time.perf_counter_ns() - t_in)
        finally:
            self.bypass = False
            qr._ingest_ns = None

    def dispatch(self) -> None:
        """Run the full stack as ONE fused device dispatch."""
        items, self.items = self.items, []
        self.qr._fused_ingests, self.ingests = self.ingests, []
        qr = self.qr
        stats = qr.app.stats
        k = len(items)
        t0 = time.perf_counter_ns() if stats.enabled else 0
        _DISPATCH[self.kind](qr, items)
        if stats.enabled:
            n = sum(int(a[-2].n) for a in items)
            stats.fused_dispatch(qr.name, k, n,
                                 time.perf_counter_ns() - t0)


def pending(qr) -> int:
    """Batches held in a runtime's fuse stack (0 for unfused runtimes)."""
    fb = qr._fuse
    return len(fb.items) if fb is not None else 0


def drain(qr) -> None:
    """Flush a runtime's partial stack (lifecycle: flush/quiesce/
    shutdown).  Takes the query lock — the producer's offer path runs
    under it too, so a concurrent send can never double-process."""
    fb = qr._fuse
    if fb is None or not fb.items:
        return
    with qr._qlock:
        fb.drain()


# ---------------------------------------------------------------------------
# fused step compilation (one per (kind, base body); jit handles K/shape
# specialization).  The cache holds the body so a replan (emission-cap
# growth swaps the plan's bodies) can never alias a recycled id().
# ---------------------------------------------------------------------------

def _fused_fn(qr, kind: str, body: Callable) -> Callable:
    cache: Dict = qr._fused_cache
    key = (kind, id(body))
    ent = cache.get(key)
    if ent is not None and ent[0] is body:
        return ent[1]
    adapter = _ADAPTERS[kind](body)
    fn = fuse_step(adapter, owner=f"fused:{qr.name}", role=f"fused_{kind}")
    cache[key] = (body, fn)
    return fn


def _adapt_plain(body):
    def fused_body(carry, x, const):
        ts, kind, valid, cols, gslot, now, pslots = x
        carry, out, _wake = body(carry, ts, kind, valid, cols, gslot,
                                 now, const, pslots)
        return carry, out
    return fused_body


def _adapt_pattern(body):
    def fused_body(carry, x, const):
        cols, ts, sel_idx, key_idx, now = x
        pstate, sel_state, out, _wake = body(
            carry[0], carry[1], cols, ts, sel_idx, key_idx, now, const)
        return (pstate, sel_state), out
    return fused_body


def _adapt_join(body):
    def fused_body(carry, x, const):
        if len(x) == 7:
            # equi-join fast path: per-batch probe (bucket slots or
            # host table candidates) rides the stack
            ts, kind, valid, cols, gslot, probe, now = x
            carry, out, _wake = body(carry, ts, kind, valid, cols,
                                     gslot, probe, const, now)
        else:
            ts, kind, valid, cols, gslot, now = x
            carry, out, _wake = body(carry, ts, kind, valid, cols, gslot,
                                     const, now)
        return carry, out
    return fused_body


def _adapt_merged(body):
    def fused_body(carry, x, const):
        ts, kind, valid, cols, gslots, now, pslots = x
        carry, out, _wake = body(carry, ts, kind, valid, cols, gslots,
                                 now, const, pslots)
        return carry, out
    return fused_body


_ADAPTERS = {"plain": _adapt_plain, "pattern": _adapt_pattern,
             "join": _adapt_join, "merged": _adapt_merged}


# ---------------------------------------------------------------------------
# per-kind dispatch: host slot prep (in arrival order), stack, one fused
# step, unstack + deliver
# ---------------------------------------------------------------------------

def _now_stack(items) -> jax.Array:
    return jnp.asarray(np.asarray([a[-1] for a in items], np.int64))


def _stack_nbytes(stack, *more) -> int:
    """Host bytes of one fused upload: the [K, B] stack plus whatever
    rides with it."""
    return _phases.nbytes(stack.ts, stack.kind, stack.valid, *stack.cols,
                          *more)


def _dispatch_plain(qr, items) -> None:
    p = qr.planned
    st, k = qr.app.stats, len(items)
    prep = [qr._slots_for_batch(staged, now) for staged, now in items]
    with _phases.phase(st, qr.name, "stage", k):
        stack = ev.StackedBatch([staged for staged, _ in items])
        gslot_np = np.stack([np.asarray(g) for g, _ in prep])
        pslots_np = [np.stack([np.asarray(ps[j]) for _, ps in prep])
                     for j in range(len(p.pair_allocs))]
    with _phases.phase(st, qr.name, "h2d", k,
                       bytes=_stack_nbytes(stack, gslot_np, *pslots_np)):
        batch = stack.to_device(p.in_schema)
        xs = (batch.ts, batch.kind, batch.valid, batch.cols,
              jnp.asarray(gslot_np), _now_stack(items),
              tuple(jnp.asarray(a) for a in pslots_np))
    const = qr.app.in_probe_tables(p.in_deps)
    fn = _fused_fn(qr, "plain", p.raw_step)
    qr.state, outs = _phases.dispatch(qr, fn, qr.state, xs, const, mult=k)
    _deliver_fused(qr, outs, [now for _, now in items])


def _prepare_pattern(qr, items) -> Tuple[Callable, Tuple, Tuple]:
    """(fused fn, stacked xs, const) for a pattern stack — also the entry
    bench.py's device_loop mode uses to time chip-side throughput with
    device-resident inputs and zero emission fetches."""
    from . import runtime as _rt
    p = qr.planned
    st, k = qr.app.stats, len(items)
    stream_id = items[0][0]
    with _phases.phase(st, qr.name, "stage", k):
        sel_np = np.stack([
            _rt._identity_sel(staged.valid.shape[0]) if staged.valid.all()
            else valid_first_sel(staged.valid) for _, staged, _ in items])
        stack = ev.StackedBatch([staged for _, staged, _ in items])
    with _phases.phase(st, qr.name, "h2d", k,
                       bytes=_stack_nbytes(stack, sel_np)):
        # the sequential pattern path ships raw staged columns (np_dtype
        # already matches the device dtypes) — mirror it exactly
        cols_k = tuple(jnp.asarray(c) for c in stack.cols)
        xs = (cols_k, jnp.asarray(stack.ts), jnp.asarray(sel_np),
              jnp.asarray(np.zeros((k, 1), np.int32)), _now_stack(items))
    return (_fused_fn(qr, "pattern", p.step_bodies[stream_id]), xs,
            qr._in_tabs())


def _dispatch_pattern(qr, items) -> None:
    if qr.planned.mesh is not None:
        return _dispatch_pattern_sharded(qr, items)
    fn, xs, const = _prepare_pattern(qr, items)
    qr.state, outs = _phases.dispatch(qr, fn, qr.state, xs, const,
                                      mult=len(items))
    _deliver_fused(qr, outs, [now for _, _, now in items])


def _dispatch_pattern_sharded(qr, items) -> None:
    """Fused dispatch of a MESH-sharded partitioned pattern: each batch
    routes through the key-space router on the host (slot binding), the
    grouped layouts pad to one common [n*Kb, E] shape across the stack,
    and the whole [K, ...] block runs as ONE shard_map'd scan dispatch
    (pattern_planner._shard_fused_step).  Then, while the chips run it
    and before anything is delivered, each batch's bookkeeping (liveness
    touch, dirty marking, key hotness, per-shard counters — the identical
    `_shard_feed` the sequential sharded path runs after its dispatch)."""
    p = qr.planned
    stream_id = items[0][0]
    preps, feeds = [], []
    for _, staged, now in items:
        key_idx, sel, fed = qr._shard_prep(stream_id, staged)
        preps.append((key_idx, sel))
        feeds.append((*fed, now, key_idx))
    n = preps[0][0].shape[0]
    Kb = max(ki.shape[1] for ki, _ in preps)
    E = max(s.shape[2] for _, s in preps)
    block = qr.shard_router.block
    st, k = qr.app.stats, len(items)
    with _phases.phase(st, qr.name, "stage", k):
        key_k = np.full((k, n, Kb), block, np.int32)
        sel_k = np.full((k, n, Kb, E), -1, np.int32)
        for i, (ki, s) in enumerate(preps):
            key_k[i, :, :ki.shape[1]] = ki
            sel_k[i, :, :s.shape[1], :s.shape[2]] = s
        stack = ev.StackedBatch([staged for _, staged, _ in items])
    with _phases.phase(st, qr.name, "h2d", k,
                       bytes=_stack_nbytes(stack, sel_k, key_k)):
        xs = (tuple(jnp.asarray(c) for c in stack.cols),
              jnp.asarray(stack.ts),
              jnp.asarray(sel_k.reshape(k, n * Kb, E)),
              jnp.asarray(key_k.reshape(k, n * Kb)),
              _now_stack(items))
    try:
        qr.state, outs = _phases.dispatch(
            qr, p.shard_fused_steps[stream_id], qr.state, xs,
            qr._in_tabs(), mult=k)
    finally:
        for feed in feeds:
            qr._shard_feed(*feed)
    _deliver_fused(qr, outs, [now for _, _, now in items])


def _dispatch_join(qr, items) -> None:
    p = qr.planned
    is_left = items[0][0]
    side = p.left if is_left else p.right
    body = p.raw_left if is_left else p.raw_right
    st, k = qr.app.stats, len(items)
    gs = [qr._join_slots(is_left, staged) for _, staged, _ in items]
    with _phases.phase(st, qr.name, "stage", k):
        stack = ev.StackedBatch([staged for _, staged, _ in items])
        host = [np.stack([np.asarray(g) for g in gs])]
        if p.fastpath == "bucket":
            # probes were bound (and the retention mirror fed) at offer
            # time, so the stack replays them verbatim
            host.append(np.stack(
                [np.asarray(qr._join_key_probe(is_left, staged)[0])
                 for _, staged, _ in items]))
        elif p.fastpath == "table":
            # candidates resolve against the table at DISPATCH time — the
            # same moment `const` snapshots its columns below
            probes = [qr._table_probe(staged) for _, staged, _ in items]
            w = max(c.shape[1] for c, _ in probes)
            b = probes[0][0].shape[0]
            cand_k = np.full((len(probes), b, w), -1, np.int32)
            ok_k = np.zeros((len(probes), b, w), np.bool_)
            for i, (c, o) in enumerate(probes):
                cand_k[i, :, :c.shape[1]] = c
                ok_k[i, :, :o.shape[1]] = o
            host += [cand_k, ok_k]
    with _phases.phase(st, qr.name, "h2d", k,
                       bytes=_stack_nbytes(stack, *host)):
        batch = stack.to_device(side.schema)
        xs = [batch.ts, batch.kind, batch.valid, batch.cols,
              jnp.asarray(host[0])]
        if p.fastpath == "bucket":
            xs.append(jnp.asarray(host[1]))
        elif p.fastpath == "table":
            xs.append((jnp.asarray(host[1]), jnp.asarray(host[2])))
        xs.append(_now_stack(items))
    # table/aggregation other-side snapshot is taken ONCE at dispatch:
    # under @fuse the per-batch read-your-writes of a concurrently
    # updated table relaxes to dispatch granularity (stream other-sides
    # live in the carry and stay exact)
    const = qr._other_table(is_left)
    fn = _fused_fn(qr, "join", body)
    qr.state, outs = _phases.dispatch(qr, fn, qr.state, tuple(xs), const,
                                      mult=k)
    _deliver_fused(qr, outs, [now for _, _, now in items])


def _dispatch_merged(qr, items) -> None:
    """Fused dispatch of a MERGE GROUP's stack (optimizer/mqo.py): K
    staged batches × N member queries in ONE lax.scan device dispatch,
    then one combined fetch feeds the per-batch, per-query demux."""
    from . import runtime as _rt
    stats = qr.app.stats
    t0 = time.perf_counter_ns() if stats.enabled else 0
    K = len(items)
    gname = f"merged:{qr.group}"
    preps = [qr._prep(staged, now) for staged, now in items]
    with _phases.phase(stats, gname, "stage", K):
        stack = ev.StackedBatch([staged for staged, _ in items])
        gslots_np = [np.stack([np.asarray(p[0][u]) for p in preps])
                     for u in range(len(qr.units))]
        pslots_np = [
            [np.stack([np.asarray(p[1][i][j]) for p in preps])
             for j in range(len(qr.members[i].planned.pair_allocs))]
            for i in range(len(qr.members))]
    with _phases.phase(
            stats, gname, "h2d", K, bytes=_stack_nbytes(
                stack, *gslots_np, *(a for ps in pslots_np for a in ps))):
        batch = stack.to_device(qr.in_schema)
        xs = (batch.ts, batch.kind, batch.valid, batch.cols,
              tuple(jnp.asarray(a) for a in gslots_np), _now_stack(items),
              tuple(tuple(jnp.asarray(a) for a in ps) for ps in pslots_np))
    fn = _fused_fn(qr, "merged", qr.raw_body)
    qr._state, outs = _phases.dispatch(
        qr, fn, qr._state, xs, qr._in_tabs(), name=gname, mult=K)
    if stats.enabled:
        stats.counter_inc(f"merged.{qr.group}.dispatches")
        stats.counter_inc(f"merged.{qr.group}.member_batches",
                          len(qr.members) * len(items))
    ingests, qr._fused_ingests = qr._fused_ingests, None
    if ingests is None or len(ingests) != K:
        ingests = [None] * K
    consumers = [i for i, m in enumerate(qr.members)
                 if _rt._has_consumers(m)]
    if consumers and not qr.members[0].defers_delivery():
        # ONE fetch for every consumed member's whole [K, ...] block;
        # per-batch views below are then numpy slices
        host = _phases.fetch(stats, gname, "rows",
                             [outs[i] for i in consumers])
        outs = list(outs)
        for i, h in zip(consumers, host):
            outs[i] = h
        outs = tuple(outs)
    batches = []
    for k, (staged, now) in enumerate(items):
        out_k = tuple(
            (o[0][k], o[1][k], o[2][k], tuple(c[k] for c in o[3]))
            if i in consumers else None
            for i, o in enumerate(outs))
        batches.append((out_k, staged, now, ingests[k]))
    qr._demux(batches, t0)


_DISPATCH = {"plain": _dispatch_plain, "pattern": _dispatch_pattern,
             "join": _dispatch_join, "merged": _dispatch_merged}


# ---------------------------------------------------------------------------
# fused delivery: one [K, 2] header fetch, per-batch unstacked emission
# ---------------------------------------------------------------------------

def _deliver_fused(qr, outs, nows: List[int]) -> None:
    """Unstack the fused [K, ...] output block and deliver each batch's
    emission in order.

    Sync mode fetches ONE combined header ([K, 2] for compacted
    pattern/join outputs; the whole capacity-bounded block for plain
    outputs) and feeds per-batch numpy slices through the standard
    emission path.  @serve/@async/@pipeline compose by re-entering
    `_emit_output` per batch — the serving ring appends stay
    dispatch-only and the drainer/deque already batch their header
    fetches.  A per-batch failure (emission-cap overflow, callback
    error) defers until every batch has been delivered, then the first
    error propagates to the junction's fault routing."""
    from . import runtime as _rt
    ingests, qr._fused_ingests = qr._fused_ingests, None
    if not _rt._has_consumers(qr):
        return
    K = len(nows)
    if ingests is None or len(ingests) != K:
        ingests = [None] * K
    if qr.defers_delivery():
        for i in range(K):
            # per-batch stamp restored so _emit_output's deferred queues
            # (drainer / @pipeline deque) carry the right e2e origin
            qr._ingest_ns = ingests[i]
            _rt._emit_output(qr, _slice_out(outs, i), nows[i], wake=None)
        qr._ingest_ns = None
        return
    first_exc = None
    _st = qr.app.stats
    if len(outs) == 6:
        # ONE fetch for the combined [K, 2] header (join headers are
        # [K, 2] vectors themselves; still one fetch)
        h0, h1 = _phases.fetch(_st, qr.name, "header",
                               (outs[0], outs[1]))
        # _emit_output_sync_impl's own test: a junction nobody reads
        # must not force a bulk fetch
        need_rows = bool(qr.callbacks) or _rt._target_live(qr) or \
            qr.planned.emits_uuid
        bulk = _phases.fetch(_st, qr.name, "rows", outs[2:]) \
            if need_rows else outs[2:]
        for i in range(K):
            out_i = (h0[i], h1[i], bulk[0][i], bulk[1][i], bulk[2][i],
                     tuple(c[i] for c in bulk[3]))
            try:
                _rt._emit_output_sync(qr, out_i, nows[i],
                                      header=(h0[i], h1[i]),
                                      ingest_ns=ingests[i])
            except Exception as exc:  # noqa: BLE001 — deliver the rest
                first_exc = first_exc or exc
    else:
        # plain outputs are window-capacity bounded and always ship
        # whole on the sequential path too: ONE fetch for the block
        ots, okind, ovalid, ocols = _phases.fetch(_st, qr.name, "rows",
                                                  outs)
        for i in range(K):
            out_i = (ots[i], okind[i], ovalid[i],
                     tuple(c[i] for c in ocols))
            try:
                _rt._emit_output_sync(qr, out_i, nows[i],
                                      ingest_ns=ingests[i])
            except Exception as exc:  # noqa: BLE001 — deliver the rest
                first_exc = first_exc or exc
    if first_exc is not None:
        raise first_exc


def _slice_out(outs, i: int):
    """Per-batch device-array view of the stacked output (for @async/
    @pipeline composition, where the fetch happens downstream)."""
    if len(outs) == 6:
        return (outs[0][i], outs[1][i], outs[2][i], outs[3][i],
                outs[4][i], tuple(c[i] for c in outs[5]))
    return (outs[0][i], outs[1][i], outs[2][i],
            tuple(c[i] for c in outs[3]))

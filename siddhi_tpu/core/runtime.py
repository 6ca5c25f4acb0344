"""App runtime: manager, junctions, input handlers, callbacks, scheduler.

Reference (what): CORE/SiddhiManager.java:49, CORE/SiddhiAppRuntimeImpl.java:99,
CORE/stream/StreamJunction.java:61, CORE/stream/input/InputHandler.java:50,
CORE/util/Scheduler.java:48.  The reference routes one pooled event at a time
through object chains with per-query locks; here the junction stages a whole
micro-batch into numpy once, each subscribing query computes its group slots
and runs its fused jitted step, and a host scheduler injects TIMER batches
for time-based windows.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import heapq
import itertools
import logging
import pickle
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..exceptions import (CannotRestoreStateError, CapacityExceededError,
                          DefinitionNotExistError, MatchOverflowError,
                          QueryNotExistError, SiddhiAppValidationError)
from ..observability import tracing as _tracing
from ..observability import phases as _phases
from ..observability import stateobs as _stateobs
from ..query_api.app import SiddhiApp
from ..query_api.definition import StreamDefinition
from ..query_api.query import Partition, Query, SingleInputStream
from . import event as ev
from . import state_rows
from .executor import CompileError
from .keyslots import SlotAllocator, valid_first_sel
from .pattern_planner import (HEAD_DTYPES, HEAD_WORDS, BandedEmission,
                              StatePacker, unpack_planes, unpack_planes_at,
                              valid_slots)
from .planner import PlannedQuery, plan_single_query
from .window import NO_WAKEUP
from .steputil import jit_step
from . import fusion as _fusion
from .. import sharding as _sharding

_NO_WAKEUP_INT = int(NO_WAKEUP)

# @app:statistics DETAIL-level event tracing (reference: log4j TRACE at
# StreamJunction.sendEvent :147 and QuerySelector.process :77)
_trace_log = logging.getLogger("siddhi_tpu.trace")

# shared no-op context for the DETAIL `query` span at OFF/BASIC (nullcontext
# enter/exit is stateless, so ONE instance serves every thread without
# allocating per batch)
_NULL_CM = contextlib.nullcontext()


def _staged_nbytes(staged) -> int:
    """Bytes `staged.to_device` will upload: nothing when the serving
    stager already did at the accept edge."""
    if staged.dev is not None:
        return 0
    return _phases.nbytes(staged.ts, staged.kind, staged.valid, *staged.cols)


def current_millis() -> int:
    return int(time.time() * 1000)


class StreamCallback:
    """Subscribe to all events of a stream (reference:
    CORE/stream/output/StreamCallback.java:38)."""

    def receive(self, events: List[ev.Event]) -> None:
        raise NotImplementedError


class QueryCallback:
    """Per-query output callback (reference: CORE/query/output/callback/
    QueryCallback.java): receive(timestamp, current_events, expired_events)."""

    def receive(self, timestamp: int, in_events: Optional[List[ev.Event]],
                out_events: Optional[List[ev.Event]]) -> None:
        raise NotImplementedError


@contextlib.contextmanager
def _query_lock(lk, stream_id: str, timeout: float = 30.0):
    """Bounded query-lock acquisition: a worker holding query X's lock and
    synchronously routing into query Y can form a cycle with another
    worker.  Rather than deadlocking forever, fail loudly with the remedy
    (mark a stream in the cycle @async to break it)."""
    if not lk.acquire(timeout=timeout):
        from ..exceptions import SiddhiAppRuntimeError
        raise SiddhiAppRuntimeError(
            f"query lock timeout dispatching {stream_id!r}: likely a "
            f"cyclic synchronous insert-into topology under concurrent "
            f"ingestion; annotate a stream in the cycle with @async to "
            f"break it")
    try:
        yield
    finally:
        lk.release()


def _acquire_all(locks):
    """All-or-nothing multi-lock acquisition with backoff.  Ingestion
    workers take query locks in routing order (a query emitting into a
    downstream stream holds its own lock while taking the next), so a
    fixed-order blocking acquisition here could deadlock; try-acquire and
    retry instead."""
    while True:
        acquired = []
        for lk in locks:
            if lk.acquire(timeout=0.05):
                acquired.append(lk)
            else:
                break
        if len(acquired) == len(locks):
            stack = contextlib.ExitStack()
            for lk in acquired:
                stack.callback(lk.release)
            return stack
        for lk in reversed(acquired):
            lk.release()
        time.sleep(0.001)


def _rebucket_for(qr, old_layout, host_state):
    """Mesh-resize restore: permute a snapshot's key-state rows into THIS
    runtime's shard layout when it was written under a different mesh
    size (sharding/snapshot.py).  Identity for same-mesh restores and
    pre-layout snapshots."""
    new_layout = _sharding.query_layout(qr)
    if not _sharding.needs_rebucket(old_layout, new_layout):
        return host_state
    return _sharding.rebucket_state(host_state, old_layout, new_layout,
                                    qr.planned)


def _host_state(qr):
    """A query's state as a snapshot carries it: numpy leaves, and a
    pattern's two 64-bit planes joined into the one int64 `b64` array
    (StatePacker.to_host), so the host / on-disk format is the same
    whatever the device layout."""
    if isinstance(qr, PatternQueryRuntime):
        packed, sel_state = qr.state
        return (StatePacker.to_host(packed),
                jax.tree.map(lambda x: np.asarray(x), sel_state))
    return jax.tree.map(lambda x: np.asarray(x), qr.state)


def _device_state(qr, host_state):
    """Inverse of `_host_state`: a snapshot's (re-bucketed) host state
    as device arrays in the runtime's own layout."""
    if isinstance(qr, PatternQueryRuntime):
        packed, sel_state = host_state
        *arrays, scalars = StatePacker.from_host(packed)
        host_state = ((*arrays, _all_scalars(qr, scalars)), sel_state)
    return jax.tree.map(lambda x: jax.numpy.asarray(x), host_state)


def _all_scalars(qr, scalars) -> tuple:
    """A snapshot's pattern-state scalars as THIS runtime's state has
    them: one written before a scalar existed (`PatternState.forked`,
    PR 55: a count pattern's second) gets the missing ones at zero."""
    mine = qr.state[0][3]
    return tuple(scalars) + tuple(
        np.zeros((), np.asarray(s).dtype) for s in mine[len(scalars):])


def _allocator_of(qr):
    """Slot allocator of a query runtime (pattern runtimes hold it
    directly, planned single queries on the plan).  Explicit None checks:
    an EMPTY allocator is len()==0 and must still be returned (a fresh
    runtime restoring a snapshot hits exactly that state)."""
    a = qr.slot_allocator
    if a is None:
        a = qr.planned.slot_allocator
    return a


_STATEOBS_ONE = np.ones(1, np.int64)


def _stateobs_feed_slots(qr, alloc, slots, span) -> None:
    """Fold one batch's resolved key slots (per-event slot ids, -1 =
    invalid) into the app's key-hotness tracker — host numpy only; a
    disabled observatory costs one memoized dict read.  `span` is the
    `obs_feed` span the call runs under: it gets `keys`, how many were
    fed."""
    if not _stateobs.obs_enabled(qr.app):
        return
    live = slots[slots >= 0]
    if live.size == 0:
        return
    if live.size == 1:
        # single-row sends dominate interactive/test traffic; skip the
        # np.unique pass (KeyHotness.update has the matching fast path)
        keys, counts = live, _STATEOBS_ONE
    else:
        keys, counts = np.unique(live, return_counts=True)
    span.set_metadata(keys=keys.size)
    qr.app.stats.stateobs.feed_keys(qr.name, alloc.capacity, keys, counts)


def _stateobs_feed_group(qr, alloc, groups, pad, span) -> None:
    """Fold one grouped batch's key set into the hotness tracker: the
    per-key row counts fall out of the already-computed [Kb, E] group
    selections (`(sel >= 0).sum(axis=1)`) — no extra np.unique pass.
    `groups` is the batch's (key_idx, sel) rectangles — one, or the
    tiers of a skewed send, whose keys are disjoint: one feed either
    way.  `span` (the `obs_feed` span around the call) gets `keys`."""
    if not _stateobs.obs_enabled(qr.app):
        return
    keys, counts = [], []
    for key_idx, sel in groups:
        key_idx = np.asarray(key_idx)
        live = key_idx < pad
        keys.append(key_idx[live])
        counts.append((np.asarray(sel) >= 0).sum(axis=1)[live])
    keys = keys[0] if len(keys) == 1 else np.concatenate(keys)
    if not keys.size:
        return
    counts = counts[0] if len(counts) == 1 else np.concatenate(counts)
    span.set_metadata(keys=keys.size)
    qr.app.stats.stateobs.feed_keys(qr.name, alloc.capacity, keys, counts)


def _count_state_rows(qr, key_rows, pad: int) -> None:
    """The row-mover's counters of one send (statistics BASIC and
    above): `<q>.state_row_keys`, the live keys whose state rows the
    gather-path step moved, and `<q>.state_row_blocks`, the distinct
    128-key blocks they lie in — per chip's rows on a mesh.  Keys over
    blocks is the block mover's hit share: 1 for scattered keys, 128 for
    a contiguous run (core/state_rows.py).  `key_rows`: one `key_idx` a
    dispatch (a shard's local rows on a mesh), ascending, pads >= `pad`."""
    st = qr.app.stats
    if not st.enabled:
        return
    keys = blocks = 0
    for rows in key_rows:
        rows = rows[rows < pad]
        if rows.size:
            keys += rows.size
            blocks += 1 + int(np.count_nonzero(
                np.diff(rows // state_rows.LANES)))
    if keys:
        st.counter_inc(f"{qr.name}.state_row_keys", keys)
        st.counter_inc(f"{qr.name}.state_row_blocks", blocks)


def _wrap_stream_callback(cb) -> Callable[[List[ev.Event]], None]:
    if isinstance(cb, StreamCallback):
        return cb.receive
    return cb


def _wrap_query_callback(cb) -> Callable:
    if isinstance(cb, QueryCallback):
        return cb.receive
    return cb


class InputHandler:
    """reference: CORE/stream/input/InputHandler.java:50

    This is the app's EXTERNAL ingest edge, so admission control
    (core/admission.py) decides every send here: under an
    `admission.max.events.per.sec` quota a send may block (caller
    backpressure to a deadline), be shed (dropped, counted in
    `siddhi_admission_shed_total`), or raise AdmissionDeniedError.
    Internal re-routing (query outputs, fault streams, error-store
    replay via `_admit=False`) is never throttled — shedding an event
    the engine already accepted would be a silent loss."""

    def __init__(self, stream_id: str, runtime: "SiddhiAppRuntime"):
        self.stream_id = stream_id
        self._runtime = runtime
        self._admit = True

    def _admitted(self, n: int) -> bool:
        if not self._admit:
            return True
        adm = self._runtime.admission
        if adm is None or not adm.ingest_enabled:
            return True
        return adm.admit_ingest(self.stream_id, n)

    def send(self, data, timestamp: Optional[int] = None) -> None:
        """Accepts one event's data list/tuple, an Event, or a list of those."""
        with self._send_span(None) as span:
            self._runtime._gate_wait()     # entry valve, see _gate_wait
            events = self._to_events(data, timestamp)
            span.set_metadata(events=len(events))
            if not self._admitted(len(events)):
                return                     # shed at the edge (counted)
            self._runtime._route(self.stream_id, events)

    def _send_span(self, n: Optional[int]):
        """`siddhi:send` over the whole call, under the next number of the
        junction's send sequence: every span this send causes, on
        whatever thread, carries it as `batch` (observability/phases.py)."""
        rt = self._runtime
        j = rt.junctions.get(self.stream_id)
        return _phases.send(rt.stats, self.stream_id,
                            next(j._batch_seq) if j is not None else 0, n)

    def _to_events(self, data, timestamp) -> List[ev.Event]:
        now = timestamp if timestamp is not None \
            else self._runtime.timestamp_millis()
        if isinstance(data, ev.Event):
            return [data]
        if isinstance(data, (list, tuple)) and data and isinstance(
                data[0], (list, tuple, ev.Event)):
            return [d if isinstance(d, ev.Event) else ev.Event(now, d)
                    for d in data]
        return [ev.Event(now, list(data))]

    def send_columns(self, cols: Sequence, timestamps=None) -> None:
        """Columnar high-throughput ingestion: `cols` is a sequence of numpy
        arrays (one per attribute, equal length; strings pre-encoded as
        interner ids).  Bypasses per-event Python staging.

        OWNERSHIP: arrays whose length exactly fills the staging bucket
        (a power of two >= 8) are ADOPTED, not copied — the caller must
        not mutate them after send (re-sending the same unchanged buffer
        is fine, and fast: repeated identical buffers dedupe on the
        device link).  This matches the reference's InputHandler.send
        (Object[] ownership transfers, InputHandler.java:70); pass a copy
        if you need to keep writing into the array."""
        n = len(cols[0]) if cols else 0
        with self._send_span(n):
            self._runtime._gate_wait()     # entry valve, see _gate_wait
            if not self._admitted(n):
                return                     # shed at the edge (counted)
            self._runtime._route_columns(self.stream_id, cols, timestamps)


class _QueryRuntimeBase:
    """What "a query runtime" is to every function that is handed one —
    the emission chain (`_emit_output` down), the junction's dispatch,
    @fuse / @serve / merge wiring, the purger, snapshots, EXPLAIN, the
    observatory.  `__init__` declares, with the value that means "not
    wired", every field that code outside the class reads or writes: a
    renamed field fails at its read, it does not come back as a default.
    The plain, pattern and join runtimes supply `process_staged`,
    `on_timer` and their state; a merge group (optimizer/mqo.py) and a
    named window run no planned query of their own (`planned` is None —
    nothing that reads a plan is handed one) and take the base for the
    lock, the wake and the deferred-delivery / @fuse fields."""

    # which rule set of fusion / EXPLAIN applies: 'plain' | 'pattern' |
    # 'join' | 'merged'; a fact of the class, not of the wiring
    _kind: Optional[str] = None
    # does the step read `staged.to_device`?  Then the @serve accept-edge
    # stager may upload the batch for it (StreamJunction._serve_stage)
    adopts_staged = True

    def __init__(self, planned, app: "SiddhiAppRuntime"):
        self.planned = planned
        self.app = app
        self.callbacks: List[Callable] = []
        self.batch_callbacks: List[Callable] = []
        self.next_wakeup: int = _NO_WAKEUP_INT
        # A runtime holds ONE pending wake-up (`_Scheduler.arm`): the
        # earliest time its state next changes by the clock alone.  Where
        # a step's wake speaks for the WHOLE state (a plain query's one
        # window, a named window) it REPLACES the one pending — what that
        # one stood for has expired in the step, or is the new one; where
        # it speaks for a part (the keys a partitioned step touched, one
        # side of a join) the earlier of the pending and the new stands.
        self._wake_replaces = False
        # may the timer steps due up to the clock be taken as ONE step at
        # the clock?  (the window's `timer_coalesces`)
        self._timers_coalesce = False
        # per-query processing lock: parallel ingestion serializes PER
        # QUERY, not per app (reference: per-query ReentrantLock chosen in
        # QueryParser.java:159-215 instead of one engine-wide lock)
        self._qlock = threading.RLock()
        # -- set at wiring (SiddhiAppRuntime._register / _wire_output) --
        # the Query this runtime was planned from (EXPLAIN, lint)
        self._query_ast = None
        # delivery mode (_emit_output): @async -> the app's drainer
        # thread; @pipeline(depth) -> held on the producer's thread;
        # @serve -> a device ring of `serve_ring_capacity` slots (0 = the
        # config's)
        self.async_emit = False
        self.pipeline_emit = 0
        self.serve_emit = False
        self.serve_ring_capacity = 0
        # @fuse(batches=K): the stack buffer (core/fusion.py) or None,
        # the K asked for, and why wiring skipped it
        self._fuse = None
        self._fuse_requested = 0
        self._fuse_excluded = None
        # fn(new cap) -> the plan re-planned with a larger emission cap
        # (adaptive overflow growth), or None
        self._replan = None
        # (op, table, cond, set_fns, key) of an `insert into / update /
        # delete <table>` output; the `output ... every` limiter
        self.table_op = None
        self.rate_limiter = None
        # optimizer.apply_merge: the group this query dispatches through,
        # or why it stayed alone
        self._merged = None
        self._merge_excluded = None
        # set by _PartitionPurger: fn(slots, now) recording key liveness
        self._touch = None
        self._touch_group = None
        # a partitioned pattern's shared key allocator and its per-key
        # dirty mask since the last (incremental) snapshot; a bucket
        # join's key retention mirror (core/join.py JoinKeyTracker)
        self.slot_allocator = None
        self._dirty = None
        self._jk = None
        # a pattern's NFA facts as its last drain read them
        # (PatternQueryRuntime.note_nfa_facts): what the scrape surfaces,
        # which never fetch, say of the slab
        self._nfa_facts = None
        # -- written per send / per delivery --
        # perf_counter_ns at send acceptance, stamped by the dispatcher
        # under the query lock; whether an inline delivery left the
        # `<query>:e2e` sample for the dispatcher to close
        self._ingest_ns = None
        self._e2e_owed = False
        # @pipeline's held emissions (a deque), @serve's EmissionRing
        self._pending_emit = None
        self._serve_ring = None
        # a fused dispatch's per-batch ingest stamps; (kind, id(body)) ->
        # (body, fused fn)
        self._fused_ingests = None
        self._fused_cache: Dict = {}
        # memos: wire bytes of one output row (_row_nbytes); the resolved
        # ShardRouter in a 1-tuple, so a resolved None is not re-resolved
        # (replans never change mesh / capacity)
        self._out_row_nbytes = None
        self._shard_router_memo = None
        # the observatory's sampled window-fill probe
        # (observability/stateobs.py arm_fill_probe)
        self._stateobs_tick = 0
        self._stateobs_probe = None
        self._stateobs_probe_caps = None
        self._stateobs_probe_off = False

    @property
    def name(self):
        return self.planned.name

    # the ONE way host code asks "is this query sharded, and how"
    # (sharding/router.py owns the layout)
    @property
    def mesh(self):
        return self.planned.mesh

    @property
    def keyed_mesh(self):
        return self.planned.keyed_mesh

    @property
    def shard_router(self):
        r = self._shard_router_memo
        if r is None:
            r = self._shard_router_memo = (_sharding.router_for(self),)
        return r[0]

    def defers_delivery(self) -> bool:
        """Does `_emit_output` hand this runtime's emissions on unfetched
        (@serve ring, @async drainer, @pipeline deque)?  A stacked
        dispatch (fusion, merge) then gives it device slices."""
        return bool(self.serve_emit or self.pipeline_emit or
                    (self.async_emit and self.app._drainer is not None))

    @staticmethod
    def _timer_batch(schema: ev.Schema, now: int) -> ev.StagedBatch:
        """The batch a timer tick sends through a step: one TIMER row."""
        staged = ev.pack_np(schema, [], capacity=8)
        staged.ts[0] = now
        staged.kind[0] = ev.TIMER
        staged.valid[0] = True
        return staged

    def _apply_wake(self, w: int) -> None:
        self.next_wakeup = w
        self.app._scheduler.arm(w, self, self._wake_replaces)

    def _emit(self, out, now: int, wake=None) -> None:
        _emit_output(self, out, now, wake)

    # restore hooks (SiddhiAppRuntime.restore / restore_increment): where
    # a restored state goes, and what host mirror is rebuilt from it
    def place_state(self, state):
        return state

    def _after_restore(self, host_state) -> None:
        pass


class _Subscription:
    """A runtime as a junction (or named window) subscribes it: the
    target and the leading arguments of its `process_staged` — a
    pattern's stream id, a join's side, none for an aggregation.
    `locks=False`: the target locks internally and is no query runtime
    (an aggregation) — the dispatcher takes no query lock for it, stamps
    nothing on it and names it after the stream."""

    __slots__ = ("_qr", "_lead", "locks")

    def __init__(self, qr, *lead, locks: bool = True):
        self._qr, self._lead, self.locks = qr, lead, locks

    def process_staged(self, staged: ev.StagedBatch, now: int) -> None:
        self._qr.process_staged(*self._lead, staged, now)


def _sub_runtime(sub):
    """The query runtime behind a junction subscriber — itself, or the
    one its adapter binds — or None (an aggregation's adapter)."""
    if isinstance(sub, _Subscription):
        return sub._qr if sub.locks else None
    return sub


def _sub_name(sub, default: str) -> str:
    """Metric name of a junction subscriber: its query runtime's."""
    qr = _sub_runtime(sub)
    return default if qr is None else qr.name


def _sub_lock(sub):
    """Per-query processing lock of a junction subscriber (aggregations
    lock internally -> None)."""
    qr = _sub_runtime(sub)
    return None if qr is None else qr._qlock


class QueryRuntime(_QueryRuntimeBase):
    """Host wrapper around one planned query: staging, group slots, routing."""

    _kind = "plain"

    def __init__(self, planned: PlannedQuery, app: "SiddhiAppRuntime"):
        super().__init__(planned, app)
        self._wake_replaces = not planned.keyed_window
        self._timers_coalesce = planned.window.timer_coalesces
        # force-copy every leaf: constant-folding can alias identical init
        # arrays into one buffer, which breaks donated-argument execution
        self._state = jax.tree.map(
            lambda x: jax.numpy.array(x, copy=True), planned.init_state())

    @property
    def state(self):
        """This query's state pytree.  Unmerged: the runtime's own
        tuple.  Merged (optimizer/mqo.py): a view into the merge
        group's stacked state — snapshots, restores, EXPLAIN, and
        memory accounting keep addressing the member by name and see
        exactly the (window, selector) tuple an unmerged plan holds."""
        mg = self._merged
        return self._state if mg is None else mg.member_state(self)

    @state.setter
    def state(self, v):
        mg = self._merged
        if mg is None:
            self._state = v
        else:
            mg.set_member_state(self, v)

    def _slots_for_batch(self, staged: ev.StagedBatch,
                         now: int) -> Tuple[np.ndarray, Tuple]:
        """Group/distinctCount slot resolution for a non-range-partition
        batch (host side effects: slot binding + purger liveness touch) —
        shared by the sequential path and fused dispatch (core/fusion.py)."""
        p = self.planned
        valid = staged.valid
        st = self.app.stats
        grouped = bool(p.group_by_positions) and p.slot_allocator is not None
        if not grouped and not p.pair_allocs:
            gslot, pslots = _zero_slots(staged.ts.shape[0]), ()
        else:
            with _phases.phase(st, self.name, "route_keys") as sp:
                gslot = p.slot_allocator.slots_for(
                    [staged.cols[i] for i in p.group_by_positions],
                    valid) if grouped else _zero_slots(staged.ts.shape[0])
                # distinctCount: (group, value) -> pair refcount slots
                pslots = tuple(
                    alloc.slots_for([gslot, staged.cols[pos]], valid)
                    for alloc, pos in p.pair_allocs)
                if grouped:
                    sp.set_metadata(bound=len(p.slot_allocator))
        if grouped or self._touch is not None:
            with _phases.phase(st, self.name, "obs_feed") as sp:
                if grouped:
                    _stateobs_feed_slots(self, p.slot_allocator, gslot, sp)
                if self._touch is not None:
                    self._touch(gslot, now)
        return gslot, pslots

    def process_staged(self, staged: ev.StagedBatch, now: int) -> None:
        p = self.planned
        dbg = self.app._debugger
        if dbg is not None:
            dbg.check_break_point(self.name, "IN", staged)
        if p.keyed_window:
            self._process_keyed(staged, now)
            return
        fb = self._fuse
        if fb is not None and fb.offer((staged, now), staged, None):
            return
        st = self.app.stats
        if p.partition_key_fn is not None:
            # range partition: derived key column; rows matching no range
            # are excluded from the query entirely
            with _phases.phase(st, self.name, "route_keys"):
                kcols, kvalid = p.partition_key_fn(staged)
                valid = staged.valid & kvalid
                if p.slot_allocator is not None:
                    key_cols = list(kcols) + [staged.cols[i]
                                              for i in p.group_by_positions]
                    gslot = p.slot_allocator.slots_for(key_cols, valid)
                else:
                    gslot = _zero_slots(staged.ts.shape[0])
                staged = ev.StagedBatch(staged.ts, staged.kind, valid,
                                        staged.cols, staged.n)
                pslots = tuple(
                    alloc.slots_for([gslot, staged.cols[pos]], valid)
                    for alloc, pos in p.pair_allocs)
            if self._touch is not None:
                with _phases.phase(st, self.name, "obs_feed"):
                    self._touch(gslot, now)
        else:
            gslot, pslots = self._slots_for_batch(staged, now)
        with _phases.phase(st, self.name, "h2d",
                           bytes=_staged_nbytes(staged) +
                           _phases.nbytes(gslot, *pslots)):
            pslots = tuple(jax.numpy.asarray(s) for s in pslots)
            batch = staged.to_device(p.in_schema)
            gslot_d = jax.numpy.asarray(gslot)
            now_d = jax.numpy.asarray(now, jax.numpy.int64)
        in_tabs = self.app.in_probe_tables(p.in_deps)
        self.state, out, wake = _phases.dispatch(
            self, p.step, self.state, batch.ts, batch.kind, batch.valid,
            batch.cols, gslot_d, now_d, in_tabs, pslots)
        # sampled window-fill probe: dispatch-only; its scalar rides the
        # delivery fetch in _deliver_output (observability/stateobs.py)
        _stateobs.arm_fill_probe(self)
        # the device-computed wake scalar rides the emission fetch (a sync
        # int(wake) here would block the send path on the step per batch)
        wake_arg = None
        if p.needs_timer:
            if p.window.host_scheduled:
                self._apply_wake(p.window.host_next_wakeup(now))
            else:
                wake_arg = wake
        self._emit(out, now, wake_arg)

    def _process_keyed(self, staged: ev.StagedBatch, now: int,
                       all_keys: bool = False) -> None:
        """Keyed-window path: events group per partition key into [Kb, E]
        and the window state slab advances under vmap (planner.kstep)."""
        p = self.planned
        valid = staged.valid
        st = self.app.stats
        if all_keys:
            # timer tick: advance EVERY key's window; each key sees the
            # TIMER row (staged row 0) so flush-on-timer windows
            # (cron/timeBatch) fire per key, and `now` drives time expiry.
            # The partition key fn is NOT applied: a TIMER row's zeroed
            # columns would fail every range condition and kill the row.
            # Timer ticks carry no data rows: no group slots to resolve.
            key_idx = np.arange(p.key_capacity, dtype=np.int32)
            sel = np.zeros((p.key_capacity, 1), np.int32)
            gslot = _zero_slots(staged.ts.shape[0])
        else:
            gslot = None
            with _phases.phase(st, self.name, "route_keys"):
                if p.partition_key_fn is not None:
                    kcols, kvalid = p.partition_key_fn(staged)
                    valid = valid & kvalid
                    kcols = list(kcols)
                else:
                    kcols = [staged.cols[i] for i in p.window_key_positions]
                _, key_idx, sel = p.window_key_allocator.slots_and_group(
                    kcols, valid, pad=p.key_capacity)
                if p.slot_allocator is not None:
                    gk = [staged.cols[i] for i in p.group_by_positions]
                    if p.partition_key_fn is not None:
                        gk = kcols + gk
                    gslot = p.slot_allocator.slots_for(gk, valid)
            with _phases.phase(st, self.name, "obs_feed") as sp:
                _stateobs_feed_group(self, p.window_key_allocator,
                                     [(key_idx, sel)], p.key_capacity, sp)
                if self._touch is not None:
                    self._touch(key_idx, now)
                if gslot is not None and self._touch_group is not None:
                    self._touch_group(gslot, now)
            if gslot is None:
                gslot = _zero_slots(staged.ts.shape[0])
        staged = ev.StagedBatch(staged.ts, staged.kind, valid, staged.cols,
                                staged.n)
        with _phases.phase(st, self.name, "h2d",
                           bytes=_staged_nbytes(staged) +
                           _phases.nbytes(gslot, key_idx, sel)):
            batch = staged.to_device(p.in_schema)
            gslot_d = jax.numpy.asarray(gslot)
            key_d = jax.numpy.asarray(key_idx)
            sel_d = jax.numpy.asarray(sel)
            now_d = jax.numpy.asarray(now, jax.numpy.int64)
        in_tabs = self.app.in_probe_tables(p.in_deps)
        self.state, out, wake = _phases.dispatch(
            self, p.step, self.state, batch.ts, batch.kind, batch.valid,
            batch.cols, gslot_d, key_d, sel_d, now_d, in_tabs)
        wake_arg = None
        if p.needs_timer:
            if p.window.host_scheduled:
                # cron-style windows schedule on the host clock
                self._apply_wake(p.window.host_next_wakeup(now))
            else:
                wake_arg = wake
        self._emit(out, now, wake_arg)

    def on_timer(self, now: int) -> None:
        p = self.planned
        staged = self._timer_batch(p.in_schema, now)
        if p.keyed_window:
            self._process_keyed(staged, now, all_keys=True)
            return
        self.process_staged(staged, now)


class PatternQueryRuntime(_QueryRuntimeBase):
    """Host wrapper for a pattern/sequence query: groups events per key into
    the [K, E] device layout and drives the per-stream NFA steps."""

    _kind = "pattern"
    # no pattern step reads `staged.to_device`: each uploads its own
    # columns (grouped by the host, a stack, a shard's share), so the
    # @serve accept-edge stager has nothing to hand it (_serve_stage)
    adopts_staged = False

    def __init__(self, planned, app: "SiddhiAppRuntime",
                 slot_allocator=None):
        super().__init__(planned, app)
        # the plan's one jitted init writes the state where it lives
        # (each chip its own [W, K/n] share under a mesh) and returns
        # buffers no other runtime of this plan holds, so the steps may
        # donate them: nothing is copied afterwards
        with _phases.phase(app.stats, planned.name, "state_init") as sp:
            # waited for: deploy is not the hot path, and a state that
            # does not fit fails here, not at the first send
            self.state = jax.block_until_ready(
                planned.init_state(planned.key_capacity))
            sp.set_metadata(bytes=_phases.tree_nbytes(self.state),
                            shards=_sharding.shard_count(planned))
        self.slot_allocator = slot_allocator  # shared per partition
        if planned.partition_positions:
            self._dirty = np.zeros(planned.key_capacity, np.bool_)
        # steady-state block memo for _grouped_slots: (k0, n) ->
        # (allocator version, key_idx, sel, keys copy, sel is the identity)
        self._block_cache: Dict = {}

    _EMIT_CAP_MAX = 512

    def _grow_emission_cap(self, n_dropped: int, n_valid: int = 0) -> bool:
        """Adaptive degradation for implicit-cap overflow (reference emits
        unbounded): size the per-key emission cap to the OBSERVED demand
        (delivered + dropped, next power of two) in one jump — each regrow
        is a full step rebuild/recompile, so doubling blindly would pay
        that minutes-long cost repeatedly on a large fan-out.  State shapes
        are cap-independent, so the live NFA slab carries over.  The
        overflowing batch already lost `n_dropped` rows (logged);
        subsequent batches get headroom.  Returns False once the growth
        budget is exhausted, surfacing the normal overflow error."""
        if self._replan is None:
            return False
        cap = self.planned.compact_rows
        need = max(n_valid + n_dropped, cap * 2)
        new_cap = min(1 << (need - 1).bit_length(), self._EMIT_CAP_MAX)
        if new_cap <= cap:
            return False
        # admission: a regrow allocates a bigger emission block AND pays
        # a recompile — past the state ceiling the growth is denied and
        # the app sheds overflow at the current cap instead of OOMing
        adm = self.app.admission
        if adm is not None and not adm.admit_growth(
                self.name, (new_cap - cap) * _row_nbytes(self)):
            return False
        import logging
        logging.getLogger("siddhi_tpu").warning(
            "%s: %d pattern match rows dropped at emission capacity %d; "
            "growing the cap to %d (set @emit(rows='N') to pre-size and "
            "silence this)", self.name, n_dropped, cap, new_cap)
        # operator-visible counter: each growth is a step recompile
        # (seconds of XLA compile on the send path) — invisible cap
        # churn was the old failure mode
        stats = self.app.stats
        if stats.enabled:
            stats.counter_inc(f"{self.name}.cap_growths")
        self.planned = self._replan(new_cap)
        return True

    def _in_tabs(self):
        """Table snapshots for `x in Table` probes inside NFA filters
        (reference: InConditionExpressionExecutor in pattern conditions)."""
        return self.app.in_probe_tables(self.planned.exec.in_deps)

    def _grouped_slots(self, key_cols, valid, p):
        """Slot resolution + [Kb, E] grouping with a steady-state block
        memo.  Keyed workloads re-send the same key blocks sweep after
        sweep (the bench's 1M-key stream cycles 8 contiguous blocks); when
        the allocator's bindings are unchanged since the block was last
        resolved (`version`) and the keys compare equal, the C pass and
        group fill are pure functions of the block and replay from cache
        (~30ms -> ~0.2ms per 131k-key send: 16% of flagship wall time).
        Returns (tiers, hottest key's count, memo hit): `tiers` is the
        batch's layout as [(key_idx, sel, identity)] — one rectangle, or
        the few a skewed batch is split into by per-key count
        (keyslots._tier_plan), their keys disjoint.  `identity` says
        that `sel` lists the batch's rows 0 .. B-1 in order, so the
        grouped columns ARE the staged ones (_group_columns).  It is
        decided here, where `sel` is made, and kept with the memo entry:
        a memo hit pays no O(B) pass for it."""
        alloc = self.slot_allocator
        keys = key_cols[0] if len(key_cols) == 1 else None
        cacheable = (keys is not None and keys.dtype.kind in "iu" and
                     keys.shape[0] >= 1024 and bool(valid.all()))
        if cacheable:
            blk = (int(keys[0]), keys.shape[0])
            ent = self._block_cache.get(blk)
            if ent is not None and ent[0] == alloc.version and \
                    np.array_equal(keys, ent[3]):
                return ent[1], ent[2], True
        _, groups, max_e = alloc.slots_and_tiers(key_cols, valid,
                                                 pad=p.key_capacity)
        tiers = [(key_idx, sel, len(groups) == 1 and
                  _is_identity_sel(sel, valid.shape[0]))
                 for key_idx, sel in groups]
        if cacheable:
            if len(self._block_cache) >= 64:
                self._block_cache.clear()
            self._block_cache[blk] = (alloc.version, tiers, max_e,
                                      keys.copy())
        return tiers, max_e, False

    def process_staged(self, stream_id: str, staged: ev.StagedBatch,
                       now: int) -> None:
        p = self.planned
        B = staged.ts.shape[0]
        # @fuse stacks BEFORE the mesh branch: sharded pattern dispatches
        # fuse too (fusion._dispatch_pattern routes stacks through the
        # shard_map'd scan step built in pattern_planner._shard_fused_step)
        fb = self._fuse
        if fb is not None and fb.offer((stream_id, staged, now), staged,
                                       stream_id):
            return
        if self.shard_router is not None:
            self._process_sharded(stream_id, staged, now)
            return
        st = self.app.stats
        cap = p.key_capacity
        # host prep, each part under its own span — key -> slot routing,
        # the ts-wire build and, for the programs that take them so
        # (p.grouped_input), the columns put in the per-key order
        # (route_keys) — then the columns and what else prep produced go
        # up (h2d, twice), then the step (dispatch): no span's clock holds
        # another's work.  What the step does not read is done after it
        # is submitted: the observatory and liveness feeds (obs_feed) run
        # once the send's last step is dispatched, while the device
        # executes it, and before the emission is handed on — the thread
        # would otherwise stand in the header fetch for the whole step.
        # A send whose keys' counts are far apart is laid out as a few
        # [Kb, E] tiers (_grouped_slots), each its own upload and dispatch
        # of the same step, the tier of the hottest keys first: its scan
        # is the send's longest piece of device work, and it runs under
        # the host's prep of the others.  The tiers' emissions leave as
        # ONE emission (BandedEmission.joined: nothing is dispatched for
        # it): one header fetch, one payload of the bands the tiers used,
        # and the timestamp order of delivery holds over all of the
        # send's keys
        with _phases.phase(st, self.name, "route_keys") as sp:
            ts_base, ts_delta = ev.encode_ts(staged.ts, staged.n)
            if p.partition_positions:
                key_cols, valid = self._partition_keys(stream_id, staged)
                tiers, max_e, hit = self._grouped_slots(key_cols, valid, p)
                nuniq = [int((k < cap).sum()) for k, _, _ in tiers]
                sp.set_metadata(
                    keys=sum(nuniq), memo_hit=int(hit), tiers=len(tiers),
                    cells=sum(sel.size for _, sel, _ in tiers),
                    ticks=sum(sel.shape[1] for _, sel, _ in tiers),
                    max_e=max_e)
            elif staged.valid.all():
                # full bucket: the identity selection is a constant per
                # capacity — cached read-only so repeat sends dedupe
                tiers, nuniq = [(None, _identity_sel(B), True)], [0]
            else:
                # the valid rows first, no hole between two of them
                tiers, nuniq = [(None, valid_first_sel(staged.valid),
                                 False)], [0]
            grouped = [(staged.cols, ts_delta)] * len(tiers)
            if p.grouped_input:
                grouped = [_group_columns(sel, ident, staged.cols, ts_delta)
                           for _, sel, ident in tiers]
                sp.set_metadata(
                    grouped="view" if tiers[0][2] else "take")
            if p.send_layout is not None:
                sp.set_metadata(**p.send_layout(B))
        outs, now_d, moved = [], None, []
        try:
            try:
                for t in reversed(range(len(tiers))):
                    key_idx_np, sel_np, _ = tiers[t]
                    cols, delta = grouped[t]
                    # contiguous-slot fast path: dynamic-slice state
                    # access instead of row-serialized gather/scatter (see
                    # dense_steps).  nuniq >= 2: the Kb=1 dense
                    # specialization trips an XLA:CPU fused-dynamic-slice
                    # codegen bug (RET_CHECK llvm_module), and a 1-row
                    # gather is as fast as a 1-row slice anyway
                    n, Kb = nuniq[t], sel_np.shape[0]
                    dense = (p.dense_steps is not None and n > 1 and
                             int(key_idx_np[0]) + Kb <= cap and
                             int(key_idx_np[n - 1]) ==
                             int(key_idx_np[0]) + n - 1)
                    with _phases.phase(st, self.name, "h2d",
                                       bytes=_phases.nbytes(*cols)):
                        cols_d = tuple(jax.numpy.asarray(c)
                                       for c in cols)
                    if dense and self._dirty is not None:
                        # the dense step also time-ticks slots beyond
                        # nuniq
                        self._dirty[int(key_idx_np[0]):
                                    int(key_idx_np[0]) + Kb] = True
                    with _phases.phase(
                            st, self.name, "h2d",
                            bytes=_phases.nbytes(delta, sel_np)):
                        # the base rides the step call as the numpy
                        # scalar it is: an upload call of its own costs
                        # as much as the delta's
                        ts_d = (ts_base, jax.numpy.asarray(delta))
                        sel_d = jax.numpy.asarray(sel_np)
                        if dense:
                            key_d = jax.numpy.asarray(
                                int(key_idx_np[0]), jax.numpy.int32)
                        elif key_idx_np is not None:
                            key_d = jax.numpy.asarray(key_idx_np)
                        else:
                            key_d = jax.numpy.asarray(
                                np.zeros((1,), np.int32))
                        if now_d is None:   # one upload serves all
                            now_d = jax.numpy.asarray(now,
                                                      jax.numpy.int64)
                    steps = p.dense_steps if dense else p.steps
                    if not dense and key_idx_np is not None:
                        moved.append(key_idx_np)
                    outs.append(self._step(steps[stream_id], cols_d,
                                           *ts_d, sel_d, key_d, now_d))
            finally:
                # fed whether or not every tier was dispatched: a tier that
                # ran has advanced its keys' state, and no snapshot or
                # purge (both take _qlock, held here) may find a key
                # advanced and not marked; a scrape takes no _qlock and
                # reads no state, only the books, whole or a send behind
                if p.partition_positions:
                    self._feed_observers(tiers, nuniq, now, moved)
        except Exception:
            # a tier that was dispatched has advanced its keys' state: what
            # it matched is delivered before the error is, so no match is
            # consumed and lost (the junction reports the send as failed;
            # the tiers after the failing one were not applied)
            for out, wake in outs:
                _emit_output(self, out, now, wake=self._wake_arg(wake))
            raise
        out, wake = outs[0]
        if len(outs) > 1:
            # the tiers' emissions as the send's one; the wakes ride the
            # header fetch together and the earliest is applied
            out = BandedEmission.joined([o for o, _ in outs])
            wake = tuple(w for _, w in outs)
        _emit_output(self, out, now, wake=self._wake_arg(wake))

    def _feed_observers(self, tiers, nuniq, now: int, moved=()) -> None:
        """What watches a partitioned send's keys, under one `obs_feed`
        span: the key-hotness feed, the purger's liveness touch, the
        snapshot's dirty marks — once for all of the send's tiers, after
        the last of their dispatches (the span says so: `after`).
        `moved`: the `key_idx` of the tiers that went through the
        row-mover (the gather-path step), for its counters."""
        with _phases.phase(self.app.stats, self.name, "obs_feed",
                           after="dispatch") as sp:
            _count_state_rows(self, moved, self.planned.key_capacity)
            _stateobs_feed_group(
                self, self.slot_allocator,
                [(key_idx, sel) for key_idx, sel, _ in tiers],
                self.planned.key_capacity, sp)
            for (key_idx, _, _), n in zip(tiers, nuniq):
                if self._touch is not None:
                    self._touch(key_idx, now)
                if self._dirty is not None and n:
                    self._dirty[key_idx[:n]] = True

    def _partition_keys(self, stream_id: str, staged: ev.StagedBatch):
        """(key columns, row validity) of a partitioned batch: the
        stream's range-partition function where it has one, else its key
        columns by position."""
        p = self.planned
        kf = (p.partition_key_fns or {}).get(stream_id)
        if kf is not None:
            key_cols, kvalid = kf(staged)
            return key_cols, staged.valid & kvalid
        return ([staged.cols[i] for i in p.partition_positions[stream_id]],
                staged.valid)

    def _step(self, step, *batch_args):
        """Dispatch one sequential step on the state and rebind what it
        returns (the state was donated); its (emission, wake)."""
        pstate, sel_state = self.state
        pstate, sel_state, out, wake = _phases.dispatch(
            self, step, pstate, sel_state, *batch_args, self._in_tabs())
        self.state = (pstate, sel_state)
        return out, wake

    def _shard_prep(self, stream_id: str, staged: ev.StagedBatch):
        """Staging-time routing of one batch through the key-space router
        (host side effect: slot binding).  Returns the grouped (key_idx
        [n, Kb], sel [n, Kb, E]) device layout and what `_shard_feed`
        takes once the step is dispatched, as the one grouping counted it
        (per-shard event counts, distinct slots, events a slot) — shared by
        the sequential sharded path and fused dispatch (core/fusion.py)."""
        router = self.shard_router
        st = self.app.stats
        with _phases.phase(st, self.name, "route_keys"):
            key_cols, valid = self._partition_keys(stream_id, staged)
            slots = self.slot_allocator.slots_for(key_cols, valid)
            # the [n, Kb, E] regroup is host staging work too, under its
            # own span: route_keys' self time is slot resolution alone
            with _phases.phase(st, self.name, "shard_group",
                               shards=router.n_shards) as sp:
                key_idx, sel, counts, keys, per_key = router.group(
                    slots, staged.valid)
                sp.set_metadata(rows=key_idx.size, keys=keys.size, passes=1)
        return key_idx, sel, (counts, keys, per_key)

    def _shard_feed(self, counts, keys, per_key, now, key_idx=None) -> None:
        """`_feed_observers` of the sharded path, from what `_shard_prep`
        counted: key hotness, liveness touch, dirty marks (`keys`: the
        send's distinct slots ascending, `per_key` their events), per-shard
        counters, the row-mover's (`key_idx`: the [n, Kb] local rows moved).
        Nothing goes to the device: it runs after the dispatch, under it."""
        st = self.app.stats
        with _phases.phase(st, self.name, "obs_feed",
                           after="dispatch") as sp:
            if key_idx is not None:
                _count_state_rows(self, key_idx, self.shard_router.block)
            if keys.size and _stateobs.obs_enabled(self.app):
                sp.set_metadata(keys=keys.size)
                st.stateobs.feed_keys(
                    self.name, self.slot_allocator.capacity, keys, per_key)
            if self._touch is not None:
                self._touch(keys, now)
            if self._dirty is not None:    # slot s: global state row of s
                self._dirty[self.shard_router.state_row(keys)] = True
            if st.enabled:
                st.shard_events(self.name, counts)

    def _process_sharded(self, stream_id: str, staged: ev.StagedBatch,
                         now: int) -> None:
        """Multi-chip path: route each key to its shard (slot % n), build the
        stacked [n*Kb, E] layout, run the shard_map step, feed the
        observers while the chips run it."""
        st = self.app.stats
        # the ts-wire build is host prep, booked where process_staged
        # books it; _shard_prep's own route_keys span is slot resolution
        # (fused dispatch shares it and ships a stacked i64 ts)
        with _phases.phase(st, self.name, "route_keys"):
            ts_base, ts_delta = ev.encode_ts(staged.ts, staged.n)
        key_idx, sel, fed = self._shard_prep(stream_id, staged)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])   # noqa: E731
        with _phases.phase(st, self.name, "h2d",
                           bytes=_phases.nbytes(ts_delta, sel, key_idx,
                                                *staged.cols),
                           shards=self.shard_router.n_shards):
            raw_cols = tuple(jax.numpy.asarray(c) for c in staged.cols)
            ts_d = (ts_base, jax.numpy.asarray(ts_delta))
            sel_d = jax.numpy.asarray(flat(sel))
            key_d = jax.numpy.asarray(flat(key_idx))
            now_d = jax.numpy.asarray(now, jax.numpy.int64)
        try:
            out, wake = self._step(self.planned.steps[stream_id], raw_cols,
                                   *ts_d, sel_d, key_d, now_d)
        finally:
            # as in process_staged: fed whether or not the step came back
            self._shard_feed(*fed, now, key_idx)
        _emit_output(self, out, now, wake=self._wake_arg(wake))

    def on_timer(self, now: int) -> None:
        p = self.planned
        if p.timer_step is None:
            return
        pstate, sel_state = self.state
        pstate, sel_state, out, wake, changed = _phases.dispatch(
            self, p.timer_step, pstate, sel_state,
            jax.numpy.asarray(now, jax.numpy.int64), self._in_tabs())
        self.state = (pstate, sel_state)
        if self._dirty is not None:
            # timer-driven expiry/absent firing mutates key NFA state;
            # without marking, incremental snapshots miss those changes and
            # a restore resurrects expired pending states.  The device
            # reports exactly which keys changed.
            self._dirty |= np.asarray(_phases.fetch(
                self.app.stats, self.name, "rows", changed))
        _emit_output(self, out, now, wake=self._wake_arg(wake))

    def _wake_arg(self, wake):
        """Only patterns with absent atoms need timer wakeups; everything
        else skips the wake fetch entirely."""
        return wake if self.planned.timer_step is not None else None

    def note_nfa_facts(self) -> Dict[str, int]:
        """After a drain (`SiddhiAppRuntime.flush`): read the slab's own
        counters ONCE, off the state as it lies — no step hands them
        over.  `forks_dropped`: `PatternState.dropped`, candidates
        (forks and seeds) that found no free slot of the key's
        `@capacity(slots='N')` — matches lost for good, said in a warning
        by the first drain that finds more of them; `forks`: continuations
        forked off a slot, where the pattern has a count atom
        (`PatternState.forked`); with statistics on, `live_threads`: the
        slots in use over all keys (one reduce over the `active` rows,
        the packed state's first P).  Kept on the runtime for the scrape
        surfaces, which never fetch (`/metrics`' `siddhi_nfa_*`,
        `state_report()["nfa"]`)."""
        with self._qlock:
            b32, _lo, _hi, scalars = self.state[0]
            read = tuple(scalars)
            if self.app.stats.enabled:
                read += (_live_threads(b32, self.planned.slots),)
            read = [int(v) for v in jax.device_get(read)]
        facts = {"forks_dropped": read[0]}
        if self.planned.exec.forks:
            facts["forks"] = read[1]
        if self.app.stats.enabled:
            facts["live_threads"] = read[-1]
        lost = facts["forks_dropped"] - \
            (self._nfa_facts or {}).get("forks_dropped", 0)
        if lost > 0:
            logging.getLogger("siddhi_tpu").warning(
                "%s: %d pattern fork(s) or seed(s) found no free slot and "
                "were dropped — their matches are lost; raise "
                "@capacity(slots='%d') on the query", self.name, lost,
                self.planned.slots)
        self._nfa_facts = facts
        return facts


@functools.partial(jax.jit, static_argnums=1)
def _live_threads(b32, slots: int):
    """Slots in use over all keys: `active` is the packed state's first
    leaf, rows [0, P) of the i32 blob."""
    return jax.numpy.sum(b32[:slots] != 0, dtype=jax.numpy.int64)


def _target_live(qr) -> bool:
    """Does anything need this output as host rows — a table op, a rate
    limiter, or a READER of its output target (a named window, a table,
    a junction with a subscribing query or stream callback)?  Statistics
    are not a reader: the output stream's throughput is counted from the
    emission header (_emit_output_sync_impl), and nothing is fetched,
    sorted or unpacked for a junction nobody reads."""
    if qr.table_op is not None or qr.rate_limiter is not None:
        return True
    tgt = qr.planned.output_target
    if not tgt:
        return False
    app = qr.app
    if tgt in app.named_windows or tgt in app.tables:
        return True
    j = app.junctions.get(tgt)
    return j is not None and bool(j.queries or j.stream_callbacks)


def _has_consumers(qr) -> bool:
    """Anything downstream that would read this output?  Checked BEFORE any
    device->host transfer so unconsumed outputs cost zero D2H traffic."""
    return bool(qr.callbacks or qr.batch_callbacks) or _target_live(qr)


def _ts_range(events) -> Tuple[Optional[int], Optional[int]]:
    """(earliest, latest) timestamp of an event list; (None, None) of an
    empty one."""
    if not events:
        return None, None
    stamps = [e.timestamp for e in events]
    return min(stamps), max(stamps)


def _earliest(wake) -> int:
    """A wake as `_apply_wake` takes it: the scalar, or the earliest of
    a tiered send's (one a tier)."""
    return int(np.min(wake))


def _headed(out) -> bool:
    """Does this emission lead with a count header — a banded pattern
    emission, or the flat 6-tuple of the other compacting emitters (joins,
    @fuse stacks, the block and timer steps)?  Its rows then stay on the
    device until somebody reads them; a plain 4-tuple ships whole."""
    return isinstance(out, BandedEmission) or len(out) == 6


def _header_of(out):
    """What a delivery path fetches first of an emission: the header of a
    headed one (a banded one's carries `ranks_used` a tier), all of a
    plain one."""
    if isinstance(out, BandedEmission):
        return out.headers
    return (out[0], out[1]) if len(out) == 6 else out


def _emit_fetched(qr, out, fetched, now: int, ingest_ns=None) -> None:
    """Deliver an emission of which `_header_of(out)` has been fetched."""
    if _headed(out):
        _emit_output_sync(qr, out, now, header=fetched, ingest_ns=ingest_ns)
    else:
        _emit_output_sync(qr, fetched, now, ingest_ns=ingest_ns)


def _emit_output(qr, out, now: int, wake=None) -> None:
    """Emission entry: async mode (@async) defers the device->host sync to a
    background drainer thread so the producer keeps dispatching device work
    (the reference's Disruptor-decoupled delivery, StreamJunction.java:276);
    @pipeline mode keeps a ONE-DEEP deferred emission on the producer
    thread itself — the device_get for step N happens only after step N+1
    has been dispatched, so host staging overlaps device compute without a
    second thread to contend with (the win on a 1-core driver host feeding
    an accelerator); sync mode delivers inline.  `wake` is the
    device-computed next-wakeup scalar (or None): fetched WITH the output
    in one roundtrip and applied before delivery."""
    if not _has_consumers(qr):
        if wake is not None:
            qr._apply_wake(_earliest(wake))
        return
    # ingest stamp (perf_counter_ns at send acceptance, stashed by the
    # junction under the query lock): rides every deferred-delivery queue
    # so the `<query>:e2e` histogram includes queue wait — None when
    # statistics are OFF or the batch arrived outside a junction dispatch
    ingest_ns = qr._ingest_ns
    if qr.serve_emit and wake is None and not qr.planned.needs_timer:
        # device-resident serving loop (siddhi_tpu/serving): the output
        # pytree appends into the query's on-device emission ring — a
        # single jitted dispatch, zero fetches — and the per-app drainer
        # thread delivers it through _emit_output_sync later.  Timer-
        # bearing queries keep their inline path (same exclusion as
        # @pipeline: a deferred wake scalar would stall expiry), and
        # serving takes precedence over @async/@pipeline below.
        from ..serving import ring_append
        # handoff(): carry the send's batch number and (armed) DETAIL
        # trace so the drainer's delivery spans join them
        ring_append(qr, out, now, ingest_ns, _phases.handoff())
        return
    if qr.async_emit and qr.app._drainer is not None:
        qr.app._drainer.enqueue(qr, out, now, wake, ingest_ns,
                                _phases.handoff())
        return
    depth = qr.pipeline_emit
    if depth and wake is None and not qr.planned.needs_timer:
        # timer-bearing queries never pipeline: a device wake scalar would
        # stall time-driven expiry if deferred, and host-scheduled (cron)
        # windows pass wake=None yet their flush emissions must not slip a
        # period — needs_timer covers both
        dq = qr._pending_emit
        if dq is None:
            dq = qr._pending_emit = collections.deque()
        dq.append((out, now, None, ingest_ns, _phases.handoff()))
        if len(dq) > depth:
            if depth == 1:
                # exactly-one-deep contract: each send delivers its
                # predecessor (the original @pipeline behavior)
                _deliver_output(qr, *dq.popleft())
            else:
                # depth-k: drain to half depth in ONE batched roundtrip —
                # the fixed per-fetch latency amortizes over ~k/2 sends
                # instead of serializing one fetch per send
                take = len(dq) - depth // 2
                _deliver_many(qr, [dq.popleft() for _ in range(take)])
        return
    if ingest_ns is not None:
        # inline delivery: flag the dispatcher to close e2e AFTER
        # process_staged fully returns, so per batch e2e >= the step
        # latency sample by construction (same end point, earlier start)
        qr._e2e_owed = True
    _deliver_output(qr, out, now, wake)


def _deliver_output(qr, out, now: int, wake, ingest_ns=None,
                    trace=None) -> None:
    """Blocking device->host fetch + delivery of one emission.  `trace`
    is the handoff token of a deferred (@pipeline) delivery whose
    originating dispatch has moved on — delivery spans adopt it."""
    with _phases.adopt(trace):
        # sampled window-fill probe rides THIS fetch (same device_get call:
        # the never-fetch guard counts calls, and this adds none)
        probe = _stateobs.take_fill_probe(qr)
        fetched, wake_h, fills = _phases.fetch(
            qr.app.stats, qr.name, "header" if _headed(out) else "rows",
            (_header_of(out), wake, probe))
        if fills is not None:
            _stateobs.record_fill(qr, fills)
        if wake_h is not None:
            qr._apply_wake(_earliest(wake_h))
        _emit_fetched(qr, out, fetched, now, ingest_ns)


def _deliver_many(qr, items) -> None:
    """Deliver several deferred emissions with ONE batched device_get for
    all their headers (same amortization as _EmissionDrainer._run)."""
    if len(items) == 1:
        _deliver_output(qr, *items[0])
        return
    st = qr.app.stats
    # latency attribution: the batched fetch wall charges to every item
    # it served (`mult`), and the serialized wait behind predecessors'
    # deliveries is queue residency — both are inside each item's e2e
    # sample (see phases.py)
    fetched = _phases.fetch(st, qr.name, "header", [
        _header_of(out) for out, _, _, _, _ in items], mult=len(items))
    loop_t0 = time.perf_counter_ns()
    for (out, now, _, t_in, trace), fetch_h in zip(items, fetched):
        _phases.waited(st, qr.name, loop_t0)
        with _phases.adopt(trace):
            _emit_fetched(qr, out, fetch_h, now, t_in)


def _drain_pending_emit(qr) -> None:
    """Deliver a @pipeline runtime's held emissions (flush/quiesce/
    shutdown).  Swap + delivery run under the query lock — the producer's
    pipeline branch in _emit_output also runs under it (junction dispatch),
    so a concurrent flush can never double-deliver the same emission."""
    if not qr._pending_emit:
        return
    with qr._qlock:
        dq = qr._pending_emit
        if not dq:
            return
        items = list(dq)
        dq.clear()
        _deliver_many(qr, items)


class _EmissionRows:
    """The rows of one emission — ts, kind, valid, cols — wherever they
    are, and THE way every consumer gets them onto the host: the batch
    payload's lazy pulls, `Event` delivery, the UUID sentinels.

    A flat emission's four members are fetched as they are (a plain
    output's are host arrays already and pass through `device_get`
    untouched).  Of a banded one (`BandedEmission`) only the bands below
    each tier's `ranks_used` are fetched — u32 buffers, decoded here
    (`unpack_planes`) into arrays of `ranks fetched x K` slots, the tiers
    in order, rank-major within a tier, under the same `valid` mask
    contract — or, where at most half of those slots are rows (`sparse`),
    into the rows alone in that same order, `valid` all True
    (`unpack_planes_at`); their `fetch` spans carry `ranks` and
    `ranks_cap`."""

    __slots__ = ("stats", "qname", "flat", "bands", "meta", "dtypes",
                 "shards", "sparse", "_keep", "_head_got")

    def __init__(self, qr, out, ranks_used=None, n_valid=None):
        self.stats, self.qname = qr.app.stats, qr.name
        self.sparse, self._keep, self._head_got = False, None, None
        if isinstance(out, BandedEmission):
            self.flat = None
            self.bands, ranks, cap = out.used(ranks_used)
            self.meta = {"ranks": ranks, "ranks_cap": cap}
            self.dtypes = qr.planned.out_schema.dtypes
            self.shards = out.shards
            # the fetched rank rectangle is sized by the send's fullest
            # key: where it is at most half rows (`n_valid`, the header's)
            # only the valid slots are decoded — the same rows in the same
            # order with `valid` all True, not `ranks x K` slots and a mask
            slots = sum(h.size for h, _ in self.bands) // HEAD_WORDS
            self.sparse = n_valid is not None and 2 * n_valid <= slots
        else:
            self.flat, self.meta = tuple(out[-4:]), {}

    def replace(self, ts, kind, valid, cols):
        """Host arrays in the rows' place (the UUID sentinels' new ids)."""
        self.flat, self.meta = (ts, kind, valid, cols), {}

    def _fetch(self, tree):
        return _phases.fetch(self.stats, self.qname, "rows", tree,
                             **self.meta)

    def _head(self, bufs):
        if self.sparse:
            if self._head_got is None:
                self._keep = valid_slots(bufs, self.shards)
                ts, kv = unpack_planes_at(bufs, HEAD_DTYPES, self._keep,
                                          self.shards)
                self._head_got = (ts, (kv & 0x7FFFFFFF).astype(np.int32),
                                  np.ones(ts.shape[0], np.bool_))
            return self._head_got
        ts, kv = unpack_planes(bufs, HEAD_DTYPES, self.shards)
        return (ts, (kv & 0x7FFFFFFF).astype(np.int32),
                (kv >> 31).astype(np.bool_))

    def _cols(self, bufs):
        if self.sparse:
            return tuple(unpack_planes_at(bufs, self.dtypes, self._keep,
                                          self.shards))
        return tuple(unpack_planes(bufs, self.dtypes, self.shards))

    def head(self):
        """(ts, kind, valid) in one roundtrip."""
        if self.flat is not None:
            return self._fetch(self.flat[:3])
        if self._head_got is not None:
            return self._head_got
        return self._head(self._fetch([h for h, _ in self.bands]))

    def cols(self):
        """The output columns, in another."""
        if self.flat is not None:
            return self._fetch(self.flat[3])
        if self.sparse and self._keep is None:
            self.head()            # which slots are rows is the head's to say
        return self._cols(self._fetch([c for _, c in self.bands]))

    def all(self):
        """(ts, kind, valid, cols) in one."""
        if self.flat is not None:
            return self._fetch(self.flat)
        got = self._fetch(self.bands)
        return (*self._head([h for h, _ in got]),
                self._cols([c for _, c in got]))


class _LazyBatchPayload(dict):
    """Batch-callback payload materializing device->host pulls on access.

    Device-computed scalar counts ('n_valid', 'n_current', 'n_expired',
    'n_dropped') are prefetched with the drainer's batched header get, so a
    counting consumer costs ZERO per-batch bulk fetches.  Bulk data
    fetches lazily in two groups — ('ts', 'kind', 'valid') in one roundtrip,
    'cols' in another — because each device_get pays a fixed sync latency
    regardless of size.  Any whole-dict access (iteration, get, `in`, ...)
    materializes everything so the plain-dict contract holds."""

    _LAZY = ("ts", "kind", "valid", "cols")
    _COUNTS = ("n_valid", "n_current", "n_expired", "n_dropped")

    def __init__(self, names, rows: _EmissionRows, counts=None):
        super().__init__()
        self._names = names
        self._rows = rows
        if counts:
            for k, v in counts.items():
                dict.__setitem__(self, k, v)

    def __missing__(self, k):
        if k in ("ts", "kind", "valid"):
            ts, kind, valid = self._rows.head()
            dict.__setitem__(self, "ts", ts)
            dict.__setitem__(self, "kind", kind)
            dict.__setitem__(self, "valid", valid)
            return dict.__getitem__(self, k)
        if k == "cols":
            v = dict(zip(self._names, self._rows.cols()))
            dict.__setitem__(self, k, v)
            return v
        if k == "n_valid":
            v = int(np.sum(self["valid"]))
        elif k == "n_current":
            v = int(np.sum(self["valid"] & (self["kind"] == ev.CURRENT)))
        elif k == "n_expired":
            v = int(np.sum(self["valid"] & (self["kind"] == ev.EXPIRED)))
        elif k == "n_dropped":
            v = 0
        else:
            raise KeyError(k)
        dict.__setitem__(self, k, v)
        return v

    def _materialize(self):
        for k in self._LAZY + self._COUNTS:
            if not dict.__contains__(self, k):
                self[k]
        return self

    def get(self, k, default=None):
        try:
            return self[k]
        except KeyError:
            return default

    def __contains__(self, k):
        return k in self._LAZY or k in self._COUNTS or \
            dict.__contains__(self, k)

    def __iter__(self):
        return iter(dict.keys(self._materialize()))

    def keys(self):
        return dict.keys(self._materialize())

    def items(self):
        return dict.items(self._materialize())

    def values(self):
        return dict.values(self._materialize())

    def __len__(self):
        # fixed key set: counting costs no device->host materialization
        extra = sum(1 for k in dict.keys(self)
                    if k not in self._LAZY and k not in self._COUNTS)
        return len(self._LAZY) + len(self._COUNTS) + extra


def _emit_output_sync(qr, out, now: int, header=None,
                      ingest_ns=None) -> None:
    """Emission on whichever thread delivers it — drainer threads run
    this under `phases.adopt`, so its spans carry the originating send's
    batch and join its DETAIL trace.  `ingest_ns` (send-acceptance
    perf_counter_ns) closes the `<query>:e2e` histogram here — after
    callbacks, downstream routing, and the synchronous sink publish they
    trigger."""
    try:
        return _emit_output_sync_impl(qr, out, now, header)
    finally:
        if ingest_ns is not None:
            st = qr.app.stats
            if st.enabled:
                st.e2e_latency(qr.name,
                               time.perf_counter_ns() - ingest_ns)


def _row_nbytes(qr) -> int:
    """Wire bytes of ONE output row from schema metadata (ts int64 +
    kind int32 + payload column itemsizes), cached per runtime — feeds
    the `<q>.emitted_bytes` tenant-accounting counter without touching
    any buffer."""
    nb = qr._out_row_nbytes
    if nb is None:
        nb = 12
        try:
            for t in qr.planned.out_schema.types:
                nb += int(np.dtype(ev.np_dtype(t)).itemsize)
        except Exception:  # noqa: BLE001 — metrics must not throw
            pass
        qr._out_row_nbytes = nb
    return nb


def _emit_output_sync_impl(qr, out, now: int, header=None) -> None:
    """Shared output emission: fan out to columnar batch callbacks first
    (zero-transfer for counting consumers — the device-computed count
    scalars ride the header fetch), then unpack to host events only if
    someone needs them (Event callbacks or downstream routing).

    Headed outputs (`_headed`: a banded pattern emission, a flat
    6-tuple) may still hold DEVICE arrays here; only the count header has
    been fetched.  Bulk rows transfer lazily through `_EmissionRows` —
    the payload's pulls, the event-delivery path below.  Plain outputs
    (len-4) arrive fully fetched (they are bounded by the window batch
    capacity).

    One `demux` span covers the delivery; the device fetches paid here
    (`fetch`) and the consumer-facing work (`sink`) nest in it, so its
    self time is the rest — header decode, unpack, ts-order restore."""
    target_live = _target_live(qr)
    if not (qr.callbacks or qr.batch_callbacks or target_live):
        return
    if qr.app.stats.detail:
        # reference: log4j TRACE at QuerySelector.process :77
        _trace_log.debug("query %s: emitting output batch @ %d",
                         qr.name, now)
    with _phases.phase(qr.app.stats, qr.name, "demux") as span:
        _demux_and_deliver(qr, out, now, header, target_live, span)


def _demux_and_deliver(qr, out, now: int, header, target_live: bool,
                       span) -> None:
    p = qr.planned
    _st = qr.app.stats
    counts = None
    overflow_exc = None
    headed = _headed(out)
    ranks_used = None
    if headed:
        if header is None:
            header = _phases.fetch(_st, qr.name, "header", _header_of(out))
        if isinstance(out, BandedEmission):
            # one (n_valid, n_dropped, ranks_used) a tier of the send
            nv = sum(int(h[0]) for h in header)
            nd = sum(int(h[1]) for h in header)
            ncur = None
            ranks_used = [int(h[2]) for h in header]
        else:
            h0 = np.asarray(header[0])
            nd = int(header[1])
            if h0.ndim:
                # join header vector [n_valid, n_current] (see join.py),
                # and behind them — from a `window.time` side's step — the
                # rows the window LOST because it was full: a wrong answer,
                # so an error (raised below, after the rows that did come
                # are delivered)
                nv, ncur = int(h0[0]), int(h0[1])
                if h0.shape[0] > 2 and int(h0[2]):
                    lost = int(h0[2])
                    qr._window_dropped += lost
                    if _st.enabled:
                        _st.counter_inc(f"{qr.name}.window_dropped", lost)
                    overflow_exc = CapacityExceededError(
                        f"{qr.name}: a join window dropped {lost} live "
                        f"row(s) this batch because it was full; raise "
                        f"@capacity(window='N') (or window.left / "
                        f"window.right) on the query to hold what the "
                        f"window's time keeps")
            else:
                nv, ncur = int(h0), None
        if nd:
            # dropped-row counter BEFORE the growth attempt: even when the
            # cap grows for the next batch, THIS batch lost nd rows
            if _st.enabled:
                _st.counter_inc(f"{qr.name}.dropped", nd)
            what = ("join result rows exceeded the emission"
                    if p.mixed_kinds
                    else "pattern match rows exceeded the per-key emission")
            if not p.emit_explicit:
                # the cap was an implicit default: losing matches silently
                # is a correctness hole.  First try ADAPTIVE GROWTH — the
                # runtime rebuilds its steps with a doubled cap (state
                # shapes don't depend on it) so subsequent batches have
                # headroom; only when growth is exhausted does the loss
                # surface as a processing error (fault stream / exception
                # listener), raised in the finally below so the error
                # reports partial loss, not total loss.
                if not qr._grow_emission_cap(nd, nv):
                    overflow_exc = MatchOverflowError(
                        f"{qr.name}: {nd} {what} capacity this batch; set "
                        f"@emit(rows='N') on the query to raise the cap or "
                        f"accept capped delivery")
            else:
                import logging
                logging.getLogger("siddhi_tpu").warning(
                    "%s: %d %s capacity this batch and were dropped",
                    qr.name, nd, what)
        if ncur is not None:
            # join emissions mix CURRENT and EXPIRED rows; both counts
            # rode the prefetched header — no bulk fetch for counting
            counts = {"n_valid": nv, "n_current": ncur,
                      "n_expired": nv - ncur, "n_dropped": nd}
        else:
            # pattern matches are always CURRENT-kind rows
            counts = {"n_valid": nv, "n_current": nv, "n_expired": 0,
                      "n_dropped": nd}
        # emission-cap demand (nv + nd rows wanted out this batch) is
        # already host-side off the header fetch — the high-water mark
        # the sizing ledger persists for @emit pre-sizing
        _cap = p.compact_rows
        if _cap is not None and _stateobs.obs_enabled(qr.app):
            with _phases.phase(_st, qr.name, "obs_feed"):
                _st.stateobs.observe(
                    qr.name, "emission_cap", nv + nd, _cap,
                    growable=not p.emit_explicit,
                    config_key="@emit(rows='N')")
    try:
        if headed:
            if nv == 0:
                return
            rows_out = nv
        else:
            ovalid_np = np.asarray(out[2])
            if not ovalid_np.any():
                return
            rows_out = int(ovalid_np.sum())
        rows = _EmissionRows(qr, out, ranks_used, nv if headed else None)
        span.set_metadata(rows=rows_out)
        if _st.enabled and rows_out:
            # per-tenant events_out/emitted_bytes accounting: row count is
            # already host-side (header / staged valid plane) and the byte
            # figure is schema metadata × rows — no extra fetch
            _st.emitted(qr.name, rows_out, rows_out * _row_nbytes(qr))
        if p.emits_uuid:
            # UUID() sentinels materialize ONCE here, at the device->host
            # emission boundary, so every consumer of this emission (event
            # callbacks, batch payloads, downstream routing, table writes)
            # observes the same id per row
            ots, okind, ovalid, ocols = rows.all() if headed else rows.flat
            changed = ev.materialize_uuid_sentinels(
                p.out_schema, np.asarray(ovalid), ocols)
            oc = list(ocols)
            for pos, col in changed or ():
                oc[pos] = col
            rows.replace(ots, okind, ovalid, tuple(oc))
        if qr.batch_callbacks:
            payload = _LazyBatchPayload(p.out_schema.names, rows, counts)
            with _phases.phase(_st, qr.name, "sink"):
                for bcb in qr.batch_callbacks:
                    bcb(now, payload)
        if not qr.callbacks and not target_live:
            if _st.enabled and p.output_target in qr.app.junctions:
                # an output stream nobody reads as events: its throughput
                # is counted from numbers already on the host (a routed
                # emission is counted by the junction's publish) — nothing
                # is fetched, sorted or unpacked for the statistics' sake
                _st.stream_in(p.output_target, _routed_rows(
                    p, rows_out, counts, rows))
            return
        if headed:
            # headed outputs are compacted rank-major on the device (a
            # banded one: the used bands, tier after tier); fetch them
            # now and restore timestamp order for event delivery with a
            # host-side stable sort of just the valid rows (O(matches),
            # runs on the drainer thread)
            ots, okind, ovalid, ocols = rows.all()
            idxv = np.nonzero(ovalid)[0]
            order = idxv[np.argsort(ots[idxv], kind="stable")]
            ots = ots[order]
            okind = okind[order]
            ocols = tuple(c[order] for c in ocols)
            ovalid = np.ones(order.shape[0], np.bool_)
        else:
            ots, okind, ovalid, ocols = rows.flat
        batch = ev.EventBatch(ots, okind, ovalid, ocols)
        pairs = ev.unpack(p.out_schema, batch,
                          want_kinds=(ev.CURRENT, ev.EXPIRED))
        if not pairs:
            return
        with _phases.phase(_st, qr.name, "sink"):
            if qr.table_op is not None:
                current = [e for k, e in pairs if k == ev.CURRENT]
                expired = [e for k, e in pairs if k == ev.EXPIRED]
                for cb in qr.callbacks:
                    cb(now, current or None, expired or None)
                _apply_table_op(qr, ots, okind, ovalid, ocols, now)
                return
            limiter = qr.rate_limiter
            if limiter is not None:
                limiter.process(pairs, now)
                return
            _deliver_pairs(qr, pairs, now)
    finally:
        if overflow_exc is not None:
            raise overflow_exc


def _routed_rows(p, rows_out: int, counts, rows) -> int:
    """How many of an emission's rows `_deliver_pairs` would route to the
    output target (`insert [current|expired|all] events into`), from
    numbers already on the host: the header's counts for compacted
    outputs, the fetched kind plane for plain ones."""
    sel = p.output_event_type
    if sel not in ("CURRENT_EVENTS", "EXPIRED_EVENTS"):
        return rows_out
    if counts is not None:
        return counts["n_current" if sel == "CURRENT_EVENTS"
                      else "n_expired"]
    want = ev.CURRENT if sel == "CURRENT_EVENTS" else ev.EXPIRED
    _, okind, ovalid, _ = rows.flat     # a plain output: host arrays
    return int((np.asarray(ovalid) & (np.asarray(okind) == want)).sum())


def _aggregation_view(agg, per: str, within) -> Tuple:
    """Padded columnar snapshot of an aggregation's buckets for the join
    device step (reference: AggregateWindowProcessor adapter role)."""
    ts, cols = agg.snapshot_rows(per, within)
    n = ts.shape[0]
    cap = ev.bucket_size(max(n, 1))
    valid = np.zeros((cap,), np.bool_)
    valid[:n] = True
    pts = np.zeros((cap,), np.int64)
    pts[:n] = ts
    padded = []
    for c in cols:
        a = np.zeros((cap,), c.dtype)
        a[:n] = c
        padded.append(jax.numpy.asarray(a))
    return (tuple(padded), jax.numpy.asarray(pts), jax.numpy.asarray(valid))


def _deliver_pairs(qr, pairs, now: int) -> None:
    """Terminal delivery: query callbacks + downstream routing (reference:
    OutputCallback implementations, CORE/query/output/callback/*)."""
    p = qr.planned
    current = [e for k, e in pairs if k == ev.CURRENT]
    expired = [e for k, e in pairs if k == ev.EXPIRED]
    dbg = qr.app._debugger
    if dbg is not None:
        dbg.check_break_point(qr.name, "OUT", current)
    for cb in qr.callbacks:
        cb(now, current or None, expired or None)
    if p.output_target:
        sel = p.output_event_type
        if sel == "CURRENT_EVENTS":
            routed = current
        elif sel == "EXPIRED_EVENTS":
            routed = expired
        else:
            routed = [e for _, e in pairs]
        if routed:
            qr.app._route(p.output_target, routed)


def _apply_table_op(qr, ots, okind, ovalid, ocols, now) -> None:
    """Table write operations from query output (reference: CORE/query/output/
    callback/{InsertIntoTable,UpdateTable,DeleteTable,UpdateOrInsertTable}
    Callback.java)."""
    op, table, cond, set_fns, key = qr.table_op
    want = okind == 0  # CURRENT rows drive table ops
    valid = jax.numpy.logical_and(ovalid, jax.numpy.asarray(np.asarray(want)))
    batch = ev.EventBatch(ots, okind, valid, ocols)
    if op == "insert":
        staged = ev.StagedBatch(
            np.asarray(ots), np.asarray(okind), np.asarray(valid),
            [np.asarray(c) for c in ocols], int(np.asarray(valid).sum()))
        table.insert(batch, staged)
    elif op == "delete":
        table.delete_where(cond, key, batch)
    elif op == "update":
        table.update_where(cond, key, batch, set_fns)
    elif op == "upsert":
        staged = ev.StagedBatch(
            np.asarray(ots), np.asarray(okind), np.asarray(valid),
            [np.asarray(c) for c in ocols], int(np.asarray(valid).sum()))
        table.update_where(cond, key, batch, set_fns, upsert=True,
                           staged=staged)


class JoinQueryRuntime(_QueryRuntimeBase):
    """Host wrapper for join queries: routes each side's batches to the
    side-specific jitted step, passing table snapshots for table sides."""

    _kind = "join"

    def __init__(self, planned, app: "SiddhiAppRuntime"):
        super().__init__(planned, app)
        self.state = self.place_state(jax.tree.map(
            lambda x: jax.numpy.array(x, copy=True), planned.init_state()))
        # equi-join bucket fast path: host retention mirror (`_jk`) + the
        # lane width the NEXT replan must keep (core/join.py
        # JoinKeyTracker)
        self._lane_k = 0
        # the last stamp each ring side admitted (a batch behind it, or out
        # of order in itself, takes the side's slow program), and the
        # deepest chain walk a batch has been given
        self._ring_last_ts = [None, None]
        self._probe_depth = 0
        # rows a full `window.time` side lost, as the steps' headers said
        self._window_dropped = 0
        if planned.fastpath == "bucket":
            from .join import JoinKeyTracker
            self._jk = JoinKeyTracker(
                planned.join_key_allocator, planned.ring_caps,
                planned.lane_buckets,
                time_ms=tuple(
                    side.window.time_ms if kind == "chain" else None
                    for side, kind in zip((planned.left, planned.right),
                                          planned.index_kind)),
                index_kind=planned.index_kind)
            self._lane_k = planned.lane_k

    _EMIT_CAP_MAX = 1 << 21   # 2M emitted rows per batch

    def _grow_emission_cap(self, n_dropped: int, n_valid: int = 0) -> bool:
        """Adaptive growth for the implicit join emission cap (same contract
        as PatternQueryRuntime._grow_emission_cap: size to observed demand
        in one jump; each regrow recompiles the side steps).  Join state
        shapes are cap-independent, so the live window/selector state
        carries over, as do the host group-slot allocators."""
        if self._replan is None:
            return False
        need = max(n_valid + n_dropped, 1024)
        cur = self.planned.compact_rows
        if cur is not None and need <= cur:
            # an earlier growth (possibly racing this one) already covers
            # the demand: the overflowing batch was compiled pre-growth —
            # not an error, the next batch delivers in full
            return True
        new_rows = min(1 << (need - 1).bit_length(), self._EMIT_CAP_MAX)
        if cur is not None and new_rows <= cur:
            return False
        # admission: deny growth past the state ceiling (see
        # PatternQueryRuntime._grow_emission_cap) — overflow keeps
        # dropping at the current cap, loudly, instead of OOMing
        adm = self.app.admission
        if adm is not None and not adm.admit_growth(
                self.name, (new_rows - (cur or 0)) * _row_nbytes(self)):
            return False
        logging.getLogger("siddhi_tpu").warning(
            "%s: %d join result rows dropped at emission capacity; growing "
            "the cap to %d (set @emit(rows='N') to pre-size and silence "
            "this)", self.name, n_dropped, new_rows)
        # operator-visible counter (see PatternQueryRuntime._grow_emission_cap)
        stats = self.app.stats
        if stats.enabled:
            stats.counter_inc(f"{self.name}.cap_growths")
        old = self.planned
        newp = self._replan(new_rows)
        # group allocators hold live host slot maps — carry them over,
        # then publish the fully-formed plan in ONE assignment (workers
        # read self.planned once; they must never observe empty allocators)
        newp.slot_allocator = old.slot_allocator
        newp.slot_allocator2 = old.slot_allocator2
        newp.join_key_allocator = old.join_key_allocator
        self.planned = newp
        return True

    def _join_key_probe(self, is_left: bool, staged: ev.StagedBatch,
                        now: Optional[int] = None):
        """Key bucket slots for one arriving batch (bucket fast path), and
        whether a ring side may take it in place (its CURRENT stamps in
        order and none behind the side's last), and how deep its probes
        walk a ring other side.
        Cached on the staged batch — keyed by (runtime, side), since a
        junction hands ONE staged object to every subscriber and a
        self-join sees it on both sides — so fused-drain re-entries and
        deferred dispatches can never double-count the retention
        mirror.  Grows the planned lane width / walk depth BEFORE the
        dispatch that would overflow it; the span says what the mirror
        holds (`lane_k`, `lane_need`, `probe_depth`, `window_rows_l` /
        `window_rows_r`, `window_dropped`)."""
        cache = staged.jprobe
        if cache is None:
            cache = staged.jprobe = {}
        key = (id(self), is_left)
        cached = cache.get(key)
        if cached is not None:
            return cached
        from .join import _norm_key_cols
        p, st = self.planned, self.app.stats
        with _phases.phase(st, self.name, "route_keys") as sp:
            kvalid = staged.valid & (staged.kind == ev.CURRENT)
            pos = p.key_left if is_left else p.key_right
            i = 0 if is_left else 1
            in_order = True
            if p.index_kind[i] == "chain":
                ts = staged.ts[kvalid]
                if ts.size:
                    last = self._ring_last_ts[i]
                    in_order = bool(
                        (last is None or ts[0] >= last) and
                        (ts.size < 2 or (ts[1:] >= ts[:-1]).all()))
                    self._ring_last_ts[i] = int(ts.max()) if last is None \
                        else max(last, int(ts.max()))
            slots = self._jk.track(
                is_left, _norm_key_cols(staged.cols, pos, p.key_dtypes),
                kvalid, ts=staged.ts, now=now, in_order=in_order)
            need = self._jk.needed_k()
            if need > p.lane_k:
                self._grow_lane_k(need)
            # a ring OTHER side is walked as deep as this batch's keys
            # reach into it (join.chain_depth)
            depth = 0
            if p.index_kind[1 - i] == "chain":
                from .join import chain_depth
                depth = chain_depth(self._jk.batch_need,
                                    self._jk.fullest_keys()[1 - i])
                self._probe_depth = max(self._probe_depth, depth)
            rows = self._jk.rows()
            sp.set_metadata(lane_k=self.planned.lane_k, lane_need=need,
                            probe_depth=depth,
                            window_rows_l=rows[0], window_rows_r=rows[1],
                            window_dropped=self._jk.dropped())
            out = np.where(kvalid, slots, -1).astype(np.int32)
        if _stateobs.obs_enabled(self.app):
            # lane demand is a running bucket-occupancy max the tracker
            # already mirrors host-side; push it so the HWM survives
            # window expiry shrinking the live lanes back down
            with _phases.phase(st, self.name, "obs_feed") as sp:
                st.stateobs.observe(
                    self.name, "join_lane", need, self.planned.lane_k,
                    growable=True,
                    config_key="auto (lane grows via replan)")
                _stateobs_feed_slots(self, p.join_key_allocator, out, sp)
        cache[key] = (out, in_order, depth)
        return cache[key]

    def join_facts(self) -> Dict:
        """What the host knows of a bucket join's windows without a fetch
        (`state_report()["join"]`, `/metrics` `siddhi_join_*`): the rows
        each side's retention mirror holds, the rows full `window.time`
        sides lost (the mirror's count, or the steps' headers' where that is
        more), the chain walks' depth.  {} off the bucket path."""
        if self._jk is None:
            return {}
        rows = self._jk.rows()
        return {"window_rows_l": rows[0], "window_rows_r": rows[1],
                "window_dropped": max(self._jk.dropped(),
                                      self._window_dropped),
                "probe_depth": self._probe_depth,
                "fullest_key": max(self._jk.fullest_keys())}

    def _grow_lane_k(self, need: int) -> None:
        """Recompile the side steps with wider candidate lanes.  Called
        BEFORE the batch that needs them dispatches, so the device
        program can never silently drop same-bucket candidates (which
        would diverge from the grid path).  State shapes are
        lane-independent — window/selector state carries over live."""
        new_k = 1 << (max(need, 1) - 1).bit_length()
        logging.getLogger("siddhi_tpu").info(
            "%s: growing equi-join candidate lanes to %d (max same-"
            "bucket window occupancy %d)", self.name, new_k, need)
        stats = self.app.stats
        if stats.enabled:
            stats.counter_inc(f"{self.name}.lane_growths")
        fb = self._fuse
        if fb is not None:
            # the pending stack was offered under the old lane width;
            # drain it sequentially first (byte-identical by contract)
            fb.drain()
        self._lane_k = new_k
        old = self.planned
        newp = self._replan(None if old.emit_explicit
                            else old.compact_rows)
        newp.slot_allocator = old.slot_allocator
        newp.slot_allocator2 = old.slot_allocator2
        newp.join_key_allocator = old.join_key_allocator
        self.planned = newp

    def _table_probe(self, staged: ev.StagedBatch):
        """Host-side table-index candidates for one trigger batch
        (table fast path): [B, K] row ids ascending per row (the grid
        path's emission order) + their validity."""
        p = self.planned
        tid = (p.left if p.table_is_left else p.right).stream_id
        table = self.app.tables[tid]
        vals = np.asarray(staged.cols[p.stream_key_pos])
        with table._lock:
            cand, ok = table.probe_rows(p.table_pos, vals)
        big = np.int32(np.iinfo(np.int32).max)
        cand = np.where(ok, cand, big)
        cand.sort(axis=1)
        ok = cand < big
        return np.where(ok, cand, -1).astype(np.int32), ok

    def _after_restore(self, host_state) -> None:
        """Re-seed the key retention mirror from restored window
        buffers (alive rows in arrival order) and re-widen lanes if the
        snapshot needs more than the current plan carries."""
        p = self.planned
        if p.fastpath != "bucket" or self._jk is None:
            return
        sides, exps = [], []
        for st, kind in zip((host_state[0], host_state[1]), p.index_kind):
            slots, exp = np.empty(0, np.int64), None
            buf = st[0] if isinstance(st, tuple) and st else None
            if buf is not None and kind == "chain":
                # a ring (window.RingSlab): the resident rows are the
                # `count` positions from the tail, oldest first
                tail, count = (int(x) for x in np.asarray(st[2]))
                at = (tail + np.arange(count)) % buf.gslot.shape[0]
                slots = np.asarray(buf.cols[-1])[at].astype(np.int64)
                lo, hi = (np.asarray(x)[at].astype(np.int64)
                          for x in buf.expire_ts)
                exp = (hi << 32) | lo
                # the side's newest stamp: a batch behind it is out of order
                side = p.left if len(sides) == 0 else p.right
                self._ring_last_ts[len(sides)] = \
                    int(exp[-1]) - side.window.time_ms if count else None
            elif buf is not None and hasattr(buf, "alive"):
                alive = np.asarray(buf.alive)
                add_seq = np.asarray(buf.add_seq)[alive]
                slots = np.asarray(buf.cols[-1])[alive][
                    np.argsort(add_seq, kind="stable")].astype(np.int64)
            sides.append(slots)
            exps.append(exp)
        self._jk.rebuild(sides, exps)
        need = self._jk.needed_k()
        if need > p.lane_k:
            self._grow_lane_k(need)

    def place_state(self, state):
        """GSPMD scale-out: shard window buffers / selector slabs on axis 0
        and let XLA partition the [R, C] join compare and buffer
        maintenance (sharding is a layout hint — semantics are preserved
        whatever the choice; scatters/sorts get collectives as needed).
        Scalars and indivisible leaves stay replicated.  Restore paths call
        this too, so a restored runtime keeps its sharding."""
        mesh = self.app.mesh
        if mesh is None or mesh.devices.size < 2:
            return state
        from .shardsafe import axis0_sharding

        def _place(x):
            s = axis0_sharding(mesh, x)
            return jax.device_put(x, s) if s is not None else x
        return jax.tree.map(_place, state)

    def _other_table(self, is_left):
        p = self.planned
        other = p.right if is_left else p.left
        if other.is_aggregation:
            agg = self.app.aggregations[other.stream_id]
            return _aggregation_view(agg, p.per_duration, p.within_range)
        if other.is_named_window:
            # probe the shared window's live buffer (reference:
            # WindowWindowProcessor.find against Window.java's chain)
            nw = self.app.named_windows[other.stream_id]
            buf = nw.wproc.current_buffer(nw.state)
            return (buf.cols, buf.ts, buf.alive)
        if other.is_table:
            t = self.app.tables[other.stream_id]
            return (t.cols, t.ts, t.valid)
        return (jax.numpy.zeros((1,), jax.numpy.float32),) * 3

    def _join_slots(self, is_left: bool,
                    staged: ev.StagedBatch) -> np.ndarray:
        """Per-side group-by slots (joined rows compose both sides' ids);
        TIMER rows carry zeroed columns — allocating for them would burn
        a phantom slot for the all-zeros key on every tick.  Shared by the
        sequential path and fused dispatch (core/fusion.py)."""
        p = self.planned
        galloc = p.slot_allocator if is_left else p.slot_allocator2
        gpos = p.gl_pos if is_left else p.gr_pos
        if galloc is None:
            return _zero_slots(staged.ts.shape[0])
        with _phases.phase(self.app.stats, self.name, "route_keys"):
            gvalid = staged.valid & (staged.kind != ev.TIMER)
            return galloc.slots_for([staged.cols[i] for i in gpos], gvalid)

    def process_staged(self, is_left: bool, staged: ev.StagedBatch,
                       now: int) -> None:
        p = self.planned
        probe, in_order, depth = None, True, 1
        if p.fastpath == "bucket":
            # slot binding + retention mirror BEFORE the fuse offer: a
            # lane-width growth must replan before this batch dispatches
            probe, in_order, depth = self._join_key_probe(
                is_left, staged, now)
            p = self.planned          # _grow_lane_k may have swapped it
        side = p.left if is_left else p.right
        # `step_left` / `step_right`, or — a ring side — its program for a
        # deeper walk of the other side's chains / for stamps out of order
        step = p.side_step(is_left, max(depth, 1), not in_order)
        if step is None:
            return
        fb = self._fuse
        if fb is not None and fb.offer((is_left, staged, now), staged,
                                       is_left):
            return
        gslot = self._join_slots(is_left, staged)
        st = self.app.stats
        extra = ()
        if p.fastpath == "bucket":
            extra = (probe,)
        elif p.fastpath == "table":
            with _phases.phase(st, self.name, "route_keys"):
                extra = self._table_probe(staged)
        with _phases.phase(st, self.name, "h2d",
                           bytes=_staged_nbytes(staged) +
                           _phases.nbytes(gslot, *extra)):
            batch = staged.to_device(side.schema)
            args = [self.state, batch.ts, batch.kind, batch.valid,
                    batch.cols, jax.numpy.asarray(gslot)]
            if p.fastpath == "bucket":
                args.append(jax.numpy.asarray(probe))
            elif p.fastpath == "table":
                args.append((jax.numpy.asarray(extra[0]),
                             jax.numpy.asarray(extra[1])))
            args += [self._other_table(is_left),
                     jax.numpy.asarray(now, jax.numpy.int64)]
        self.state, out, wake = _phases.dispatch(self, step, *args)
        _emit_output(self, out, now,
                     wake=wake if p.needs_timer else None)

    def on_timer(self, now: int) -> None:
        p = self.planned
        for is_left, side in ((True, p.left), (False, p.right)):
            if side.window is not None and side.window.needs_timer:
                self.process_staged(
                    is_left, self._timer_batch(side.schema, now), now)


class TriggerRuntime:
    """Event generator into a stream named after the trigger (reference:
    CORE/trigger/{PeriodicTrigger,CronTrigger,StartTrigger}.java).  Rides the
    app scheduler: each firing publishes one event `[triggered_time]` and
    reschedules itself."""

    def __init__(self, tdef, app: "SiddhiAppRuntime"):
        self.definition = tdef
        self.app = app
        self.stream_id = tdef.id
        self._cron = None
        if tdef.at is not None and tdef.at.lower() != "start":
            from ..utils.cron import CronExpression
            self._cron = CronExpression(tdef.at)

    def start(self, now: int) -> None:
        d = self.definition
        if d.at is not None and d.at.lower() == "start":
            self.app._scheduler.notify_at(now, self)
        elif d.at_every is not None:
            self.app._scheduler.notify_at(now + d.at_every, self)
        elif self._cron is not None:
            self.app._scheduler.notify_at(self._cron.next_fire(now), self)

    def on_timer(self, now: int) -> None:
        self.app._route(self.stream_id, [ev.Event(now, [now])])
        d = self.definition
        if d.at_every is not None:
            self.app._scheduler.notify_at(now + d.at_every, self)
        elif self._cron is not None:
            self.app._scheduler.notify_at(self._cron.next_fire(now), self)


class NamedWindowRuntime(_QueryRuntimeBase):
    """A shared window instance (reference: CORE/window/Window.java:65 —
    `define window W (...) <window>(...) output <type> events`).  Queries
    insert into it; reader queries subscribe to its CURRENT/EXPIRED output.

    TPU design: one jitted step wrapping the window processor; output rows are
    staged once to numpy (kinds preserved) and fanned out to subscribers."""

    def __init__(self, wdef, schema: ev.Schema, app: "SiddhiAppRuntime"):
        import jax.numpy as jnp
        from .window import Rows, create_window

        super().__init__(None, app)
        self.definition = wdef
        self.schema = schema
        w = wdef.window
        if w is None:
            raise CompileError(
                f"window definition {wdef.id!r} needs a window function")
        self.wproc = create_window(
            (w.namespace + ":" if w.namespace else "") + w.name,
            schema, w.parameters, batch_capacity=512)
        if self.wproc.session_key_pos is not None:
            # the keyed-window slab is a query-planner construct; a shared
            # named window has no key axis — running the key-less processor
            # would silently merge every key into ONE session
            raise CompileError(
                "session(gap, key) is not supported on a `define window` "
                "shared instance; use it on a query's input stream")
        self.needs_timer = self.wproc.needs_timer
        self._wake_replaces = True
        self._timers_coalesce = self.wproc.timer_coalesces
        self.output_event_type = wdef.output_event_type or "ALL_EVENTS"
        self.subscribers: List = []      # QueryRuntime-likes (process_staged)
        self.stream_callbacks: List[Callable] = []
        # `_qlock` serializes ingest (via _route) against scheduler timers
        # and snapshot reads of self.state
        wproc = self.wproc

        def step(state, ts, kind, valid, cols, now):
            rows = Rows(ts=ts, kind=kind, valid=valid,
                        seq=jnp.zeros_like(ts),
                        gslot=jnp.full(ts.shape, -1, jnp.int32), cols=cols)
            state, wout = wproc.process(state, rows, now)
            o = wout.rows
            return state, (o.ts, o.kind, o.valid, o.cols), wout.next_wakeup

        # NOT donated: join queries probe this window's live buffer
        # (_other_table) without holding _qlock through their own step —
        # donation would let a concurrent ingest delete the buffers a
        # join just captured
        self._step = jit_step(step, owner=f"window:{wdef.id}",
                              role="window_step")
        self.state = jax.tree.map(
            lambda x: jax.numpy.array(x, copy=True), wproc.init_state())

    @property
    def name(self):
        return self.definition.id

    def process_staged(self, staged: ev.StagedBatch, now: int) -> None:
        with _phases.phase(self.app.stats, self.name, "h2d",
                           bytes=_staged_nbytes(staged)):
            batch = staged.to_device(self.schema)
            now_d = jax.numpy.asarray(now, jax.numpy.int64)
        self.state, out, wake = _phases.dispatch(
            self, self._step, self.state, batch.ts, batch.kind,
            batch.valid, batch.cols, now_d)
        self._fanout(out, now)
        if self.needs_timer:
            self._apply_wake(int(wake))

    def on_timer(self, now: int) -> None:
        self.process_staged(self._timer_batch(self.schema, now), now)

    def _fanout(self, out, now: int) -> None:
        ots, okind, ovalid, ocols = out
        ovalid_np = np.asarray(ovalid)
        if not ovalid_np.any():
            return
        okind_np = np.asarray(okind)
        sel = self.output_event_type
        if sel == "CURRENT_EVENTS":
            keep = okind_np == ev.CURRENT
        elif sel == "EXPIRED_EVENTS":
            keep = okind_np == ev.EXPIRED
        else:
            keep = (okind_np == ev.CURRENT) | (okind_np == ev.EXPIRED)
        ovalid_np = ovalid_np & keep
        if not ovalid_np.any():
            return
        staged = ev.StagedBatch(
            np.asarray(ots), okind_np, ovalid_np,
            [np.asarray(c) for c in ocols], int(ovalid_np.sum()))
        for cb in self.stream_callbacks:
            batch = ev.EventBatch(staged.ts, staged.kind, ovalid_np,
                                  tuple(staged.cols))
            pairs = ev.unpack(self.schema, batch,
                              want_kinds=(ev.CURRENT, ev.EXPIRED))
            cb([e for _, e in pairs])
        for q in self.subscribers:
            lk = _sub_lock(q)
            if lk is not None:
                with _query_lock(lk, self.definition.id):
                    q.process_staged(staged, now)
            else:
                q.process_staged(staged, now)


class StreamJunction:
    """Per-stream pub/sub hub (reference: CORE/stream/StreamJunction.java:61).
    Packs each published chunk to numpy once; subscribers share the staging.

    `@OnError(action='STREAM')` on the stream definition routes events whose
    processing raised, together with the error, into the `!stream` fault
    stream (reference: StreamJunction.handleError :368-430 +
    FaultStreamEventConverter); the default action logs and drops."""

    def __init__(self, schema: ev.Schema, stream_id: str = "",
                 on_error: str = "LOG", app=None):
        self.schema = schema
        self.stream_id = stream_id
        self.on_error = on_error
        self.app = app
        self.queries: List[QueryRuntime] = []
        self.stream_callbacks: List[Callable] = []
        # per-junction send sequence: the `batch` every span of one send
        # carries, on whatever thread it runs (observability/phases.py)
        self._batch_seq = itertools.count(1)
        self._sub_names_memo: Optional[Tuple[str, ...]] = None
        # does the accept-edge stager run here?  Memoized at the first
        # dispatch (_serve_stage): wiring is complete by then
        self._serve_staging: Optional[bool] = None
        # @async(buffer.size, workers): bounded ingress queue + worker
        # threads (the reference's Disruptor ring,
        # StreamJunction.java:276-313).  None => synchronous dispatch.
        self._async_q = None
        self._async_policy = "block"
        self._async_shed_warn = 0.0
        self._async_workers: List[threading.Thread] = []

    def enable_async(self, buffer_size: int = 256, workers: int = 1,
                     policy: str = "block") -> None:
        """Decouple ingestion: sends enqueue (bounded) and worker threads
        dispatch to the queries.  `queue.policy` picks the full-queue
        behavior: 'block' (default) backpressures the producer — the
        reference's Disruptor blocking-wait; 'shed' drops the send
        loudly instead (`siddhi_async_shed_total{app,stream}`), for
        feeds where stale events are worth less than producer liveness.
        With workers > 1, cross-batch ordering within the stream is
        relaxed — same trade as the reference's multi-consumer
        Disruptor."""
        if self._async_q is not None:
            return
        if policy not in ("block", "shed"):
            raise CompileError(
                f"@async(queue.policy={policy!r}) on {self.stream_id!r}: "
                "policy must be 'block' or 'shed'")
        import queue
        self._async_policy = policy
        self._async_q = queue.Queue(maxsize=max(1, buffer_size))
        for i in range(max(1, workers)):
            t = threading.Thread(
                target=self._drain_async, daemon=True,
                name=f"siddhi-ingest-{self.stream_id}-{i}")
            # exempt from the snapshot ingress gate: a worker whose callback
            # re-ingests must keep draining or _quiesce's queue join would
            # deadlock against the closed gate
            t._siddhi_internal = True
            t.start()
            self._async_workers.append(t)

    def sub_names(self) -> Tuple[str, ...]:
        """Names of the subscribing queries: a junction-level span (stage,
        accept-edge upload) charges each of them, as each one's e2e
        sample contains it (see phases.py).  Memoized: subscriptions
        change at wiring time only (subscribe_query, the merge pass)."""
        names = self._sub_names_memo
        if names is None:
            names = self._sub_names_memo = tuple(
                _sub_name(q, self.stream_id) for q in self.queries)
        return names

    def _serve_stage(self, staged) -> None:
        """Double-buffered H2D staging (serving/staging.py): when a
        subscriber runs the serving loop and a subscriber takes the
        staged batch as it is (`adopts_staged`: its step reads
        `staged.to_device`), the batch's device upload starts HERE at
        the accept edge — batch N+1's transfer overlaps batch N's
        compute (and, on the @async path, the queue wait).  A junction
        whose subscribers all upload columns of their own (the pattern
        path: grouped by the host) stages nothing: one upload a batch.
        Idempotent: a batch staged at enqueue is skipped at dispatch."""
        on = self._serve_staging
        if on is None:
            # an aggregation (no query runtime: None) takes the staged
            # batch as it is, and serves nothing
            subs = [_sub_runtime(q) for q in self.queries]
            on = self._serve_staging = \
                any(q is not None and q.serve_emit for q in subs) and \
                any(q is None or q.adopts_staged for q in subs)
        if on and self.app is not None and staged.dev is None:
            with _phases.phase(self.app.stats, self.sub_names(), "h2d",
                               bytes=_staged_nbytes(staged)):
                self.app._serve_stager.stage(staged, self.schema)

    def enqueue(self, tag: str, payload, now: int) -> None:
        q = self._async_q
        stats = self.app.stats if self.app is not None else None
        if tag == "staged":
            # @async accept-edge upload: paid here, not in
            # dispatch_staged's idempotent re-call
            self._serve_stage(payload)
        # ingest stamp taken BEFORE the queue put: the `<query>:e2e`
        # histogram must include @async queue wait, not start at dispatch
        t_in = time.perf_counter_ns() \
            if stats is not None and stats.enabled else None
        if q is None:          # raced with stop_async: process inline
            if tag == "staged":
                self.dispatch_staged(payload, now, ingest_ns=t_in)
            else:
                self.publish(payload, now, ingest_ns=t_in)
            return
        # the send's batch number rides the queue beside the stamp, so
        # the worker's spans carry it
        item = (tag, payload, now, t_in, _phases.current_batch())
        if self._async_policy == "shed":
            import queue as _queue
            try:
                q.put_nowait(item)
            except _queue.Full:
                self._shed_async(tag, payload)
            return
        q.put(item)

    def _shed_async(self, tag: str, payload) -> None:
        """@async(queue.policy='shed') full-queue drop: loud and counted
        (`async.<stream>.shed` counter -> siddhi_async_shed_total,
        sampler series, /healthz stream classification) — never a
        silent loss."""
        n = payload.n if tag == "staged" else len(payload)
        stats = self.app.stats if self.app is not None else None
        if stats is not None and stats.enabled:
            stats.counter_inc(f"async.{self.stream_id}.shed", n)
        t = time.monotonic()
        if t - self._async_shed_warn >= 10.0:   # rate-limited
            self._async_shed_warn = t
            import logging
            logging.getLogger("siddhi_tpu").warning(
                "@async queue for %r full: shed %d events "
                "(queue.policy='shed')", self.stream_id, n)

    def _drain_async(self) -> None:
        while True:
            tag, payload, now, t_in, batch = self._async_q.get()
            try:
                if tag == "stop":
                    return
                with _phases.batch_scope(batch):
                    if tag == "staged":
                        self.dispatch_staged(payload, now, ingest_ns=t_in)
                    else:
                        self.publish(payload, now, ingest_ns=t_in)
            except Exception:  # noqa: BLE001 — worker must survive
                import traceback
                traceback.print_exc()
            finally:
                self._async_q.task_done()

    def flush_async(self) -> None:
        if self._async_q is not None:
            self._async_q.join()

    def pending_async(self) -> int:
        return self._async_q.unfinished_tasks if self._async_q is not None \
            else 0

    def queue_depth(self) -> int:
        """Batches sitting in the @async ingress queue RIGHT NOW (0 for
        synchronous junctions).  Distinct from pending_async(): qsize
        excludes the batch a worker is currently processing, so this is
        the pure queue-wait backlog the sampler/healthz watch."""
        q = self._async_q
        try:
            return q.qsize() if q is not None else 0
        except Exception:  # noqa: BLE001 — metrics must not throw
            return 0

    def stop_async(self) -> None:
        """Drain remaining batches, then terminate the workers (clean
        shutdown keeps at-least-once delivery for accepted sends)."""
        if self._async_q is None:
            return
        self._async_q.join()
        for _ in self._async_workers:
            self._async_q.put(("stop", None, 0, None, 0))
        for t in self._async_workers:
            t.join(timeout=2.0)
        self._async_workers.clear()
        self._async_q = None

    def subscribe_query(self, q: QueryRuntime) -> None:
        self.queries.append(q)
        self._sub_names_memo = None

    def subscribe_callback(self, cb: Callable) -> None:
        self.stream_callbacks.append(cb)

    def _dispatch_one(self, q, staged: ev.StagedBatch, now: int,
                      stats, n: int, traced: bool,
                      ingest_ns=None) -> None:
        """One subscriber's processing, with per-query latency histogram
        and (at DETAIL with an active trace) a per-query span.
        `ingest_ns` (send-acceptance stamp) is stashed on the runtime
        UNDER the query lock so the emission path — however deferred
        (@pipeline deque, @fuse stack, @async drainer) — can close the
        `<query>:e2e` histogram against the right batch.  The stamp must
        land on the REAL runtime (`_sub_runtime`, same deref as
        _sub_name/_sub_lock) — _emit_output reads it from the runtime the
        emission belongs to, so stamping a `_Subscription` would silently
        drop e2e for every pattern/join query."""
        tgt = _sub_runtime(q)
        locked = _query_lock(tgt._qlock, self.stream_id) \
            if tgt is not None else _NULL_CM
        if stats is None:
            with locked:
                q.process_staged(staged, now)
            return
        qname = tgt.name if tgt is not None else self.stream_id
        t0 = time.perf_counter_ns()
        try:
            with (_tracing.span("query", query=qname) if traced
                  else _NULL_CM), locked:
                if tgt is not None:
                    tgt._ingest_ns = ingest_ns
                try:
                    q.process_staged(staged, now)
                finally:
                    # cleared so a later timer-driven emission can't
                    # close e2e against this batch's stamp
                    if tgt is not None:
                        tgt._ingest_ns = None
        finally:
            stats.query_latency(qname, n, time.perf_counter_ns() - t0)
            if tgt is not None and tgt._e2e_owed:
                # emission delivered inline during this dispatch: close
                # `<query>:e2e` here, after the step AND delivery — the
                # stamp predates t0, so e2e >= the step-latency sample
                tgt._e2e_owed = False
                if ingest_ns is not None:
                    stats.e2e_latency(qname,
                                      time.perf_counter_ns() - ingest_ns)

    def dispatch_staged(self, staged: ev.StagedBatch, now: int,
                        ingest_ns=None) -> None:
        """Run every subscribed query over a staged batch, serialized per
        QUERY (not per app) so queries on different streams — or workers of
        different streams — process concurrently."""
        self._serve_stage(staged)
        stats = self.app.stats if self.app is not None else None
        if stats is None or not stats.enabled:
            for q in self.queries:
                try:
                    self._dispatch_one(q, staged, now, None, 0, False)
                except Exception as exc:  # noqa: BLE001 — fault routing
                    self._handle_error_staged(staged, exc, now)
            return
        if ingest_ns is None:
            ingest_ns = time.perf_counter_ns()   # synchronous send path
        stats.stream_in(self.stream_id, staged.n)
        tr = stats.tracer.start(self.stream_id, staged.n) \
            if stats.detail else None
        if stats.detail:
            # reference: log4j TRACE at StreamJunction.sendEvent :147
            _trace_log.debug("junction %s: dispatching %d staged rows to "
                             "%d queries @ %d", self.stream_id, staged.n,
                             len(self.queries), now)
        j0 = time.perf_counter_ns()
        try:
            for q in self.queries:
                try:
                    self._dispatch_one(q, staged, now, stats, staged.n,
                                       tr is not None, ingest_ns)
                except Exception as exc:  # noqa: BLE001 — fault routing
                    self._handle_error_staged(staged, exc, now)
        finally:
            stats.junction_latency(self.stream_id,
                                   time.perf_counter_ns() - j0)
            if tr is not None:
                stats.tracer.finish(tr)

    def publish(self, events: List[ev.Event], now: int,
                ingest_ns=None) -> None:
        stats = self.app.stats if self.app is not None else None
        if stats is None or not stats.enabled:
            if self.stream_callbacks:
                with _phases.phase(stats, None, "sink"):
                    for cb in self.stream_callbacks:
                        cb(events)
            if self.queries:
                with _phases.phase(stats, self.sub_names(), "stage"):
                    staged = ev.pack_np(self.schema, events)
                self._serve_stage(staged)
                for q in self.queries:
                    try:
                        self._dispatch_one(q, staged, now, None, 0, False)
                    except Exception as exc:  # noqa: BLE001 — fault route
                        self._handle_error(events, exc, now)
            return
        if ingest_ns is None:
            ingest_ns = time.perf_counter_ns()   # synchronous send path
        stats.stream_in(self.stream_id, len(events))
        tr = stats.tracer.start(self.stream_id, len(events)) \
            if stats.detail else None
        if stats.detail:
            # reference: log4j TRACE at StreamJunction.sendEvent :147
            _trace_log.debug(
                "junction %s: dispatching %d events to %d queries @ %d",
                self.stream_id, len(events), len(self.queries), now)
        j0 = time.perf_counter_ns()
        try:
            if self.stream_callbacks:
                with _phases.phase(stats, None, "sink"):
                    for cb in self.stream_callbacks:
                        cb(events)
            if self.queries:
                # pack and upload walls charge to every subscriber, as
                # their e2e does (see phases.py)
                with _phases.phase(stats, self.sub_names(), "stage"):
                    staged = ev.pack_np(self.schema, events)
                self._serve_stage(staged)
                for q in self.queries:
                    try:
                        self._dispatch_one(q, staged, now, stats,
                                           len(events), tr is not None,
                                           ingest_ns)
                    except Exception as exc:  # noqa: BLE001 — fault route
                        self._handle_error(events, exc, now)
        finally:
            stats.junction_latency(self.stream_id,
                                   time.perf_counter_ns() - j0)
            if tr is not None:
                stats.tracer.finish(tr)

    def _handle_error(self, events, exc: Exception, now: int) -> None:
        import logging
        if self.on_error == "STREAM" and self.app is not None:
            fault_id = "!" + self.stream_id
            if fault_id in self.app.junctions:
                fault_events = [
                    ev.Event(e.timestamp, list(e.data) + [repr(exc)])
                    for e in events]
                self.app._route(fault_id, fault_events)
                return
        if self.on_error == "STORE" and self.app is not None:
            # @OnError(action='STORE'): capture the failed events for
            # inspection/replay (reference: ErrorStore.saveOnError)
            store = getattr(self.app, "error_store", None)
            if store is not None and events:
                store.store(self.stream_id, events, exc, origin="junction")
                return
        logging.getLogger("siddhi_tpu").error(
            "error processing %r events: %s", self.stream_id, exc)
        listener = getattr(self.app, "exception_listener", None)
        if listener is not None:
            listener(exc)

    def _handle_error_staged(self, staged: ev.StagedBatch, exc: Exception,
                             now: int) -> None:
        """Columnar-path twin of _handle_error: rows decode to host events
        only when a fault stream or the error store actually consumes
        them."""
        wants_events = (
            self.on_error == "STREAM" and self.app is not None and
            ("!" + self.stream_id) in self.app.junctions) or (
            self.on_error == "STORE" and
            getattr(self.app, "error_store", None) is not None)
        if wants_events:
            idx = np.nonzero(staged.valid)[0]
            events = []
            for i in idx.tolist():
                data = [self.schema.decode_value(t, c[i]) for t, c in
                        zip(self.schema.types, staged.cols)]
                events.append(ev.Event(int(staged.ts[i]), data))
            self._handle_error(events, exc, now)
            return
        self._handle_error([], exc, now)


class _PartitionPurger:
    """Idle partition-key GC (reference: @purge config,
    PartitionRuntimeImpl.java:120-147).

    Tracks the last event time per key slot across a partition's queries;
    keys idle past `idle.period` free their allocator slots and their state
    columns reset to initial values — slot capacity recycles instead of
    ratcheting up until CapacityExceededError."""

    def __init__(self, app, shared_alloc, runtimes, interval_ms: int,
                 idle_ms: int):
        self.app = app
        self.shared_alloc = shared_alloc
        self.runtimes = runtimes
        self.interval_ms = interval_ms
        self.idle_ms = idle_ms
        self._seen_shared = np.zeros(shared_alloc.capacity, np.int64)
        self._seen_q: Dict[int, np.ndarray] = {}
        self._init_cols: Dict[int, Tuple] = {}
        for qr in runtimes:
            if isinstance(qr, PatternQueryRuntime):
                qr._touch = self._make_touch(self._seen_shared)
                self._init_cols[id(qr)] = tuple(
                    jax.numpy.asarray(c)
                    for c in qr.planned.init_columns()[:3])
                continue
            if isinstance(qr, JoinQueryRuntime):
                # join runtimes feed no liveness hook: purging their group
                # allocator would judge ACTIVE slots idle and corrupt
                # aggregates; leave them out of the GC
                continue
            if qr.planned.pair_allocs:
                # distinctCount pair slots key on the group slot; recycling
                # group slots under them would corrupt refcounts
                import logging
                logging.getLogger("siddhi_tpu").warning(
                    "@purge skips query %s: distinctCount state is not "
                    "purgeable yet", qr.name)
                continue
            if qr.planned.keyed_window:
                # keyed-window runtimes share the partition key allocator
                qr._touch = self._make_touch(self._seen_shared)
            # per-query group-by allocator (keyed-window queries have BOTH:
            # the shared window-key axis and their own group slots)
            alloc = qr.planned.slot_allocator
            if alloc is not None:
                seen = np.zeros(alloc.capacity, np.int64)
                self._seen_q[id(qr)] = seen
                if qr.planned.keyed_window:
                    qr._touch_group = self._make_touch(seen)
                else:
                    qr._touch = self._make_touch(seen)
        app._scheduler.notify_at(
            app.timestamp_millis() + interval_ms, self)

    @staticmethod
    def _make_touch(seen: np.ndarray):
        cap = seen.shape[0]

        def touch(slots: np.ndarray, now: int) -> None:
            live = slots[(slots >= 0) & (slots < cap)]
            if live.size:
                seen[live] = now
        return touch

    @staticmethod
    def _idle_slots(alloc, seen: np.ndarray, now: int,
                    cutoff: int) -> np.ndarray:
        used = np.nonzero(alloc._used)[0]
        # slots never touched since this purger saw them (e.g. restored
        # from a snapshot) start aging NOW, not at epoch — else a restore
        # followed by one purge tick would wipe every restored key
        fresh = used[seen[used] == 0]
        if fresh.size:
            seen[fresh] = now
        return used[seen[used] < cutoff]

    def on_timer(self, now: int) -> None:
        cutoff = now - self.idle_ms
        # barrier over every runtime this purger mutates: state resets must
        # not interleave with their ingestion workers
        locks = [qr._qlock for qr in self.runtimes]
        with _acquire_all(locks):
            idle = self._idle_slots(self.shared_alloc, self._seen_shared,
                                    now, cutoff)
            if idle.size:
                self.shared_alloc.purge(idle.tolist())
                for qr in self.runtimes:
                    if isinstance(qr, PatternQueryRuntime):
                        self._reset_pattern_keys(qr, idle)
                    elif qr.planned.keyed_window:
                        self._reset_keyed_window(qr, idle)
            for qr in self.runtimes:
                if isinstance(qr, PatternQueryRuntime):
                    continue
                alloc = qr.planned.slot_allocator
                seen = self._seen_q.get(id(qr))
                if alloc is None or seen is None:
                    continue
                qidle = self._idle_slots(alloc, seen, now, cutoff)
                if qidle.size:
                    alloc.purge(qidle.tolist())
                    self._reset_selector_slots(qr, qidle)
        self.app._scheduler.notify_at(now + self.interval_ms, self)

    @staticmethod
    def _key_mask(idx: np.ndarray, capacity: int):
        from .shardsafe import key_mask
        return key_mask(idx, capacity)

    @staticmethod
    def _masked_fill(arr, mask, init, key_axis: int = 0):
        from .shardsafe import masked_fill
        return masked_fill(arr, mask, init, key_axis)

    def _reset_pattern_keys(self, qr, idx: np.ndarray) -> None:
        (*arrays, scalars), sel_state = qr.state   # b32, lo64, hi64
        router = qr.shard_router
        if router is not None:
            # the sharded path routes allocator slot s to state column
            # router.state_row(s) (keys round-robin over devices,
            # _process_sharded) — the reset must hit the same columns
            idx = router.state_row(idx)
        mask = self._key_mask(idx, arrays[0].shape[1])
        arrays = [self._masked_fill(a, mask, init, key_axis=1)
                  for a, init in zip(arrays, self._init_cols[id(qr)])]
        # selector accumulators (per-key sums etc.) key on the same shared
        # slots — same [K] axis, same mask: a recycled slot must NOT leak
        # the purged key's aggregates into whatever key comes next
        specs = qr.planned.selector_exec.bank.specs
        sel_state = tuple(
            a if s.slot_src is not None
            else self._masked_fill(a, mask, s.init)
            for a, s in zip(sel_state, specs))
        qr.state = ((*arrays, scalars), sel_state)
        if qr._dirty is not None:
            qr._dirty[idx] = True

    def _reset_selector_slots(self, qr, idx: np.ndarray) -> None:
        wstate, astate = qr.state
        specs = qr.planned.selector_exec.bank.specs
        router = _sharding.group_router_for(qr)
        if router is not None:
            # sharded plain step stores slot s at row router.state_row(s)
            idx = router.state_row(idx)
        # pair-indexed specs (distinctCount refcounts) live in a different
        # slot space; queries carrying them are excluded from purge at
        # registration, this guard is defense in depth
        astate = tuple(
            a if s.slot_src is not None
            else self._masked_fill(a, self._key_mask(idx, a.shape[0]),
                                   s.init)
            for a, s in zip(astate, specs))
        qr.state = (wstate, astate)

    def _reset_keyed_window(self, qr, idx: np.ndarray) -> None:
        wslab, astate = qr.state
        single = qr.planned.window.init_state()
        router = qr.shard_router
        if router is not None:
            # sharded slab stores key k at row router.state_row(k)
            idx = router.state_row(idx)
        mask = self._key_mask(idx, qr.planned.key_capacity)
        wslab = jax.tree.map(
            lambda s, i0: self._masked_fill(s, mask, i0),
            wslab, single)
        qr.state = (wslab, astate)


_BUCKET_PLANES: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
_IDENTITY_SEL: Dict[int, np.ndarray] = {}
_ZERO_SLOTS: Dict[int, np.ndarray] = {}


def _zero_slots(cap: int) -> np.ndarray:
    """[cap] all-zero int32 group-slot column, cached read-only per size —
    every send of every keyed stream allocated this afresh before (consumers
    only read it: device upload and purger liveness touch)."""
    z = _ZERO_SLOTS.get(cap)
    if z is None:
        z = np.zeros((cap,), np.int32)
        z.setflags(write=False)
        _ZERO_SLOTS[cap] = z
    return z


def _identity_sel(cap: int) -> np.ndarray:
    """[1, cap] arange selection for a full single-key bucket, cached
    read-only so repeat sends ship the identical (deduped) buffer."""
    s = _IDENTITY_SEL.get(cap)
    if s is None:
        s = np.arange(cap, dtype=np.int32)[None, :]
        s.setflags(write=False)
        _IDENTITY_SEL[cap] = s
    return s


def _is_identity_sel(sel: np.ndarray, B: int) -> bool:
    """Does the grouping `sel` [Kb, E] list the batch's rows 0 .. B-1 in
    order, each once (so nothing is padding)?  Two O(1) rejections, then
    one pass (~0.4 ms at 524,288)."""
    return (sel.size == B and int(sel[0, 0]) == 0 and
            int(sel[-1, -1]) == B - 1 and
            np.array_equal(sel.reshape(-1), _identity_sel(B)[0]))


def _group_columns(sel: np.ndarray, identity: bool, cols, ts_delta):
    """The staged columns and the ts delta in the per-key order of `sel`
    [Kb, E], each as the flat `[Kb * E]` buffer the grouped programs
    reshape (pattern_planner._jit_sequential): the values a device gather
    by the clipped `sel` gives, a padding cell carrying row 0's.  The
    delta is gathered as the narrow wire it is; the i64 timestamp forms
    on the device.  Where `sel` is the identity the grouped buffers ARE
    the staged ones: no copy."""
    if identity:
        return cols, ts_delta
    idx = sel.reshape(-1)
    return ([c.take(idx, mode="clip") for c in cols],
            ts_delta.take(idx, mode="clip"))


def _full_bucket_planes(cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """(all-true valid, all-zero kind) for a full bucket, cached read-only
    so repeat sends allocate nothing (every send is still a real H2D)."""
    ent = _BUCKET_PLANES.get(cap)
    if ent is None:
        valid = np.ones((cap,), np.bool_)
        valid.setflags(write=False)
        kind = np.zeros((cap,), np.int32)
        kind.setflags(write=False)
        ent = _BUCKET_PLANES[cap] = (valid, kind)
    return ent


class _EmissionDrainer:
    """Background thread pulling device outputs and delivering callbacks.
    Bounded queue gives backpressure (reference: Disruptor ring buffer
    capacity, @async(buffer.size)).

    Every device_get costs one fixed-latency host<->device sync
    REGARDLESS of payload size, so the drainer
    drains every queued output in ONE batched device_get — under load the
    fetch latency amortizes across batches instead of serializing them."""

    def __init__(self, capacity: int = 64):
        import queue
        self._q = queue.Queue(maxsize=capacity)
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="siddhi-drain")
        self._thread._siddhi_internal = True   # see StreamJunction workers
        self._stop = object()
        self._started = False

    def start(self):
        if not self._started:
            self._started = True
            self._thread.start()

    def enqueue(self, qr, out, now, wake=None, ingest_ns=None,
                trace=None):
        self.start()
        # start the D2H copy of everything the drainer will fetch NOW
        # (non-blocking): by the time the drainer's device_get runs, the
        # bytes are already on the host and the get costs ~0 instead of one
        # blocking transfer per drain cycle
        targets = (_header_of(out), wake)
        for leaf in jax.tree_util.tree_leaves(targets):
            fn = getattr(leaf, "copy_to_host_async", None)
            if fn is not None:
                try:
                    fn()
                except Exception:  # noqa: BLE001 — best-effort prefetch
                    pass
        self._q.put((qr, out, now, wake, ingest_ns, trace))

    def flush(self):
        self._q.join()

    def pending(self) -> int:
        """Outputs accepted but not yet delivered (public accessor for the
        buffered-emissions metric; safe on a never-started drainer)."""
        return self._q.unfinished_tasks

    def depth(self) -> int:
        """Outputs sitting in the drainer queue right now (qsize; excludes
        the item being delivered) — the siddhi_drainer_queue_depth gauge."""
        try:
            return self._q.qsize()
        except Exception:  # noqa: BLE001 — metrics must not throw
            return 0

    def stop(self):
        if self._started:
            self._q.join()

    def _run(self):
        import queue as queue_mod
        import traceback
        while True:
            items = [self._q.get()]
            while len(items) < 32:
                try:
                    items.append(self._q.get_nowait())
                except queue_mod.Empty:
                    break
            # one roundtrip for ALL queued outputs: pattern outs (len 6)
            # contribute only their 16-byte count header; plain outs are
            # window-capacity bounded and ship whole.  Latency
            # attribution: the batched fetch charges to every item it
            # served, and a later item's serialized wait behind its
            # predecessors' deliveries counts as queue residency — both
            # inside its e2e sample (see phases.py).  The drainer is one
            # app's, so one statistics manager; the span carries the
            # first item's batch
            st = items[0][0].app.stats
            try:
                with _phases.adopt(items[0][5]):
                    fetched = _phases.fetch(
                        st, tuple(it[0].name for it in items), "header", [
                            (_header_of(out), wake)
                            for _, out, _, wake, _, _ in items])
            except Exception:  # noqa: BLE001 — drainer must survive
                traceback.print_exc()
                fetched = [(None, None)] * len(items)
            loop_t0 = time.perf_counter_ns()
            for (qr, out, now, _, t_in, trace), (fetch_h, wake_h) in \
                    zip(items, fetched):
                try:
                    _phases.waited(st, qr.name, loop_t0)
                    if wake_h is not None:
                        qr._apply_wake(_earliest(wake_h))
                    if fetch_h is None:
                        continue
                    with _phases.adopt(trace):
                        _emit_fetched(qr, out, fetch_h, now, t_in)
                except Exception as exc:  # noqa: BLE001 — drainer survives
                    # route to the app error path (reference: the Disruptor
                    # ExceptionHandler) — MatchOverflowError and callback
                    # failures must reach the exception listener, not stderr
                    import logging
                    logging.getLogger("siddhi_tpu").error(
                        "async emission error in %s: %s", qr.name, exc)
                    listener = qr.app.exception_listener
                    if listener is not None:
                        try:
                            listener(exc)
                        except Exception:  # noqa: BLE001
                            traceback.print_exc()
                    else:
                        traceback.print_exc()
                finally:
                    self._q.task_done()


class _Scheduler:
    """Host timer thread injecting TIMER batches
    (reference: CORE/util/Scheduler.java:48).

    Two kinds of entry stand on the heap.  `notify_at` pushes a timer its
    target asked for and will ask for again when it fires (a trigger, a
    rate limiter, a purge): each fires at its own time.  `arm` keeps a
    query runtime's ONE wake-up — the earliest time its state next
    changes by the clock alone: re-arming replaces it, so a step that
    leaves live rows does not pile a timer on the last step's."""

    def __init__(self, app: "SiddhiAppRuntime"):
        self.app = app
        # (time, push order, target, is it the target's armed wake-up)
        self._heap: List[Tuple[int, int, Any, bool]] = []
        self._armed: Dict[Any, int] = {}
        self._cv = threading.Condition()
        self._counter = 0
        self._running = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        # since the app started: timer steps fired, wake-ups armed
        self.timer_steps = 0
        self.wakeups_armed = 0

    def start(self):
        if self.app.playback:
            return  # event-driven time: timers fire from _route drains
        self._running = True
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="siddhi-scheduler")
        self._thread._siddhi_internal = True   # see StreamJunction workers
        self._thread.start()

    def drain_playback(self, now: int) -> None:
        """Fire what is due at the event clock `now`, inside one
        `timer_drain` span (`fired`: the timer steps it ran).  A wake-up
        whose target's timer steps coalesce fires ONCE, at the clock —
        not once for every distinct time something of its state came
        due."""
        if self._draining or not self._heap or self._heap[0][0] > now:
            return
        self._draining = True
        try:
            with _phases.phase(self.app.stats, None, "timer_drain",
                               clock=now) as span:
                fired = 0
                while self._heap and self._heap[0][0] <= now:
                    ts, q, armed = self._pop()
                    self._fire(q, now if armed and q._timers_coalesce
                               else ts)
                    fired += 1
                span.set_metadata(fired=fired)
        finally:
            self._draining = False

    def pending(self) -> int:
        """Timers on the heap right now (the `siddhi_timers_pending`
        gauge): at most one a query runtime, plus the periodic ones."""
        return len(self._heap)

    def _pop(self):
        """(time, target, was it the target's armed wake-up) of the
        earliest entry, taken off the heap."""
        ts, _, q, armed = heapq.heappop(self._heap)
        if armed:
            del self._armed[q]
        return ts, q, armed

    def _fire(self, q, ts: int) -> None:
        """One timer step under the target's query lock, inside a `timer`
        span carrying the heap's depth.  Targets without a query lock get
        their own (NOT the app lock — a timer target holding the app lock
        while taking query locks downstream could deadlock against a
        worker emitting into a named window)."""
        # `q` is any timer target — a query runtime, or a trigger, a rate
        # limiter, an aggregation, the purger: the probes stay
        lk = getattr(q, "_qlock", None)
        if lk is None:
            lk = q.__dict__.setdefault("_qlock", threading.RLock())
        name = q.name if isinstance(q, _QueryRuntimeBase) \
            else getattr(q, "stream_id", "timer")
        self.timer_steps += 1
        with _phases.phase(self.app.stats, name, "timer",
                           pending=len(self._heap)), lk:
            q.on_timer(ts)

    def stop(self):
        with self._cv:
            self._running = False
            self._cv.notify_all()
        if self._thread:
            self._thread.join(timeout=2.0)

    def notify_at(self, ts: int, q) -> None:
        self._push(ts, q, False)

    def arm(self, ts: int, q, replace: bool) -> None:
        """`q`'s one wake-up is at `ts` (`_NO_WAKEUP_INT` or later: none).
        `replace` False keeps the earlier of the pending and the new."""
        with self._cv:
            old = self._armed.get(q)
            if old is not None:
                if old == ts or (not replace and old <= ts):
                    return
                del self._armed[q]
                self._heap = [e for e in self._heap
                              if not (e[3] and e[2] is q)]
                heapq.heapify(self._heap)
            if ts < _NO_WAKEUP_INT:
                self._armed[q] = ts
                self.wakeups_armed += 1
                self._push(ts, q, True)

    def _push(self, ts: int, q, armed: bool) -> None:
        with self._cv:
            self._counter += 1
            heapq.heappush(self._heap, (ts, self._counter, q, armed))
            self._cv.notify_all()

    def _run(self):
        while True:
            with self._cv:
                if not self._running:
                    return
                if not self._heap:
                    self._cv.wait(timeout=0.2)
                    continue
                ts = self._heap[0][0]
                now = self.app.timestamp_millis()
                if ts > now:
                    self._cv.wait(timeout=min((ts - now) / 1000.0, 0.2))
                    continue
                ts, q, _ = self._pop()
            try:
                # serialized against the target's ingestion workers
                self._fire(q, max(ts, self.app.timestamp_millis()))
            except Exception:  # noqa: BLE001 - scheduler must survive
                import traceback
                traceback.print_exc()


class SiddhiAppRuntime:
    """reference: CORE/SiddhiAppRuntimeImpl.java:99"""

    def __init__(self, app: SiddhiApp, manager: "SiddhiManager",
                 name: Optional[str] = None, mesh=None):
        self.app = app
        self.manager = manager
        self.mesh = mesh  # jax.sharding.Mesh with a 'shard' axis, or None
        self.name = name or app.name or "SiddhiApp"
        self.interner = manager.interner
        # system-wide properties + per-extension ConfigReaders; handed to the
        # planner so extensions can read config at compile time
        self.config_manager = manager.config_manager
        self.objects = ev.ObjectRegistry()
        self._lock = threading.RLock()
        # open => InputHandler sends flow; cleared by _quiesce so snapshots
        # can drain async queues without racing persistent producers
        # (reference: ThreadBarrier, CORE/util/ThreadBarrier.java:27)
        self._ingress_gate = threading.Event()
        self._ingress_gate.set()
        self._scheduler = _Scheduler(self)
        self._drainer = _EmissionDrainer()
        # device-resident serving loop (siddhi_tpu/serving): ring drainer
        # (thread lazy-starts on the first ring) + H2D staging pipeline
        from ..serving import (DoubleBufferedStager, ServingDrainer,
                               serving_config)
        self._serve_drainer = ServingDrainer(
            self, serving_config(self)["drain_interval_ms"])
        self._serve_stager = DoubleBufferedStager()
        # on-demand plan LRU: query string -> (parsed AST, OnDemandPlanMemo)
        self._ondemand_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._ondemand_cache_lock = threading.Lock()
        self._started = False
        # playback: event-driven time (reference: @app:playback,
        # CORE/util/timestamp/TimestampGeneratorImpl.java:118)
        pb = app.get_annotation("app:playback")
        self.playback = pb is not None
        self._playback_time = 0
        # @app:playback(idle.time='...', increment='...'): when the input
        # goes quiet for idle.time (wall clock), advance the event clock by
        # increment and fire the timers it passes, so time windows/patterns
        # still flush (reference: TimestampGeneratorImpl.java:118-140).
        self._playback_idle_ms: Optional[int] = None
        self._playback_increment_ms = 1000
        self._playback_last_wall = current_millis()
        self._idle_stop: Optional[threading.Event] = None
        self._idle_thread: Optional[threading.Thread] = None
        if pb is not None:
            from .aggregation import parse_time_ms
            it = pb.element("idle.time")
            if it is not None:
                self._playback_idle_ms = parse_time_ms(str(it))
                inc = pb.element("increment", "1 sec")
                self._playback_increment_ms = parse_time_ms(str(inc)) or 1000

        # statistics (reference: @app:statistics levels OFF/BASIC/DETAIL)
        from ..utils.statistics import OFF, StatisticsManager
        st_ann = app.get_annotation("app:statistics")
        level = OFF
        if st_ann is not None:
            v = st_ann.element() or st_ann.element("level") or "BASIC"
            level = str(v).upper()
            if level == "TRUE":
                level = "BASIC"
            elif level == "FALSE":
                level = OFF
        self.stats = StatisticsManager(
            level, include=str(st_ann.element("include", ""))
            if st_ann is not None else "")
        # @app:statistics(reporter='console', interval='5 sec') starts a
        # periodic reporter with the app (reference: startReporting :55)
        self._stats_reporter = None
        if st_ann is not None and \
                str(st_ann.element("reporter", "")).lower() == "console":
            from ..utils.statistics import ConsoleReporter
            from .aggregation import parse_time_ms
            iv = parse_time_ms(st_ann.element("interval", "5 sec")) or 5000
            self._stats_reporter = ConsoleReporter(self, iv / 1000.0)
        self.exception_listener = None
        # attached by debug(); built once the queries are planned (below)
        self._debugger = None
        self.admission = None
        self.merged_groups: Dict[str, object] = {}

        # error store: failed events captured by @OnError(action='STORE')
        # and @sink(on.error='store'), replayable via replay_errors()/
        # REST (reference: core.util.error.handler ErrorStore).  SPI:
        # assign a custom ErrorStore before start().
        from ..io.errorstore import InMemoryErrorStore
        es_ann = app.get_annotation("app:errorStore")
        self.error_store = InMemoryErrorStore(
            capacity=int(es_ann.element("capacity", 1024))
            if es_ann is not None else 1024)
        # snapshot revisions skipped as corrupt/unreadable during
        # restore_last_revision (siddhi_restore_fallbacks_total)
        self.restore_fallbacks = 0

        # schemas & junctions
        self.schemas: Dict[str, ev.Schema] = {}
        self.junctions: Dict[str, StreamJunction] = {}
        for sid, sdef in list(app.stream_definition_map.items()):
            self._define_stream_runtime(sdef)

        # tables (reference: CORE/table/InMemoryTable.java; @store tables
        # back onto a RecordTable SPI store, AbstractRecordTable.java:449)
        from .table import RecordTableRuntime, TableRuntime
        self.tables: Dict[str, TableRuntime] = {}
        for tid, tdef in app.table_definition_map.items():
            schema = ev.Schema(tdef, self.interner)
            store_ann = tdef.get_annotation("store")
            if store_ann is not None:
                from ..io.store import CacheTable, create_store
                stype = store_ann.element("type")
                if stype is None:
                    raise CompileError(
                        f"@store on table {tid!r} needs a type element")
                props = {k: v for k, v in store_ann.named_elements().items()
                         if k != "type"}
                reader = self.config_manager.generate_config_reader(
                    "store", str(stype))
                store = create_store(str(stype), tdef, schema, props, reader)
                cache = None
                for sub in store_ann.annotations:
                    if sub.name.lower() == "cache":
                        pk = tdef.get_annotation("PrimaryKey")
                        kpos = [schema.position(v)
                                for v in pk.positional_elements()] if pk else \
                            list(range(len(schema.names)))
                        cache = CacheTable(
                            store, kpos,
                            max_size=int(sub.element("size",
                                                     sub.element("max.size",
                                                                 10))),
                            policy=str(sub.element("policy",
                                                   sub.element("cache.policy",
                                                               "FIFO"))))
                self.tables[tid] = RecordTableRuntime(
                    tdef, schema, store, self.interner, cache=cache)
            else:
                self.tables[tid] = TableRuntime(tdef, schema)

        # named windows (reference: CORE/window/Window.java:65)
        self.named_windows: Dict[str, NamedWindowRuntime] = {}
        for wid, wdef in getattr(app, "window_definition_map", {}).items():
            schema = ev.Schema(wdef, self.interner)
            self.schemas[wid] = schema
            self.named_windows[wid] = NamedWindowRuntime(wdef, schema, self)

        # incremental aggregations (reference: CORE/aggregation/*)
        from .aggregation import AggregationRuntime
        self.aggregations: Dict[str, AggregationRuntime] = {}
        for aid, adef in app.aggregation_definition_map.items():
            agg = AggregationRuntime(adef, self)
            self.aggregations[aid] = agg
            self.junctions[agg.input_stream_id].subscribe_query(
                _Subscription(agg, locks=False))
            if agg.purge_enabled or agg._store_tables:
                # periodic retention purge + store write-through
                # (reference: IncrementalDataPurger scheduled executor)
                self._scheduler.notify_at(
                    self.timestamp_millis() + agg.purge_interval_ms, agg)

        # triggers define a stream `<id> (triggered_time long)` (reference:
        # QAPI/definition/TriggerDefinition -> DefinitionParserHelper)
        self.triggers: Dict[str, TriggerRuntime] = {}
        for tid, tdef in app.trigger_definition_map.items():
            if tid not in self.schemas:
                sdef = StreamDefinition(tid).attribute(
                    "triggered_time", "LONG")
                app.stream_definition_map[tid] = sdef
                self._define_stream_runtime(sdef)
            self.triggers[tid] = TriggerRuntime(tdef, self)

        # sources & sinks from @source/@sink stream annotations (reference:
        # DefinitionParserHelper.addEventSource/addEventSink)
        from ..io.sink import SinkRuntime
        from ..io.source import SourceRuntime
        self.sources: List[SourceRuntime] = []
        self.sinks: List[SinkRuntime] = []
        for sid, sdef in list(app.stream_definition_map.items()):
            for ann in sdef.annotations:
                n = ann.name.lower()
                if n == "source":
                    self.sources.append(SourceRuntime(sid, ann, self))
                elif n == "sink":
                    sk = SinkRuntime(sid, ann, self)
                    self.sinks.append(sk)
                    self.junctions[sid].subscribe_callback(sk)

        # plan queries
        self.query_runtimes: Dict[str, QueryRuntime] = {}
        self._timed_limiters: List = []
        self._partition_purgers: List[_PartitionPurger] = []
        qi = 0
        for element in app.execution_element_list:
            if isinstance(element, Query):
                qname = self._query_name(element, qi)
                qi += 1
                self._add_query(element, qname)
            elif isinstance(element, Partition):
                qi = self._add_partition(element, qi)

        # whole-app multi-query optimizer (siddhi_tpu/optimizer): merge
        # co-resident queries on one junction into shared dispatches.
        # Runs AFTER per-query planning (it stacks the planned step
        # bodies) and BEFORE admission registration (merged owners get
        # compile-gate labels too).
        self._merge_reasons: Dict[str, str] = {}
        from ..optimizer import apply_merge
        apply_merge(self)

        # admission control: per-app quotas + overload ladder
        # (core/admission.py).  Registered with the shared CompileGate
        # HERE (not start()) — the first trace can happen before start()
        # via a direct process call or EXPLAIN deep mode.
        from .admission import AdmissionController
        self.admission = AdmissionController(self)
        self.admission.register_owners(
            self.stats._owners_of(self) or [])

    # -- construction ---------------------------------------------------------
    def _define_stream_runtime(self, sdef: StreamDefinition):
        schema = ev.Schema(sdef, self.interner, objects=None)
        self.schemas[sdef.id] = schema
        on_error = "LOG"
        ann = sdef.get_annotation("OnError")
        if ann is not None:
            on_error = (ann.element("action") or "LOG").upper()
        self.junctions[sdef.id] = StreamJunction(
            schema, stream_id=sdef.id, on_error=on_error, app=self)
        if on_error == "STREAM" and not sdef.id.startswith("!"):
            self._ensure_fault_stream(sdef.id)

    def _ensure_fault_stream(self, stream_id: str) -> None:
        """Auto-define the `!stream` fault stream: original attrs +
        `_error` (reference: FaultStreamEventConverter).  Used by
        @OnError(action='STREAM') and @sink(on.error='stream') — both
        route failures into the same junction."""
        fault_id = "!" + stream_id
        if fault_id in self.junctions or stream_id.startswith("!"):
            return
        sdef = self.app.stream_definition_map[stream_id]
        fdef = StreamDefinition(fault_id)
        for a in sdef.attribute_list:
            fdef.attribute(a.name, a.type)
        fdef.attribute("_error", "STRING")
        self.app.stream_definition_map[fdef.id] = fdef
        self._define_stream_runtime(fdef)

    def _query_name(self, q: Query, i: int) -> str:
        info = q.get_annotation("info")
        if info:
            n = info.element("name")
            if n:
                return n
        return f"query{i + 1}"

    def _add_query(self, q: Query, name: str):
        from ..query_api.query import JoinInputStream, StateInputStream
        if isinstance(q.input_stream, JoinInputStream):
            self._add_join_query(q, name)
            return
        if isinstance(q.input_stream, StateInputStream):
            from .pattern_planner import plan_pattern_query
            import functools
            # @capacity(slots='N') bounds the pending-state slab for
            # non-partitioned patterns too (the reference's pending list is
            # unbounded, StreamPreStateProcessor.java:80; P is our bound)
            nfa_slots = 8
            cap_ann = q.get_annotation("capacity")
            if cap_ann is not None:
                nfa_slots = int(cap_ann.element("slots", nfa_slots))
            plan = functools.partial(
                plan_pattern_query, q, name, self.schemas, self.interner,
                slots=nfa_slots,
                script_functions=self.app.function_definition_map)
            planned = plan()
            self._validate_in_deps(planned.exec.in_deps, name)
            runtime = PatternQueryRuntime(planned, self)
            # the SAME partial replans on emission-cap growth: initial plan
            # and regrow can never drift apart
            runtime._replan = lambda cap, _p=plan: _p(
                compact_rows_override=cap)
            self._register(runtime, q, name)
            for sid in planned.spec.stream_ids:
                self.junctions[sid].subscribe_query(
                    _Subscription(runtime, sid))
            self._wire_output(runtime, q, planned, name)
            return
        in_sid = q.input_stream.unique_stream_id
        from_window = in_sid in self.named_windows
        # @capacity(window='N') bounds the window state slab for this query
        wch, wch_set = 2048, False
        cap_ann = q.get_annotation("capacity")
        if cap_ann is not None and cap_ann.element("window"):
            wch, wch_set = int(cap_ann.element("window")), True
        # session(gap, key) runs the keyed-window slab outside partitions:
        # per-key batch slices are small (E rows), so the per-key window
        # capacity and the batch capacity shrink like the partition path's
        from ..query_api.query import Window as _Win
        skeyed = any(
            isinstance(h, _Win) and h.name == "session" and
            len(h.parameters) >= 2
            for h in getattr(q.input_stream, "stream_handlers", []))
        kw = dict(window_capacity_hint=wch)
        if skeyed:
            kcap = 4096
            if cap_ann is not None and cap_ann.element("keys"):
                kcap = int(cap_ann.element("keys"))
            if self.mesh is not None:
                n = self.mesh.devices.size
                kcap = ((kcap + n - 1) // n) * n
            kw = dict(
                batch_capacity=64,
                window_capacity_hint=wch if wch_set else 128,
                window_key_allocator=SlotAllocator(
                    kcap, name=f"{name}:sessionkey"),
                key_capacity=kcap, mesh=self.mesh)
        planned = plan_single_query(
            q, name, self.app.stream_definition_map, self.schemas,
            self.interner, named_window_input=from_window,
            config_manager=self.config_manager,
            script_functions=self.app.function_definition_map,
            **kw)
        self._validate_in_deps(planned.in_deps, name)
        runtime = QueryRuntime(planned, self)
        self._register(runtime, q, name)
        if from_window:
            self.named_windows[in_sid].subscribers.append(runtime)
        else:
            self.junctions[planned.input_stream_id].subscribe_query(runtime)
        self._wire_output(runtime, q, planned, name)

    def _attach_rate_limiter(self, q: Query, runtime) -> None:
        """`output [all|first|last] every ... | snapshot every t` (reference:
        OutputParser.constructOutputRateLimiter, OutputParser.java:282)."""
        from .ratelimit import create_rate_limiter
        if q.output_rate is None:
            return
        group_positions = None
        if q.selector.group_by_list:
            # positions of projected group-by attributes in the OUTPUT row
            # (the GroupBy limiter variants key on them; reference:
            # ratelimit/event/FirstGroupByPerEventOutputRateLimiter etc.)
            from ..query_api.expression import Variable as V

            def _matches(oa_expr) -> bool:
                # match qualified group-by vars by (stream, attr) so a
                # same-named attribute from another join side cannot
                # satisfy the check
                if not isinstance(oa_expr, V):
                    return False
                for v in q.selector.group_by_list:
                    if v.attribute_name != oa_expr.attribute_name:
                        continue
                    if v.stream_id is None or oa_expr.stream_id is None \
                            or v.stream_id == oa_expr.stream_id:
                        return True
                return False
            group_positions = [
                i for i, oa in enumerate(q.selector.selection_list)
                if _matches(oa.expression)] or None
            if group_positions is None and \
                    q.output_rate.behavior in ("FIRST", "LAST"):
                # the grouped limiter keys on the group attrs in the OUTPUT
                # row; without them it would silently degrade to ungrouped
                # first/last (reference keys on the internal group key)
                raise CompileError(
                    f"output {q.output_rate.behavior.lower()} with group "
                    f"by requires projecting the group-by attribute(s) in "
                    f"the select clause")
        lim = create_rate_limiter(
            q.output_rate,
            lambda pairs, now, _rt=runtime: _deliver_pairs(_rt, pairs, now),
            group_positions)
        runtime.rate_limiter = lim
        if lim is not None and lim.needs_timer:
            lim._schedule = lambda ts, _l=lim: \
                self._scheduler.notify_at(ts, _l)
            self._timed_limiters.append(lim)

    def _wire_output(self, runtime, q: Query, planned, name: str):
        """Route query output: stream (define if missing), table op, or
        window insert."""
        self._attach_rate_limiter(q, runtime)
        from ..query_api.query import (
            DeleteStream,
            UpdateOrInsertStream,
            UpdateStream,
        )
        tgt = planned.output_target
        out_stream = q.output_stream
        if tgt and tgt in self.tables:
            table = self.tables[tgt]
            out_key = "__out__"
            scope_schema = planned.out_schema
            if isinstance(out_stream, (DeleteStream, UpdateStream,
                                       UpdateOrInsertStream)):
                cond_expr = (out_stream.on_delete_expression
                             if isinstance(out_stream, DeleteStream)
                             else out_stream.on_update_expression)
                from .executor import Scope, compile_expression
                scope = Scope()
                scope.interner = self.interner
                scope.add_source(out_key, scope_schema)
                # table attrs must be qualified (T.attr); unqualified names
                # resolve to the query output side, as in the reference
                scope.add_source(tgt, table.schema, default=False)
                cond = table.plan_condition(cond_expr, scope)
                set_fns = []
                us = getattr(out_stream, "update_set", None)
                if us is None and not isinstance(out_stream, DeleteStream):
                    # default set: overwrite all same-named columns
                    for n in table.schema.names:
                        if n in scope_schema.names:
                            from ..query_api.expression import Variable as V
                            e = compile_expression(V(n, stream_id=out_key),
                                                   scope)
                            set_fns.append((table.schema.position(n), e.fn))
                elif us is not None:
                    for sa in us.set_attribute_list:
                        pos = table.schema.position(
                            sa.table_variable.attribute_name)
                        e = compile_expression(sa.value_expression, scope)
                        set_fns.append((pos, e.fn))
                op = ("delete" if isinstance(out_stream, DeleteStream) else
                      "upsert" if isinstance(out_stream, UpdateOrInsertStream)
                      else "update")
                runtime.table_op = (op, table, cond, set_fns, out_key)
            else:
                if len(table.schema.names) != len(planned.out_schema.names):
                    raise CompileError(
                        f"query {name!r} output arity does not match table "
                        f"{tgt!r}")
                runtime.table_op = ("insert", table, None, [], out_key)
            return
        self._define_output_for(planned, name)

    def _add_join_query(self, q: Query, name: str):
        import functools
        from .join import plan_join_query
        # @capacity(window='N') bounds each side's window slab; `window.left`
        # / `window.right` a side of its own; `keys` the distinct join keys
        # both may hold together (the key-slot allocator and the rings' head
        # tables; default: every row its own key)
        from .plan_facts import capacity_annotation, join_window_hints
        caps = capacity_annotation(q, None)
        plan = functools.partial(
            plan_join_query, q, name, self.schemas, self.tables,
            self.interner, aggregations=self.aggregations,
            named_windows=self.named_windows, mesh=self.mesh,
            window_caps=join_window_hints(caps, None),
            key_capacity=caps.get("keys"))
        planned = plan()
        runtime = JoinQueryRuntime(planned, self)

        # the SAME partial replans on emission-cap growth AND equi-join
        # lane growth; the runtime's current lane width always rides
        # along so one growth can never silently reset the other
        def _join_replan(rows=None, _p=plan, _rt=runtime, **kw):
            if _rt._lane_k:
                kw.setdefault("lane_k_override", _rt._lane_k)
            return _p(emit_rows_override=rows, **kw)
        runtime._replan = _join_replan
        self._register(runtime, q, name)
        for side, is_left in ((planned.left, True), (planned.right, False)):
            if not side.is_table:
                self.junctions[side.stream_id].subscribe_query(
                    _Subscription(runtime, is_left))
            elif side.is_named_window and (
                    planned.step_left if is_left else
                    planned.step_right) is not None:
                # bidirectional named-window join: events flowing through
                # the shared window trigger the join side too (reference:
                # Window.java:145-184 publishes to subscribing queries)
                self.named_windows[side.stream_id].subscribers.append(
                    _Subscription(runtime, is_left))
        self._wire_output(runtime, q, planned, name)

    def _serve_enabled(self, q) -> bool:
        """Device-resident serving loop (siddhi_tpu/serving): emissions
        append to an on-device ring (dispatch-only send path) and the
        per-app drainer thread delivers them asynchronously.  Enabled by
        @serve on the query / any input stream / @app:serve
        (plan_facts.serve_enabled — the one implementation, shared with
        the merge planner and lint) or app-wide by the `serving.enabled`
        config property; @serve(enabled='false') opts a query out of
        either blanket.  Takes precedence over @async/@pipeline in
        _emit_output; timer-bearing queries fall back to inline
        delivery there (same exclusion @pipeline has)."""
        from .plan_facts import serve_enabled
        if serve_enabled(self.app, q):
            return True
        # any explicit @serve annotation that did NOT enable is an
        # opt-out — the config blanket must not override it
        if q.get_annotation("serve") is not None or \
                self.app.get_annotation("app:serve") is not None:
            return False
        from ..serving import serving_config
        return bool(serving_config(self)["enabled"])

    def _register(self, runtime, q: Query, name: str) -> None:
        """The ONE wiring block every query runtime passes through with
        its AST: the delivery mode, the @fuse stack, the app's name for
        it.  The annotations are read by plan_facts (one implementation,
        shared with the merge planner and lint); the emission hot path
        reads the attributes set here.

        @async: on the app, the query, or any input stream definition
        (reference: StreamJunction.startProcessing :276-313).
        @pipeline(depth='k'): deferred emission, so host staging of batch
        N+1 overlaps the device step of batch N with no extra thread;
        depth 1 delivers each send's predecessor, depth k lets emissions
        lag up to k sends and drains them in batched device_gets.  The
        WHOLE delivery lags until flush(): callbacks, table writes,
        downstream inserts — a reader query in the same app sees this
        query's effects up to k batches behind (the relaxation @async
        makes, minus the thread).  @serve: see `_serve_enabled`.
        @fuse(batches='K'): K staged micro-batches run as ONE lax.scan
        dispatch (core/fusion.py); composes with the delivery modes
        (per-batch emissions re-enter them) and @emit.  Timer-bearing
        queries are excluded from all of them where they are used."""
        from . import plan_facts
        runtime._query_ast = q
        runtime.async_emit = plan_facts.async_enabled(self.app, q)
        runtime.pipeline_emit = plan_facts.pipeline_depth(self.app, q)
        runtime.serve_emit = self._serve_enabled(q)
        if runtime.serve_emit:
            runtime.serve_ring_capacity = \
                plan_facts.serve_ring_capacity(self.app, q)
        self.query_runtimes[name] = runtime
        k = plan_facts.fuse_depth(self.app, q)
        if k <= 0:
            return
        runtime._fuse_requested = k
        why = _fusion.ineligible_reason(runtime, runtime._kind)
        if why is not None:
            # kept for explain(): the concrete reason @fuse skipped this
            # query, not just a log line that scrolled away
            runtime._fuse_excluded = why
            logging.getLogger("siddhi_tpu").warning(
                "@fuse(batches=%d) ignored on query %s: %s", k,
                runtime.name, why)
            return
        runtime._fuse = _fusion.FuseBuffer(runtime, k, runtime._kind)

    def _add_partition(self, part: Partition, qi: int) -> int:
        """Partitions: key-scoped state clones (reference:
        CORE/partition/PartitionRuntimeImpl.java:75).  Here the partition key
        becomes an explicit key axis: pattern queries get per-key NFA slabs,
        aggregations compose the partition key into their group key."""
        from ..query_api.query import (
            JoinInputStream,
            RangePartitionType,
            StateInputStream,
            ValuePartitionType,
        )
        from ..query_api.expression import Variable as V
        from .pattern_planner import plan_pattern_query

        # partition key attribute position per stream (value partitions) or
        # a derived-key fn (range partitions: first matching range's label,
        # reference: RangePartitionExecutor.java:45; non-matching rows drop)
        positions: Dict[str, List[int]] = {}
        key_fns: Dict[str, Callable] = {}
        for sid, pt in part.partition_type_map.items():
            schema = self.schemas.get(sid)
            if schema is None:
                raise CompileError(f"undefined partitioned stream {sid!r}")
            if isinstance(pt, RangePartitionType):
                from .executor import Scope, compile_expression
                scope = Scope()
                scope.interner = self.interner
                scope.add_source(sid, schema)
                conds = []
                for rp in pt.ranges:
                    c = compile_expression(rp.condition, scope)
                    if c.type != "BOOL":
                        raise CompileError(
                            "range partition conditions must be boolean")
                    conds.append((self.interner.intern(rp.partition_key),
                                  c))

                def make_fn(sid=sid, conds=conds):
                    def fn(staged):
                        env = {sid: tuple(staged.cols),
                               "__ts__": staged.ts, "__now__": staged.ts}
                        ids = np.full(staged.ts.shape[0], -1, np.int32)
                        for label, c in conds:
                            m = np.asarray(c.fn(env)).astype(bool)
                            ids = np.where((ids < 0) & m, label, ids)
                        return [ids], ids >= 0
                    return fn
                key_fns[sid] = make_fn()
                positions[sid] = []
                continue
            assert isinstance(pt, ValuePartitionType)
            if not isinstance(pt.expression, V):
                raise CompileError(
                    "partition-by expression must be a plain attribute in "
                    "this build")
            positions[sid] = [schema.position(pt.expression.attribute_name)]

        # capacity annotation: @capacity(keys='..', slots='..') on the
        # partition or any of its queries
        keys_cap, nfa_slots = 4096, 8
        # per-key window slab rows for windows inside the partition (small
        # default: the slab is keys x window-capacity)
        win_cap = 128
        all_anns = list(part.annotations)
        for q in part.query_list:
            all_anns.extend(q.annotations)
        for ann in all_anns:
            if ann.name.lower() == "capacity":
                keys_cap = int(ann.element("keys", keys_cap))
                nfa_slots = int(ann.element("slots", nfa_slots))
                win_cap = int(ann.element("window", win_cap))
        if self.mesh is not None:
            n = self.mesh.devices.size
            keys_cap = ((keys_cap + n - 1) // n) * n

        shared_allocator = SlotAllocator(keys_cap, name="partition")
        part_runtimes: List = []

        for q in part.query_list:
            qname = self._query_name(q, qi)
            qi += 1
            if isinstance(q.input_stream, StateInputStream):
                spec_streams = q.input_stream.all_stream_ids
                ppos = {}
                pfns = {}
                for sid in spec_streams:
                    if sid not in positions:
                        raise CompileError(
                            f"pattern stream {sid!r} has no partition key")
                    ppos[sid] = positions[sid]
                    if sid in key_fns:
                        pfns[sid] = key_fns[sid]
                import functools
                plan = functools.partial(
                    plan_pattern_query, q, qname, self.schemas,
                    self.interner, key_capacity=keys_cap, slots=nfa_slots,
                    partition_positions=ppos,
                    partition_key_fns=pfns or None, mesh=self.mesh,
                    script_functions=self.app.function_definition_map)
                planned = plan()
                self._validate_in_deps(planned.exec.in_deps, qname)
                runtime = PatternQueryRuntime(planned, self,
                                              slot_allocator=shared_allocator)
                # same partial => initial plan and regrow cannot drift
                runtime._replan = lambda cap, _p=plan: _p(
                    compact_rows_override=cap)
                self._register(runtime, q, qname)
                part_runtimes.append(runtime)
                for sid in planned.spec.stream_ids:
                    self.junctions[sid].subscribe_query(
                        _Subscription(runtime, sid))
                self._attach_rate_limiter(q, runtime)
                self._define_output_for(planned, qname)
            elif isinstance(q.input_stream, JoinInputStream):
                # partitioned join: lower to a plain join whose `on`
                # condition additionally requires equal partition keys on
                # both sides — only same-key rows match, the partition
                # isolation semantics of the reference's per-key clone
                # (PartitionParser.java:137).  NOTE: join-side window
                # CAPACITY is shared across keys here (tune @capacity),
                # unlike the reference's per-key window instances.
                jis = q.input_stream
                lsis, rsis = jis.left_input_stream, jis.right_input_stream
                lsid = lsis.unique_stream_id
                rsid = rsis.unique_stream_id
                if lsid in key_fns or rsid in key_fns:
                    raise CompileError(
                        "range-partitioned joins are not supported")
                from ..query_api.expression import Expression as E
                sides = []
                for sis, ssid in ((lsis, lsid), (rsis, rsid)):
                    if ssid in self.tables or \
                            ssid in self.named_windows or \
                            ssid in self.aggregations:
                        continue        # shared collections: no key column
                    pos = positions.get(ssid)
                    if not pos:
                        # mirror the single-stream branch: a plain stream
                        # side without a partition key would silently join
                        # across partitions
                        raise CompileError(
                            f"stream {ssid!r} has no partition key")
                    schema = self.schemas[ssid]
                    ref = sis.stream_reference_id or ssid
                    sides.append(E.variable(
                        schema.names[pos[0]]).of_stream(ref))
                if len(sides) == 2:
                    eq = E.compare(sides[0], "==", sides[1])
                    jis.on_compare = E.and_(jis.on_compare, eq) \
                        if jis.on_compare is not None else eq
                self._add_join_query(q, qname)
                part_runtimes.append(self.query_runtimes[qname])
                continue
            else:
                ist = q.input_stream
                if not isinstance(ist, SingleInputStream):
                    raise CompileError(
                        "only single-stream, pattern and join queries are "
                        "supported inside partitions")
                sid = ist.unique_stream_id
                ppos = positions.get(sid)
                if ppos is None and not ist.is_inner_stream:
                    raise CompileError(
                        f"stream {sid!r} has no partition key")
                from ..query_api.query import Window as _QWindow
                has_window = any(isinstance(h, _QWindow)
                                 for h in ist.stream_handlers)
                planned = plan_single_query(
                    q, qname, self.app.stream_definition_map, self.schemas,
                    self.interner, group_slots=max(keys_cap, 4096),
                    # keyed windows see per-key E-row batches, so their
                    # window shapes key off a small batch capacity; the
                    # flat (no-window) path keeps the full default
                    batch_capacity=64 if has_window else 512,
                    window_capacity_hint=win_cap,
                    partition_positions=ppos,
                    partition_key_fn=key_fns.get(sid),
                    window_key_allocator=shared_allocator,
                    key_capacity=keys_cap,
                    config_manager=self.config_manager,
                    script_functions=self.app.function_definition_map,
                    mesh=self.mesh)
                self._validate_in_deps(planned.in_deps, qname)
                runtime = QueryRuntime(planned, self)
                self._register(runtime, q, qname)
                part_runtimes.append(runtime)
                self.junctions[sid].subscribe_query(runtime)
                self._attach_rate_limiter(q, runtime)
                self._define_output_for(planned, qname)

        # @purge(enable, interval='1 sec', idle.period='10 min'): idle-key
        # GC recycling slots through the allocators (reference:
        # PartitionRuntimeImpl.java:120-147).  Accepted on the partition or
        # any of its queries.
        for ann in all_anns:
            if ann.name.lower() == "purge":
                enabled = str(ann.element("enable", "true")).lower() == "true"
                if not enabled:
                    break
                from ..core.aggregation import parse_time_ms
                interval = parse_time_ms(
                    ann.element("interval", "1 sec")) or 1000
                idle = parse_time_ms(
                    ann.element("idle.period", "5 min")) or 300_000
                purger = _PartitionPurger(
                    self, shared_allocator, part_runtimes, interval, idle)
                self._partition_purgers.append(purger)
                break
        return qi

    def _define_output_for(self, planned, name: str):
        # define the output stream if missing
        tgt = planned.output_target
        if tgt and tgt in self.named_windows:
            nw = self.named_windows[tgt]
            if len(nw.schema.names) != len(planned.out_schema.names):
                raise CompileError(
                    f"query {name!r} output arity does not match window "
                    f"{tgt!r}")
            return
        if tgt and tgt not in self.junctions:
            sdef = StreamDefinition(tgt)
            for a in planned.out_schema.definition.attribute_list:
                sdef.attribute(a.name, a.type)
            self.app.stream_definition_map[tgt] = sdef
            self._define_stream_runtime(sdef)
        elif tgt:
            # validate compatibility
            tdef = self.app.stream_definition_map.get(tgt)
            if tdef is not None and len(tdef.attribute_list) != len(
                    planned.out_schema.names):
                raise CompileError(
                    f"query {name!r} output arity does not match stream {tgt!r}")

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> None:
        if not self._started:
            self._scheduler.start()
            self._started = True
            now = self.timestamp_millis()
            # @async(buffer.size, workers) streams get an ingress queue +
            # workers (reference: Disruptor ring per junction).  Playback
            # keeps synchronous dispatch: event-time must stay ordered.
            if not self.playback:
                for sid, j in self.junctions.items():
                    sdef = self.app.stream_definition_map.get(sid)
                    ann = sdef.get_annotation("async") \
                        if sdef is not None else None
                    if ann is not None:
                        j.enable_async(
                            int(ann.element("buffer.size", 256) or 256),
                            int(ann.element("workers", 1) or 1),
                            str(ann.element("queue.policy", "block")
                                or "block").lower())
            for sk in self.sinks:
                sk.start()
            for src in self.sources:
                src.start()
            for tr in self.triggers.values():
                tr.start(now)
            for lim in self._timed_limiters:
                self._scheduler.notify_at(now + lim.interval, lim)
            if self._stats_reporter is not None:
                self._stats_reporter.start()
            if self.playback and self._playback_idle_ms:
                self._playback_last_wall = current_millis()
                self._idle_stop = threading.Event()
                self._idle_thread = threading.Thread(
                    target=self._run_playback_idle, daemon=True,
                    name="siddhi-playback-idle")
                self._idle_thread._siddhi_internal = True
                self._idle_thread.start()

    def _run_playback_idle(self) -> None:
        """Quiet-input clock advance for @app:playback(idle.time, increment)
        (reference: TimestampGeneratorImpl.java:118-140: a periodic task
        checks wall-clock idleness and bumps the event clock)."""
        idle_s = self._playback_idle_ms / 1000.0
        while not self._idle_stop.wait(idle_s):
            if current_millis() - self._playback_last_wall \
                    < self._playback_idle_ms:
                continue
            with self._lock:
                self._playback_time += self._playback_increment_ms
                self._scheduler.drain_playback(self._playback_time)

    def shutdown(self) -> None:
        if self._started:
            for src in self.sources:
                src.stop()
            if self._stats_reporter is not None:
                self._stats_reporter.stop()
            if self._idle_stop is not None:
                self._idle_stop.set()
                if self._idle_thread is not None:
                    self._idle_thread.join(timeout=2.0)
            for j in self.junctions.values():
                j.stop_async()       # drain accepted sends, stop workers
            for qr in self._step_runtimes():
                # buffered @fuse stacks (per-query AND merged-group) and
                # held @pipeline emissions deliver before teardown: an
                # accepted send's output must not vanish (at-least-once)
                _fusion.drain(qr)
                _drain_pending_emit(qr)
            # serving rings drain BEFORE sinks stop: fuse/pipeline drains
            # above may have appended, and an accepted send's output must
            # not die in device memory (at-least-once)
            self._serve_drainer.stop()
            for sk in self.sinks:
                sk.stop()
            self._drainer.stop()
            self._scheduler.stop()
            self._started = False
        # release this app's compile-gate owner labels whether or not it
        # ever started (deploy-then-undeploy without traffic is common)
        if self.admission is not None:
            self.admission.unregister()

    def pause_sources(self) -> None:
        """reference: SiddhiAppRuntimeImpl pauses Sources around persist."""
        for src in self.sources:
            src.pause()

    def resume_sources(self) -> None:
        for src in self.sources:
            src.resume()

    def flush(self) -> None:
        """Wait until all asynchronously ingested batches are processed and
        all asynchronously emitted output has been delivered.  Iterates to
        a fixpoint: drained output may re-enter another @async stream."""
        for _ in range(64):
            for j in self.junctions.values():
                j.flush_async()
            for qr in self._step_runtimes():
                _fusion.drain(qr)   # partial @fuse stacks process NOW
                _drain_pending_emit(qr)
            self._drainer.flush()
            self._serve_drainer.drain_all()   # serving rings -> empty
            if all(j.pending_async() == 0 for j in self.junctions.values()) \
                    and not any(qr._pending_emit or _fusion.pending(qr)
                                for qr in self._step_runtimes()) \
                    and self._serve_drainer.pending() == 0:
                for qr in self.query_runtimes.values():
                    if isinstance(qr, PatternQueryRuntime):
                        qr.note_nfa_facts()
                return
        import logging
        logging.getLogger("siddhi_tpu").warning(
            "flush() gave up after 64 rounds with async batches still "
            "pending (sustained re-ingestion?)")

    def _step_runtimes(self):
        """Every runtime that can hold a @fuse stack or deferred
        emissions: the per-query runtimes plus merged-group dispatchers
        (optimizer/mqo.py) — flush/quiesce/shutdown drain them all."""
        return list(self.query_runtimes.values()) + \
            list(self.merged_groups.values())

    def in_probe_tables(self, deps):
        """Snapshots for `x in Table` probes: (first column, validity) per
        dep — the ONE place defining what an In-probe sees (plain, keyed,
        and pattern steps all ship these into their jitted programs)."""
        return tuple((self.tables[d].cols[0], self.tables[d].valid)
                     for d in deps)

    def _validate_in_deps(self, deps, qname: str) -> None:
        """`x in <id>` only probes DEFINED TABLES (reference:
        InConditionExpressionExecutor resolves a table); reject named
        windows / aggregations / typos at plan time, not as a KeyError on
        the first send."""
        for d in deps:
            if d not in self.tables:
                raise CompileError(
                    f"query {qname!r}: `in {d}` requires a defined table "
                    f"(named windows and aggregations are not probe-able "
                    f"with `in`; defined tables: {sorted(self.tables)})")

    def _gate_wait(self) -> None:
        """Entry valve (reference: InputEntryValve + ThreadBarrier): external
        producer threads block while a snapshot quiesces the app.  The
        app's OWN threads (async ingest workers, emission drainer,
        scheduler) are exempt — a worker whose callback re-ingests must
        keep draining or _quiesce's queue join would deadlock against the
        closed gate."""
        if getattr(threading.current_thread(), "_siddhi_internal", False):
            return
        self._ingress_gate.wait()

    @contextlib.contextmanager
    def _quiesce(self):
        """Close the ingress gate (producers block at the entry valve),
        drain async queues, then acquire the app lock plus EVERY query lock
        (the reference's ThreadBarrier quiescing event threads for
        snapshots).  The gate must close BEFORE the drain: joining a queue
        that a persistent producer keeps refilling livelocks — observed as
        an indefinitely-spinning snapshot under load.  Accepted-but-queued
        events still land in the snapshotted state (at-least-once across a
        persist/restore)."""
        self._ingress_gate.clear()
        cur = threading.current_thread()
        prev_internal = getattr(cur, "_siddhi_internal", False)
        # the quiescing thread delivers held @pipeline emissions below; a
        # delivery callback that re-ingests must not block on the gate THIS
        # thread closed (it would deadlock the snapshot) — mark it internal
        # for the duration, and iterate drain+deliver to a fixpoint so
        # re-ingested events land in the snapshotted state too
        cur._siddhi_internal = True
        try:
            for _ in range(64):
                for j in self.junctions.values():
                    j.flush_async()
                for qr in self._step_runtimes():
                    # @fuse stacks hold UNPROCESSED events — they must
                    # land in the snapshotted state, not vanish
                    _fusion.drain(qr)
                    _drain_pending_emit(qr)
                # serving rings drain to EMPTY under quiesce: ring
                # contents are in-flight output, never snapshotted state
                self._serve_drainer.drain_all()
                if all(j.pending_async() == 0
                       for j in self.junctions.values()) and \
                        not any(qr._pending_emit or _fusion.pending(qr)
                                for qr in self._step_runtimes()) and \
                        self._serve_drainer.pending() == 0:
                    break
            locks = [self._lock]
            for qname in sorted(self.query_runtimes):
                locks.append(self.query_runtimes[qname]._qlock)
            for wid in sorted(self.named_windows):
                locks.append(self.named_windows[wid]._qlock)
            with _acquire_all(locks):
                yield
        finally:
            cur._siddhi_internal = prev_internal
            self._ingress_gate.set()

    def timestamp_millis(self) -> int:
        if self.playback:
            return self._playback_time
        return current_millis()

    # -- I/O ------------------------------------------------------------------
    def get_input_handler(self, stream_id: str) -> InputHandler:
        if stream_id not in self.junctions:
            raise DefinitionNotExistError(f"undefined stream {stream_id!r}")
        return InputHandler(stream_id, self)

    def replay_errors(self, ids=None, stream_id: Optional[str] = None
                      ) -> Dict[str, int]:
        """Re-inject error-store entries through the normal InputHandler
        path, original timestamps preserved (reference: the error
        store's replay admin API).  Entries leave the store BEFORE
        injection — exactly-once handoff; if re-processing fails again
        the failure path captures them as fresh entries.  Returns
        {"entries": n, "events": m, "skipped": k}."""
        taken = self.error_store.take(ids=ids, stream_id=stream_id)
        n_entries = n_events = skipped = 0
        for entry in taken:
            if entry.stream_id not in self.junctions:
                # stream vanished (app edit between capture and replay):
                # keep the events instead of silently losing them
                self.error_store.store(
                    entry.stream_id, entry.events,
                    RuntimeError(f"replay skipped: stream "
                                 f"{entry.stream_id!r} no longer exists"),
                    origin=entry.origin)
                skipped += 1
                continue
            h = self.get_input_handler(entry.stream_id)
            # replay is exactly-once recovery of events the engine
            # already accepted — the admission rate limit must not
            # shed them a second time
            h._admit = False
            for e in entry.events:
                h.send(e)
            n_entries += 1
            n_events += len(entry.events)
        return {"entries": n_entries, "events": n_events,
                "skipped": skipped}

    def add_batch_callback(self, query_name: str, cb) -> None:
        """High-throughput query callback receiving columnar numpy batches
        (ts, kind, valid, cols dict) without per-event decoding."""
        if query_name not in self.query_runtimes:
            raise QueryNotExistError(f"no query named {query_name!r}")
        self.query_runtimes[query_name].batch_callbacks.append(cb)

    def add_callback(self, name: str, cb) -> None:
        """Stream name -> StreamCallback; query name -> QueryCallback."""
        if name in self.named_windows:
            self.named_windows[name].stream_callbacks.append(
                _wrap_stream_callback(cb))
        elif name in self.junctions and name not in self.query_runtimes:
            self.junctions[name].subscribe_callback(_wrap_stream_callback(cb))
        elif name in self.query_runtimes:
            self.query_runtimes[name].callbacks.append(_wrap_query_callback(cb))
        else:
            raise QueryNotExistError(f"no stream or query named {name!r}")

    def _route_columns(self, stream_id: str, cols, timestamps) -> None:
        junction = self.junctions.get(stream_id)
        if junction is None:
            raise DefinitionNotExistError(f"undefined stream {stream_id!r}")
        with _phases.phase(self.stats, junction.sub_names(), "stage"):
            staged = self._stage_columns(junction.schema, cols, timestamps)
        n = staged.n
        if self.playback:
            ts = staged.ts[:n]
            now = self._playback_advance(
                int(ts.min()) if n and self._scheduler.pending() else None,
                int(ts.max()) if n else None)
            junction.dispatch_staged(staged, now)
            self._playback_behind(now)
            return
        now = self.timestamp_millis()
        if junction._async_q is not None:
            junction.enqueue("staged", staged, now)
            return
        junction.dispatch_staged(staged, now)

    def _playback_advance(self, first: Optional[int],
                          last: Optional[int]) -> int:
        """@app:playback: move the event clock with the rows about to be
        dispatched — `first` and `last` their earliest and latest
        timestamp (None: no rows; `first` None: nothing is pending, so
        nothing can be due before them) — and return the `now` they are
        dispatched with.  Timers due at or before the FIRST row fire
        here, on that row's clock, before any row is processed; the clock
        then stands at the LAST row.  What comes due among the rows is
        the step's own to expire in its place (a sliding time window
        merges its expiries with the arrivals by time) and fires behind
        them (`_playback_behind`): no timer runs ahead of the rows of its
        own send."""
        with self._lock:   # vs the idle-advance thread's bump
            if last is not None:
                self._playback_last_wall = current_millis()
            if first is not None and first > self._playback_time:
                self._playback_time = first
            self._scheduler.drain_playback(self._playback_time)
            if last is not None and last > self._playback_time:
                self._playback_time = last
            return self._playback_time

    def _playback_behind(self, now: int) -> None:
        """Timers that came due among the rows just dispatched, and any
        the rows armed in the past."""
        with self._lock:
            self._scheduler.drain_playback(now)

    def _stage_columns(self, schema, cols, timestamps) -> ev.StagedBatch:
        """Columnar pad/adopt staging of one send_columns call."""
        n = len(cols[0])
        cap = ev.bucket_size(max(n, 1))
        if timestamps is None:
            ts0 = self.timestamp_millis()
            ts = np.full((cap,), ts0, np.int64)
        elif n == cap and isinstance(timestamps, np.ndarray) and \
                timestamps.dtype == np.int64 and timestamps.flags.c_contiguous:
            # zero-copy staging: a full-bucket send adopts the caller's
            # buffers (send_columns transfers ownership — callers must not
            # mutate after send).  This skips a host memcpy only: every
            # send is still a real H2D of the full batch (~15 MB for the
            # flagship's 524288-event send), re-sent buffers included
            ts = timestamps
        else:
            ts = np.zeros((cap,), np.int64)
            ts[:n] = timestamps
        if n == cap:
            # full buckets share immutable all-true/all-zero planes: no
            # per-send allocation or fill for them
            valid, kind = _full_bucket_planes(cap)
        else:
            valid = np.zeros((cap,), np.bool_)
            valid[:n] = True
            kind = np.zeros((cap,), np.int32)
        padded = []
        for c, t in zip(cols, schema.types):
            d = ev.np_dtype(t)
            if n == cap and isinstance(c, np.ndarray) and c.dtype == d \
                    and c.flags.c_contiguous:
                padded.append(c)
                continue
            a = np.zeros((cap,), d)
            a[:n] = c
            padded.append(a)
        return ev.StagedBatch(ts, kind, valid, padded, n)

    def _route(self, stream_id: str, events: List[ev.Event]) -> None:
        if stream_id in self.named_windows:
            nw = self.named_windows[stream_id]
            now = self._playback_advance(*_ts_range(events)) \
                if self.playback else self.timestamp_millis()
            with nw._qlock:
                nw.process_staged(ev.pack_np(nw.schema, events), now)
            if self.playback:
                self._playback_behind(now)
            return
        junction = self.junctions.get(stream_id)
        if junction is None:
            raise DefinitionNotExistError(f"undefined stream {stream_id!r}")
        if self.playback:
            # timers the event clock has passed by the first event fire
            # first (they are earlier in event time than the new events)
            now = self._playback_advance(*_ts_range(events))
            junction.publish(events, now)
            self._playback_behind(now)
            return
        now = self.timestamp_millis()
        if junction._async_q is not None:
            junction.enqueue("pub", events, now)
            return
        junction.publish(events, now)

    # -- statistics / debugging -----------------------------------------------
    def statistics(self) -> Dict:
        """Metric report (reference: SiddhiStatisticsManager)."""
        return self.stats.report(self)

    def buffered_emissions(self) -> int:
        """Device outputs queued in the async emission drainer (public
        accessor — reference: SiddhiBufferedEventsMetric).  Returns 0 on a
        stopped or mid-teardown app instead of raising."""
        d = getattr(self, "_drainer", None)
        if d is None:
            return 0
        try:
            return d.pending()
        except Exception:  # noqa: BLE001 — metrics must not throw
            return 0

    def buffered_ingress(self) -> Dict[str, int]:
        """Batches pending in @async ingress queues, per stream (only
        streams with a non-zero backlog).  Safe mid-shutdown: a junction
        whose queue was already torn down reports nothing."""
        out: Dict[str, int] = {}
        for sid, j in list(self.junctions.items()):
            try:
                n = j.pending_async()
            except Exception:  # noqa: BLE001 — metrics must not throw
                n = 0
            if n > 0:
                out[sid] = n
        return out

    def queue_depths(self) -> Dict[str, int]:
        """Current @async ingress queue depth per stream (only streams
        running an async queue; zero-depth queues ARE reported so the
        gauge exists before the first backlog).  Host-side qsize reads —
        safe mid-shutdown."""
        out: Dict[str, int] = {}
        for sid, j in list(self.junctions.items()):
            try:
                if j._async_q is not None:
                    out[sid] = j.queue_depth()
            except Exception:  # noqa: BLE001 — metrics must not throw
                pass
        return out

    def drainer_depth(self) -> int:
        """Device outputs sitting in the async emission drainer queue
        (siddhi_drainer_queue_depth; 0 on a stopped app)."""
        d = getattr(self, "_drainer", None)
        if d is None:
            return 0
        try:
            return d.depth()
        except Exception:  # noqa: BLE001 — metrics must not throw
            return 0

    def timers_pending(self) -> int:
        """Timers on the scheduler's heap (siddhi_timers_pending)."""
        return self._scheduler.pending()

    def timer_facts(self) -> Dict[str, int]:
        """The scheduler's books: timers `pending` now; `timer_steps`
        fired and `wakeups_armed` since the app started."""
        s = self._scheduler
        return {"pending": s.pending(), "timer_steps": s.timer_steps,
                "wakeups_armed": s.wakeups_armed}

    def serve_rings(self) -> Dict[str, "object"]:
        """{query: EmissionRing} for every runtime that has opened a
        serving ring (host-side attribute reads only)."""
        out: Dict[str, object] = {}
        for qname, qr in list(self.query_runtimes.items()):
            ring = qr._serve_ring
            if ring is not None:
                out[qname] = ring
        return out

    def serve_staging_facts(self) -> Dict:
        """Counters of the accept-edge H2D stager (serving/staging.py):
        staged/adopted/fallback totals — /healthz `serving.staging`."""
        return self._serve_stager.facts()

    def compiled_steps(self, query_name: str) -> List[Tuple]:
        """(role, jitted fn, argspecs) for every XLA program on the hot
        path of `query_name`, its serving ring's included; argspecs is
        None for a program that has not run yet
        (observability/explain.compiled_steps)."""
        from ..observability.explain import compiled_steps as _cs
        return _cs(self.query_runtimes[query_name])

    def ring_occupancies(self) -> Dict[str, int]:
        """Pending (appended, undrained) serving-ring entries per query
        — the siddhi_ring_occupancy gauge (safe mid-shutdown)."""
        out: Dict[str, int] = {}
        for qname, ring in self.serve_rings().items():
            try:
                out[qname] = ring.occupancy()
            except Exception:  # noqa: BLE001 — metrics must not throw
                out[qname] = 0
        return out

    def serve_drainer_depth(self) -> int:
        """Ring entries awaiting the serving drainer across all rings
        (the serving analog of drainer_depth; 0 on a stopped app)."""
        d = getattr(self, "_serve_drainer", None)
        if d is None:
            return 0
        try:
            return d.depth()
        except Exception:  # noqa: BLE001 — metrics must not throw
            return 0

    def timeseries(self) -> Dict:
        """Windowed time-series report for this app: every sampled series
        (ring-buffer {t, v} arrays), the per-tenant account, and the SLO
        state — filled by the manager's TimeSeriesSampler
        (observability/timeseries.py; `enabled` is False until it has
        ticked).  Served as `GET /siddhi-apps/<name>/timeseries`."""
        store = self.__dict__.get("_timeseries")
        out: Dict = {
            "app": self.name,
            "enabled": store is not None,
            "series": store.to_dict() if store is not None else {},
        }
        acct = self.__dict__.get("_tenant_account")
        if acct is not None:
            out["tenant"] = acct
        slo = self.__dict__.get("_slo_state")
        if slo is not None:
            out["slo"] = slo
        return out

    def trace_dump(self, query: Optional[str] = None,
                   limit: int = 64) -> List[Dict]:
        """Recent DETAIL-level batch traces, newest first, optionally only
        those that touched `query` (see observability/tracing.py)."""
        return self.stats.tracer.dump(query, limit)

    def phase_report(self) -> Dict:
        """Per-query phase budget (seconds + share per pipeline phase)
        against the `<query>:e2e` histogram, unattributed remainder as
        `other` — see observability/phases.py.  Host-side reads only:
        safe to call on a live app."""
        from ..observability.phases import phase_report as _pr
        return _pr(self)

    def state_report(self) -> Dict:
        """State observatory report: per-(query, structure) occupancy /
        capacity / high-water utilization, key hotness (count-min +
        top-K), near-capacity verdicts, and the sizing-hints ledger a
        snapshot would persist — see observability/stateobs.py.  Host-
        side reads only: safe to call on a live app."""
        from ..observability.stateobs import state_report as _sr
        return _sr(self)

    def explain(self, query_name: Optional[str] = None,
                deep: bool = True) -> Dict:
        """EXPLAIN report: planned operator tree + per-step XLA cost
        analysis (flops, bytes accessed, estimated peak memory), state
        shapes and nbytes, emission caps, fusion eligibility with the
        concrete exclusion reason, and recompile history.  One query, or
        every query when `query_name` is None (then shallow by default —
        see observability/explain.py).  May compile; this is an on-demand
        diagnostic, never called from the scrape path."""
        from ..observability.explain import explain_app, explain_query
        if query_name is None:
            return explain_app(self, deep=False)
        return explain_query(self, query_name, deep=deep)

    def state_memory(self) -> Dict:
        """{owner: {component: nbytes}} across the app's device state —
        window buffers, pattern slot blocks, selector slabs, tables,
        named windows, aggregations, fuse stacks.  Metadata-only walk
        (no device fetch); also exported as `siddhi_state_bytes` in
        /metrics (observability/memory.py)."""
        from ..observability.memory import component_bytes
        return component_bytes(self)

    def health(self) -> Dict:
        """Host-side health report for this app: readiness/liveness
        verdicts, per-stream last-event age + ingress backlog, and
        sliding-window drop/recompile rates (observability/health.py)."""
        from ..observability.health import app_health
        return app_health(self)

    def analyze(self, config=None) -> Dict:
        """Static lint findings for this app from its ACTUAL compiled
        plans (real emission caps, measured state bytes, mesh-aware
        fusion exclusions) — attribute and metadata reads only, never
        executes or traces (siddhi_tpu/analysis).  Also served as
        `GET /siddhi-apps/<app>/lint` and echoed into explain()."""
        from ..analysis import analyze as _analyze, report as _report
        findings = _analyze(self, config=config,
                            source_name=f"<{self.name}>")
        rep = _report(findings)
        rep["app"] = self.name
        return rep

    def set_statistics_level(self, level: str) -> None:
        self.stats.level = level.upper()

    def set_exception_listener(self, fn) -> None:
        """reference: SiddhiAppRuntimeImpl.handleRuntimeExceptionWith"""
        self.exception_listener = fn

    def debug(self):
        """Attach a debugger; returns it (reference:
        SiddhiAppRuntimeImpl.debug :657-675)."""
        from .debugger import SiddhiDebugger
        self._debugger = SiddhiDebugger(self)
        return self._debugger

    # -- on-demand (store) queries --------------------------------------------
    _ONDEMAND_CACHE_MAX = 50   # reference: SiddhiAppRuntimeImpl.java:304-367

    def query(self, q) -> List[ev.Event]:
        """Execute a one-shot store query against tables/windows/aggregations
        (reference: SiddhiAppRuntimeImpl.query :304-367).  String queries
        hit an LRU (≤50) of parsed+compiled plans, so a repeated store query
        re-plans nothing — only the data pass runs."""
        from ..query_api.query import OnDemandQuery
        from .ondemand import OnDemandPlanMemo, execute_on_demand
        memo = None
        if isinstance(q, str):
            with self._ondemand_cache_lock:
                ent = self._ondemand_cache.get(q)
                if ent is not None:
                    self._ondemand_cache.move_to_end(q)
            if ent is None:
                from ..compiler import SiddhiCompiler
                parsed = SiddhiCompiler.parse_on_demand_query(q)
                ent = (parsed, OnDemandPlanMemo())
                with self._ondemand_cache_lock:
                    self._ondemand_cache[q] = ent
                    while len(self._ondemand_cache) > \
                            self._ONDEMAND_CACHE_MAX:
                        self._ondemand_cache.popitem(last=False)
            q, memo = ent
        assert isinstance(q, OnDemandQuery)
        with self._quiesce():
            return execute_on_demand(self, q, memo)

    # -- snapshot/restore ------------------------------------------------------
    def snapshot(self) -> bytes:
        """Full state snapshot (reference: SnapshotService.fullSnapshot
        CORE/util/snapshot/SnapshotService.java:90) — here simply the state
        pytrees + slot maps, no stop-the-world object walk needed."""
        with self._quiesce():
            states = {}
            for name, qr in self.query_runtimes.items():
                host_state = _host_state(qr)
                alloc = _allocator_of(qr)
                alloc2 = qr.planned.slot_allocator2
                jk = qr.planned.join_key_allocator
                states[name] = {
                    "state": host_state,
                    "slots": alloc.snapshot() if alloc is not None else None,
                    "slots2": alloc2.snapshot()
                    if alloc2 is not None else None,
                    "slots_jk": jk.snapshot() if jk is not None else None,
                    "slots_pairs": [
                        a.snapshot() for a, _ in
                        qr.planned.pair_allocs] or None,
                    "wake": qr.next_wakeup,
                    # key-state row order (mesh layout) this snapshot is
                    # written in: restore re-buckets through the router
                    # when the target runtime's mesh size differs
                    "layout": _sharding.query_layout(qr),
                }
            windows = {
                wid: jax.tree.map(lambda x: np.asarray(x), nw.state)
                for wid, nw in self.named_windows.items()}
            aggs = {aid: {d: dict(s) for d, s in a.stores.items()}
                    for aid, a in self.aggregations.items()}
            from .table import _table_state
            tables = {tid: _table_state(t) for tid, t in self.tables.items()}
            _stateobs.collect(self)
            payload = {
                "states": states,
                "windows": windows,
                "aggregations": aggs,
                "tables": tables,
                "interner": list(self.interner._to_str),
                # sizing-hints ledger: learned high-water marks ride the
                # snapshot so a restarted app reports its observed
                # capacities from tick zero (observability/stateobs.py)
                "sizing": self.stats.stateobs.ledger(),
            }
            # a full snapshot resets the incremental baseline
            for qr in self.query_runtimes.values():
                if qr._dirty is not None:
                    qr._dirty[:] = False
                alloc = _allocator_of(qr)
                if alloc is not None:
                    alloc.journal.clear()
            for a in self.aggregations.values():
                a.clear_snapshot_baseline()
            return pickle.dumps(payload)

    def snapshot_incremental(self) -> bytes:
        """Delta since the last snapshot: for partitioned pattern queries
        only the state columns of keys touched since then (plus their slot
        journal); small states ship whole (reference: incremental snapshots
        via per-element op-logs, SnapshotService.incrementalSnapshot :189 —
        here the op-log is the host-tracked dirty key mask)."""
        with self._quiesce():
            deltas = {}
            for name, qr in self.query_runtimes.items():
                alloc = _allocator_of(qr)
                dirty = qr._dirty
                if dirty is not None and isinstance(qr.state, tuple) and \
                        len(qr.state) == 2 and isinstance(qr.state[0], tuple):
                    idx = np.nonzero(dirty)[0]
                    b32, lo64, hi64, scalars = qr.state[0]
                    deltas[name] = {
                        "kind": "keyed",
                        "slots": idx,
                        "b32": np.asarray(b32)[:, idx],
                        # the delta's format is the host's: one int64
                        # array, joined from the planes' dirty columns
                        "b64": StatePacker.join_host(
                            np.asarray(lo64)[:, idx],
                            np.asarray(hi64)[:, idx]),
                        "scalars": [np.asarray(s) for s in scalars],
                        "sel_state": jax.tree.map(
                            lambda x: np.asarray(x), qr.state[1]),
                        "journal": alloc.drain_journal()
                        if alloc is not None else [],
                        "wake": qr.next_wakeup,
                        "layout": _sharding.query_layout(qr),
                    }
                    dirty[:] = False
                else:
                    alloc2 = qr.planned.slot_allocator2
                    jk = qr.planned.join_key_allocator
                    deltas[name] = {
                        "kind": "full",
                        "state": _host_state(qr),
                        "slots": alloc.snapshot()
                        if alloc is not None else None,
                        "slots2": alloc2.snapshot()
                        if alloc2 is not None else None,
                        "slots_jk": jk.snapshot()
                        if jk is not None else None,
                        "slots_pairs": [
                            a.snapshot() for a, _ in
                            qr.planned.pair_allocs] or None,
                        "wake": qr.next_wakeup,
                        "layout": _sharding.query_layout(qr),
                    }
            from .table import _table_state
            payload = {
                "deltas": deltas,
                "windows": {
                    wid: jax.tree.map(lambda x: np.asarray(x), nw.state)
                    for wid, nw in self.named_windows.items()},
                # delta: only buckets written since the last baseline
                "aggregations": {aid: a.snapshot_delta()
                                 for aid, a in self.aggregations.items()},
                "agg_delta": True,
                "tables": {tid: _table_state(t)
                           for tid, t in self.tables.items()},
                "interner": list(self.interner._to_str),
            }
            _stateobs.collect(self)
            payload["sizing"] = self.stats.stateobs.ledger()
            return pickle.dumps(payload)

    def restore_increment(self, blob: bytes) -> None:
        payload = pickle.loads(blob)
        with self._quiesce():
            for s in payload["interner"]:
                self.interner.intern(s)
            for name, d in payload["deltas"].items():
                qr = self.query_runtimes.get(name)
                if qr is None:
                    continue
                alloc = _allocator_of(qr)
                if d["kind"] == "keyed":
                    arrays = qr.state[0][:3]       # b32, lo64, hi64
                    # the delta carries the host format: its int64
                    # columns split into the planes' columns here
                    cols = (np.asarray(d["b32"]),) + \
                        StatePacker.split_host(d["b64"])
                    # incremental deltas index by state ROW: remap rows
                    # (and the full selector tree riding along) when the
                    # snapshot was cut under a different mesh size
                    old_l = d.get("layout")
                    new_l = _sharding.query_layout(qr)
                    d_slots = np.asarray(d["slots"])
                    sel_host = d["sel_state"]
                    if _sharding.needs_rebucket(old_l, new_l):
                        d_slots = _sharding.rebucket_rows(
                            d_slots, old_l, new_l)
                        sel_host = _sharding.rebucket_selector(
                            sel_host, old_l, new_l, qr.planned)
                    sharding = getattr(arrays[0], "sharding", None)
                    if sharding is not None and \
                            len(sharding.device_set) > 1:
                        # host-context scatters into sharded slabs drop
                        # remote-shard columns (core/shardsafe.py): go
                        # through a dense masked where instead
                        from .shardsafe import key_mask, masked_fill
                        mask = key_mask(d_slots, arrays[0].shape[1])

                        def put(arr, c):
                            up = np.zeros(arr.shape, c.dtype)
                            up[:, d_slots] = c
                            return masked_fill(arr, mask,
                                               jax.numpy.asarray(up),
                                               key_axis=1)
                    else:
                        idx = jax.numpy.asarray(d_slots)

                        def put(arr, c):
                            return arr.at[:, idx].set(jax.numpy.asarray(c))
                    arrays = tuple(put(a, c) for a, c in zip(arrays, cols))
                    scalars = tuple(jax.numpy.asarray(s) for s in
                                    _all_scalars(qr, d["scalars"]))
                    sel_state = jax.tree.map(lambda x: jax.numpy.asarray(x),
                                             sel_host)
                    qr.state = ((*arrays, scalars), sel_state)
                    if alloc is not None:
                        alloc.apply_journal(d["journal"])
                else:
                    host_state = _rebucket_for(qr, d.get("layout"),
                                               d["state"])
                    restored = _device_state(qr, host_state)
                    qr.state = qr.place_state(restored)
                    if d["slots"] is not None and alloc is not None:
                        alloc.restore(d["slots"])
                    alloc2 = qr.planned.slot_allocator2
                    if d.get("slots2") is not None and alloc2 is not None:
                        alloc2.restore(d["slots2"])
                    jk = qr.planned.join_key_allocator
                    if d.get("slots_jk") is not None and jk is not None:
                        jk.restore(d["slots_jk"])
                    pairs = d.get("slots_pairs")
                    if pairs:
                        for (a, _), snap in zip(qr.planned.pair_allocs,
                                                pairs):
                            a.restore(snap)
                    qr._after_restore(host_state)
                w = d.get("wake")
                if w is not None:
                    qr._apply_wake(int(w))
            self._restore_shared(payload)

    def restore(self, blob: bytes) -> None:
        payload = pickle.loads(blob)
        with self._quiesce():
            for s in payload["interner"]:
                self.interner.intern(s)
            for name, data in payload["states"].items():
                qr = self.query_runtimes.get(name)
                if qr is None:
                    continue
                host_state = _rebucket_for(qr, data.get("layout"),
                                           data["state"])
                restored = _device_state(qr, host_state)
                qr.state = qr.place_state(restored)
                alloc = _allocator_of(qr)
                if data["slots"] is not None and alloc is not None:
                    alloc.restore(data["slots"])
                alloc2 = qr.planned.slot_allocator2
                if data.get("slots2") is not None and alloc2 is not None:
                    alloc2.restore(data["slots2"])
                jk = qr.planned.join_key_allocator
                if data.get("slots_jk") is not None and jk is not None:
                    jk.restore(data["slots_jk"])
                pairs = data.get("slots_pairs")
                if pairs:
                    for (a, _), snap in zip(qr.planned.pair_allocs, pairs):
                        a.restore(snap)
                qr._after_restore(host_state)
                # re-arm pending timers (absent deadlines, window expiry):
                # the scheduler of this fresh runtime knows nothing of the
                # wakeups the snapshotted state still expects
                w = data.get("wake")
                if w is not None:
                    qr._apply_wake(int(w))
            self._restore_shared(payload)

    def _restore_shared(self, payload) -> None:
        from .table import _restore_table_state
        for wid, wstate in payload.get("windows", {}).items():
            nw = self.named_windows.get(wid)
            if nw is not None:
                nw.state = jax.tree.map(
                    lambda x: jax.numpy.asarray(x), wstate)
        agg_delta = payload.get("agg_delta", False)
        for aid, stores in payload.get("aggregations", {}).items():
            agg = self.aggregations.get(aid)
            if agg is None:
                continue
            if agg_delta:
                agg.apply_delta(stores)
            else:
                agg.stores = {d: dict(s) for d, s in stores.items()}
        for tid, tdata in payload.get("tables", {}).items():
            t = self.tables.get(tid)
            if t is not None:
                _restore_table_state(t, tdata)
        # sizing-hints ledger: max-merge the snapshotted high-water
        # marks so the restored app reports them from tick zero
        sizing = payload.get("sizing")
        if sizing:
            self.stats.stateobs.adopt_ledger(sizing)


def _resolve_mesh(app: SiddhiApp, mesh):
    """The mesh an app deploys on: the `mesh=` argument, or the one its
    own text asks for with `@app:mesh(shards='N')` — the first N of
    `jax.devices()` on a 'shard' axis, so a tenant who deploys by text
    alone (REST, a file) can shard.  `shards='1'` is the unsharded
    runtime (None).  Fewer devices than asked for is a deploy error that
    names both numbers, never a quiet one-chip deployment; so is an
    explicit mesh whose size disagrees with the annotation (one that
    agrees wins, devices and all)."""
    from .plan_facts import mesh_shards
    n = mesh_shards(app)
    if n is None:
        return mesh
    if mesh is not None:
        have = _sharding.shard_count(mesh)
        if have != n:
            raise SiddhiAppValidationError(
                f"@app:mesh(shards='{n}') disagrees with the mesh= "
                f"argument, which has {have} device(s): drop one of them "
                f"or make them agree")
        return mesh
    if n == 1:
        return None
    devs = jax.devices()
    if len(devs) < n:
        raise SiddhiAppValidationError(
            f"@app:mesh(shards='{n}') asks for {n} devices and jax has "
            f"{len(devs)} ({devs[0].platform}); deploy on a host with at "
            f"least {n}, or on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    from jax.sharding import Mesh
    return Mesh(np.array(devs[:n]), ("shard",))


class SiddhiManager:
    """reference: CORE/SiddhiManager.java:49"""

    def __init__(self):
        from ..utils.config import ConfigManager
        from ..utils.persistence import InMemoryPersistenceStore
        self.interner = ev.StringInterner()
        from ..utils.persistence import AsyncSnapshotPersistor
        self.runtimes: Dict[str, SiddhiAppRuntime] = {}
        self.persistence_store = InMemoryPersistenceStore()
        self.config_manager = ConfigManager()
        self._persistor = AsyncSnapshotPersistor()
        self._has_base: set = set()
        # time-series sampler (observability/timeseries.py): started on
        # demand (REST service auto-starts one; bench --mode soak too)
        self._sampler = None

    def set_persistence_store(self, store) -> None:
        """reference: SiddhiManager.setPersistenceStore (full or
        incremental store)."""
        self.persistence_store = store
        self._has_base.clear()

    def set_config_manager(self, config_manager) -> None:
        """reference: SiddhiManager.setConfigManager — supplies system-wide
        properties and per-extension ConfigReaders (utils/config.py)."""
        self.config_manager = config_manager

    def set_extension(self, name: str, impl) -> None:
        """reference: SiddhiManager.setExtension :213 — register a custom
        extension by `namespace:name`.  The implementation kind is
        inferred: WindowProcessor subclasses register as windows, Source/
        Sink subclasses as transports, callables as scalar functions
        (returning a CompiledExpr from a list of compiled args)."""
        from ..io.mappers import SinkMapper, SourceMapper
        from ..io.sink import DistributionStrategy, Sink, register_sink_type
        from ..io.source import Source, register_source_type
        from .extension import (AttributeAggregator,
                                IncrementalAttributeAggregator,
                                attribute_aggregator, distribution_strategy,
                                incremental_attribute_aggregator,
                                scalar_function, sink_mapper, source_mapper,
                                window_extension)
        from .window import WindowProcessor
        if isinstance(impl, type) and issubclass(impl, WindowProcessor):
            window_extension(name, replace=True)(impl)
        elif isinstance(impl, type) and issubclass(impl, AttributeAggregator):
            attribute_aggregator(name, replace=True)(impl)
        elif isinstance(impl, type) and issubclass(
                impl, IncrementalAttributeAggregator):
            incremental_attribute_aggregator(name, replace=True)(impl)
        elif isinstance(impl, type) and issubclass(impl, DistributionStrategy):
            distribution_strategy(name, replace=True)(impl)
        elif isinstance(impl, type) and issubclass(impl, SourceMapper):
            source_mapper(name, replace=True)(impl)
        elif isinstance(impl, type) and issubclass(impl, SinkMapper):
            sink_mapper(name, replace=True)(impl)
        elif isinstance(impl, type) and issubclass(impl, Source):
            register_source_type(name, impl)
        elif isinstance(impl, type) and issubclass(impl, Sink):
            register_sink_type(name, impl)
        elif callable(impl):
            scalar_function(name, replace=True)(impl)
        else:
            raise TypeError(
                f"cannot infer extension kind for {type(impl).__name__}; "
                f"use the @scalar_function/@window_extension/"
                f"@attribute_aggregator/@source_mapper/@sink_mapper "
                f"decorators or register_source_type/register_sink_type "
                f"directly")

    def create_sandbox_siddhi_app_runtime(
            self, app: Union[str, SiddhiApp],
            mesh=None) -> "SiddhiAppRuntime":
        """reference: SiddhiManager.createSandboxSiddhiAppRuntime — deploy
        an app with its EXTERNAL dependencies stripped for testing: only
        inMemory sources/sinks survive, @store tables become plain
        in-memory tables (SandboxTestCase expectations)."""
        from ..compiler import SiddhiCompiler
        if isinstance(app, str):
            app = SiddhiCompiler.parse(app)
        else:
            # never mutate the caller's app object: the same SiddhiApp may
            # be deployed for real afterwards with its transports intact
            import copy
            app = copy.deepcopy(app)

        def keep(ann) -> bool:
            if ann.name.lower() not in ("source", "sink"):
                return True
            t = ann.element("type") or ann.element(None)
            return str(t).lower() == "inmemory"

        for sdef in app.stream_definition_map.values():
            sdef.annotations = [a for a in sdef.annotations if keep(a)]
        for tdef in app.table_definition_map.values():
            tdef.annotations = [a for a in tdef.annotations
                                if a.name.lower() != "store"]
        # aggregations may also carry @store (distributed shardId mode) —
        # a sandboxed app must not reach that external DB either
        for adef in app.aggregation_definition_map.values():
            adef.annotations = [a for a in adef.annotations
                                if a.name.lower() != "store"]
        return self.create_siddhi_app_runtime(app, mesh=mesh)

    setPersistenceStore = set_persistence_store
    setConfigManager = set_config_manager
    setExtension = set_extension
    createSandboxSiddhiAppRuntime = create_sandbox_siddhi_app_runtime

    def create_siddhi_app_runtime(
            self, app: Union[str, SiddhiApp],
            mesh=None) -> SiddhiAppRuntime:
        if isinstance(app, str):
            from ..compiler import SiddhiCompiler
            app = SiddhiCompiler.parse(app)
        # deploy-time admission gate: the static state estimate is
        # checked against the configured memory ceilings BEFORE the
        # runtime is constructed — a denial provably precedes any
        # planning, tracing, or device allocation (core/admission.py)
        from .admission import check_deploy
        mesh = _resolve_mesh(app, mesh)
        check_deploy(app, self, mesh=mesh)
        runtime = SiddhiAppRuntime(app, self, mesh=mesh)
        self.runtimes[runtime.name] = runtime
        return runtime

    # camelCase alias mirroring the reference API surface
    createSiddhiAppRuntime = create_siddhi_app_runtime

    def persist(self) -> List[str]:
        """Snapshot every app into the persistence store (reference:
        SiddhiManager.persist :281; sources pause around the snapshot as in
        SiddhiAppRuntimeImpl.persist :677-691).

        With an IncrementalPersistenceStore, the first persist writes a full
        BASE snapshot and subsequent calls write dirty-key INCREMENTS.  The
        store write happens on the async persistor thread (reference:
        AsyncSnapshotPersistor); call wait_for_persistence() to block on it.
        Returns the revision ids."""
        from ..utils.persistence import (
            IncrementalPersistenceStore,
            new_revision,
        )
        store = self.persistence_store
        incremental = isinstance(store, IncrementalPersistenceStore)
        # a failed async write leaves a hole in the increment chain; demote
        # the affected app to a fresh BASE snapshot instead of stacking
        # increments on the hole
        for tag in self._persistor.take_failed_tags():
            import logging
            logging.getLogger("siddhi_tpu").warning(
                "previous persist of %s failed; writing a full base "
                "snapshot", tag)
            self._has_base.discard(tag)
        revs = []
        for name, rt in self.runtimes.items():
            rt.pause_sources()
            try:
                rev = new_revision(name)
                if incremental:
                    if name not in self._has_base:
                        blob = rt.snapshot()
                        self._persistor.submit(store.save_base, name, rev,
                                               blob, tag=name)
                        self._has_base.add(name)
                    else:
                        blob = rt.snapshot_incremental()
                        self._persistor.submit(store.save_increment, name,
                                               rev, blob, tag=name)
                else:
                    self._persistor.submit(store.save, name, rev,
                                           rt.snapshot(), tag=name)
                revs.append(rev)
            finally:
                rt.resume_sources()
        return revs

    def wait_for_persistence(self) -> None:
        self._persistor.flush()

    def restore_revision(self, revision: str) -> None:
        """Restore every app from a specific full-snapshot revision
        (reference: SiddhiAppRuntimeImpl.restoreRevision)."""
        self.wait_for_persistence()
        store = self.persistence_store
        if not hasattr(store, "load"):
            raise CannotRestoreStateError(
                "revision restore requires a full-snapshot PersistenceStore")
        for name, rt in self.runtimes.items():
            blob = store.load(name, revision)
            if blob is None:
                raise CannotRestoreStateError(
                    f"revision {revision!r} not found for app {name!r}")
            rt.restore(blob)

    def restore_last_revision(self) -> None:
        """Restore every app from its newest INTACT revision.  A corrupt
        or unreadable revision (torn write, CRC mismatch, truncation —
        see utils/persistence.seal/unseal) is skipped with a warning and
        the previous revision is tried, bumping the app's
        `restore_fallbacks` counter (siddhi_restore_fallbacks_total);
        CannotRestoreStateError is raised only when revisions exist but
        NONE of them restores."""
        import logging
        from ..utils.persistence import IncrementalPersistenceStore
        _log = logging.getLogger("siddhi_tpu")
        self.wait_for_persistence()
        store = self.persistence_store
        for name, rt in self.runtimes.items():
            if isinstance(store, IncrementalPersistenceStore):
                try:
                    chain = store.load_chain(name)
                except Exception as exc:  # noqa: BLE001 — corrupt base
                    rt.restore_fallbacks += 1
                    _log.error(
                        "incremental chain for %s unrestorable (%r); "
                        "state NOT restored", name, exc)
                    continue
                if chain is None:
                    continue
                base, incs = chain
                rt.restore(base)
                for inc in incs:
                    rt.restore_increment(inc)
                continue
            revs = store.get_revisions(name)
            if not revs:
                continue
            restored = False
            for rev in reversed(revs):
                try:
                    blob = store.load(name, rev)
                    if blob is None:
                        continue
                    rt.restore(blob)
                    restored = True
                    break
                except Exception as exc:  # noqa: BLE001 — fall back
                    rt.restore_fallbacks += 1
                    _log.warning(
                        "revision %r of %s unrestorable (%r); falling "
                        "back to the previous revision", rev, name, exc)
            if not restored:
                raise CannotRestoreStateError(
                    f"no intact revision among {len(revs)} stored for "
                    f"app {name!r}")

    def start_sampler(self, interval_s=None, window=None, rules=None,
                      clock=None):
        """Start (or return) the manager's in-process time-series sampler:
        a daemon thread snapshotting every app's host-side metrics into
        ring-buffer series each tick and evaluating the SLO rules over
        them (observability/timeseries.py, observability/slo.py).
        Interval/window default from config properties
        `metrics.sampler.interval.seconds` / `metrics.sampler.window`.
        Idempotent; pass `clock`+drive `tick()` yourself in tests."""
        if self._sampler is None:
            from ..observability.timeseries import TimeSeriesSampler
            self._sampler = TimeSeriesSampler(
                self, interval_s=interval_s, window=window, rules=rules,
                clock=clock)
            if clock is None:      # test-driven samplers tick manually
                self._sampler.start()
        return self._sampler

    def stop_sampler(self) -> None:
        s, self._sampler = self._sampler, None
        if s is not None:
            s.stop()

    def shutdown(self) -> None:
        self.stop_sampler()
        for rt in self.runtimes.values():
            rt.shutdown()

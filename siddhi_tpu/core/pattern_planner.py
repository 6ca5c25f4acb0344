"""Planner + host runtime glue for pattern/sequence queries.

Reference role: CORE/util/parser/StateInputStreamParser.java (NFA build) +
pattern receivers (CORE/query/input/stream/state/receiver/*).  Each pattern
query compiles to one jitted step per input stream; the host groups incoming
events by partition key into a [K, E] layout and the device scan does the
sequential-per-key NFA advance.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..query_api.definition import StreamDefinition
from ..query_api.query import Query, StateInputStream
from . import event as ev
from . import plan_facts
from . import state_rows
from .executor import CompileError
from .pattern import PatternExec, PatternSpec, linearize, oh_take
from .pattern_block import block_eligible, block_layout, make_block_step
from .selector import SelectorExec
from .window import NO_WAKEUP, Rows
from .steputil import (from_u32_planes, jit_step, join64, pmin_i64, split64,
                       u32_planes)

# test hook: force the sequential scan path even for block-eligible specs
# (golden cross-checks compare the two implementations on the same input)
_FORCE_SCAN = False


class StatePacker:
    """Pack a per-key state pytree (array leaves with leading K axis) into
    three arrays stored [W, K] (key axis MINOR): `b32`, one i32 blob
    (i32 / f32-bitcast / bool leaves), and the i64 leaves as two SEPARATE
    u32 planes, `lo64` (low words) and `hi64` (high words), rows in the
    order the leaves come.  The packed state is
    `(b32, lo64, hi64, scalars)`; `scalars` are the 0-d leaves.

    Why blobs at all: XLA:TPU scatter has a large per-op cost, roughly
    independent of row width (an earlier remote-chip reading was ~7 ms for
    32k rows; not measured on the current chip).  The
    NFA state has ~24 leaf arrays; scattering each per batch dominated the
    step.  Packing reduces the per-batch key-state update to one gather
    and one scatter per array.

    Why three arrays and not an i32 blob plus an i64 blob: the TPU has no
    64-bit integers.  XLA's X64 rewrite turns every s64 array INSIDE a
    program into a (low u32, high u32) pair, but an s64 array that is a
    PARAMETER or a RESULT of the program is converted at the boundary,
    all of it, on every call: `X64SplitLow` + `X64SplitHigh` of the
    argument, `X64Combine` into the result.  The state is a donated
    argument and a result of every step, so that cost went with the keys
    RESIDENT, not the keys a send touches: 29.6 of the 76.7 ms of a
    sharded step over `s64[40, 8388608]` a chip (PERF.md section 6, PR 26
    trace), and a temporary the size of the blob.  As two u32 planes the
    state crosses the boundary as it lies; 64-bit values exist on the
    device only for the `[W64, Kb]` rows a step gathered or sliced
    (`unpack` joins them, `pack` splits the result).  Nothing narrows:
    the join and the split are bit-exact over the whole i64 range.

    Why two planes and not one stacked `[2*W64, K]` / `[2, W64, K]` u32
    array: compiled for v5e at 1,048,576 keys the stacked forms cost a
    whole-blob layout copy per step (537 MB / 1.08 GB of temporaries)
    where two planes cost none (ISSUE 27's compile table;
    tests/test_state_planes.py repeats it).  The same copy meets ANY fold
    of the three arrays into one wider blob (one gather / scatter pass a
    step instead of three): compiled for v5e:2x2, a gather + scatter on
    `u32[W, 1048576]` by `s32[4096]` indices has, by W (ISSUE 36),

        W                               temp_size_in_bytes
        40, 50                          0
        64, 80, 96, 120, 127, 128       536,935,424
        130, 136                        1,078,114,304

    — from W = 64 up layout assignment wants the blob key-major for the
    scatter and copies the WHOLE blob there and back every step.  Do not
    re-try the fold; what a step pays for its keys' rows is the
    row-mover's business (`state_rows.py`: by the 128-key block on the
    TPU, three arrays as they lie).

    Why [W, K] and not [K, W]: with keys leading, XLA:TPU layout assignment
    picked a key-major {0,1} layout for the [K, W] blobs, so every per-key
    row gather touched W whole (8,128) tiles — ~15 GB of HBM traffic per
    131k-key step (an earlier reading; not measured on the current chip).
    With keys minor, per-key access rides the
    tiled minor axis and batch key indices arrive sorted (keyslots group
    ascending), so gather/scatter granules are dense.

    On the host and on disk (snapshots) the two planes are ONE int64
    array `b64 [W64, K]`, as before the planes existed: `to_host` /
    `from_host` convert at that boundary.
    """

    def __init__(self, example):
        leaves, self.treedef = jax.tree_util.tree_flatten(example)
        self.recs = []   # (kind, dtype, tail_shape, offset, width)
        self.w32 = 0
        self.w64 = 0
        self.scalars = []
        for i, leaf in enumerate(leaves):
            if leaf.ndim == 0:
                self.recs.append(("scalar", leaf.dtype, (), len(self.scalars),
                                  0))
                self.scalars.append(i)
                continue
            head = leaf.shape[:-1]     # K is the LAST axis on every leaf
            width = 1
            for d in head:
                width *= d
            if leaf.dtype == jnp.int64:
                self.recs.append(("i64", leaf.dtype, head, self.w64, width))
                self.w64 += width
            else:
                self.recs.append(("i32", leaf.dtype, head, self.w32, width))
                self.w32 += width

    def pack(self, state):
        leaves = jax.tree_util.tree_flatten(state)[0]
        K = None
        parts32, parts64, scal = [], [], []
        for leaf, (kind, dtype, head, off, width) in zip(leaves, self.recs):
            if kind == "scalar":
                scal.append(leaf)
                continue
            K = leaf.shape[-1]
            flat = leaf.reshape(width, K)            # pure reshape, K minor
            if kind == "i64":
                parts64.append(flat.astype(jnp.int64))
            else:
                if dtype == jnp.float32:
                    flat = lax.bitcast_convert_type(flat, jnp.int32)
                else:
                    flat = flat.astype(jnp.int32)
                parts32.append(flat)
        b32 = jnp.concatenate(parts32, axis=0) if parts32 else \
            jnp.zeros((0, K), jnp.int32)
        b64 = jnp.concatenate(parts64, axis=0) if parts64 else \
            jnp.zeros((0, K), jnp.int64)
        lo64, hi64 = split64(b64)
        return b32, lo64, hi64, tuple(scal)

    def unpack(self, b32, lo64, hi64, scalars):
        leaves = []
        K = b32.shape[1]
        b64 = join64(lo64, hi64)
        for kind, dtype, head, off, width in self.recs:
            if kind == "scalar":
                leaves.append(scalars[off])
                continue
            if kind == "i64":
                flat = lax.dynamic_slice_in_dim(b64, off, width, axis=0)
                leaf = flat.reshape(head + (K,))
            else:
                flat = lax.dynamic_slice_in_dim(b32, off, width, axis=0)
                if dtype == jnp.float32:
                    flat = lax.bitcast_convert_type(flat, jnp.float32)
                leaf = flat.reshape(head + (K,))
                if dtype == jnp.bool_:
                    leaf = leaf != 0
                elif dtype != jnp.float32:
                    leaf = leaf.astype(dtype)
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    @staticmethod
    def join_host(lo64, hi64) -> np.ndarray:
        """Two u32 planes (or any same-shaped columns of them) -> the
        int64 array the host and every snapshot hold."""
        lo64 = np.asarray(lo64)
        pair = np.empty(lo64.shape + (2,), "<u4")
        pair[..., 0] = lo64
        pair[..., 1] = np.asarray(hi64)
        return pair.view("<i8")[..., 0]

    @staticmethod
    def split_host(b64):
        """The host's int64 array -> (low words, high words), u32 each."""
        pair = np.ascontiguousarray(b64, "<i8").view("<u4").reshape(
            np.shape(b64) + (2,))
        return (np.ascontiguousarray(pair[..., 0]),
                np.ascontiguousarray(pair[..., 1]))

    @classmethod
    def to_host(cls, packed):
        """Device packed state -> the host / on-disk form
        `(b32, b64, scalars)`, numpy, `b64` int64."""
        b32, lo64, hi64, scalars = packed
        return (np.asarray(b32), cls.join_host(lo64, hi64),
                tuple(np.asarray(s) for s in scalars))

    @classmethod
    def from_host(cls, host):
        """The host form -> the packed state's arrays (numpy; the caller
        places them)."""
        b32, b64, scalars = host
        return (np.asarray(b32),) + cls.split_host(b64) + (tuple(scalars),)


@dataclasses.dataclass
class PlannedPatternQuery:
    name: str
    spec: PatternSpec
    exec: PatternExec
    in_schemas: Dict[str, ev.Schema]
    out_schema: ev.Schema
    output_target: str
    output_event_type: str
    # stream_id -> jitted step.  Every sequential step (these, the dense
    # ones, the sharded one) takes its timestamps on the wire of
    # core/event.py: (base i64 scalar, delta i32 [B]), see _jit_sequential.
    # Off the mesh these and the dense ones take the columns and the delta
    # GROUPED by the host, flat [Kb * E]; the block step and the sharded
    # one take the staged [B] batch
    steps: Dict[str, Callable]
    timer_step: Optional[Callable]
    # (K) -> ((b32, lo64, hi64, scalars), sel_state): the one jitted init,
    # placed by the plan's mesh (_init_program)
    init_state: Callable
    # () -> ([W32, 1], [W64, 1], [W64, 1], scalars): one fresh key's
    # packed column
    init_columns: Callable
    key_capacity: int
    slots: int
    partition_positions: Optional[Dict[str, List[int]]] = None
    # un-jitted scan bodies over the staged [B] batch (they gather the
    # [Kb, E] layout on the device: _gathering)
    raw_steps: Optional[Dict[str, Callable]] = None
    mesh: Any = None
    # contiguous-slot fast path: takes a scalar key_lo instead of key_idx and
    # reads/writes the state slab with dynamic slices — generic row
    # gather/scatter on TPU is row-serialized (~0.3us/row; 131k-key batch =
    # ~90ms), a contiguous slice is DMA-speed
    dense_steps: Optional[Dict[str, Callable]] = None
    # `steps` / `dense_steps` are the one-chip scan programs: the host
    # hands them the columns already in the per-key [Kb, E] order
    # (runtime._group_columns) and they gather nothing
    grouped_input: bool = False
    # the sequential scan programs (`steps`, `dense_steps`, the sharded
    # step) hand their emission over as a BandedEmission: rank bands on
    # the u32 wire, so delivery fetches the ranks a send used and no
    # 64-bit array.  True wherever there is a key axis to cut ranks over
    banded_emission: bool = False
    # False when the per-key emission cap is an implicit default: overflow
    # then raises instead of dropping rows (@emit(rows=N) opts into capping)
    emit_explicit: bool = True
    # (B) -> {tiers, cells, ticks, max_e}: what a send of B events costs a
    # step whose layout no key grouping decides (the block step), for the
    # `route_keys` span; None where the host lays the send out per send
    send_layout: Optional[Callable] = None
    # range partitions: stream_id -> host fn(staged) -> (key_cols, valid)
    # overriding positional key extraction (reference:
    # RangePartitionExecutor.java:45)
    partition_key_fns: Optional[Dict[str, Callable]] = None
    # the SelectorExec whose per-key accumulator slabs ride sel_state —
    # purge resets them through bank.specs (init values / slot spaces)
    selector_exec: Any = None
    # UUID() appears in this query: emission materializes sentinels once
    emits_uuid: bool = False
    # per-key emission row cap the steps compiled with (adaptive growth
    # doubles it after an implicit-cap overflow)
    compact_rows: int = 8
    # the un-jitted bodies `steps` was built from (block bodies when the
    # block path is active, else the scan bodies) — @fuse(batches=K) wraps
    # THESE in its lax.scan so fused and sequential execution run the
    # identical per-batch program (core/fusion.py); None on the mesh path
    step_bodies: Optional[Dict[str, Callable]] = None
    # mesh path's @fuse entry: one shard_map dispatch scanning K stacked
    # batches per device (fusion._dispatch_pattern_sharded); None off-mesh
    shard_fused_steps: Optional[Dict[str, Callable]] = None
    # what shared code (emission, the purger, snapshots, the observatory,
    # lint) reads off ANY plan and only a plain or a join plan sets: a
    # pattern ticks through `timer_step` and its wake rides the emission
    # (no `needs_timer` window), keeps no keyed-window slab, and its one
    # key allocator is the runtime's (shared per partition), not the
    # plan's
    needs_timer: bool = False
    keyed_window: bool = False
    keyed_mesh: Any = None
    mixed_kinds: bool = False
    slot_allocator: Any = None
    slot_allocator2: Any = None
    join_key_allocator: Any = None
    window_key_allocator: Any = None
    pair_allocs: Tuple = ()

    # the compact_rows default means "effectively uncapped" for
    # non-partitioned patterns (a per-key cap with K=1 would cap the
    # batch); the sentinel value and its rendering are shared with lint /
    # explain / healthz through core/plan_facts.py
    _UNCAPPED = plan_facts.UNCAPPED_SENTINEL

    def describe(self) -> Dict:
        """Compiled-plan facts for EXPLAIN (observability/explain.py):
        the NFA layout the planner built — key/slot capacities, emission
        cap, which step specializations exist — beyond the query AST."""
        d: Dict[str, Any] = {
            "streams": list(self.spec.stream_ids),
            "nfa_states": self.spec.n_states,
            "state_type": self.spec.state_type,
            "within_ms": self.spec.within,
            "key_capacity": self.key_capacity,
            "nfa_slots_per_key": self.slots,
            "partitioned": bool(self.partition_positions),
            "out_columns": list(self.out_schema.names),
            # per-batch step specializations the runtime can dispatch to
            "dense_slot_fast_path": self.dense_steps is not None,
            # a partitioned send whose keys' event counts are far apart
            # is laid out as tiers, each a dispatch of the same step,
            # their rank bands the send's one emission (runtime
            # process_staged; keyslots._tier_plan has the rule)
            "tiered_send_layout": bool(self.grouped_input and
                                       self.partition_positions),
            "banded_emission": self.banded_emission,
            "timer_step": self.timer_step is not None,
        }
        d["emission_cap_rows"] = plan_facts.render_cap(self.compact_rows)
        d["emission_cap_explicit"] = bool(self.emit_explicit)
        if self.mesh is not None:
            d["sharded_over_devices"] = int(self.mesh.devices.size)
            d["shard_fused_step"] = self.shard_fused_steps is not None
        # @serve (serving/): patterns are ring-eligible — wake-bearing
        # batches (within-window timers) still deliver inline, everything
        # else appends to the device ring
        d["serve_eligible"] = True
        return d


def plan_pattern_query(
    query: Query,
    name: str,
    schemas: Dict[str, ev.Schema],
    interner: ev.StringInterner,
    key_capacity: int = 1,
    slots: int = 8,
    count_cap: int = 8,
    partition_positions: Optional[Dict[str, List[int]]] = None,
    partition_key_fns: Optional[Dict[str, Callable]] = None,
    mesh=None,
    script_functions=None,
    compact_rows_override: Optional[int] = None,
) -> PlannedPatternQuery:
    sis = query.input_stream
    assert isinstance(sis, StateInputStream)
    # per-key emission row cap (device output compaction); overflow counted
    # in the out[1] scalar.  Tune with @emit(rows='N') on the query.  Only
    # partitioned queries compact by default: for K=1 a per-key cap would
    # cap the whole batch.  compact_rows_override carries the runtime's
    # adaptive growth after an implicit-cap overflow (state shapes do not
    # depend on the cap, so only the step functions rebuild).
    compact_rows = compact_rows_override or (
        8 if partition_positions else plan_facts.UNCAPPED_SENTINEL)
    emit_explicit = False
    for ann in query.annotations:
        if ann.name.lower() == "emit":
            compact_rows = int(ann.element("rows", compact_rows))
            emit_explicit = True
    spec = linearize(sis, count_cap=count_cap)
    for sid in spec.stream_ids:
        if sid not in schemas:
            raise CompileError(f"undefined stream {sid!r} in pattern")
    pexec = PatternExec(spec, schemas, interner, slots=slots,
                        emit_refs=_used_refs(query, spec),
                        script_functions=script_functions)

    out_target = query.output_stream.target_id if query.output_stream else ""
    # per-key aggregation: the selector's group slots are the partition keys
    group_slots = key_capacity if partition_positions else 64
    sel = SelectorExec(query.selector, pexec.scope,
                       _first_schema(spec, schemas), group_slots,
                       out_target or name, interner)
    if sel.bank.pair_sources:
        raise CompileError(
            "distinctCount/unionSet in pattern queries lands in a later "
            "phase")

    out_def = StreamDefinition(out_target or f"#{name}.out")
    for n, t in zip(sel.out_names, sel.out_types):
        out_def.attribute(n, t)
    out_schema = ev.Schema(out_def, interner)

    P = pexec.P
    refs = [a.ref for a in spec.all_atoms() if not a.absent]
    depths = {a.ref: a.capture_depth for a in spec.all_atoms() if not a.absent}

    packer = StatePacker(pexec.init_state(1))

    def make_step(stream_id: str, dense: bool = False,
                  banded: bool = False):
        schema = schemas[stream_id]

        def step(packed, sel_state, cols, ts, sel_idx, key_ref, now,
                 in_tabs=()):
            # cols/ts are the batch GROUPED per key, [Kb, E]: a key's
            # events along E in arrival order.  sel_idx [Kb, E] holds each
            # cell's batch index (-1 = padding; a padding cell carries
            # row 0's values, which no valid selection reads).
            # The body is a list of SECTIONS, each a `jax.named_scope`:
            # op-name metadata only, the compiled program is the same
            # without them (tests/test_spans.py).  A device trace carries
            # an op's section in its EVENT METADATA (the `tf_op` stat of
            # the plane's `event_metadata`, not a stat of the event), where
            # `benchmarks/harness/step_sections.py` reads it.
            *arrays, scalars = packed      # b32, lo64, hi64: each [W, K]
            with jax.named_scope("event_load"):
                cols = tuple(c.astype(d)
                             for c, d in zip(cols, schema.dtypes))
                valid = sel_idx >= 0
                ord_ = jnp.maximum(sel_idx, 0).astype(jnp.int64)
            Kb = ts.shape[0]
            with jax.named_scope("state_load"):
                if dense:
                    # key_ref is a scalar key_lo: the batch's slots are the
                    # contiguous range [key_lo, key_lo+Kb) -> DMA-speed
                    # slices
                    key_lo = jnp.asarray(key_ref, jnp.int32)
                    z = jnp.asarray(0, jnp.int32)
                    key_idx = key_lo + jnp.arange(Kb, dtype=jnp.int32)
                    subs = [lax.dynamic_slice(a, (z, key_lo),
                                              (a.shape[0], Kb))
                            for a in arrays]
                else:
                    # generic path: the keys' rows by the row-mover — by
                    # the 128-key block on the TPU, XLA's gathers riding
                    # the minor (key) axis elsewhere (state_rows.py)
                    key_idx = key_ref
                    n_live = state_rows.live_count(key_idx,
                                                   arrays[0].shape[1])
                    subs = state_rows.load(arrays, key_idx, n_live)
                # 64-bit values exist from here on, for these Kb keys only
                sub = packer.unpack(*subs, scalars)

            def body(carry, xs):
                st = carry
                cols_e, ts_e, valid_e = xs
                now_k = jnp.where(valid_e, ts_e, now)
                st, emit = pexec.tick(st, stream_id, cols_e, ts_e, valid_e,
                                      now_k, in_tabs)
                return st, emit

            # traced where it always was, after the rows' gather: the
            # scopes change no equation's place in the program
            with jax.named_scope("event_load"):
                xs = (tuple(c.T for c in cols), ts.T, valid.T)  # scan over E
            with jax.named_scope("nfa_advance"):
                sub, emits = lax.scan(body, sub, xs)

            with jax.named_scope("state_store"):
                *news, nscal = packer.pack(sub)
                if dense:
                    arrays = [lax.dynamic_update_slice(a, n, (z, key_lo))
                              for a, n in zip(arrays, news)]
                else:
                    # out-of-bounds (padding) rows are dropped
                    arrays = state_rows.store(arrays, news, key_idx, n_live)

            sel_state, out, wake = _emit_matches(
                pexec, sel, spec, emits, ord_, sel_state, sub, now,
                key_idx=key_idx, compact_rows=compact_rows, banded=banded)
            return (*arrays, nscal), sel_state, out, wake

        return step

    # raw_steps gather on the device: what ships an UNGROUPED batch — the
    # mesh steps, the @fuse stacks — runs these, and they keep the flat
    # emission (a @fuse stack is sliced per batch).  The sequential
    # programs — one-chip (the host has grouped: _jit_sequential) and
    # sharded — hand over rank bands wherever there is a key axis
    raw_steps = {sid: _gathering(make_step(sid)) for sid in spec.stream_ids}
    banded = partition_positions is not None

    dense_steps = None
    step_bodies = None
    shard_fused_steps = None
    grouped_input = False
    send_layout = None
    if mesh is None and partition_positions is None and \
            block_eligible(spec) and not _FORCE_SCAN:
        # single-key simple chain: the sequential scan would walk E tiny
        # [P, 1] ticks a send; the block path advances a whole chunk in
        # S-1 vectorized stages — see pattern_block.py
        step_bodies = {sid: make_block_step(
            spec, pexec, sel, schemas, packer, sid, compact_rows)
            for sid in spec.stream_ids}
        send_layout = functools.partial(block_layout, P=pexec.P, spec=spec)
        steps = {sid: _jit_sequential(b, name, "pattern_block")
                 for sid, b in step_bodies.items()}
    elif mesh is None:
        step_bodies = raw_steps
        grouped_input = True
        steps = {sid: _jit_sequential(make_step(sid, banded=banded), name,
                                      "pattern_step", grouped=True)
                 for sid in spec.stream_ids}
        dense_steps = {sid: _jit_sequential(
            make_step(sid, dense=True, banded=banded), name,
            "pattern_dense", grouped=True) for sid in spec.stream_ids}
    else:
        steps = {sid: _shard_step(
            _gathering(make_step(sid, banded=banded)), mesh, packer, sel,
            owner=name, banded=banded) for sid in spec.stream_ids}
        # @fuse over the mesh: scan-of-K-batches inside the shard_map
        # (fusion._dispatch_pattern routes stacks here)
        shard_fused_steps = {
            sid: _shard_fused_step(body, mesh, packer, sel,
                                   owner=f"fused:{name}")
            for sid, body in raw_steps.items()}

    timer_step = None
    if spec.has_absent:
        any_sid = spec.stream_ids[0]
        schema0 = schemas[any_sid]

        def tstep(packed, sel_state, now, in_tabs=()):
            pstate = packer.unpack(*packed)
            K = pstate.active.shape[-1]
            zero_cols = tuple(
                jnp.full((K,), ev.default_value(t), dtype=d)
                for t, d in zip(schema0.types, schema0.dtypes))
            ts_e = jnp.full((K,), now, jnp.int64)
            valid_e = jnp.zeros((K,), jnp.bool_)
            now_k = jnp.full((K,), now, jnp.int64)
            st, emit = pexec.tick(pstate, any_sid, zero_cols, ts_e, valid_e,
                                  now_k, in_tabs)
            emits = jax.tree.map(lambda x: x[None], emit)  # E=1
            ord_ = jnp.zeros((K, 1), jnp.int64)
            sel_state, out, wake = _emit_matches(
                pexec, sel, spec, emits, ord_, sel_state, st, now)
            npacked = packer.pack(st)
            # per-key changed mask so the host marks ONLY mutated keys dirty
            # (a full-slab dirty would turn every incremental snapshot after
            # a timer fire into a full one); the planes compare as they lie
            changed = functools.reduce(jnp.logical_or, (
                jnp.any(n != o, axis=0)
                for n, o in zip(npacked[:-1], packed[:-1])))
            return npacked, sel_state, out, wake, changed

        timer_step = jit_step(tstep, owner=name, role="pattern_timer",
                              donate_argnums=(0, 1))

    init_state = _init_program(packer, pexec, sel, mesh)
    init_columns = functools.partial(_init_columns, packer, pexec)

    return PlannedPatternQuery(
        name=name, spec=spec, exec=pexec,
        in_schemas={sid: schemas[sid] for sid in spec.stream_ids},
        out_schema=out_schema,
        output_target=out_target,
        output_event_type=(query.output_stream.output_event_type
                           if query.output_stream and
                           query.output_stream.output_event_type
                           else "CURRENT_EVENTS"),
        steps=steps, dense_steps=dense_steps,
        timer_step=timer_step, init_state=init_state,
        init_columns=init_columns,
        key_capacity=key_capacity, slots=slots,
        partition_positions=partition_positions,
        partition_key_fns=partition_key_fns,
        raw_steps=raw_steps, mesh=mesh,
        grouped_input=grouped_input,
        banded_emission=banded,
        emit_explicit=emit_explicit, selector_exec=sel,
        emits_uuid=pexec.scope.uses_uuid,
        compact_rows=compact_rows, step_bodies=step_bodies,
        shard_fused_steps=shard_fused_steps, send_layout=send_layout)


def _gathering(body):
    """A sequential step body for callers that ship the UNGROUPED batch
    `[B]`: the `[Kb, E]` gather by `sel_idx` happens here, on the device.
    The mesh steps keep it — a shard's grouped layout is half padding and
    their host is the busier side — and so do the @fuse stacks.

    ONE gather for all the columns and the timestamp: their u32 planes
    (`u32_planes`) stacked `[P, B]` and gathered along B.  The v5e gathers by
    the SLICE, not by the element: six planes a slice cost 0.88 ms at
    262,144 slices where six gathers of one element cost 1.87 ms EACH —
    and 5.3-7.2 ms each once XLA's memory-space assignment put their
    results in HBM, which a change to the program's OUTPUTS was enough
    to cause (PERF.md, PR 31)."""
    def step(packed, sel_state, raw_cols, raw_ts, sel_idx, key_ref, now,
             in_tabs=()):
        with jax.named_scope("event_load"):
            arrays = (*raw_cols, raw_ts)
            csel = jnp.clip(sel_idx, 0, raw_ts.shape[0] - 1)
            planes = iter(jnp.stack(
                [pl for a in arrays for pl in u32_planes(a)])[:, csel])
            *cols, ts = (from_u32_planes(planes, a.dtype) for a in arrays)
        return body(packed, sel_state, tuple(cols), ts, sel_idx, key_ref,
                    now, in_tabs)
    return step


def _jit_sequential(body, owner, role, grouped=False):
    """The jitted form of a sequential step body, one-chip or
    shard_map'd: where the body takes `raw_ts`, an `i64 [B]` column, the
    program takes `(ts_base, ts_delta)`, the timestamp wire the host ships
    (`ev.encode_ts`), and decodes it here, once, inside the same jit.
    `ts_delta` is int32 on every batch spanning under 2**31 ms; a wider
    batch sends int64 and this same callable specialises on it (one
    compile, counted under `owner` like any other).  The bodies keep
    `raw_ts`: @fuse scans them over a stacked `i64 [K, B]` (fusion.py).

    `grouped`: the host has put the columns and the delta in the per-key
    order already (`PatternQueryRuntime.process_staged`) and ships each
    as the flat `[Kb * E]` buffer; the program reshapes to `sel_idx`'s
    `[Kb, E]` and gathers nothing.  Flat, because a 2-D host array with a
    minor dimension of E meets the TPU's 128-lane tiling at upload."""
    def step(packed, sel_state, raw_cols, ts_base, ts_delta, sel_idx,
             key_ref, now, in_tabs=()):
        # the program's outermost scope names its [Kb, E] rectangle (static:
        # a rectangle is a signature already), so a trace tells a tiered
        # send's executions apart; the sections of the body nest in it
        with jax.named_scope("rect_%dx%d" % sel_idx.shape):
            with jax.named_scope("event_load"):
                ts = ev.decode_ts(ts_base, ts_delta)
                if grouped:
                    raw_cols = tuple(c.reshape(sel_idx.shape)
                                     for c in raw_cols)
                    ts = ts.reshape(sel_idx.shape)
            return body(packed, sel_state, raw_cols, ts, sel_idx, key_ref,
                        now, in_tabs)
    return jit_step(step, owner=owner, role=role, donate_argnums=(0, 1))


def _first_schema(spec: PatternSpec, schemas) -> ev.Schema:
    return schemas[spec.stream_ids[0]]


def _used_refs(query: Query, spec: PatternSpec) -> set:
    """Refs whose captures the selector can touch (emission pruning)."""
    from ..query_api.expression import Variable, walk
    refs = {a.ref for a in spec.all_atoms() if not a.absent}
    sel = query.selector
    if sel.is_select_all:
        return refs      # select * touches everything
    used = set()
    exprs = [oa.expression for oa in sel.selection_list]
    if sel.having_expression is not None:
        exprs.append(sel.having_expression)
    exprs.extend(sel.group_by_list)
    exprs.extend(ob.variable for ob in sel.order_by_list)
    unqualified = False
    for e in exprs:
        for node in walk(e):
            if isinstance(node, Variable):
                if node.stream_id is not None and node.stream_id in refs:
                    used.add(node.stream_id)
                elif node.stream_id is None:
                    unqualified = True
    if unqualified:
        return refs      # can't prove which source an unqualified attr hits
    return used


def _shard_specs(packer: "StatePacker", sel: SelectorExec):
    """(pattern-state spec, selector-state spec) for the sharded pattern
    layouts — the i32 blob and the two 64-bit planes are [W, K] with the
    key (shard) axis at axis 1; selector slabs shard axis 0; scalars
    replicate.  Read from shapes
    alone (`eval_shape`): nothing is allocated to learn a layout."""
    from jax.sharding import PartitionSpec as P

    def leaf_spec(x):
        return P() if x.ndim == 0 else P("shard")

    pspec = (P(None, "shard"),) * 3 + (tuple(P() for _ in packer.scalars),)
    sspec = jax.tree.map(leaf_spec, jax.eval_shape(sel.init_state))
    return pspec, sspec


def _init_columns(packer: "StatePacker", pexec: PatternExec):
    """([W32, 1], [W64, 1], [W64, 1], scalars): the packed state of ONE
    fresh key —
    what `_init_program` broadcasts along the key axis and what the
    partition purger writes back over a recycled key's column."""
    return packer.pack(pexec.init_state(1))


def _init_program(packer: "StatePacker", pexec: PatternExec,
                  sel: SelectorExec, mesh):
    """The ONE state-init path, mesh or no mesh: `init_state(K)` ->
    ((b32, lo64, hi64, scalars), selector state) from one jitted program whose
    `out_shardings` are the NamedShardings of `_shard_specs` (none
    without a mesh: the default device).

    Every NFA leaf starts key-uniform (`PatternExec.init_state` fills
    each with one value — the purger's reset column rests on the same
    fact), so a blob or plane is its one-key column broadcast along the
    key axis:
    XLA writes each [W, K/n] share where it lives, in place.  No chip
    ever holds a per-leaf slab, a concatenated second copy or another
    chip's share — at 33,554,432 keys the whole state is 17.4 GB and no
    single chip could.  Every call returns buffers of its own, so two
    runtimes of one plan (or a regrown one) never alias each other and
    each may donate its state to its steps; nothing needs copying
    afterwards."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def pattern_init(K: int):
        *cols, scalars = _init_columns(packer, pexec)
        return ((*(jnp.broadcast_to(c, (c.shape[0], K)) for c in cols),
                 scalars), sel.init_state())

    shardings = None
    if mesh is not None:
        shardings = jax.tree.map(
            lambda spec: NamedSharding(mesh, spec),
            _shard_specs(packer, sel),
            is_leaf=lambda x: isinstance(x, P))
    return jax.jit(pattern_init, static_argnums=0, out_shardings=shardings)


def _shard_local(body):
    """Per-device body shared by the sequential sharded step and the
    fused (scan) variant: replicated inputs are marked device-varying,
    the unmodified single-device `body` runs over local key rows, and
    the replicated outputs merge (header psum, scalar-counter delta
    psum, wake pmin)."""

    def local(packed, sel_state, raw_cols, raw_ts, sel_idx, key_idx, now,
              in_tabs=()):
        *arrays, scalars = packed
        old_scalars = scalars
        with jax.named_scope("mesh_reduce"):
            # replicated scalar counters become device-varying inside; mark
            # them
            scalars = tuple(lax.pcast(s, ("shard",), to="varying")
                            for s in scalars)
            raw_cols = tuple(lax.pcast(c, ("shard",), to="varying")
                             for c in raw_cols)
            raw_ts = lax.pcast(raw_ts, ("shard",), to="varying")
            in_tabs = jax.tree.map(
                lambda x: lax.pcast(x, ("shard",), to="varying"), in_tabs)
        ps, ss, out, wake = body((*arrays, scalars), sel_state, raw_cols,
                                 raw_ts, sel_idx, key_idx, now, in_tabs)
        # a chip that finishes early waits in these collectives, and the
        # trace books the wait as busy: the section is where skew shows
        with jax.named_scope("mesh_reduce"):
            if isinstance(out, BandedEmission):
                # a plain pair out of the shard_map (its out_specs name the
                # header's scalars and the bands apart); _shard_step
                # re-wraps
                (n_valid, n_dropped, ranks_used), bands = out.tiers[0]
                out = ((lax.psum(n_valid, "shard"),
                        lax.psum(n_dropped, "shard"),
                        lax.pmax(ranks_used, "shard")), bands)
            else:
                out = (lax.psum(out[0], "shard"),
                       lax.psum(out[1], "shard")) + out[2:]
            *narrays, nscal = ps
            # re-replicate scalar counters: old + psum(local delta)
            nscal = tuple(
                old + lax.psum(
                    new - lax.pcast(old, ("shard",), to="varying"), "shard")
                for old, new in zip(old_scalars, nscal))
            wake = pmin_i64(wake, "shard")
        return (*narrays, nscal), ss, out, wake

    return local


def _shard_step(body, mesh, packer: "StatePacker", sel: SelectorExec,
                owner=None, banded: bool = False):
    """Shard the pattern step over the mesh 'shard' axis.

    Design (scaling-book style): partition keys are the shard axis — each
    device owns K/n key rows of NFA + aggregation state, the host routes
    events to their key's shard (sharding/router.py: slot % n), and the
    per-device step is the unmodified single-device body.  Keys are
    independent so the data path needs NO cross-device communication;
    only the scalar next-wakeup reduction (pmin) and the overflow counter
    (psum) ride the ICI.  This replaces the reference's
    thread-per-Disruptor scale-up (CORE/stream/StreamJunction.java:296)
    with SPMD scale-out.

    `banded`: the body hands over rank bands (`_emit_matches`); a band's
    buffer is one more `bspec` output — every shard's planes of its own
    [rb, Kb] ranks, shard after shard — and `ranks_used` one more
    replicated scalar, the `pmax` beside the header's `psum`.
    """
    from jax.sharding import PartitionSpec as P

    pspec, sspec = _shard_specs(packer, sel)
    bspec = P("shard")    # sharded inputs: [n*Kb, ...] on axis 0
    rspec = P()           # raw event columns [B]: replicated to all shards
    ospec = ((P(), P(), P()), bspec) if banded else \
        (P(), P(), bspec, bspec, bspec, bspec)
    sharded = jax.shard_map(
        _shard_local(body), mesh=mesh,
        in_specs=(pspec, sspec, rspec, rspec, bspec, bspec, P(), P()),
        out_specs=(pspec, sspec, ospec, P()))
    if not banded:
        return _jit_sequential(sharded, owner, "pattern_step_sharded")
    n = int(mesh.devices.size)

    def step(*args):
        ps, ss, tier, wake = sharded(*args)
        return ps, ss, BandedEmission((tier,), shards=n), wake
    return _jit_sequential(step, owner, "pattern_step_sharded")


def _shard_fused_step(body, mesh, packer: "StatePacker",
                      sel: SelectorExec, owner=None):
    """@fuse(batches=K) over the MESH: one shard_map dispatch whose local
    body is a lax.scan over K stacked batches — per-dispatch overhead
    (and the per-send emission fetch) divides by K per shard,
    the design lever ROADMAP item 1 names for the sharded serving path.
    The scan sits INSIDE the shard_map, so every iteration runs the same
    per-device program as the sequential sharded step and parity is
    byte-identical; stacked inputs carry a leading [K] axis with the
    sharded [n*Kb] axes shifted to axis 1."""
    from jax.sharding import PartitionSpec as P
    from .steputil import strongify

    pspec, sspec = _shard_specs(packer, sel)
    local = _shard_local(body)
    bspec2 = P(None, "shard")   # stacked sharded inputs: [K, n*Kb, ...]

    def fused_local(carry, xs, in_tabs):
        def scan_body(c, x):
            packed, sel_state = c
            raw_cols, raw_ts, sel_idx, key_idx, now = x
            ps, ss, out, _wake = local(packed, sel_state, raw_cols,
                                       raw_ts, sel_idx, key_idx, now,
                                       in_tabs)
            return strongify((ps, ss)), out
        return lax.scan(scan_body, carry, xs)

    sharded = jax.shard_map(
        fused_local, mesh=mesh,
        in_specs=((pspec, sspec), (P(), P(), bspec2, bspec2, P()), P()),
        out_specs=((pspec, sspec),
                   (P(), P(), bspec2, bspec2, bspec2, bspec2)))
    return jit_step(sharded, owner=owner, role="fused_pattern_sharded",
                    donate_argnums=(0,))


def band_edges(R: int) -> tuple:
    """Rank edges of a banded emission of `R` ranks: (0, 1, 4, 16, 64,
    256, R) cut off at R — band b holds ranks [edges[b], edges[b + 1]).
    Fixed at plan time and a factor 4 apart: a buffer costs ~75 us in a
    `device_get` whatever it holds (PERF.md, PR 31), so a finer cut would
    pay more in buffers than it saves in slots."""
    edges, e = [0], 1
    while e < R:
        edges.append(e)
        e *= 4
    return (*edges, R)


def unpack_planes(bufs, dtypes, shards: int = 1) -> list:
    """Host side of `steputil.u32_planes`: `bufs` are the fetched u32
    buffers of the
    bands delivery took (each: every shard's planes of `dtypes`' arrays
    over the shard's slots of the band, shard after shard), the result
    one array a dtype over all of their slots, the bands end to end.
    Every word is written once, where it ends up."""
    words = [2 if np.dtype(d).itemsize == 8 else 1 for d in dtypes]
    sizes = [b.size // sum(words) for b in bufs]
    outs = [np.empty(sum(sizes), d) for d in dtypes]
    at = 0
    for buf, m in zip(bufs, sizes):
        pl = buf.reshape(shards, sum(words), m // shards)
        p = 0
        for o, w in zip(outs, words):
            dst = o[at:at + m]
            if dst.dtype == np.bool_:
                np.not_equal(pl[:, p], 0, out=dst.reshape(shards, -1))
            else:
                dst = dst.view(np.uint32).reshape(shards, -1, w)
                for i in range(w):          # little-endian: low word first
                    dst[:, :, i] = pl[:, p + i]
            p += w
        at += m
    return outs


# what rides the head buffer of a band: ts, then kind with valid in bit 31
HEAD_DTYPES = (np.int64, np.uint32)
HEAD_WORDS = 3
_VALID_BIT = 31


def valid_slots(head_bufs, shards: int = 1) -> list:
    """Per fetched band, the slots (in the order `unpack_planes` lays them
    out) whose row is valid: bit 31 of the head buffer's last plane."""
    return [np.flatnonzero(
        buf.reshape(shards, HEAD_WORDS, -1)[:, -1].reshape(-1) >> _VALID_BIT)
        for buf in head_bufs]


def unpack_planes_at(bufs, dtypes, keeps, shards: int = 1) -> list:
    """`unpack_planes` for the slots `keeps` lists a band (`valid_slots`)
    and no other: each word is read where it lies on the wire and written
    once.  A rank rectangle is sized by its fullest key — at a tenth full
    (a count pattern: 32 ranks for 3.3 rows a key) decoding every slot and
    masking afterwards costs six times what the rows do."""
    words = [2 if np.dtype(d).itemsize == 8 else 1 for d in dtypes]
    outs = [np.empty(sum(k.size for k in keeps), d) for d in dtypes]
    at = 0
    for buf, keep in zip(bufs, keeps):
        pl = buf.reshape(shards, sum(words), -1)
        p = 0
        for o, w in zip(outs, words):
            dst = o[at:at + keep.size]
            if dst.dtype == np.bool_:
                np.not_equal(pl[:, p].reshape(-1)[keep], 0, out=dst)
            else:
                dst = dst.view(np.uint32).reshape(-1, w)
                for i in range(w):          # little-endian: low word first
                    dst[:, i] = pl[:, p + i].reshape(-1)[keep]
            p += w
        at += keep.size
    return outs


@jax.tree_util.register_pytree_node_class
class BandedEmission:
    """A pattern send's emission as RANK BANDS: what the sequential scan
    programs hand over wherever there is a key axis (`_emit_matches`,
    `banded`), a type of its own so that no delivery path mistakes it for
    the flat 6-tuple the other emitters keep.

    `tiers`: one `(header, bands)` a `[Kb, E]` tier of the send (one for
    a one-rectangle send; the runtime puts a tiered send's end to end,
    `joined`).  `header` = (n_valid i64, n_dropped i64, ranks_used i32):
    `ranks_used` is the highest per-key row count of the tier, clipped to
    its R — a key with c rows fills ranks 0 .. c-1, so no row sits at or
    above it.  `bands[b]` = (head, cols): two u32 buffers holding the
    planes (`u32_planes`) of ranks [edges[b], edges[b + 1]) x K slots,
    rank-major — `head` the timestamp's two planes and `kind | valid <<
    31`, `cols` the output columns' planes in schema order.  Band 0 is
    one rank, so a buffer's size says how many ranks it holds.
    `shards`: under a mesh a buffer holds every shard's planes, shard
    after shard, each over its own Kb keys.

    Delivery fetches the headers, then only the bands below `ranks_used`
    (`used`), and decodes them on the host (`unpack_planes`)."""

    __slots__ = ("tiers", "shards")

    def __init__(self, tiers, shards: int = 1):
        self.tiers = tuple(tiers)
        self.shards = shards

    def tree_flatten(self):
        return (self.tiers,), self.shards

    @classmethod
    def tree_unflatten(cls, shards, children):
        return cls(children[0], shards)

    @staticmethod
    def joined(emissions) -> "BandedEmission":
        """The emissions of a send's tiers as the send's one."""
        return BandedEmission(
            tuple(t for e in emissions for t in e.tiers), emissions[0].shards)

    @property
    def headers(self):
        """What delivery fetches first: ((n_valid, n_dropped, ranks_used),
        ...), one a tier."""
        return tuple(h for h, _ in self.tiers)

    def used(self, ranks_used):
        """(bands to fetch, ranks they hold, ranks of all bands): of
        each tier the bands that start below its `ranks_used`, in tier
        order."""
        take, ranks, cap = [], 0, 0
        for (_, bands), ru in zip(self.tiers, ranks_used):
            lo = 0
            for head, cols in bands:
                rb = head.size // bands[0][0].size
                if lo < ru:
                    take.append((head, cols))
                    ranks += rb
                lo += rb
            cap += lo
        return take, ranks, cap


def compact_emission(out, EP: int, K: int, compact_rows: int,
                     band_types=None):
    """The selector's output rows over the [EP, K] grid — (ts, kind,
    valid, cols), each [EP * K] — as the step's emission: per key the
    first R = min(compact_rows, EP) valid rows in grid order (rank r of
    key k), the rest counted as dropped.

    Flat (`band_types` None): (n_valid, n_dropped, ts, kind, valid, cols)
    over [R * K] slots, rank r of key k at r * K + k; where R == EP the
    grid as it is.  Banded (`band_types`: the output columns' attribute
    types): a `BandedEmission` of one tier — always rank-major (where R
    == EP too: the same contraction, R x EP x K cells), cut into
    `band_edges(R)`, on the u32 wire, `ranks_used` in its header.  R, and
    with it what is dropped, is the same in both."""
    ots, okind, ovalid, ocols = out
    banded = band_types is not None
    R = min(compact_rows, EP)
    if R < EP or banded:
        with jax.named_scope("emission_compaction"):
            v2 = ovalid.reshape(EP, K)
            rank = jnp.cumsum(v2.astype(jnp.int32), axis=0) - 1
            keep_oh = jnp.logical_and(
                jnp.arange(R, dtype=jnp.int32)[:, None, None] == rank[None],
                v2[None])                          # [R,EP,K]
            cmask = jnp.any(keep_oh, axis=1)       # [R,K]
            n_valid = jnp.sum(cmask.astype(jnp.int64))
            n_dropped = jnp.sum(v2.astype(jnp.int64)) - n_valid

            def cmp(x):                            # [B] -> [R,K]
                return oh_take(x.reshape(EP, K)[None], keep_oh, 1)

            if not banded:
                out = (cmp(ots).reshape(R * K), cmp(okind).reshape(R * K),
                       cmask.reshape(R * K),
                       tuple(cmp(c).reshape(R * K) for c in ocols))
    else:
        with jax.named_scope("emission_compaction"):
            n_valid = jnp.sum(ovalid.astype(jnp.int64))
            n_dropped = jnp.zeros((), jnp.int64)
    if not banded:
        # leading scalars: valid-row count (drainer skips empty outputs
        # with one 16-byte read) and overflow count (rows beyond R
        # matches/key/batch)
        return (n_valid, n_dropped) + out
    with jax.named_scope("emission_bands"):
        kind_valid = cmp(okind).astype(jnp.uint32) | \
            (cmask.astype(jnp.uint32) << _VALID_BIT)
        head = u32_planes(cmp(ots)) + [kind_valid]
        # the columns in the out schema's own dtypes: what the host
        # decodes the planes by (runtime._EmissionRows)
        body = [pl for c, t in zip(ocols, band_types)
                for pl in u32_planes(cmp(c).astype(ev.dtype_of(t)))]

        def band(planes, lo, hi):                  # [R,K] each -> u32 wire
            return jnp.concatenate(
                [pl[lo:hi].reshape((hi - lo) * K) for pl in planes])

        edges = band_edges(R)
        bands = tuple((band(head, lo, hi), band(body, lo, hi))
                      for lo, hi in zip(edges, edges[1:]))
        # int32 throughout: XLA:TPU lowers no 64-bit pmax (the mesh's)
        ranks_used = jnp.max(jnp.sum(cmask, axis=0, dtype=jnp.int32))
    return BandedEmission((((n_valid, n_dropped, ranks_used), bands),))


def _emit_matches(pexec: PatternExec, sel: SelectorExec, spec: PatternSpec,
                  emits, ord_, sel_state, pstate, now, key_idx=None,
                  compact_rows: int = 8, banded: bool = False):
    """Flatten scan emissions [E,P+1,K] into selector Rows + env, then
    compact the selector's OUTPUT rows per key.

    The selector over the full E*(P+1)*K grid is cheap (elementwise, XLA
    fuses it); only the final output rows are compacted, [EP,K] -> [R,K],
    as a one-hot contraction over the tiny EP axis — no device gathers (a
    searchsorted/sort compaction costs ~80ms at 131k keys: TPU lowers both
    to serialized gathers; compacting the ~25 capture arrays instead of the
    ~7 output arrays costs GBs of HBM traffic).  Valid rows beyond R
    matches per key per batch are counted in the out[1] dropped scalar
    (`compact_emission`; `banded`: as a `BandedEmission`)."""
    with jax.named_scope("match_rows"):
        rows, env, EP, K = _match_rows(spec, emits, ord_, now, key_idx)
    with jax.named_scope("selector"):
        sel_state, out = sel.process(sel_state, rows, env)

    out = compact_emission(out, EP, K, compact_rows,
                           sel.out_types if banded else None)

    with jax.named_scope("match_rows"):
        wake = _next_wake(spec, pstate)
    return sel_state, out, wake


def _match_rows(spec: PatternSpec, emits, ord_, now, key_idx):
    """(rows, env, EP, K): the scan's emissions [E,P+1,K] flattened into
    the selector's Rows over the E*(P+1)*K grid, and the capture env."""
    mask = emits["mask"]                       # [E,P+1,K]
    E, P1, K = mask.shape
    EP = E * P1
    B = EP * K

    flat = lambda x: x.reshape(B)
    rows_ts = flat(emits["ts"])
    # order: by arrival (ord), then slot index
    slot_rank = jnp.broadcast_to(
        jnp.arange(P1, dtype=jnp.int64)[None, :, None], mask.shape)
    ord_ekp = jnp.broadcast_to(
        jnp.transpose(ord_)[:, None, :].astype(jnp.int64), mask.shape)
    seq = flat(ord_ekp * (P1 + 1) + slot_rank)

    env: Dict[str, Any] = {"__ts__": rows_ts, "__now__": now}
    for a in spec.all_atoms():
        if a.absent or a.ckey not in emits:
            continue
        cap_ts, cap_cols = emits[a.ckey]       # [E,P+1,D,K]
        D = cap_ts.shape[2]
        env[a.ref] = tuple(c[:, :, 0, :].reshape(B) for c in cap_cols)
        for i in range(D):
            env[f"{a.ref}@{i}"] = tuple(
                c[:, :, i, :].reshape(B) for c in cap_cols)
        # e1[last] = deepest FILLED capture row; the count scalar is
        # position-local (resets when a fork advances past the count atom)
        # so the fill depth derives from the capture ts plane (unfilled
        # rows hold -1; a real event at timestamp 0 still counts)
        # PART `last_capture`: the fill depth and a one-hot contraction
        # over D a column, all E * (P + 1) * K rows of it
        with jax.named_scope("last_capture"):
            nfill = jnp.sum((cap_ts >= 0).astype(jnp.int32),
                            axis=2)                     # [E,P+1,K]
            last_i = jnp.clip(nfill - 1, 0, D - 1)
            last_oh = (jnp.arange(D)[None, None, :, None] ==
                       last_i[:, :, None, :])           # [E,P+1,D,K]
            env[f"{a.ref}@-1"] = tuple(
                flat(oh_take(c, last_oh, 2)) for c in cap_cols)

    if key_idx is not None:
        gslot = flat(jnp.broadcast_to(
            key_idx[None, None, :].astype(jnp.int32), mask.shape))
        gslot = jnp.maximum(gslot, 0)
    else:
        gslot = jnp.zeros((B,), jnp.int32)
    rows = Rows(
        ts=rows_ts,
        kind=jnp.full((B,), ev.CURRENT, jnp.int32),
        valid=flat(mask),
        seq=seq,
        gslot=gslot,
        cols=(),
    )
    return rows, env, EP, K


def _next_wake(spec: PatternSpec, pstate):
    """Next wakeup: earliest absent deadline (standalone `not X for t` atoms
    and timed absent sides of logical pairs whose wait hasn't elapsed)."""
    wake = jnp.asarray(NO_WAKEUP, jnp.int64)
    for a in spec.atoms:
        if a.absent:
            at_pos = jnp.logical_and(pstate.active, pstate.pos == a.pos)
            w = jnp.min(jnp.where(at_pos, pstate.entry_ts + a.waiting_time,
                                  NO_WAKEUP))
            wake = jnp.minimum(wake, w)
        elif a.partner is not None and a.partner.absent and \
                a.partner.waiting_time is not None:
            at_pos = jnp.logical_and(
                jnp.logical_and(pstate.active, pstate.pos == a.pos),
                (pstate.lmask & 2) == 0)
            w = jnp.min(jnp.where(
                at_pos, pstate.entry_ts + a.partner.waiting_time, NO_WAKEUP))
            wake = jnp.minimum(wake, w)
    return wake

"""Query planner: query_api AST -> compiled, jitted step functions.

Reference role (what): CORE/util/parser/QueryParser.java:90 +
SingleInputStreamParser/SelectorParser/OutputParser — there the "plan" is a
graph of interpreter objects.  Here each query compiles to ONE pure function
    step(state, batch, gslot, now) -> (state', output rows, next_wakeup)
traced and compiled once per batch bucket by XLA, with all filters, the
window, aggregation scans and projections fused into a single device program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..query_api.definition import StreamDefinition
from ..query_api.query import (
    Filter,
    Query,
    SingleInputStream,
    StreamFunction,
    Window,
)
from . import event as ev
from .executor import CompileError, Scope, compile_expression
from .steputil import jit_step, pmin_i64
from .keyslots import SlotAllocator
from .selector import SelectorExec
from .window import NoWindow, Rows, WindowProcessor, create_window


@dataclasses.dataclass
class PlannedQuery:
    """Compiled single-input query."""

    name: str
    input_stream_id: str
    in_schema: ev.Schema
    out_schema: ev.Schema
    output_target: str                 # target stream/table id ('' => return)
    output_event_type: str             # CURRENT_EVENTS/EXPIRED_EVENTS/ALL_EVENTS
    window: WindowProcessor
    group_by_positions: List[int]
    selector_exec: SelectorExec
    step: Callable                     # jitted
    init_state: Callable
    slot_allocator: Optional[SlotAllocator]
    batch_capacity: int
    needs_timer: bool
    in_deps: List[str] = dataclasses.field(default_factory=list)
    # range partitions: host fn(staged) -> (key_id col int32, valid mask);
    # rows matching no range are excluded (reference:
    # RangePartitionExecutor.java:45 returns null -> event dropped)
    partition_key_fn: Optional[Callable] = None
    # keyed windows (windows inside partitions): one window state per
    # partition key, vmapped over the key axis
    keyed_window: bool = False
    window_key_allocator: Optional[SlotAllocator] = None
    window_key_positions: Optional[List[int]] = None
    key_capacity: int = 0
    # distinctCount: (pair allocator, value-column position) per call —
    # (group, value) pairs resolve to refcount slots on the host
    pair_allocs: List[Tuple[SlotAllocator, int]] = \
        dataclasses.field(default_factory=list)
    # set when the windowless group-by step is sharded over a device mesh
    # (slot s lives at state row (s % n) * (G/n) + s // n — purge resets
    # must remap through this layout, _PartitionPurger)
    mesh: Any = None
    # set when the keyed-window slab is sharded (key k at row
    # (k % n) * (K/n) + k // n; selector state stays replicated)
    keyed_mesh: Any = None
    # UUID() appears in this query: emission materializes sentinels once
    emits_uuid: bool = False
    # un-jitted step body for @fuse(batches=K) scan fusion (core/fusion.py);
    # None on the keyed-window and sharded paths, which don't fuse
    raw_step: Optional[Callable] = None
    # the two halves of raw_step, exposed for the whole-app multi-query
    # optimizer (siddhi_tpu/optimizer): stage_body runs the pre-window
    # chain + window (shared once per merge group), select_body runs the
    # post-chain + selector over the window's output rows (stacked per
    # member).  raw_step == stage_body ∘ select_body by construction.
    stage_body: Optional[Callable] = None
    select_body: Optional[Callable] = None
    # what shared code (emission, snapshots, the observatory, lint) reads
    # off ANY plan and only a pattern or a join plan sets: no emission
    # cap (a plain emission ships whole), no second-side / join-key
    # allocator, CURRENT-only counts in a header
    compact_rows: Optional[int] = None
    emit_explicit: bool = False
    mixed_kinds: bool = False
    slot_allocator2: Optional[SlotAllocator] = None
    join_key_allocator: Optional[SlotAllocator] = None

    def describe(self) -> Dict:
        """Compiled-plan facts for EXPLAIN (observability/explain.py):
        what the planner chose — window processor, capacities, slot
        spaces, sharding — beyond what the query AST shows."""
        d: Dict[str, Any] = {
            "input_stream": self.input_stream_id,
            "batch_capacity": self.batch_capacity,
            "window_processor": type(self.window).__name__,
            "needs_timer": self.needs_timer,
            "in_columns": list(self.in_schema.names),
            "out_columns": list(self.out_schema.names),
        }
        if self.slot_allocator is not None:
            d["group_slot_capacity"] = self.slot_allocator.capacity
        if self.keyed_window:
            d["keyed_window"] = True
            d["key_capacity"] = self.key_capacity
        if self.partition_key_fn is not None:
            d["range_partition"] = True
        if self.pair_allocs:
            d["distinct_pair_slots"] = [a.capacity
                                        for a, _ in self.pair_allocs]
        if self.selector_exec.has_aggregation:
            d["selector_layout"] = self.selector_exec.bank.layout
        if self.mesh is not None or self.keyed_mesh is not None:
            m = self.mesh or self.keyed_mesh
            d["sharded_over_devices"] = int(m.devices.size)
        if self.in_deps:
            d["table_probes"] = list(self.in_deps)
        # @serve (serving/): timer-bearing windows deliver inline so wake
        # scheduling stays synchronous — same exclusion as @pipeline
        d["serve_eligible"] = not self.needs_timer
        return d


def _env_for(scope_key: str, cols, ts):
    return {scope_key: cols, "__ts__": ts}


def _apply_chain(chain, env, sid, cols, keep, data_row):
    """Run a filter/stream-fn handler chain over columnar rows.  Filters
    only gate `data_row` rows (TIMER/RESET pass through untouched)."""
    for entry in chain:
        if entry[0] == "filter":
            m = entry[1].fn(env)
            keep = jnp.logical_and(
                keep, jnp.logical_or(jnp.logical_not(data_row), m))
        else:
            _, dtypes, fn = entry
            new_cols, keep = fn(env, keep)
            cols = cols + tuple(
                jnp.asarray(c, d) for c, d in zip(new_cols, dtypes))
            env[sid] = cols
    return env, cols, keep


def _merge_rows(ovalid, col):
    """Merge row-aligned per-device outputs: each row is valid on exactly
    one device, so zero-the-rest + psum reconstructs the global row."""
    from jax import lax
    z = jnp.where(ovalid, col, jnp.zeros_like(col))
    if col.dtype == jnp.bool_:
        return lax.psum(z.astype(jnp.int32), "shard") > 0
    return lax.psum(z, "shard")


def _shard_plain_step(step, mesh, sel, wproc, group_slots: int,
                      owner=None):
    """Shard a windowless partitioned group-by step over the mesh.

    Design (same scaling-book recipe as the pattern path): group slots are
    the shard axis — each device owns a G/n block of every accumulator
    slab.  Event rows replicate to all devices; each device masks `valid`
    to the rows whose slot falls in its block and runs the unmodified
    single-device body over local slot ids.  Groups are independent, so
    the data path needs no communication; output rows (each owned by
    exactly one device) merge with psum, the wake scalar with pmin.
    This scales group capacity and segment-op work G/n per chip — the
    reference's thread-per-Disruptor scale-up becomes SPMD scale-out
    (CORE/stream/StreamJunction.java:296)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n = mesh.devices.size
    blk = group_slots // n

    ex_w = wproc.init_state()
    ex_s = sel.init_state()
    wspec = jax.tree.map(lambda x: P(), ex_w)     # NoWindow state: scalars
    sspec = jax.tree.map(lambda x: P("shard"), ex_s)
    rspec = P()                                   # event rows: replicated

    def local(state, ts, kind, valid, cols, gslot, now, in_tabs, pslots):
        dev = lax.axis_index("shard")
        ts = lax.pcast(ts, ("shard",), to="varying")
        kind = lax.pcast(kind, ("shard",), to="varying")
        valid = lax.pcast(valid, ("shard",), to="varying")
        cols = tuple(lax.pcast(c, ("shard",), to="varying") for c in cols)
        gslot = lax.pcast(gslot, ("shard",), to="varying")
        in_tabs = jax.tree.map(
            lambda x: lax.pcast(x, ("shard",), to="varying"), in_tabs)
        wstate, astate = state
        old_w = wstate
        wstate = jax.tree.map(
            lambda x: lax.pcast(x, ("shard",), to="varying"), wstate)
        # round-robin ownership (slot % n): sequential slot allocation
        # would park every early group on device 0 under a block split —
        # same layout as the pattern path, device column = (s%n)*blk + s//n
        owned = (gslot % n) == dev
        local_slot = jnp.where(owned, gslot // n, 0)
        lvalid = jnp.logical_and(valid, owned)
        (wstate, astate), (ots, okind, ovalid, ocols), wake = step(
            (wstate, astate), ts, kind, lvalid, cols, local_slot, now,
            in_tabs, pslots)
        # outputs stay ROW-ALIGNED to the input batch (NoWindow.compact is
        # off on this path), so each row is valid on exactly its owner
        # device and a psum merge preserves single-device delivery order
        ots = _merge_rows(ovalid, ots)
        okind = _merge_rows(ovalid, okind)
        ocols = tuple(_merge_rows(ovalid, c) for c in ocols)
        ovalid = lax.psum(ovalid.astype(jnp.int32), "shard") > 0
        wake = pmin_i64(wake, "shard")
        # NoWindow's state is the additive seq counter: re-replicate as
        # old + sum of per-device deltas (pattern-path recipe)
        wstate = jax.tree.map(
            lambda old, new: old + lax.psum(
                new - lax.pcast(old, ("shard",), to="varying"), "shard"),
            old_w, wstate)
        return (wstate, astate), (ots, okind, ovalid, ocols), wake

    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=((wspec, sspec), rspec, rspec, rspec, rspec, rspec, P(),
                  rspec, rspec),
        out_specs=((wspec, sspec), (P(), P(), P(), P()), P()))
    return jit_step(sharded, owner=owner, role="plain_step_sharded",
                    donate_argnums=(0,))


def _shard_keyed_step(kstep, mesh, K: int, owner=None):
    """Shard the keyed-window step over the mesh 'shard' axis.

    Partition keys are the shard axis: each device owns the window-state
    rows of keys with `key_idx % n == dev` (round-robin — sequential key
    allocation would park early keys on device 0), stored at local row
    key_idx // n. Event rows and the [Kb, E] per-key grouping replicate;
    non-owned keys turn into pad rows (sentinel K) whose window writes
    drop and whose output rows invalidate. Selector accumulators stay
    REPLICATED (group slots interleave keys arbitrarily, so they cannot
    share the key layout); each group slot is written by exactly one
    device per batch, so states merge exactly with a changed-delta psum.
    Outputs stay row-aligned — the psum merge preserves single-device
    delivery order. Wake scalars ride pmin."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n = mesh.devices.size

    def dmerge(old, new):
        """Exact merge when at most one device changed each element.
        `old` must be the replicated (unvaried) input so old + psum(delta)
        is statically replicated."""
        is_bool = old.dtype == jnp.bool_
        oi = old.astype(jnp.int32) if is_bool else old
        ni = new.astype(jnp.int32) if is_bool else new
        oi_v = lax.pcast(oi, ("shard",), to="varying")
        changed = ni != oi_v
        merged = oi + lax.psum(
            jnp.where(changed, ni - oi_v, jnp.zeros_like(ni)), "shard")
        return merged.astype(jnp.bool_) if is_bool else merged

    def local(state, ts, kind, valid, cols, gslot, key_idx, sel_idx, now,
              in_tabs):
        dev = lax.axis_index("shard")
        vary = lambda x: lax.pcast(x, ("shard",), to="varying")  # noqa: E731
        ts, kind, valid, gslot = vary(ts), vary(kind), vary(valid), \
            vary(gslot)
        cols = tuple(vary(c) for c in cols)
        key_idx, sel_idx = vary(key_idx), vary(sel_idx)
        in_tabs = jax.tree.map(vary, in_tabs)
        wslab, astate = state
        old_a = astate
        astate = jax.tree.map(vary, astate)
        # host pad rows carry sentinel key_idx == K: they must stay pads on
        # EVERY device (K % n would otherwise claim them as a real key)
        owned = jnp.logical_and((key_idx % n) == dev, key_idx < K)
        key_l = jnp.where(owned, key_idx // n, K)   # K == drop sentinel
        (wslab, astate), (ots, okind, ovalid, ocols), wake = kstep(
            (wslab, astate), ts, kind, valid, cols, gslot, key_l, sel_idx,
            now, in_tabs)
        ots = _merge_rows(ovalid, ots)
        okind = _merge_rows(ovalid, okind)
        ocols = tuple(_merge_rows(ovalid, c) for c in ocols)
        ovalid = lax.psum(ovalid.astype(jnp.int32), "shard") > 0
        wake = pmin_i64(wake, "shard")
        astate = jax.tree.map(dmerge, old_a, astate)
        return (wslab, astate), (ots, okind, ovalid, ocols), wake

    wspec = P("shard")
    rspec = P()
    sharded = jax.shard_map(
        local, mesh=mesh,
        in_specs=((wspec, rspec), rspec, rspec, rspec, rspec, rspec, rspec,
                  rspec, P(), rspec),
        out_specs=((wspec, rspec), (P(), P(), P(), P()), P()))
    return jit_step(sharded, owner=owner, role="keyed_step_sharded",
                    donate_argnums=(0,))


def plan_single_query(
    query: Query,
    name: str,
    definitions: Dict[str, StreamDefinition],
    schemas: Dict[str, ev.Schema],
    interner: ev.StringInterner,
    batch_capacity: int = 512,
    group_slots: int = 4096,
    window_capacity_hint: int = 2048,
    partition_positions: Optional[List[int]] = None,
    partition_key_fn: Optional[Callable] = None,
    window_key_allocator: Optional[SlotAllocator] = None,
    key_capacity: int = 0,
    named_window_input: bool = False,
    config_manager=None,
    script_functions=None,
    mesh=None,
) -> PlannedQuery:
    ist = query.input_stream
    assert isinstance(ist, SingleInputStream)
    sid = ist.unique_stream_id
    if sid not in schemas:
        raise CompileError(f"undefined stream {sid!r}")
    in_schema = schemas[sid]

    # `in` operator table dependencies (reference: InConditionExpressionExecutor)
    from ..query_api.expression import In as _In, walk as _walk
    in_deps: List[str] = []
    def _scan_in(e):
        for node in _walk(e):
            if isinstance(node, _In) and node.source_id not in in_deps:
                in_deps.append(node.source_id)
    for h in ist.stream_handlers:
        if isinstance(h, Filter):
            _scan_in(h.expression)
    for oa in query.selector.selection_list:
        _scan_in(oa.expression)
    if query.selector.having_expression is not None:
        _scan_in(query.selector.having_expression)

    scope = Scope()
    scope.interner = interner
    scope.add_source(sid, in_schema, alias=ist.stream_reference_id)
    # extensions read per-extension config via
    # scope.config_manager.generate_config_reader(namespace, name)
    # (reference: ConfigReader wired in SingleInputStreamParser :205-217)
    scope.config_manager = config_manager
    scope.script_functions = script_functions

    # ---- handlers: filters/stream-functions before/after the window --------
    # chain entries: ('filter', compiled) | ('fn', dtypes, fn)
    pre_chain, post_chain = [], []
    if named_window_input:
        from .window import PassAllWindow
        window_proc: WindowProcessor = PassAllWindow(
            in_schema, [], batch_capacity)
    else:
        window_proc = NoWindow(in_schema, [], batch_capacity)
    seen_window = False
    chain_schema = in_schema   # grows as stream functions append attributes
    for h in ist.stream_handlers:
        if isinstance(h, Filter):
            c = compile_expression(h.expression, scope)
            if c.type != "BOOL":
                raise CompileError("filter expression must be boolean")
            (post_chain if seen_window else pre_chain).append(("filter", c))
        elif isinstance(h, Window):
            if named_window_input:
                raise CompileError(
                    "cannot apply a window to a named-window input")
            if seen_window:
                raise CompileError("only one window per input stream")
            seen_window = True
            window_proc = create_window(
                (h.namespace + ":" if h.namespace else "") + h.name,
                chain_schema, h.parameters, batch_capacity,
                capacity_hint=window_capacity_hint)
        elif isinstance(h, StreamFunction):
            from .streamfn import STREAM_FUNCTIONS
            fname = (h.namespace + ":" if h.namespace else "") + h.name
            sfn = STREAM_FUNCTIONS.get(fname)
            if sfn is None:
                raise CompileError(
                    f"unknown stream function {fname!r}; registered: "
                    f"{sorted(STREAM_FUNCTIONS)}")
            names, types, fn = sfn.compile(h.parameters, scope, sid)
            if names:
                sdef = StreamDefinition(sid)
                for a in chain_schema.definition.attribute_list:
                    sdef.attribute(a.name, a.type)
                for n, t in zip(names, types):
                    sdef.attribute(n, t)
                chain_schema = ev.Schema(sdef, interner,
                                         objects=in_schema.objects)
                scope.add_source(sid, chain_schema,
                                 alias=ist.stream_reference_id,
                                 default=False)
            dtypes = [ev.dtype_of(t) for t in types]
            (post_chain if seen_window else pre_chain).append(
                ("fn", dtypes, fn))

    # ---- selector -----------------------------------------------------------
    out_target = query.output_stream.target_id if query.output_stream else ""
    sel = SelectorExec(query.selector, scope, chain_schema, group_slots,
                       out_target or name, interner)

    # output schema
    out_def = StreamDefinition(out_target or f"#{name}.out")
    for n, t in zip(sel.out_names, sel.out_types):
        out_def.attribute(n, t)
    out_schema = ev.Schema(out_def, interner, objects=in_schema.objects)

    # group-by slot allocation (host side).  Inside a partition, the
    # partition key is prepended to the group key: state isolation per
    # partition key composes with group-by
    # (reference: PartitionStateHolder's nested partitionKey->groupByKey map)
    gpos = list(sel.group_by_positions)
    if any(p >= len(in_schema.names) for p in gpos):
        raise CompileError(
            "group by on stream-function-appended attributes is not yet "
            "supported")
    keyed_window = bool(
        (partition_positions or partition_key_fn) and seen_window)
    window_key_positions = list(partition_positions or [])
    skey_pos = window_proc.session_key_pos
    if skey_pos is not None:
        # session(gap, key): standalone keyed window — the session key
        # scopes the window slab exactly like a partition key would
        # (reference: SessionWindowProcessor.java sessionKey overload)
        if partition_positions or partition_key_fn:
            raise CompileError(
                "session(gap, key) inside `partition with` is redundant: "
                "the partition key already scopes the session window")
        if skey_pos >= len(in_schema.names):
            # key slots resolve on raw staged columns; appended attributes
            # don't exist there (same bound as the group-by guard above)
            raise CompileError(
                "session key on stream-function-appended attributes is "
                "not yet supported")
        keyed_window = True
        window_key_positions = [skey_pos]
    if keyed_window and (window_key_allocator is None or key_capacity <= 0):
        raise CompileError(
            "windows inside partitions (and session(gap, key) queries) "
            "need a key allocator" if skey_pos is None else
            "internal: session-key query planned without its key "
            "allocator (runtime wiring bug)")
    if partition_positions:
        if sel.has_aggregation or gpos:
            gpos = [p for p in partition_positions if p not in gpos] + gpos
    needs_alloc = bool(gpos) or (
        partition_key_fn is not None and (sel.has_aggregation or gpos))
    allocator = SlotAllocator(group_slots, name=f"{name}:groupby") \
        if needs_alloc else None
    # no allocator (no group by, no partition key of either kind): the
    # runtime stages slot 0 for every row, so the selector's rows are
    # already in segment order (AggregatorBank.layout)
    sel.bank.single_slot = allocator is None

    # distinctCount pair slots: (group, value) -> refcount slot
    pair_allocs: List[Tuple[SlotAllocator, int]] = []
    if sel.bank.pair_sources:
        if seen_window or keyed_window:
            raise CompileError(
                "distinctCount over windowed queries lands in a later "
                "phase (expired-row pair slots need buffer plumbing)")
        for j, v in enumerate(sel.bank.pair_sources):
            _, pos, _ = scope.resolve(v)
            if pos >= len(in_schema.names):
                raise CompileError(
                    "distinctCount on stream-function-appended attributes "
                    "is not yet supported")
            pair_allocs.append((SlotAllocator(
                sel.bank.K * 8, name=f"{name}:distinct{j}"), pos))

    out_event_type = (query.output_stream.output_event_type
                      if query.output_stream and
                      query.output_stream.output_event_type
                      else "CURRENT_EVENTS")

    # ---- the fused step -----------------------------------------------------
    wproc = window_proc

    def _probe_env(in_tabs):
        """`x in Table` probe closures for this query's table deps —
        pure functions of the snapshot columns, rebuilt identically in
        both step halves."""
        env = {}
        for dep, (tcol0, tvalid) in zip(in_deps, in_tabs):
            def probe(vals, _tc=tcol0, _tv=tvalid):
                return jnp.any(jnp.logical_and(
                    vals[:, None] == _tc[None, :], _tv[None, :]), axis=1)
            env["__in__:" + dep] = probe
        return env

    def stage_body(wstate, ts, kind, valid, cols, gslot, now, in_tabs):
        """Pre-window chain + window advance: the half of the step a
        merge group shares (one buffer, staged once per dispatch)."""
        # device-trace sections of the plain step (`jax.named_scope`:
        # op-name metadata, the compiled program is the same without
        # them): `plain_chain` here, the window's and the selector's in
        # the code that owns the work (window.py, selector.py)
        with jax.named_scope("plain_chain"):
            env = {sid: cols, "__ts__": ts, "__now__": now,
                   "__kind__": kind}
            env.update(_probe_env(in_tabs))
            keep = valid
            is_current = kind == ev.CURRENT
            if named_window_input:
                # expired rows must pass the same filters so signed
                # aggregation stays balanced (reference: filter sits
                # after the shared window)
                is_current = jnp.logical_or(is_current, kind == ev.EXPIRED)
            env, cols, keep = _apply_chain(pre_chain, env, sid, cols, keep,
                                           is_current)
            rows = Rows(ts=ts, kind=kind, valid=keep,
                        seq=jnp.zeros_like(ts), gslot=gslot, cols=cols)
        wstate, wout = wproc.process(wstate, rows, now)
        return wstate, wout.rows, wout.next_wakeup

    def select_body(astate, orows, now, in_tabs, pslots):
        """Post-window chain + selector over the window's output rows:
        the per-query half, stacked per member in a merged dispatch."""
        env2 = {sid: orows.cols, "__ts__": orows.ts, "__now__": now,
                "__kind__": orows.kind}
        env2.update(_probe_env(in_tabs))
        # distinctCount pair slots (unwindowed: orows is the input order)
        for j in range(len(pair_allocs)):
            env2[f"__pslot__{j}"] = pslots[j]
        if post_chain:
            with jax.named_scope("plain_chain"):
                data_row = jnp.logical_or(orows.kind == ev.CURRENT,
                                          orows.kind == ev.EXPIRED)
                env2, ocols, keep2 = _apply_chain(
                    post_chain, env2, sid, orows.cols, orows.valid,
                    data_row)
                orows = orows._replace(valid=keep2, cols=ocols)
        return sel.process(astate, orows, env2)

    def step(state, ts, kind, valid, cols, gslot, now, in_tabs=(),
             pslots=()):
        wstate, astate = state
        wstate, orows, wake = stage_body(wstate, ts, kind, valid, cols,
                                         gslot, now, in_tabs)
        astate, (ots, okind, ovalid, ocols) = select_body(
            astate, orows, now, in_tabs, pslots)
        return ((wstate, astate), (ots, okind, ovalid, ocols), wake)

    plain_mesh = None
    keyed_mesh = None
    raw_step = None
    if keyed_window:
        # ---- keyed window: one window state per partition key ------------
        # The window processor is a pure (state, rows, now) -> (state', out)
        # function, so per-key isolation is jax.vmap over a [K, ...] state
        # slab with events arranged [Kb, E] per key (same layout as the
        # pattern NFA path).  Reference semantics: each partition key owns a
        # private window instance (PartitionRuntimeImpl clone-per-key).
        K = key_capacity

        def kstep(state, ts, kind, valid, cols, gslot, key_idx, sel_idx,
                  now, in_tabs=()):
            wslab, astate = state
            env = {sid: cols, "__ts__": ts, "__now__": now,
                   "__kind__": kind}
            for dep, (tcol0, tvalid) in zip(in_deps, in_tabs):
                def probe(vals, _tc=tcol0, _tv=tvalid):
                    return jnp.any(jnp.logical_and(
                        vals[:, None] == _tc[None, :], _tv[None, :]),
                        axis=1)
                env["__in__:" + dep] = probe
            env, cols, keep = _apply_chain(pre_chain, env, sid, cols, valid,
                                           kind == ev.CURRENT)
            sidx = jnp.clip(sel_idx, 0)
            take = lambda a: a[sidx]                      # noqa: E731
            evalid = jnp.logical_and(sel_idx >= 0, take(keep))
            rows_k = Rows(ts=take(ts), kind=take(kind), valid=evalid,
                          seq=jnp.zeros_like(take(ts)), gslot=take(gslot),
                          cols=tuple(take(c) for c in cols))
            kidx = jnp.clip(key_idx, 0, K - 1)
            st_k = jax.tree.map(lambda x: x[kidx], wslab)
            st_k2, wout = jax.vmap(
                wproc.process, in_axes=(0, 0, None))(st_k, rows_k, now)
            # pad rows (key_idx == K) drop on scatter-back
            wslab = jax.tree.map(
                lambda s, n: s.at[key_idx].set(n, mode="drop"),
                wslab, st_k2)
            ork = wout.rows
            flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
            pad_live = (key_idx < K)[:, None]
            orows = Rows(
                ts=flat(ork.ts), kind=flat(ork.kind),
                valid=flat(jnp.logical_and(ork.valid, pad_live)),
                seq=flat(ork.seq), gslot=flat(ork.gslot),
                cols=tuple(flat(c) for c in ork.cols))
            env2 = {sid: orows.cols, "__ts__": orows.ts, "__now__": now,
                    "__kind__": orows.kind}
            for k2, v2 in env.items():
                if k2.startswith("__in__:"):
                    env2[k2] = v2
            if post_chain:
                data_row = jnp.logical_or(orows.kind == ev.CURRENT,
                                          orows.kind == ev.EXPIRED)
                env2, ocols, keep2 = _apply_chain(
                    post_chain, env2, sid, orows.cols, orows.valid,
                    data_row)
                orows = orows._replace(valid=keep2, cols=ocols)
            astate, outs = sel.process(astate, orows, env2)
            return ((wslab, astate), outs, jnp.min(wout.next_wakeup))

        kshardable = (
            mesh is not None and mesh.devices.size > 1
            and K % mesh.devices.size == 0 and not pair_allocs
            and not sel._order_by and query.selector.limit is None
            and query.selector.offset is None
            and not wproc.host_scheduled
            # RESET-emitting batch windows reset ALL selector slots on any
            # device that sees the flush — multiple writers per slot break
            # the replicated-state delta merge; they stay single-device
            and not wproc.emits_reset)
        if kshardable:
            step_fn = _shard_keyed_step(kstep, mesh, K, owner=name)
            keyed_mesh = mesh
        else:
            step_fn = jit_step(kstep, owner=name, role="keyed_step",
                               donate_argnums=(0,))
            keyed_mesh = None

        def init_state():
            single = wproc.init_state()
            slab = jax.tree.map(
                lambda x: jnp.array(jnp.broadcast_to(
                    jnp.asarray(x)[None],
                    (K,) + jnp.asarray(x).shape)), single)
            return (slab, sel.init_state())
    else:
        shardable = (
            mesh is not None and allocator is not None
            and isinstance(wproc, NoWindow) and not pair_allocs
            and not sel._order_by and query.selector.limit is None
            and query.selector.offset is None
            and allocator.capacity % mesh.devices.size == 0)
        if shardable:
            # keep outputs row-aligned so the sharded psum merge preserves
            # single-device delivery order
            wproc.compact = False
            step_fn = _shard_plain_step(step, mesh, sel, wproc,
                                        allocator.capacity, owner=name)
            plain_mesh = mesh
        else:
            step_fn = jit_step(step, owner=name, role="plain_step",
                               donate_argnums=(0,))
            plain_mesh = None
            raw_step = step

        def init_state():
            return (wproc.init_state(), sel.init_state())

    return PlannedQuery(
        name=name,
        input_stream_id=sid,
        in_schema=in_schema,
        out_schema=out_schema,
        output_target=out_target,
        output_event_type=out_event_type,
        window=wproc,
        group_by_positions=gpos,
        selector_exec=sel,
        step=step_fn,
        init_state=init_state,
        slot_allocator=allocator,
        batch_capacity=batch_capacity,
        needs_timer=wproc.needs_timer,
        in_deps=in_deps,
        partition_key_fn=partition_key_fn,
        keyed_window=keyed_window,
        window_key_allocator=window_key_allocator,
        window_key_positions=window_key_positions,
        key_capacity=key_capacity,
        pair_allocs=pair_allocs,
        mesh=plain_mesh,
        keyed_mesh=keyed_mesh,
        emits_uuid=scope.uses_uuid,
        raw_step=raw_step,
        stage_body=stage_body if raw_step is not None else None,
        select_body=select_body if raw_step is not None else None,
    )

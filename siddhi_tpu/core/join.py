"""Join queries: stream-stream (windowed), stream-table, stream-named-window.

Reference behavior (what): CORE/query/input/stream/join/JoinProcessor.java:45
— each CURRENT/EXPIRED event on one side probes the other side's window via
find() (an EXPIRED one only where the output expects expired events:
`expired_joined` below); left/right/full outer emit unmatched rows with
nulls; unidirectional restricts the triggering side.

TPU-native design (how): each side's window is the columnar Buffer; a batch
of trigger-side rows joins against the other side's buffer as one masked
[R, C] cross evaluation of the compiled on-condition — the reference's
per-event find() loop becomes a single fused comparison + gather.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query_api.definition import StreamDefinition
from ..query_api.expression import Constant
from ..query_api.query import (
    InsertIntoStream,
    JoinInputStream,
    Query,
    SingleInputStream,
)
from . import event as ev
from .executor import CompileError, CompiledExpr, Scope, compile_expression
from .keyslots import SlotAllocator
from .plan_facts import JOIN_LANE_K_MIN, join_fastpath, table_probe_attrs_of
from .selector import SelectorExec
from .steputil import jit_step
from .window import (NO_WAKEUP, Buffer, NoWindow, Rows, TimeRingWindow,
                     TimeWindow, WindowProcessor, create_window, ring_age,
                     ring_search, ring_write, slab_take)


@dataclasses.dataclass
class JoinSide:
    stream_id: str
    key: str                      # scope key (alias or stream id)
    schema: ev.Schema
    window: Optional[WindowProcessor]   # None => table / named window side
    is_table: bool = False
    is_aggregation: bool = False
    # `define window` shared instance probed like a table: the join reads
    # its live buffer per step (reference: WindowWindowProcessor adapter)
    is_named_window: bool = False
    pre_filters: List[CompiledExpr] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class PlannedJoinQuery:
    name: str
    left: JoinSide
    right: JoinSide
    join_type: str
    trigger: str
    out_schema: ev.Schema
    output_target: str
    output_event_type: str
    selector_exec: SelectorExec
    step_left: Optional[Callable]
    step_right: Optional[Callable]
    init_state: Callable
    batch_capacity: int
    needs_timer: bool
    within_range: Optional[Tuple[int, int]] = None
    per_duration: Optional[str] = None
    # group-by in joins: per-side group keys resolve to per-side slots on
    # the host; the joined row's group slot composes on device as
    # gl * (Kr + 1) + gr (the +1 factor is the outer-join null group)
    slot_allocator: Optional[Any] = None      # left-side group allocator
    slot_allocator2: Optional[Any] = None     # right-side group allocator
    gl_pos: List[int] = dataclasses.field(default_factory=list)
    gr_pos: List[int] = dataclasses.field(default_factory=list)
    # UUID() appears in this query: emission materializes sentinels once
    emits_uuid: bool = False
    # device-side emission compaction: the [R*C] join grid is squeezed to
    # `compact_rows` valid-first rows before the host fetch (None = the
    # per-trace default max(2R, 1024)).  emit_explicit marks a user
    # @emit(rows='N') — overflow then warns instead of growing.
    compact_rows: Optional[int] = None
    emit_explicit: bool = False
    # join emissions carry CURRENT and EXPIRED rows; the runtime must not
    # assume all-current when deriving batch counts from the header
    mixed_kinds: bool = True
    # un-jitted side bodies for @fuse(batches=K) scan fusion (core/fusion.py)
    raw_left: Optional[Callable] = None
    raw_right: Optional[Callable] = None
    # ---- equi-join fast path (ROADMAP item 2) ----
    # 'bucket': both stream windows carry a key-slot column; the step
    # probes only same-bucket pairs through a lane table derived from
    # the buffer each dispatch.  'table': the table side's hash index
    # answers [B, K] candidates host-side.  None: full [R, C] grid.
    fastpath: Optional[str] = None
    # why an equality conjunct exists but the fast path stays off
    # (plan_facts.join_fastpath wording — lint JOIN002 prints the same)
    fastpath_reason: Optional[str] = None
    key_attrs: List[Tuple[str, str]] = dataclasses.field(
        default_factory=list)            # [(left attr, right attr)]
    key_left: List[int] = dataclasses.field(default_factory=list)
    key_right: List[int] = dataclasses.field(default_factory=list)
    key_dtypes: List[Any] = dataclasses.field(default_factory=list)
    residual: bool = False       # ON carries conjuncts beyond the keys
    lane_k: int = 0              # candidate lane width (bucket mode)
    lane_buckets: Tuple[int, int] = (0, 0)   # per-side lane-table rows
    ring_caps: Tuple[int, int] = (0, 0)      # per-side retention bound
    # per side: how its rows are found by key — "chain" (a `window.time`
    # side kept as a ring: a head table by key slot and a link a row to the
    # older row of its key, kept across steps) or "lanes" (the `[buckets,
    # K]` table re-derived from the buffer each dispatch)
    index_kind: Tuple[str, str] = ("", "")
    # a ring side's programs beyond `step_left` / `step_right` (which walk
    # the other side's chains 1 deep, in place): `side_step(is_left, depth,
    # slow)` builds — once, then from `side_steps` — the side's program that
    # walks `depth` deep (`chain_depth`: the runtime asks for what the
    # batch's own keys hold on the other side) and, `slow`, takes a batch
    # whose stamps are out of order through the whole-slab `process`.  Each
    # is traced and compiled when first asked for
    step_maker: Optional[Callable] = None
    side_steps: Dict = dataclasses.field(default_factory=dict)

    def side_step(self, is_left: bool, depth: int = 1, slow: bool = False):
        key = (bool(is_left), int(depth), bool(slow))
        if key == (key[0], 1, False) or self.step_maker is None:
            return self.step_left if is_left else self.step_right
        fn = self.side_steps.get(key)
        if fn is None:
            fn = self.side_steps[key] = self.step_maker(*key)
        return fn
    # shared key->slot allocator (both sides; carried across replans)
    join_key_allocator: Optional[Any] = None
    # table mode: which side is the table and the probe columns
    table_is_left: bool = False
    table_pos: int = -1          # indexed table column
    stream_key_pos: int = -1     # stream-side key column
    # the cap's order is taken BEFORE the pair rows are gathered and
    # selected (make_step: `late_pairs`), so columns exist at the cap only
    late_pairs: bool = False
    # the trigger window's EXPIRED output rows join too.  False where
    # nothing could read what they make (`plan_join_query`): the step then
    # takes CURRENT trigger rows alone
    expired_joined: bool = True
    # what shared code (emission, the purger, snapshots, the observatory,
    # lint) reads off ANY plan and only a plain or a pattern plan sets: a
    # join keeps no key axis (GSPMD row sharding under the app's mesh,
    # no key layout), no keyed-window slab, no distinctCount pairs
    mesh: Any = None
    keyed_mesh: Any = None
    keyed_window: bool = False
    key_capacity: int = 0
    window_key_allocator: Optional[Any] = None
    pair_allocs: Tuple = ()

    @staticmethod
    def _describe_side(s: "JoinSide") -> Dict:
        kind = "aggregation" if s.is_aggregation else \
            "named_window" if s.is_named_window else \
            "table" if s.is_table else "stream"
        d: Dict[str, Any] = {"id": s.stream_id, "kind": kind,
                             "columns": list(s.schema.names)}
        if s.window is not None:
            d["window_processor"] = type(s.window).__name__
        if s.pre_filters:
            d["pre_filters"] = len(s.pre_filters)
        return d

    def describe(self) -> Dict:
        """Compiled-plan facts for EXPLAIN (observability/explain.py):
        side kinds (stream/table/window/aggregation), the window
        processors chosen, emission compaction — beyond the query AST."""
        d: Dict[str, Any] = {
            "join_type": self.join_type,
            "trigger": self.trigger,
            "left": self._describe_side(self.left),
            "right": self._describe_side(self.right),
            "needs_timer": self.needs_timer,
            "out_columns": list(self.out_schema.names),
            "emission_cap_rows": self.compact_rows,
            "emission_cap_explicit": bool(self.emit_explicit),
            "pair_rows_materialised": "cap" if self.late_pairs else "all",
            "expired_rows_joined": bool(self.expired_joined),
        }
        if self.slot_allocator is not None:
            d["group_slot_capacity"] = (
                self.slot_allocator.capacity,
                self.slot_allocator2.capacity
                if self.slot_allocator2 is not None else None)
        if self.per_duration is not None:
            d["aggregation_per"] = self.per_duration
        if self.selector_exec.has_aggregation:
            d["selector_layout"] = self.selector_exec.bank.layout
        d["equi_fastpath"] = self.fastpath_facts()
        return d

    def fastpath_facts(self) -> Dict:
        """Bucket stats for EXPLAIN / lint: the fast-path mode, the key
        attributes it buckets on, the candidate lane capacity, and
        whether a residual predicate rides the probe."""
        node: Dict[str, Any] = {"active": self.fastpath is not None}
        if self.fastpath is not None:
            node["mode"] = self.fastpath
            node["key_attrs"] = [list(p) for p in self.key_attrs]
            node["residual_predicate"] = bool(self.residual)
            if self.fastpath == "bucket":
                node["lane_k"] = int(self.lane_k)
                node["lane_buckets"] = list(self.lane_buckets)
                # each side: the rows its window may hold, how its rows are
                # found by key, how deep a probe of it walks
                node["window_bound_rows"] = list(self.ring_caps)
                node["index_kind"] = list(self.index_kind)
                # the deepest walk OF each side a program has been built for
                node["probe_depth"] = [
                    max([1] + [d for (left, d, _s) in self.side_steps
                               if left != probed_is_left])
                    if kind == "chain" else 0
                    for probed_is_left, kind in
                    zip((True, False), self.index_kind)]
                node["key_capacity"] = (
                    self.join_key_allocator.capacity
                    if self.join_key_allocator is not None else None)
        elif self.fastpath_reason is not None:
            node["reason"] = self.fastpath_reason
        return node


# A-B kill switch: bench `--mode join_compare` and the parity tests plan
# one runtime with the fast path off to prove byte-identical outputs.
# Consulted once at plan time; never flipped on a live runtime.
FASTPATH_ENABLED = True

JSLOT_COL = "#jslot"


def _probe_schema(schema: ev.Schema) -> ev.Schema:
    """The window-buffer schema of a bucketed join side: the stream's
    columns plus one synthetic INT column carrying the key's bucket
    slot.  The column rides the buffer through every window gather, so
    EXPIRED trigger rows keep the slot they were bucketed under at
    arrival — no re-hashing of buffered rows, ever."""
    d = StreamDefinition(f"{schema.id}{JSLOT_COL}")
    for n, t in zip(schema.names, schema.types):
        d.attribute(n, t)
    d.attribute(JSLOT_COL, "INT")
    return ev.Schema(d, schema.interner)


def _mk_side(sis: SingleInputStream, schemas, tables, batch_capacity,
             scope: Scope, window_capacity_hint: int,
             aggregations=None, named_windows=None,
             probe_col: bool = False) -> JoinSide:
    sid = sis.stream_id
    key = sis.stream_reference_id or sid
    if aggregations and sid in aggregations:
        # aggregation side: columnar snapshot per step (reference:
        # AggregationRuntime.find via AggregateWindowProcessor adapter)
        schema = aggregations[sid].make_schema()
        scope.add_source(key, schema, alias=None)
        return JoinSide(sid, key, schema, None, is_table=True,
                        is_aggregation=True)
    if named_windows and sid in named_windows:
        nw = named_windows[sid]
        if nw.wproc.current_buffer(nw.state) is None:
            raise CompileError(
                f"named window {sid!r} ({nw.wproc.name}) does not expose a "
                f"probe-able buffer for joins")
        schema = nw.schema
        scope.add_source(key, schema, alias=None)
        # bidirectional (reference: Window.java:145-184 — the join both
        # probes the shared window's buffer AND triggers on events flowing
        # through it).  The trigger path gets a pass-through window: rows
        # the named window emits probe the other side; retention lives in
        # the NamedWindowRuntime, never here.
        from .window import PassAllWindow
        return JoinSide(sid, key, schema,
                        PassAllWindow(schema, [], batch_capacity),
                        is_table=True, is_named_window=True)
    is_table = sid in tables
    schema = tables[sid].schema if is_table else schemas[sid]
    scope.add_source(key, schema, alias=None)
    win = None
    if not is_table:
        wh = sis.window_handler
        # bucketed sides build their buffers with the key-slot column
        # appended (the side's visible schema stays the original)
        win_schema = _probe_schema(schema) if probe_col else schema
        if wh is None:
            # windowless stream side: valid when probing a table-like side
            # (reference: JoinInputStreamParser wraps it in an empty window)
            win = NoWindow(win_schema, [], batch_capacity)
        else:
            win = create_window(
                (wh.namespace + ":" if wh.namespace else "") + wh.name,
                win_schema, wh.parameters, batch_capacity,
                capacity_hint=window_capacity_hint)
            if win.name not in ("length", "time"):
                raise CompileError(
                    f"join windows must be sliding (length/time), got "
                    f"{win.name!r}")
    side = JoinSide(sid, key, schema, win, is_table)
    return side


def _constrain_state(state, mesh):
    """Pin the persistent state's sharding INSIDE the jitted step.  The
    host-side device_put in JoinQueryRuntime.place_state only seeds the
    layout; without an in-graph constraint GSPMD is free to (and does)
    choose replicated output shardings, silently un-distributing the
    window buffers after the first step.  One constraint per eligible leaf
    keeps each buffer at 1/n rows per device across steps."""
    if mesh is None or mesh.devices.size < 2:
        return state
    from .shardsafe import axis0_sharding

    def _c(x):
        s = axis0_sharding(mesh, x)
        return jax.lax.with_sharding_constraint(x, s) if s is not None else x
    return jax.tree.map(_c, state)


def plan_join_query(
    query: Query,
    name: str,
    schemas: Dict[str, ev.Schema],
    tables: Dict[str, Any],
    interner: ev.StringInterner,
    batch_capacity: int = 512,
    window_capacity_hint: int = 512,
    aggregations=None,
    named_windows=None,
    mesh=None,
    emit_rows_override: Optional[int] = None,
    lane_k_override: Optional[int] = None,
    window_caps: Tuple[Optional[int], Optional[int]] = (None, None),
    key_capacity: Optional[int] = None,
) -> PlannedJoinQuery:
    """`window_caps`: the rows each side's window may hold
    (`@capacity(window='N')`, or `window.left` / `window.right`; None: the
    default `window_capacity_hint`).  `key_capacity`: the distinct join keys
    both windows may hold together (`@capacity(keys='N')`; None: every row
    its own key)."""
    jis = query.input_stream
    assert isinstance(jis, JoinInputStream)

    # equi-join fast path: decided from the AST BEFORE the sides build,
    # so bucketed windows can carry the key-slot column from birth
    def _side_kind(sid: str) -> str:
        if aggregations and sid in aggregations:
            return "aggregation"
        if named_windows and sid in named_windows:
            return "named_window"
        if sid in tables:
            return "table"
        return "stream"

    fp_mode, fp_pairs, fp_reason = join_fastpath(
        jis, _side_kind,
        lambda sid: table_probe_attrs_of(tables[sid].definition))
    if not FASTPATH_ENABLED and fp_mode is not None:
        fp_mode, fp_reason = None, "fast path disabled (A-B comparison)"

    scope = Scope()
    scope.interner = interner
    left = _mk_side(jis.left_input_stream, schemas, tables, batch_capacity,
                    scope, window_caps[0] or window_capacity_hint,
                    aggregations, named_windows,
                    probe_col=fp_mode == "bucket")
    right = _mk_side(jis.right_input_stream, schemas, tables, batch_capacity,
                     scope, window_caps[1] or window_capacity_hint,
                     aggregations, named_windows,
                     probe_col=fp_mode == "bucket")
    if left.is_table and right.is_table and \
            not (left.is_named_window or right.is_named_window):
        raise CompileError("cannot join two tables in a streaming query")
    if not left.is_table and not right.is_table and (
            isinstance(left.window, NoWindow) or
            isinstance(right.window, NoWindow)):
        raise CompileError(
            "stream-stream joins need a window on each side")

    within_range = per_duration = None
    if left.is_aggregation or right.is_aggregation:
        from .aggregation import parse_per, parse_within
        within_range = parse_within(jis.within)
        per_duration = parse_per(jis.per)

    # side filters ([filter] before window)
    for side, sis in ((left, jis.left_input_stream),
                      (right, jis.right_input_stream)):
        from ..query_api.query import Filter
        fscope = Scope()
        fscope.interner = interner
        fscope.add_source(side.key, side.schema)
        for h in sis.stream_handlers:
            if isinstance(h, Filter):
                side.pre_filters.append(
                    compile_expression(h.expression, fscope))

    on = None
    if jis.on_compare is not None:
        on = compile_expression(jis.on_compare, scope)

    # group-by in joins (reference: JoinProcessor + QuerySelector
    # processGroupBy, JoinProcessor.java:107-190): group attrs resolve to
    # per-side slot ids at ingestion; the joined row's slot composes the two
    gl_pos: List[int] = []
    gr_pos: List[int] = []
    for v in query.selector.group_by_list:
        key, pos, _ = scope.resolve(v)
        if key == left.key:
            if left.is_table:
                raise CompileError(
                    "join group-by attributes must come from stream sides")
            gl_pos.append(pos)
        elif key == right.key:
            if right.is_table:
                raise CompileError(
                    "join group-by attributes must come from stream sides")
            gr_pos.append(pos)
        else:
            raise CompileError(
                f"cannot resolve group-by attribute {v.attribute_name!r} "
                f"to a join side")
    if gl_pos and gr_pos:
        Kl = Kr = 63
    elif gl_pos:
        Kl, Kr = 2047, 0
    elif gr_pos:
        Kl, Kr = 0, 2047
    else:
        Kl = Kr = 0
    gl_alloc = SlotAllocator(Kl, name=f"{name}:gl") if gl_pos else None
    gr_alloc = SlotAllocator(Kr, name=f"{name}:gr") if gr_pos else None
    # ungrouped: Kl = Kr = 0, so every joined row's composed slot is 0
    sel = SelectorExec(query.selector, scope, left.schema,
                       max((Kl + 1) * (Kr + 1), 64),
                       (query.output_stream.target_id
                        if query.output_stream else name), interner,
                       single_slot=not (gl_pos or gr_pos))
    if sel.bank.pair_sources:
        raise CompileError(
            "distinctCount/unionSet in join queries lands in a later phase")
    # late materialisation of the pair rows: the emission cap's order may
    # move ahead of the selector only where the selector keeps nothing
    # across rows and drops none — no aggregator (a row past the cap would
    # still have to update a running value), no `having` (it decides which
    # rows count against the cap), no `order by` / `limit` / `offset`
    # (they rank over the whole chunk).  A projection is that.
    qsel = query.selector
    late_pairs = not (sel.has_aggregation or
                      qsel.having_expression is not None or
                      qsel.order_by_list or
                      qsel.limit is not None or qsel.offset is not None)

    out_target = query.output_stream.target_id if query.output_stream else ""
    out_event_type = (query.output_stream.output_event_type
                      if query.output_stream else None) or "CURRENT_EVENTS"
    # which kinds of the trigger window's output rows are join triggers: an
    # EXPIRED row's joined rows are EXPIRED rows, and where the query says
    # `insert into` a stream (CURRENT events, routed as they come: no table
    # op, no `output ... every` counting them) and the selector is the
    # projection above, nothing reads them and they change no other row —
    # so they are not made (reference: JoinProcessor skips an EXPIRED event
    # unless `outputExpectsExpiredEvents`).  Anything else keeps them: an
    # aggregator needs the retraction, `having` / `order by` / `limit` see
    # every row of the chunk.
    expired_joined = not (
        late_pairs and isinstance(query.output_stream, InsertIntoStream)
        and out_event_type == "CURRENT_EVENTS"
        and out_target not in tables and query.output_rate is None)
    # ---- equi-join fast-path plan details ---------------------------------
    key_attrs: List[Tuple[str, str]] = []
    key_left: List[int] = []
    key_right: List[int] = []
    key_dtypes: List[Any] = []
    lane_k = 0
    lane_buckets = (0, 0)
    ring_caps = (0, 0)
    index_kind = ("", "")
    jk_alloc = None
    table_is_left = False
    table_pos = -1
    stream_key_pos = -1
    if fp_mode == "bucket":
        for _c, lv, rv in fp_pairs:
            lp = left.schema.position(lv.attribute_name)
            rp = right.schema.position(rv.attribute_name)
            key_left.append(lp)
            key_right.append(rp)
            key_attrs.append((lv.attribute_name, rv.attribute_name))
            # both sides hash the PROMOTED encoding, so any two values
            # the compiled `==` would call equal land in one bucket
            key_dtypes.append(np.promote_types(
                ev.np_dtype(left.schema.types[lp]),
                ev.np_dtype(right.schema.types[rp])))
        # a `window.time` side of a join that reads CURRENT rows alone is
        # kept as a ring with its same-key chain (TimeRingWindow): a step
        # costs what arrives.  A plan fact; GSPMD row sharding keeps the
        # compacting form
        if not expired_joined and (mesh is None or mesh.devices.size < 2):
            for side, cap in ((left, window_caps[0]), (right, window_caps[1])):
                if type(side.window) is TimeWindow:
                    w = side.window
                    side.window = TimeRingWindow(
                        w.schema, [Constant(w.time_ms, "LONG")],
                        batch_capacity,
                        capacity_hint=cap or w.capacity)
        index_kind = tuple(
            "chain" if isinstance(s_.window, TimeRingWindow) else "lanes"
            for s_ in (left, right))
        ring_caps = (_retention_rows(left.window),
                     _retention_rows(right.window))
        lane_buckets = (_lane_bucket_count(ring_caps[0]),
                        _lane_bucket_count(ring_caps[1]))
        # initial lane width: cover small windows outright (occupancy
        # can never exceed the retention bound, so tiny-window joins
        # never pay a growth recompile) and start larger shapes at the
        # K a roughly-uniform key spread settles into
        auto_k = 1 << (max(1, min(max(ring_caps), 16)) - 1).bit_length()
        lane_k = max(JOIN_LANE_K_MIN, auto_k, int(lane_k_override or 0))
        # key slots live while EITHER ring retains them plus one batch
        # of new arrivals in flight (JoinKeyTracker evicts before it
        # allocates, so this bound holds transiently too);
        # `@capacity(keys='N')` states fewer where the rows share keys
        jk_alloc = SlotAllocator(
            min(ring_caps[0] + ring_caps[1], key_capacity or (1 << 62))
            + 2 * max(batch_capacity, 8192),
            name=f"{name}:joinkey")
    elif fp_mode == "table":
        tside, sside = (left, right) if left.is_table else (right, left)
        table_is_left = left.is_table
        _c, lv, rv = fp_pairs[0]
        t_var, s_var = (lv, rv) if table_is_left else (rv, lv)
        table_pos = tside.schema.position(t_var.attribute_name)
        stream_key_pos = sside.schema.position(s_var.attribute_name)
        key_attrs = [(lv.attribute_name, rv.attribute_name)]
    n_conj = _conjunct_count(jis.on_compare)
    fp_residual = fp_mode is not None and n_conj > len(key_attrs)

    out_def = StreamDefinition(out_target or f"#{name}.out")
    for n, t in zip(sel.out_names, sel.out_types):
        out_def.attribute(n, t)
    out_schema = ev.Schema(out_def, interner)

    jt = jis.type
    trigger = jis.trigger

    # emission compaction cap: @emit(rows='N') = total delivered rows per
    # batch (pattern queries use per-key rows; joins have no key axis).
    # Without it the per-trace default max(2R, 1024) covers ~1 match per
    # window row and adaptive growth (JoinQueryRuntime._grow_emission_cap)
    # handles denser fan-outs.
    emit_ann = query.get_annotation("emit")
    emit_explicit = emit_ann is not None and emit_rows_override is None
    emit_rows = emit_rows_override
    if emit_explicit:
        emit_rows = int(emit_ann.element("rows", 0)) or None

    def make_step(this: JoinSide, other: JoinSide, this_is_left: bool,
                  slow: bool = False, depth_other: int = 1):
        """Step for a batch arriving on `this` side (`slow`: a ring side's
        batch through the whole-slab `process`; `depth_other`: how deep a
        probe walks a ring `other` side's same-key chains)."""
        emit_unmatched_this = (
            (jt == "LEFT_OUTER_JOIN" and this_is_left) or
            (jt == "RIGHT_OUTER_JOIN" and not this_is_left) or
            jt == "FULL_OUTER_JOIN")
        K_other = Kr if this_is_left else Kl
        # fast-path shape facts baked into the trace
        bucket = fp_mode == "bucket"
        table_probe = fp_mode == "table" and not this.is_table
        nbl_other = (lane_buckets[1] if this_is_left else
                     lane_buckets[0]) if bucket else 0
        # CURRENT triggers alone, of a window whose CURRENT rows are its
        # arrivals: the trigger rows are the step's input rows (R = B, not
        # the window's out_capacity) and the window only updates its state
        feed = this.window.admit if not expired_joined and \
            this.window.current_is_arrivals else this.window.process
        # a ring side (window.TimeRingWindow) takes `_ring_feed`; a probe OF
        # one walks its kept chain, `depth_other` deep
        this_ring = isinstance(this.window, TimeRingWindow)
        other_ring = isinstance(other.window, TimeRingWindow)
        # a time window that is full drops its oldest row: the step counts
        # them and the header carries the count (the runtime reports it)
        counts_drops = isinstance(this.window, TimeWindow)

        def step(state, ts, kind, valid, cols, gslot, *rest):
            if bucket or table_probe:
                probe, other_table_cols, now = rest
            else:
                other_table_cols, now = rest
            wl_state, wr_state, sel_state = state
            this_state = wl_state if this_is_left else wr_state
            other_state = wr_state if this_is_left else wl_state

            # device-trace sections (jax.named_scope: op-name metadata
            # only), read by benchmarks/harness/join_sections.py
            with jax.named_scope("join_window"):
                env0 = {this.key: cols, "__ts__": ts, "__now__": now}
                keep = valid
                is_cur = kind == ev.CURRENT
                for f in this.pre_filters:
                    keep = jnp.logical_and(keep, jnp.logical_or(
                        jnp.logical_not(is_cur), f.fn(env0)))
                in_cols = cols
                if bucket:
                    # key bucket slot rides the window buffer as a column
                    in_cols = cols + (probe,)
                elif table_probe:
                    # original batch row index rides the (windowless) window
                    # so compacted trigger rows can find their host-computed
                    # table candidates
                    in_cols = cols + (jnp.arange(ts.shape[0],
                                                 dtype=jnp.int32),)
                rows = Rows(ts=ts, kind=kind, valid=keep,
                            seq=jnp.zeros_like(ts), gslot=gslot, cols=in_cols)
                if counts_drops and not this_ring:
                    b0 = this_state[0]
                    w_dropped = jnp.maximum(
                        jnp.sum(jnp.logical_and(
                            b0.alive, b0.expire_ts > now).astype(jnp.int32))
                        + jnp.sum(jnp.logical_and(
                            keep, is_cur).astype(jnp.int32))
                        - this.window.capacity, 0)
                if not this_ring:
                    this_state, wout = feed(this_state, rows, now)
                    orows, wake = wout.rows, wout.next_wakeup   # [R]
            if this_ring:
                # expiry is the tail's place and `expire_ts > stamp`: no
                # timer step has anything to do
                this_state, orows, w_dropped = _ring_feed(
                    this.window, this_state, rows, probe, now, slow)
                wake = jnp.asarray(NO_WAKEUP, jnp.int64)
            if bucket or table_probe:
                trig_extra = orows.cols[-1]
                t_cols = orows.cols[:-1]
            else:
                trig_extra = None
                t_cols = orows.cols

            # other side's buffer (gslot rides the window buffer rows)
            if other.is_table:
                o_cols, o_ts, o_alive = other_table_cols
                o_gslot = jnp.zeros(o_ts.shape, jnp.int32)
            else:
                obuf: Buffer = other_state[0]
                if other_ring:
                    # a ring's slab: columns behind `column[idx]`, no
                    # `alive` plane (residence is the ring's `pos`)
                    o_cols = other.window.slab_columns(obuf)
                    o_ts, o_alive = obuf.gslot, None
                else:
                    o_cols, o_ts, o_alive = obuf.cols, obuf.ts, obuf.alive
                o_gslot = obuf.gslot
                if bucket:
                    o_jslot = o_cols[-1]
                    o_cols = o_cols[:-1]

            R = orows.ts.shape[0]
            C = o_ts.shape[0]
            if bucket and not other_ring:
                with jax.named_scope("join_lanes"):
                    # [R, K] same-bucket candidates instead of the [R, C]
                    # grid: the lane table is re-derived from the buffer's
                    # slot column each dispatch (O(C log C), never O(R*C)),
                    # the full ON-condition re-verifies every candidate, so
                    # hash/lane collisions only cost work, never matches
                    lanes = _bucket_lanes(o_jslot, o_alive, nbl_other,
                                          lane_k)
            with jax.named_scope("join_probe"):
                is_trigger = orows.kind == ev.CURRENT
                if expired_joined:
                    is_trigger = jnp.logical_or(is_trigger,
                                                orows.kind == ev.EXPIRED)
                data_row = jnp.logical_and(orows.valid, is_trigger)
                if other_ring:
                    # the trigger row's own key's rows alone, and of them
                    # the ones its stamp still sees
                    ri2, cand_ok = _chain_walk(
                        other_state[3], other_state[4], obuf.expire_ts,
                        other_state[2], trig_extra.astype(jnp.int32),
                        orows.ts, depth_other)
                    env = {
                        this.key: tuple(c[:, None] for c in t_cols),
                        other.key: tuple(c[ri2] for c in o_cols),
                        "__ts__": orows.ts[:, None],
                        "__now__": now,
                    }
                    m = jnp.broadcast_to(on.fn(env), ri2.shape)
                    m = jnp.logical_and(m, cand_ok)
                elif bucket:
                    tb = trig_extra.astype(jnp.int32) % nbl_other
                    cand = lanes[tb]                       # [R, K]
                    cand_ok = cand < C
                    ri2 = jnp.minimum(cand, C - 1)
                    env = {
                        this.key: tuple(c[:, None] for c in t_cols),
                        other.key: tuple(c[ri2] for c in o_cols),
                        "__ts__": orows.ts[:, None],
                        "__now__": now,
                    }
                    m = jnp.broadcast_to(on.fn(env), ri2.shape)
                    m = jnp.logical_and(m, cand_ok)
                    m = jnp.logical_and(m, o_alive[ri2])
                elif table_probe:
                    cand_b, ok_b = probe                   # [B, K] host probe
                    B = cand_b.shape[0]
                    bix = jnp.clip(trig_extra, 0, B - 1)
                    cand = cand_b[bix]                     # [R, K]
                    cand_ok = jnp.logical_and(ok_b[bix], cand >= 0)
                    ri2 = jnp.clip(cand, 0, C - 1)
                    env = {
                        this.key: tuple(c[:, None] for c in t_cols),
                        other.key: tuple(c[ri2] for c in o_cols),
                        "__ts__": orows.ts[:, None],
                        "__now__": now,
                    }
                    m = jnp.broadcast_to(on.fn(env), ri2.shape)
                    m = jnp.logical_and(m, cand_ok)
                    m = jnp.logical_and(m, o_alive[ri2])
                else:
                    env = {
                        this.key: tuple(c[:, None] for c in t_cols),
                        other.key: tuple(c[None, :] for c in o_cols),
                        "__ts__": orows.ts[:, None],
                        "__now__": now,
                    }
                    if on is None:
                        m = jnp.ones((R, C), jnp.bool_)
                    else:
                        m = jnp.broadcast_to(on.fn(env), (R, C))
                    m = jnp.logical_and(m, o_alive[None, :])
                    ri2 = jnp.broadcast_to(
                        jnp.arange(C, dtype=jnp.int32)[None, :], (R, C))
                m = jnp.logical_and(m, data_row[:, None])

            # `join_pairs` has three parts (a second scope level, listed in
            # observability/phases.py): `index` — the flags, `pos` / `li` /
            # `ri`, the composed group slot — `take_this` (what is gathered
            # by `li`) and `take_other` (what is gathered by `ri`)
            with jax.named_scope("join_pairs"), jax.named_scope("index"):
                # matched pair rows [R*Q] + unmatched rows [R] for outer
                # joins: their flags are complete when the probe ends
                Q = m.shape[1]
                pair_valid = m.reshape(-1)
                unmatched = jnp.logical_and(data_row, jnp.logical_not(
                    jnp.any(m, axis=1)))
                all_valid = jnp.concatenate([pair_valid, unmatched]) \
                    if emit_unmatched_this else pair_valid
                N = all_valid.shape[0]
                cap = min(N, emit_rows if emit_rows is not None
                          else max(2 * R, 1024))
            order = None
            if late_pairs and cap < N:
                with jax.named_scope("join_compact"):
                    # the cap's order FIRST: the selector keeps nothing
                    # across rows and drops none, so the first `cap` valid
                    # flat pair positions are the rows delivered — every
                    # column below is gathered once, over `cap` rows
                    order = jnp.argsort(jnp.logical_not(all_valid),
                                        stable=True)[:cap]
                    n_tot = jnp.sum(all_valid).astype(jnp.int32)
            with jax.named_scope("join_pairs"):
                with jax.named_scope("index"):
                    # ri carries REAL buffer positions so seq/order match
                    # the grid path bit for bit
                    right_idx = ri2.astype(jnp.int32).reshape(-1)
                    if emit_unmatched_this:
                        right_idx = jnp.concatenate(
                            [right_idx, jnp.zeros((R,), jnp.int32)])
                    if order is None:
                        # every candidate row, in flat pair position
                        pos, ri = jnp.arange(N, dtype=jnp.int32), right_idx
                        row_valid = all_valid
                    else:
                        # the cap's rows alone; valid-first and stable, so
                        # the first n_tot of them are the valid ones
                        pos = order.astype(jnp.int32)
                        ri = right_idx[pos]
                        row_valid = jnp.arange(cap, dtype=jnp.int32) < n_tot
                    # the outer join's unmatched rows stand after the R*Q
                    # pairs
                    tail = pos >= R * Q
                    li = jnp.where(tail, pos - R * Q, pos // Q)
                    null_tail = jnp.logical_and(tail, row_valid)

                with jax.named_scope("take_this"):
                    this_cols = tuple(c[li] for c in t_cols)
                # unmatched outer-join rows carry REAL nulls on the other side
                # (reference: JoinProcessor.java:107-190 emits null attributes;
                # numerics use the reserved in-band null, core/event.py)
                with jax.named_scope("take_other"):
                    other_cols_g = tuple(
                        jnp.where(null_tail,
                                  jnp.asarray(ev.null_value(t),
                                              dtype=c.dtype),
                                  c[ri])
                        for c, t in zip(o_cols, other.schema.types))
                with jax.named_scope("take_this"):
                    sel_env = {
                        this.key: this_cols,
                        other.key: other_cols_g,
                        "__ts__": orows.ts[li],
                        "__now__": now,
                    }
                    tg = orows.gslot[li]
                # composed group slot: gl * (Kr + 1) + gr; unmatched outer rows
                # take the other side's null-group id (K_other)
                with jax.named_scope("take_other"):
                    og = jnp.where(null_tail, K_other,
                                   o_gslot[jnp.clip(ri, 0, C - 1)])
                with jax.named_scope("index"):
                    if this_is_left:
                        comp = tg * (Kr + 1) + og
                    else:
                        comp = og * (Kr + 1) + tg
                with jax.named_scope("take_this"):
                    j_ts, j_kind, j_seq = \
                        orows.ts[li], orows.kind[li], orows.seq[li]
                with jax.named_scope("index"):
                    jrows = Rows(
                        ts=j_ts,
                        kind=j_kind,
                        valid=row_valid,
                        seq=j_seq * (C + 1) + ri,
                        gslot=comp.astype(jnp.int32),
                        cols=(),
                    )
            with jax.named_scope("join_select"):
                sel_state, out = sel.process(sel_state, jrows, sel_env)
            with jax.named_scope("join_compact"):
                # device-side compaction: the host fetches `cap` slots, never
                # the N = R*Q(+R) candidate pair rows.  Where the order went
                # first (`late_pairs`) the rows already stand at the cap;
                # elsewhere the selector ran over all N rows (its running
                # values, `having`, `order by` / `limit` read every one) and
                # the same stable valid-first argsort squeezes its output
                # here.  Rows beyond the cap are counted as dropped and the
                # runtime grows the cap (a planned recompile) when the cap
                # was implicit.
                o_ts, o_kind, o_valid, o_cols = out
                if order is None:
                    n_tot = jnp.sum(o_valid).astype(jnp.int32)
                    if cap < N:
                        order = jnp.argsort(jnp.logical_not(o_valid),
                                            stable=True)[:cap]
                        o_ts, o_kind, o_valid = \
                            o_ts[order], o_kind[order], o_valid[order]
                        o_cols = tuple(c[order] for c in o_cols)
                n_del = jnp.minimum(n_tot, jnp.int32(cap))
                # header ships [n_valid, n_current] so count-only consumers
                # (the common bench/monitoring shape) cost ZERO bulk fetches;
                # n_expired derives as n_valid - n_current host-side
                n_cur = jnp.sum(jnp.logical_and(
                    o_valid, o_kind == ev.CURRENT)).astype(jnp.int32)
                head = [n_del, n_cur]
                if counts_drops:
                    head.append(w_dropped.astype(jnp.int32))
                out = (jnp.stack(head), n_tot - n_del,
                       o_ts, o_kind, o_valid, o_cols)
            nstate = ((this_state, other_state) if this_is_left
                      else (other_state, this_state))
            new_state = _constrain_state(
                (nstate[0], nstate[1], sel_state), mesh)
            return new_state, out, wake

        return step

    # raw (un-jitted) bodies are kept on the plan: @fuse(batches=K) wraps
    # them in its lax.scan so fused execution runs the identical per-batch
    # program (core/fusion.py)
    step_left = raw_left = None
    step_right = raw_right = None
    # named-window sides trigger too (bidirectional, Window.java:145-184);
    # plain table/aggregation sides stay probe-only
    if (not left.is_table or left.is_named_window) and \
            trigger in ("ALL_EVENTS", "LEFT"):
        raw_left = make_step(left, right, True)
    if (not right.is_table or right.is_named_window) and \
            trigger in ("ALL_EVENTS", "RIGHT"):
        raw_right = make_step(right, left, False)
    # non-triggering stream sides still need their window maintained
    if not left.is_table and raw_left is None:
        raw_left = _make_feed_only(left, True, mesh, fp_mode)
    if not right.is_table and raw_right is None:
        raw_right = _make_feed_only(right, False, mesh, fp_mode)
    if raw_left is not None:
        step_left = jit_step(raw_left, owner=name, role="join_left",
                             donate_argnums=(0,))
    if raw_right is not None:
        step_right = jit_step(raw_right, owner=name, role="join_right",
                              donate_argnums=(0,))
    def step_maker(is_left: bool, depth: int, slow: bool):
        """A ring side's program at another walk depth / for stamps out of
        order (PlannedJoinQuery.side_step)."""
        side, other = (left, right) if is_left else (right, left)
        triggers = trigger in ("ALL_EVENTS", "LEFT" if is_left else "RIGHT")
        raw = make_step(side, other, is_left, slow=slow,
                        depth_other=depth) if triggers else \
            _make_feed_only(side, is_left, mesh, fp_mode, slow=slow)
        return jit_step(raw, owner=name,
                        role="join_left" if is_left else "join_right",
                        donate_argnums=(0,))

    def init_state():
        def side_state(side):
            if side.window is None:
                return ()
            if isinstance(side.window, TimeRingWindow):
                return _ring_side_init(side.window, jk_alloc.capacity)
            return side.window.init_state()
        return (side_state(left), side_state(right), sel.init_state())

    return PlannedJoinQuery(
        name=name, left=left, right=right, join_type=jt, trigger=trigger,
        within_range=within_range, per_duration=per_duration,
        out_schema=out_schema,
        output_target=out_target,
        output_event_type=out_event_type,
        selector_exec=sel,
        step_left=step_left, step_right=step_right,
        init_state=init_state, batch_capacity=batch_capacity,
        slot_allocator=gl_alloc, slot_allocator2=gr_alloc,
        gl_pos=gl_pos, gr_pos=gr_pos,
        needs_timer=(left.window is not None and left.window.needs_timer) or
                    (right.window is not None and right.window.needs_timer),
        emits_uuid=scope.uses_uuid,
        compact_rows=emit_rows, emit_explicit=emit_explicit,
        raw_left=raw_left, raw_right=raw_right,
        fastpath=fp_mode, fastpath_reason=fp_reason,
        key_attrs=key_attrs, key_left=key_left, key_right=key_right,
        key_dtypes=key_dtypes, residual=fp_residual,
        lane_k=lane_k, lane_buckets=lane_buckets, ring_caps=ring_caps,
        index_kind=index_kind,
        step_maker=step_maker if "chain" in index_kind else None,
        join_key_allocator=jk_alloc,
        table_is_left=table_is_left, table_pos=table_pos,
        stream_key_pos=stream_key_pos, late_pairs=late_pairs,
        expired_joined=expired_joined)


def _make_feed_only(side: JoinSide, is_left: bool, mesh=None,
                    fp_mode: Optional[str] = None, slow: bool = False):
    takes_probe = fp_mode in ("bucket", "table")
    ring = isinstance(side.window, TimeRingWindow)

    def step(state, ts, kind, valid, cols, gslot, *rest):
        if takes_probe:
            probe, other_table_cols, now = rest
        else:
            other_table_cols, now = rest
        wl_state, wr_state, sel_state = state
        this_state = wl_state if is_left else wr_state
        with jax.named_scope("join_window"):
            env0 = {side.key: cols, "__ts__": ts, "__now__": now}
            keep = valid
            is_cur = kind == ev.CURRENT
            for f in side.pre_filters:
                keep = jnp.logical_and(keep, jnp.logical_or(
                    jnp.logical_not(is_cur), f.fn(env0)))
            in_cols = cols
            if fp_mode == "bucket":
                in_cols = cols + (probe,)
            elif fp_mode == "table":
                in_cols = cols + (jnp.arange(ts.shape[0], dtype=jnp.int32),)
            rows = Rows(ts=ts, kind=kind, valid=keep, seq=jnp.zeros_like(ts),
                        gslot=gslot, cols=in_cols)
            if not ring:
                this_state, wout = side.window.process(this_state, rows, now)
                wake = wout.next_wakeup
        if ring:
            this_state, _cur, _dropped = _ring_feed(
                side.window, this_state, rows, probe, now, slow)
            wake = jnp.asarray(NO_WAKEUP, jnp.int64)
        out_empty = (
            jnp.zeros((1,), jnp.int64), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.bool_), tuple())
        new_state = (this_state, wr_state, sel_state) if is_left else \
            (wl_state, this_state, sel_state)
        return _constrain_state(new_state, mesh), out_empty, wake

    return step


# ---------------------------------------------------------------------------
# equi-join fast path machinery (ROADMAP item 2)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# the kept index of a ring side: a head table by key slot, a link a row
# ---------------------------------------------------------------------------
#
# A `window.time` side kept as a ring (window.TimeRingWindow) carries, beside
# its window state `(RingSlab, seq, pos)`, `head [keys]` — the ring position of
# the NEWEST row of each key slot, -1 none — and `prev [C]` — for each ring
# position the position of the next OLDER row of its key, -1 none.  Arrivals
# are linked in as they are written; nothing is ever unlinked: a walk follows
# a link only to a STRICTLY OLDER resident row (`ring_age` falling, below
# `count`), so a link into a row that has expired, or into a position a newer
# row has since taken, ends the walk — and every resident row of a key stands
# before any such link, because a ring expires oldest first.  A head that
# points at a position another key has taken costs a candidate the ON
# condition rejects, never a match.

CHAIN_EXP_BY_SEARCH = 16    # deeper walks bound expiry by one search a row


def chain_depth(need: int, fullest: int) -> int:
    """The walk depth a batch is given: 1 where the probed side has never
    held two rows of a key; else the power of four at or above the most
    rows any of the BATCH's keys holds there (`need`), 4 at the least — a
    few programs (1, 4, 16, 64, ...), each compiled when first needed, and a
    side of unique keys is probed 1 deep while the other holds a 300-row
    key."""
    if fullest <= 1:
        return 1
    d = 4
    while d < need:
        d *= 4
    return d


def _ring_side_init(win: TimeRingWindow, n_keys: int):
    return win.init_state() + (
        jnp.full((n_keys,), -1, jnp.int32),
        jnp.full((win.capacity,), -1, jnp.int32))


def _key_runs(ss):
    """Of keys in sorted order: (is the row before of the same key, is this
    the last row of its key)."""
    same = ss[1:] == ss[:-1]
    return (jnp.concatenate([jnp.zeros((1,), jnp.bool_), same]),
            jnp.concatenate([jnp.logical_not(same),
                             jnp.ones((1,), jnp.bool_)]))


def _chain_insert(head, slot, is_cur, pos):
    """Link a batch's arrivals (`slot`, ring position `pos`, [B]) in: ->
    (head', the `prev` value of each arrival).  An arrival's older row is
    the batch's previous arrival of its slot, else the slot's old head."""
    n_keys = head.shape[0]
    key = jnp.where(is_cur, slot, n_keys)
    o = jnp.argsort(key, stable=True).astype(jnp.int32)
    ss, pp = key[o], pos[o]
    same, last = _key_runs(ss)
    older = jnp.where(
        same, jnp.concatenate([pp[:1], pp[:-1]]),
        head.at[jnp.minimum(ss, n_keys - 1)].get(mode="promise_in_bounds"))
    head = head.at[jnp.where(last, ss, n_keys)].set(
        pp, mode="drop", unique_indices=True)
    return head, older[jnp.argsort(o)]


def _chain_build(jslot, count, n_keys: int):
    """`head`, `prev` from nothing, for a ring that starts at 0 with `count`
    rows (after `ring_process`): one sort of the slab by (slot, age)."""
    C = jslot.shape[0]
    live = jnp.arange(C, dtype=jnp.int32) < count
    key = jnp.where(live, jslot.astype(jnp.int32), n_keys)
    o = jnp.argsort(key, stable=True).astype(jnp.int32)
    ss = key[o]
    same, last = _key_runs(ss)
    older = jnp.where(same, jnp.concatenate([o[:1], o[:-1]]), -1)
    prev = jnp.full((C,), -1, jnp.int32).at[o].set(older)
    head = jnp.full((n_keys,), -1, jnp.int32).at[
        jnp.where(last, ss, n_keys)].set(o, mode="drop")
    return head, prev


def _chain_walk(head, prev, exp, rp, slot, ts, depth: int):
    """The resident rows of each trigger row's key that its stamp still
    sees, oldest first: -> (cand [R, depth] ring positions, ok [R, depth])."""
    C = prev.shape[0]
    tail, count = rp[0], rp[1]
    known = slot >= 0
    cur = head.at[jnp.clip(slot, 0, head.shape[0] - 1)].get(
        mode="promise_in_bounds")
    age = ring_age(cur, tail, C)
    ok = jnp.logical_and(known, jnp.logical_and(cur >= 0, age < count))
    by_search = depth > CHAIN_EXP_BY_SEARCH
    if by_search:
        # a ring is in expiry order: what a stamp no longer sees is a
        # prefix, `seen` rows long — and a key whose newest row is in it has
        # no row left
        seen = ring_search(exp, tail, count, ts, C)
        ok = jnp.logical_and(ok, age >= seen)

    def hop(c, _):
        cur, age, ok = c
        nxt = prev.at[jnp.clip(cur, 0, C - 1)].get(mode="promise_in_bounds")
        nage = ring_age(nxt, tail, C)
        nok = jnp.logical_and(ok, jnp.logical_and(nxt >= 0, nage < age))
        return (nxt, nage, nok), (cur, ok)

    if depth == 1:
        cand, oks = cur[:, None], ok[:, None]
    else:
        _, (cands, okss) = jax.lax.scan(hop, (cur, age, ok), None,
                                       length=depth)
        if by_search:
            okss = jnp.logical_and(
                okss, ring_age(cands, tail, C) >= seen[None, :])
        # newest first as walked; the grid's order is oldest first
        cand, oks = cands[::-1].T, okss[::-1].T
    cand = jnp.clip(cand, 0, C - 1)
    if not by_search:
        oks = jnp.logical_and(oks, slab_take(exp, cand) > ts[:, None])
    return cand, oks


def _ring_feed(win: TimeRingWindow, side_state, rows: Rows, slot, now,
               slow: bool):
    """One batch into a ring side: -> (side state, the arrivals as CURRENT
    trigger rows where they stand, rows dropped for capacity).  The window's
    ops stand under `join_window`, the index upkeep under `join_lanes`."""
    wstate, head, prev = side_state[:3], side_state[3], side_state[4]
    if slow or rows.capacity > win.capacity:
        with jax.named_scope("join_window"):
            wstate, cur, dropped = win.ring_process(wstate, rows, now)
        with jax.named_scope("join_lanes"):
            head, prev = _chain_build(wstate[0].cols[-1], wstate[2][1],
                                      head.shape[0])
        return wstate + (head, prev), cur, dropped
    with jax.named_scope("join_window"):
        wstate, cur, pos, dropped, plan = win.ring_admit(wstate, rows, now)
    with jax.named_scope("join_lanes"):
        head, older = _chain_insert(head, slot.astype(jnp.int32), cur.valid,
                                    pos)
        prev, = ring_write((prev,), (older,), *plan)
    return wstate + (head, prev), cur, dropped


def _retention_rows(win: Optional[WindowProcessor]) -> int:
    """Upper bound on rows a join window retains: length windows keep
    exactly `length`; time windows drop-oldest above `capacity`."""
    if win is None:
        return 0
    n = getattr(win, "length", None)
    if n is None:
        n = getattr(win, "capacity", None)
    return int(n if n is not None else win.batch_capacity)


def _lane_bucket_count(ring: int) -> int:
    """Power-of-two lane-table rows for a buffer bound: ~2 buckets per
    resident row keeps slot-modulo collisions (which only widen lanes,
    never lose matches) rare while the device table stays small."""
    return max(64, min(1 << 17, 1 << (2 * max(ring, 1) - 1).bit_length()))


def _conjunct_count(on) -> int:
    from ..query_api.expression import And
    if on is None:
        return 0
    if isinstance(on, And):
        return _conjunct_count(on.left) + _conjunct_count(on.right)
    return 1


def _bucket_lanes(jslot, alive, nbl: int, k: int):
    """Derive the per-bucket candidate lane table [nbl, k] from a window
    buffer's key-slot column: entries are buffer positions ascending
    within each bucket (grid-path emission order), `C` where a lane is
    empty.  O(C log C) work on the buffer only — never on the grid.
    Lane overflow cannot happen by construction: the host
    JoinKeyTracker grows the planned `k` past the worst same-bucket
    occupancy BEFORE the batch that would need it dispatches."""
    C = jslot.shape[0]
    bkt = jnp.where(alive, jslot.astype(jnp.int32) % nbl, nbl)
    order = jnp.argsort(bkt, stable=True).astype(jnp.int32)
    sb = bkt[order]
    first = jnp.searchsorted(sb, sb, side="left")
    rank = jnp.arange(C, dtype=jnp.int32) - first.astype(jnp.int32)
    lanes = jnp.full((nbl + 1, k + 1), C, jnp.int32)
    lanes = lanes.at[jnp.minimum(sb, nbl),
                     jnp.minimum(rank, k)].set(order)
    return lanes[:nbl, :k]


def _norm_key_cols(staged_cols, positions, dtypes) -> List[np.ndarray]:
    """Key columns normalized to the promoted compare dtype so both
    sides of `L.a == R.b` hash identically (float -0.0 folds into +0.0,
    same as table_index.AttributeIndex._key_cols)."""
    out = []
    for pos, dt in zip(positions, dtypes):
        c = np.asarray(staged_cols[pos]).astype(dt, copy=False)
        if np.issubdtype(dt, np.floating):
            c = c + np.dtype(dt).type(0.0)
        out.append(np.ascontiguousarray(c))
    return out


class _TrackSide:
    """One side's retention ring on the host: the key slot of every row the
    device window holds, oldest first, with — for a `window.time` side —
    the stamp each expires at; the rows a slot holds (`cnt`), the fullest
    key ever (`deep`), and, for a side probed through lanes, the per-lane
    (slot % nbl) occupancy."""

    __slots__ = ("cap", "nbl", "ring", "exp", "time_ms", "head", "n", "lane",
                 "cnt", "deep", "dropped")

    def __init__(self, cap: int, nbl: int, n_keys: int,
                 time_ms: Optional[int] = None, lanes: bool = True):
        self.cap = max(1, int(cap))
        self.nbl = max(1, int(nbl))
        self.ring = np.full(self.cap, -1, np.int32)
        self.time_ms = time_ms
        self.exp = None if time_ms is None else np.zeros(self.cap, np.int64)
        self.head = 0
        self.n = 0
        self.lane = np.zeros(self.nbl, np.int64) if lanes else None
        self.cnt = np.zeros(n_keys, np.int32)
        self.deep = 0
        self.dropped = 0          # rows a full `window.time` side lost

    def _span(self, start: int, k: int) -> np.ndarray:
        return (self.head + start + np.arange(k)) % self.cap

    def due(self, now: int) -> int:
        """How many of the oldest rows have expired by `now` (the ring is
        in expiry order: two sorted runs where it wraps)."""
        if self.exp is None or not self.n:
            return 0
        end = self.head + self.n
        if end <= self.cap:
            return int(np.searchsorted(self.exp[self.head:end], now,
                                       side="right"))
        first = self.exp[self.head:]
        k = int(np.searchsorted(first, now, side="right"))
        if k == first.size:
            k += int(np.searchsorted(self.exp[:end - self.cap], now,
                                     side="right"))
        return k

    def pop(self, k: int) -> np.ndarray:
        """Forget the `k` oldest rows; -> the distinct slots they held."""
        old = self.ring[self._span(0, k)]
        self.head = (self.head + k) % self.cap
        self.n -= k
        u, c = np.unique(old, return_counts=True)
        self.cnt[u] -= c.astype(np.int32)
        if self.lane is not None:
            self.lane -= np.bincount(old % self.nbl, minlength=self.nbl)
        return u

    def push(self, slots: np.ndarray, exp=None) -> None:
        idx = self._span(self.n, slots.size)
        self.ring[idx] = slots
        if self.exp is not None:
            self.exp[idx] = exp
        self.n += slots.size
        u, c = np.unique(slots, return_counts=True)
        self.cnt[u] += c.astype(np.int32)
        self.deep = max(self.deep, int(self.cnt[u].max()))
        if self.lane is not None:
            self.lane += np.bincount(slots % self.nbl, minlength=self.nbl)

    def in_expiry_order(self) -> None:
        """After stamps out of order: the rows oldest-expiry first from 0,
        as the device's `ring_process` leaves them."""
        idx = self._span(0, self.n)
        order = np.argsort(self.exp[idx], kind="stable")
        self.ring[:self.n] = self.ring[idx][order]
        self.exp[:self.n] = self.exp[idx][order]
        self.head = 0


class JoinKeyTracker:
    """Host mirror of per-key window retention for the bucketed
    equi-join fast path.  Numpy throughout: a send costs a few passes over
    ITS rows, whatever the windows hold.

    Conservative invariant: each side's ring holds the key slots of a
    SUPERSET of the rows alive in that side's device buffer — exactly the
    rows for a length window (the last `length` arrivals) and for a
    `window.time` side whose stamps come in order (what the clock has not
    expired by the side's own last step, the newest `cap` of them; a row a
    full window loses is COUNTED, `dropped`).  Two guarantees ride on it:
    (1) the fullest lane (`needed_k`) and the fullest key (`fullest_keys`)
    never under-count the device buffers, so the planned lane width and
    walk depth always cover every candidate; (2) a key slot recycles only
    when NEITHER ring retains it, so no row the device still shows can be
    left holding a slot that a new key re-binds."""

    def __init__(self, alloc: SlotAllocator, ring_caps, lane_buckets,
                 time_ms=(None, None), index_kind=("lanes", "lanes")):
        self.alloc = alloc
        self.sides = tuple(
            _TrackSide(ring_caps[i], lane_buckets[i], alloc.capacity,
                       time_ms[i], lanes=index_kind[i] != "chain")
            for i in (0, 1))
        self.batch_need = 0

    def needed_k(self) -> int:
        return max((int(s.lane.max(initial=0)) for s in self.sides
                    if s.lane is not None), default=0)

    def fullest_keys(self) -> Tuple[int, int]:
        return (self.sides[0].deep, self.sides[1].deep)

    def rows(self) -> Tuple[int, int]:
        return (self.sides[0].n, self.sides[1].n)

    def dropped(self) -> int:
        return self.sides[0].dropped + self.sides[1].dropped

    def track(self, is_left: bool, key_cols, valid, ts=None, now=None,
              in_order: bool = True) -> np.ndarray:
        """Allocate bucket slots for one batch and fold it into the
        side's ring.  Evicts BEFORE allocating — what the clock has expired
        by `now`, then what the batch pushes out of a full ring — so the
        allocator's capacity bound holds transiently, and purges any slot
        neither ring retains afterwards."""
        s = self.sides[0 if is_left else 1]
        nv = int(valid.sum())
        dead = []
        k = s.due(now) if now is not None else 0
        if k:
            dead.append(s.pop(k))
        over = min(max(s.n + min(nv, s.cap) - s.cap, 0), s.n) if nv else 0
        if over:
            if s.exp is not None:
                s.dropped += over
            dead.append(s.pop(over))
        slots = self.alloc.slots_for(key_cols, valid)
        ins = slots[valid].astype(np.int32)
        # the most rows any of the batch's keys holds on the OTHER side:
        # how deep its probes have to walk
        o = self.sides[1 if is_left else 0]
        self.batch_need = int(o.cnt[ins].max()) if ins.size else 0
        exp = None if s.exp is None else \
            np.asarray(ts)[valid].astype(np.int64) + s.time_ms
        if ins.size > s.cap:
            # a batch larger than the window: only its last `cap` rows
            # survive the step's own eviction — earlier rows join
            # transiently within the step but retain nothing
            dead.append(np.unique(ins[:-s.cap]))
            if s.exp is not None:
                s.dropped += ins.size - s.cap
                exp = exp[-s.cap:]
            ins = ins[-s.cap:]
        if ins.size:
            s.push(ins, exp)
            if not in_order:
                s.in_expiry_order()
        if dead:
            d = np.concatenate(dead)
            gone = d[(self.sides[0].cnt[d] == 0) &
                     (self.sides[1].cnt[d] == 0)]
            if gone.size:
                self.alloc.purge(np.unique(gone))
        return slots

    def rebuild(self, per_side_slots, per_side_exp=(None, None)) -> None:
        """Restore path: re-seed both rings from the snapshot's buffer
        contents (alive rows in arrival order) and drop every allocator
        binding neither window retains."""
        self.sides = tuple(
            _TrackSide(s.cap, s.nbl, self.alloc.capacity, s.time_ms,
                       lanes=s.lane is not None) for s in self.sides)
        for s, slots, exp in zip(self.sides, per_side_slots, per_side_exp):
            arr = np.asarray(slots, np.int32)[-s.cap:]
            if arr.size:
                s.push(arr, None if s.exp is None else
                       np.asarray(exp, np.int64)[-s.cap:])
        live = np.zeros(self.alloc.capacity, bool)
        for key, slot in self.alloc.snapshot().items():
            live[slot] = True
        held = (self.sides[0].cnt > 0) | (self.sides[1].cnt > 0)
        gone = np.nonzero(live & ~held)[0]
        if gone.size:
            self.alloc.purge(gone)

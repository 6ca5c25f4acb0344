"""Incremental time-granularity aggregation.

Reference behavior (what): CORE/aggregation/AggregationRuntime.java:81,
IncrementalExecutor.java:48 (execute :102-130), AggregationParser.java —
`define aggregation A from S select g, avg(x) as ax ... group by g
aggregate by ts every sec...year` maintains running aggregates per duration
bucket (seconds..years); avg decomposes into sum+count base attributes
(incremental/AvgIncrementalAttributeAggregator.java:57-95); queries join
against a duration's buckets `within` a time range (`per "days"`).
Out-of-order events (OutOfOrderEventsDataAggregator.java:177), bucket
purging (IncrementalDataPurger.java:307), restart rebuild from backing
tables (IncrementalExecutorsInitialiser.java:203) and distributed shardId
mode (AggregationParser.java:173-197) are part of the surface.

TPU-native design (how): the reference cascades one executor per duration,
rolling finer buckets into coarser on rollover, which is why it needs
special out-of-order handling (only the current bucket is live in memory).
Here each duration keeps a DEVICE-RESIDENT slab [n_base, capacity] of
running base values; (group-key, bucket-start) pairs resolve to slab slots
through the native SlotAllocator staging path and the per-event merge is a
single jitted scatter (`.at[idx].add/min/max`) on device — no cascade, no
per-bucket dicts, and any bucket (past or present) is updatable, so
out-of-order arrival is the normal path, not a special case.  Purging
frees slots back to the allocator and resets slab columns to the identity.
With a @store annotation the slabs write through to per-duration record
tables (rows tagged with the configured shardId); on start the slabs
rebuild by merging table rows across every shard.  Join and on-demand
reads materialize a padded columnar snapshot (AGG_TIMESTAMP + declared
outputs) that drops into the existing table-join device path.
"""
from __future__ import annotations

import calendar
import datetime
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query_api.expression import Constant, Variable
from . import event as ev
from .executor import CompileError, Scope, compile_expression
from .steputil import jit_step

DURATION_MS = {
    "SECONDS": 1000,
    "MINUTES": 60_000,
    "HOURS": 3_600_000,
    "DAYS": 86_400_000,
    # MONTHS / YEARS are calendar-based; handled specially
}

_DUR_ALIASES = {
    "sec": "SECONDS", "second": "SECONDS", "seconds": "SECONDS",
    "min": "MINUTES", "minute": "MINUTES", "minutes": "MINUTES",
    "hour": "HOURS", "hours": "HOURS",
    "day": "DAYS", "days": "DAYS",
    "month": "MONTHS", "months": "MONTHS",
    "year": "YEARS", "years": "YEARS",
}


def normalize_duration(name: str) -> str:
    d = _DUR_ALIASES.get(name.strip().lower())
    if d is None:
        raise CompileError(f"unknown aggregation duration {name!r}")
    return d


def truncate_buckets(ts_ms: np.ndarray, duration: str) -> np.ndarray:
    """Bucket start per timestamp (vectorized; calendar months/years via
    per-unique conversion, matching the reference's calendar semantics —
    IncrementalUnixTimeFunctionUtil)."""
    if duration in DURATION_MS:
        d = DURATION_MS[duration]
        return (ts_ms // d) * d
    uniq, inv = np.unique(ts_ms, return_inverse=True)
    outs = np.empty_like(uniq)
    for i, t in enumerate(uniq):
        dt = datetime.datetime.fromtimestamp(t / 1000.0, datetime.timezone.utc)
        if duration == "MONTHS":
            dt = dt.replace(day=1, hour=0, minute=0, second=0, microsecond=0)
        else:  # YEARS
            dt = dt.replace(month=1, day=1, hour=0, minute=0, second=0,
                            microsecond=0)
        outs[i] = int(calendar.timegm(dt.timetuple()) * 1000)
    return outs[inv]


_DATE_FIELDS = ("year", "month", "day", "hour", "minute", "second")


def _parse_date_string(s: str) -> Tuple[int, Optional[str]]:
    """Parse `yyyy-MM-dd HH:mm:ss` (components optional from the right, or
    `**` wildcards) -> (epoch_ms_start, wildcard_field | None).
    Reference: within-clause time formats, aggregation docs."""
    s = s.strip()
    import re
    m = re.match(
        r"^(\d{4}|\*\*)(?:-(\d{1,2}|\*\*))?(?:-(\d{1,2}|\*\*))?"
        r"(?:[ T](\d{1,2}|\*\*))?(?::(\d{1,2}|\*\*))?(?::(\d{1,2}|\*\*))?",
        s)
    if not m or m.group(1) == "**":
        raise CompileError(f"cannot parse within-time {s!r}")
    vals = []
    wildcard = None
    for i, g in enumerate(m.groups()):
        if g is None or g == "**":
            if wildcard is None:
                wildcard = _DATE_FIELDS[i]
            vals.append(None)
        else:
            if wildcard is not None:
                raise CompileError(
                    f"non-wildcard after wildcard in {s!r}")
            vals.append(int(g))
    y = vals[0]
    dt = datetime.datetime(
        y, vals[1] or 1, vals[2] or 1, vals[3] or 0, vals[4] or 0,
        vals[5] or 0)
    return int(calendar.timegm(dt.timetuple()) * 1000), wildcard


def _advance(dt_ms: int, field: str) -> int:
    dt = datetime.datetime.fromtimestamp(dt_ms / 1000.0, datetime.timezone.utc)
    if field == "year":
        dt = dt.replace(year=dt.year + 1)
    elif field == "month":
        dt = dt.replace(year=dt.year + (dt.month == 12),
                        month=dt.month % 12 + 1)
    else:
        delta = {"day": 86_400, "hour": 3_600, "minute": 60, "second": 1}
        return dt_ms + delta[field] * 1000
    return int(calendar.timegm(dt.timetuple()) * 1000)


def _bound_of(expr) -> Tuple[int, Optional[str]]:
    if isinstance(expr, Constant):
        if expr.type in ("LONG", "INT"):
            return int(expr.value), None
        if expr.type == "STRING":
            return _parse_date_string(str(expr.value))
    raise CompileError(
        "within bounds must be time-string or epoch-ms constants")


def parse_within(within) -> Tuple[int, int]:
    """within '2020-01-01 ...' [, '2020-02-01 ...'] -> [start, end) ms."""
    if within is None:
        raise CompileError(
            "aggregation reads need a `within` clause (reference: "
            "AggregationRuntime.compileExpression)")
    if isinstance(within, tuple):
        s, _ = _bound_of(within[0])
        e, _ = _bound_of(within[1])
        return s, e
    s, wildcard = _bound_of(within)
    if wildcard is None:
        # single full timestamp: that instant's smallest covered unit
        return s, _advance(s, "second")
    return s, _advance(s, {"month": "year", "day": "month",
                           "hour": "day", "minute": "hour",
                           "second": "minute"}[wildcard])


def parse_per(per) -> str:
    if per is None:
        raise CompileError("aggregation reads need a `per` duration")
    if isinstance(per, Constant) and per.type == "STRING":
        return normalize_duration(str(per.value))
    if isinstance(per, Variable):
        return normalize_duration(per.attribute_name)
    raise CompileError("per must be a duration name")


class _BaseAgg:
    """One base (decomposed) aggregation: a compiled value expression and a
    merge rule."""

    def __init__(self, kind: str, value_fn, dtype):
        self.kind = kind          # 'sum' | 'count' | 'min' | 'max'
        self.value_fn = value_fn  # env -> [B] values (None for count)
        self.dtype = dtype

    def identity(self) -> float:
        if self.kind == "min":
            return np.inf
        if self.kind == "max":
            return -np.inf
        return 0.0

    def merge(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        if self.kind == "min":
            return np.minimum(a, b)
        if self.kind == "max":
            return np.maximum(a, b)
        return a + b

    def np_reduce_at(self, acc: np.ndarray, idx: np.ndarray,
                     vals: np.ndarray) -> None:
        if self.kind == "min":
            np.minimum.at(acc, idx, vals)
        elif self.kind == "max":
            np.maximum.at(acc, idx, vals)
        else:
            np.add.at(acc, idx, vals)


# reference retention defaults (IncrementalDataPurger.java:307 /
# aggregation docs); None = keep forever ("all")
_DEFAULT_RETENTION_MS = {
    "SECONDS": 120_000,
    "MINUTES": 24 * 3_600_000,
    "HOURS": 30 * 86_400_000,
    "DAYS": 366 * 86_400_000,
    "MONTHS": None,
    "YEARS": None,
}

_TIME_UNITS_MS = {
    "ms": 1, "millisec": 1, "millisecond": 1, "milliseconds": 1,
    "sec": 1000, "second": 1000, "seconds": 1000,
    "week": 7 * 86_400_000, "weeks": 7 * 86_400_000,
    "min": 60_000, "minute": 60_000, "minutes": 60_000,
    "hour": 3_600_000, "hours": 3_600_000,
    "day": 86_400_000, "days": 86_400_000,
    "month": 30 * 86_400_000, "months": 30 * 86_400_000,
    "year": 365 * 86_400_000, "years": 365 * 86_400_000,
}


def parse_time_ms(s: str) -> Optional[int]:
    """'120 sec' / '24 hours' / 'all' -> milliseconds (None = unbounded)."""
    s = str(s).strip().lower()
    if s == "all":
        return None
    parts = s.split()
    if len(parts) == 2 and parts[1] in _TIME_UNITS_MS:
        return int(float(parts[0]) * _TIME_UNITS_MS[parts[1]])
    if s.isdigit():
        return int(s)
    raise CompileError(f"cannot parse time value {s!r}")


class _DurationStore:
    """Device-resident bucket slab for one duration: running base values
    [n_base, capacity] indexed by slot, with (group-bits..., bucket) keys
    resolved through the native SlotAllocator (reference role: the
    per-duration BaseIncrementalValueStore maps + backing table)."""

    def __init__(self, agg_name: str, dur: str, identities: np.ndarray,
                 capacity: int, mesh=None):
        from .keyslots import SlotAllocator
        self.dur = dur
        self.capacity = capacity
        self.alloc = SlotAllocator(capacity, f"{agg_name}:{dur}")
        self.identities = identities                    # [n_base] f64
        self.mesh = mesh
        self.slab = self.place(jnp.asarray(
            np.tile(identities[:, None], (1, capacity))))
        # slots written since the last table flush (@store write-through)
        self.dirty = np.zeros(capacity, np.bool_)
        # slots written since the last (incremental) snapshot baseline
        self.snap_dirty = np.zeros(capacity, np.bool_)

    def place(self, slab):
        """Bucket axis shards over the mesh (GSPMD: the jitted scatter-
        merge auto-partitions; replicated indices route to shard owners).
        Scale-out story for aggregation state — reference's equivalent is
        the shardId multi-JVM store split (AggregationParser :173-197)."""
        if self.mesh is None:
            return slab
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(slab, NamedSharding(self.mesh,
                                                  P(None, "shard")))

    def decode_keys(self) -> Tuple[np.ndarray, np.ndarray]:
        """(slots [n], key_words [n, ng+1] int64) for live slots."""
        mapping = self.alloc.snapshot()
        n = len(mapping)
        if n == 0:
            return np.zeros((0,), np.int64), np.zeros((0, 1), np.int64)
        slots = np.fromiter(mapping.values(), np.int64, n)
        words = np.frombuffer(b"".join(mapping.keys()), np.int64)
        return slots, words.reshape(n, -1)

    def reset_slots(self, slots: np.ndarray) -> None:
        if not len(slots):
            return
        if self.mesh is None:
            self.slab = self.slab.at[:, jnp.asarray(slots)].set(
                jnp.asarray(self.identities)[:, None])
        else:
            # host-context scatters into a sharded slab drop remote-shard
            # updates: go through the shared masked-where helper
            from .shardsafe import key_mask, masked_fill
            self.slab = masked_fill(
                self.slab, key_mask(slots, self.capacity),
                jnp.asarray(self.identities)[:, None], key_axis=1)
        self.dirty[slots] = False

    def scatter_rows(self, slots: np.ndarray, rows_t: np.ndarray) -> None:
        """Write rows_t [n_base, n] into slab columns `slots` (restore
        paths)."""
        if not len(slots):
            return
        if self.mesh is None:   # sparse fast path (no dense temp)
            self.slab = self.slab.at[:, jnp.asarray(slots)].set(
                jnp.asarray(rows_t))
            return
        from .shardsafe import key_mask, masked_fill
        upd = np.zeros((rows_t.shape[0], self.capacity), np.float64)
        upd[:, slots] = rows_t
        self.slab = masked_fill(
            self.slab, key_mask(slots, self.capacity), jnp.asarray(upd),
            key_axis=1)


def _null_of(attr_type: str) -> float:
    """The output type's in-band null as float64 (int sentinels are exact
    in f64 for the reserved minima)."""
    v = ev.null_value(attr_type)
    return float(v)


class _Output:
    """One declared output attribute and how to finalize it from base
    values (reference: IncrementalAttributeAggregator SPI)."""

    def __init__(self, name: str, attr_type: str, kind: str,
                 base_idx: Tuple[int, ...], group_pos: int = -1,
                 custom_fn=None):
        self.name = name
        self.type = attr_type
        self.kind = kind  # 'group'|'sum'|'count'|'min'|'max'|'avg'|'custom'
        self.base_idx = base_idx
        self.group_pos = group_pos  # index into group key tuple for 'group'
        self.custom_fn = custom_fn  # custom SPI: fn([cols]) -> col

    def finalize(self, base: np.ndarray) -> np.ndarray:
        """base: [n_rows, n_base] -> [n_rows] output column.  A bucket
        whose inputs were ALL null yields null (the in-band value of the
        output type — NaN would crash int decode for LONG sums)."""
        nullv = float(_null_of(self.type))
        if self.kind == "avg":
            s, c = base[:, self.base_idx[0]], base[:, self.base_idx[1]]
            return np.where(c > 0, s / np.maximum(c, 1), nullv)
        if self.kind == "custom":
            return np.asarray(self.custom_fn(
                [base[:, i] for i in self.base_idx]))
        col = base[:, self.base_idx[0]]
        if self.kind in ("sum", "min", "max") and len(self.base_idx) > 1:
            # the paired non-null count decides emptiness — sniffing the
            # accumulator for its identity would misread legitimate ±inf
            # data as an empty bucket
            return np.where(base[:, self.base_idx[1]] > 0, col, nullv)
        return col


class AggregationRuntime:
    """Host+device runtime for one `define aggregation`."""

    def __init__(self, adef, app):
        self.definition = adef
        self.app = app
        sis = adef.basic_single_input_stream
        self.input_stream_id = sis.unique_stream_id
        schema = app.schemas.get(self.input_stream_id)
        if schema is None:
            raise CompileError(
                f"aggregation {adef.id!r}: undefined stream "
                f"{self.input_stream_id!r}")
        self.in_schema = schema
        self._lock = threading.RLock()

        scope = Scope()
        scope.interner = app.interner
        scope.add_source(self.input_stream_id, schema,
                         alias=sis.stream_reference_id)

        # filters on the input stream
        from ..query_api.query import Filter
        self._filters = []
        for h in sis.stream_handlers:
            if isinstance(h, Filter):
                c = compile_expression(h.expression, scope)
                if c.type != "BOOL":
                    raise CompileError("aggregation filter must be boolean")
                self._filters.append(c)
            else:
                raise CompileError(
                    "aggregation input supports filters only")

        # group-by columns
        self.group_names = [v.attribute_name
                            for v in (adef.selector.group_by_list or [])]
        self.group_positions = [schema.position(n) for n in self.group_names]
        self.group_types = [schema.types[p] for p in self.group_positions]

        # aggregate-by timestamp attribute (or event ts)
        self.ts_pos = -1
        if adef.aggregate_attribute is not None:
            self.ts_pos = schema.position(
                adef.aggregate_attribute.attribute_name)

        # decompose selection into base aggregations + outputs
        self.base: List[_BaseAgg] = []
        self.outputs: List[_Output] = []
        self._decompose(adef.selector, scope)

        self.durations = [normalize_duration(d) for d in adef.time_periods] \
            or ["SECONDS"]
        self._identities = np.array([b.identity() for b in self.base],
                                    np.float64)
        cap_ann = adef.get_annotation("capacity") if \
            hasattr(adef, "get_annotation") else None
        self.bucket_capacity = int(cap_ann.element("buckets")) \
            if cap_ann is not None and cap_ann.element("buckets") else 1 << 16
        from ..sharding import shard_count
        agg_mesh = app.mesh
        if agg_mesh is not None and (
                shard_count(agg_mesh) < 2 or
                self.bucket_capacity % shard_count(agg_mesh) != 0):
            agg_mesh = None
        self._dstores: Dict[str, _DurationStore] = {
            d: _DurationStore(adef.id, d, self._identities,
                              self.bucket_capacity, mesh=agg_mesh)
            for d in self.durations}

        # retention per duration: defaults from the reference, overridable
        # with @retentionPeriod(sec='120 sec', min='24 hours', ..., or 'all')
        self.retention_ms: Dict[str, Optional[int]] = {
            d: _DEFAULT_RETENTION_MS[d] for d in self.durations}
        ret_ann = adef.get_annotation("retentionPeriod") if \
            hasattr(adef, "get_annotation") else None
        if ret_ann is not None:
            alias = {"sec": "SECONDS", "min": "MINUTES", "hours": "HOURS",
                     "days": "DAYS", "months": "MONTHS", "years": "YEARS"}
            for k, dur in alias.items():
                v = ret_ann.element(k)
                if v is not None and dur in self.retention_ms:
                    self.retention_ms[dur] = parse_time_ms(v)
        # @purge(enable='true'|'false', interval='10 sec')
        purge_ann = adef.get_annotation("purge") if \
            hasattr(adef, "get_annotation") else None
        self.purge_enabled = True
        self.purge_interval_ms = 15_000
        if purge_ann is not None:
            if purge_ann.element("enable") is not None:
                self.purge_enabled = str(
                    purge_ann.element("enable")).lower() == "true"
            if purge_ann.element("interval") is not None:
                iv = parse_time_ms(purge_ann.element("interval"))
                if not iv or iv <= 0:
                    raise CompileError(
                        f"@purge interval must be a positive time value, "
                        f"got {purge_ann.element('interval')!r}")
                self.purge_interval_ms = iv

        # distributed mode: rows written to the backing store are tagged
        # with this process's shardId; reads merge across shards
        # (reference: AggregationParser :173-197, shardId system config)
        sysconf = {}
        if getattr(app, "config_manager", None) is not None:
            try:
                sysconf = app.config_manager.extract_system_configs() or {}
            except Exception:   # noqa: BLE001 — config is best-effort
                sysconf = {}
        self.shard_id = str(sysconf.get("shardId", ""))
        self._store_tables: Dict[str, object] = {}
        store_ann = adef.get_annotation("store") if \
            hasattr(adef, "get_annotation") else None
        if store_ann is not None:
            self._init_store_tables(store_ann)

        # device step: batch -> (valid mask, stacked base values)
        filters = self._filters
        base = self.base
        sid = self.input_stream_id

        def step(ts, kind, valid, cols, now):
            env = {sid: cols, "__ts__": ts, "__now__": now}
            keep = jnp.logical_and(valid, kind == ev.CURRENT)
            for f in filters:
                keep = jnp.logical_and(keep, f.fn(env))
            vals = []
            for b in base:
                if b.value_fn is None:
                    vals.append(jnp.ones(ts.shape, jnp.float64))
                    continue
                raw = b.value_fn(env)
                v = jnp.asarray(raw, jnp.float64)
                if b.dtype is not None:
                    # null inputs contribute the accumulator identity —
                    # one NaN would otherwise poison its bucket FOREVER
                    # (reference: incremental aggregators skip nulls)
                    v = jnp.where(ev.null_mask(raw, b.dtype),
                                  jnp.asarray(b.identity(), jnp.float64), v)
                vals.append(v)
            return keep, jnp.stack(vals) if vals else jnp.zeros((0,) + ts.shape)

        self._step = jit_step(step, owner=f"agg:{adef.id}", role="agg_step")

        # device merge: one scatter per base row into the duration slab
        kinds = tuple(b.kind for b in self.base)
        cap = self.bucket_capacity

        def merge(slab, idx, vals):
            # idx: [B] int32, -1 (invalid) mapped out-of-bounds -> dropped
            ii = jnp.where(idx >= 0, idx, cap)
            rows = []
            for bi, k in enumerate(kinds):
                r = slab[bi]
                if k == "min":
                    r = r.at[ii].min(vals[bi], mode="drop")
                elif k == "max":
                    r = r.at[ii].max(vals[bi], mode="drop")
                else:
                    r = r.at[ii].add(vals[bi], mode="drop")
                rows.append(r)
            return jnp.stack(rows)

        self._merge = jit_step(merge, owner=f"agg:{adef.id}",
                               role="agg_merge", donate_argnums=(0,))

    # -- construction ---------------------------------------------------------
    def _decompose(self, selector, scope: Scope) -> None:
        from ..query_api.expression import AttributeFunction as Function
        sel_list = selector.selection_list
        if not sel_list:
            raise CompileError("aggregation needs an explicit select list")
        for oa in sel_list:
            e = oa.expression
            name = oa.rename or (
                e.attribute_name if isinstance(e, Variable) else None)
            if name is None:
                raise CompileError(
                    "aggregation outputs need names (use `as`)")
            if isinstance(e, Variable):
                if e.attribute_name not in self.group_names:
                    raise CompileError(
                        f"aggregation projection {e.attribute_name!r} must "
                        f"be a group-by attribute or an aggregate")
                gpos = self.group_names.index(e.attribute_name)
                self.outputs.append(_Output(
                    name, self.group_types[gpos], "group", (), gpos))
                continue
            if not isinstance(e, Function):
                raise CompileError(
                    "aggregation selections must be group attrs or "
                    "sum/count/min/max/avg aggregates")
            if e.namespace:
                # custom incremental aggregator (reference:
                # IncrementalAttributeAggregator SPI resolved through
                # IncrementalAttributeAggregatorExtensionHolder): it
                # DECLARES base sum/count/min/max accumulators and a
                # finalize over their running values — same decomposition
                # contract the built-in avg uses
                from .extension import incremental_aggregator_registry
                full = f"{e.namespace}:{e.name}"
                ext_cls = incremental_aggregator_registry().get(full)
                if ext_cls is None:
                    raise CompileError(
                        f"unknown incremental aggregator {full!r}; "
                        f"registered: "
                        f"{sorted(incremental_aggregator_registry())}")
                args_c = [compile_expression(p, scope)
                          for p in e.parameters]
                inst = ext_cls()
                idxs, fin = inst.decompose(args_c, self._add_base)
                self.outputs.append(_Output(
                    name, inst.return_type.upper(), "custom",
                    tuple(idxs), custom_fn=fin))
                continue
            fn = e.name
            if fn == "count":
                i = self._add_base("count", None, None)
                self.outputs.append(_Output(name, "LONG", "count", (i,)))
                continue
            if fn not in ("sum", "avg", "min", "max"):
                raise CompileError(
                    f"aggregator {fn!r} not supported in incremental "
                    f"aggregations (reference supports "
                    f"sum/count/avg/min/max/distinctCount)")
            if len(e.parameters) != 1:
                raise CompileError(f"{fn}() takes one argument")
            # ONE CompiledExpr per distinct argument expression: this is
            # what lets _add_base's identity dedup and _count_nonnull's
            # memo actually share slab rows across sum/avg/min/max of the
            # same expr
            from .selector import _expr_fingerprint
            if not hasattr(self, "_arg_cache"):
                self._arg_cache = {}
            akey = _expr_fingerprint(e.parameters[0])
            c = self._arg_cache.get(akey)
            if c is None:
                c = compile_expression(e.parameters[0], scope)
                self._arg_cache[akey] = c
            if c.type not in ("INT", "LONG", "FLOAT", "DOUBLE"):
                raise CompileError(f"{fn}() needs a numeric argument")
            is_int = c.type in ("INT", "LONG")
            if fn == "sum":
                i = self._add_base("sum", c.fn, c.type)
                ci = self._add_base("count", self._count_nonnull(c), None)
                self.outputs.append(_Output(
                    name, "LONG" if is_int else "DOUBLE", "sum", (i, ci)))
            elif fn in ("min", "max"):
                i = self._add_base(fn, c.fn, c.type)
                ci = self._add_base("count", self._count_nonnull(c), None)
                self.outputs.append(_Output(name, c.type, fn, (i, ci)))
            else:  # avg -> sum + count (reference: Avg...Aggregator :57-95)
                si = self._add_base("sum", c.fn, c.type)
                # nulls count for neither the sum nor the divisor
                ci = self._add_base("count", self._count_nonnull(c), None)
                self.outputs.append(_Output(name, "DOUBLE", "avg", (si, ci)))

    def _count_nonnull(self, c):
        """Shared per-argument non-null counter base fn (sum+avg of one
        expr share a single scatter row)."""
        if not hasattr(self, "_cnt_fns"):
            self._cnt_fns = {}
        fn = self._cnt_fns.get(id(c))
        if fn is None:
            def fn(env, _c=c):
                v = _c.fn(env)
                return jnp.where(ev.null_mask(v, _c.type), 0.0, 1.0)
            self._cnt_fns[id(c)] = fn
        return fn

    def _add_base(self, kind: str, value_fn, value_type) -> int:
        # also the custom IncrementalAttributeAggregator SPI's entry: an
        # unknown kind would silently fall through to the additive merge
        if kind not in ("sum", "count", "min", "max"):
            raise CompileError(
                f"incremental base accumulator kind {kind!r} is not one of "
                f"sum/count/min/max")
        # reuse identical base aggs (avg+sum of same expr share the sum)
        key = (kind, id(value_fn) if value_fn else None)
        for i, b in enumerate(self.base):
            if b.kind == kind and b.value_fn is value_fn:
                return i
        self.base.append(_BaseAgg(kind, value_fn, value_type))
        return len(self.base) - 1

    # -- ingestion ------------------------------------------------------------
    def process_staged(self, staged: ev.StagedBatch, now: int) -> None:
        """Merge a batch into every duration slab.  Any bucket (past or
        future) is addressable, so out-of-order events need no special
        path (reference: OutOfOrderEventsDataAggregator.java:177)."""
        batch = staged.to_device(self.in_schema)
        keep_d, vals_d = self._step(
            batch.ts, batch.kind, batch.valid, batch.cols,
            jnp.asarray(now, jnp.int64))
        keep = np.asarray(keep_d)
        if not keep.any():
            return
        ts = (staged.cols[self.ts_pos].astype(np.int64)
              if self.ts_pos >= 0 else staged.ts)
        gcols = [staged.cols[p] for p in self.group_positions]

        with self._lock:
            for dur in self.durations:
                ds = self._dstores[dur]
                buckets = truncate_buckets(ts, dur)
                key_cols = [self._bits(c) for c in gcols] + [buckets]
                slots = ds.alloc.slots_for(key_cols, valid=keep)
                ds.slab = self._merge(ds.slab, jnp.asarray(slots), vals_d)
                live = slots[slots >= 0]
                if live.size:
                    ds.dirty[live] = True
                    ds.snap_dirty[live] = True

    @staticmethod
    def _bits(col: np.ndarray) -> np.ndarray:
        """Lossless int64 encoding of a key column (floats via bit view)."""
        if col.dtype in (np.float32, np.float64):
            return col.astype(np.float64).view(np.int64)
        return col.astype(np.int64)

    # -- purging (reference: IncrementalDataPurger.java:307) ------------------
    def on_timer(self, now: int) -> None:
        if self.purge_enabled:
            self.purge_old(now)
        if self._store_tables:
            self.flush_to_store()
        self.app._scheduler.notify_at(now + self.purge_interval_ms, self)

    def purge_old(self, now: int) -> None:
        """Free buckets past their duration's retention period; their slots
        recycle through the allocator free list."""
        with self._lock:
            for dur in self.durations:
                ret = self.retention_ms.get(dur)
                if ret is None:
                    continue
                ds = self._dstores[dur]
                slots, words = ds.decode_keys()
                if not len(slots):
                    continue
                old = words[:, -1] < (now - ret)
                if old.any():
                    # store rows for purged buckets vanish at the next
                    # flush (flush_to_store rewrites this shard wholesale)
                    doomed = slots[old]
                    ds.alloc.purge(doomed.tolist())
                    ds.reset_slots(doomed)
                    ds.dirty[doomed] = True     # force a table rewrite

    # -- reads ----------------------------------------------------------------
    @property
    def out_names(self) -> List[str]:
        return ["AGG_TIMESTAMP"] + [o.name for o in self.outputs]

    @property
    def out_types(self) -> List[str]:
        return ["LONG"] + [o.type for o in self.outputs]

    def make_schema(self) -> ev.Schema:
        from ..query_api.definition import StreamDefinition
        sdef = StreamDefinition(self.definition.id)
        for n, t in zip(self.out_names, self.out_types):
            sdef.attribute(n, t)
        return ev.Schema(sdef, self.app.interner)

    def _local_rows(self, per: str) -> Tuple[np.ndarray, np.ndarray]:
        """(keys [n, ng+1] int64 — group bits then bucket, base [n, n_base])
        from this process's device slab."""
        ds = self._dstores[per]
        with self._lock:
            slots, words = ds.decode_keys()
            slab = np.asarray(ds.slab)
        if not len(slots):
            return (np.zeros((0, len(self.group_positions) + 1), np.int64),
                    np.zeros((0, len(self.base))))
        return words, slab[:, slots].T

    def snapshot_rows(self, per: str, within: Optional[Tuple[int, int]]
                      ) -> Tuple[np.ndarray, List[np.ndarray]]:
        """Materialize (bucket_ts[n], out_cols) for duration `per` within
        the [start, end) range (reference: AggregationRuntime.find +
        IncrementalDataAggregator combining table + running values).  In
        distributed mode rows from OTHER shards merge in from the backing
        table (reference: shardId reads, AggregationParser :464-470)."""
        per = normalize_duration(per)
        if per not in self._dstores:
            raise CompileError(
                f"aggregation {self.definition.id!r} has no duration "
                f"{per!r}; declared: {self.durations}")
        keys, base = self._local_rows(per)
        if self._store_tables:
            okeys, obase = self._other_shard_rows(per)
            if len(okeys):
                keys, base = self._merge_rows(
                    np.concatenate([keys, okeys]),
                    np.concatenate([base, obase]))
        if within is not None:
            s, e = within
            m = (keys[:, -1] >= s) & (keys[:, -1] < e)
            keys, base = keys[m], base[m]
        ts = keys[:, -1].copy() if len(keys) else np.zeros((0,), np.int64)
        cols: List[np.ndarray] = [ts]
        for o in self.outputs:
            if o.kind == "group":
                bits = keys[:, o.group_pos].copy()
                if o.type in ("FLOAT", "DOUBLE"):
                    cols.append(bits.view(np.float64).astype(
                        ev.np_dtype(o.type)))
                else:
                    cols.append(bits.astype(ev.np_dtype(o.type)))
            else:
                cols.append(o.finalize(base).astype(ev.np_dtype(o.type)))
        return ts, cols

    def _merge_rows(self, keys: np.ndarray, base: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """Merge duplicate (group..., bucket) rows with each base's rule."""
        uniq, inv = np.unique(keys, axis=0, return_inverse=True)
        out = np.tile(self._identities, (len(uniq), 1))
        for bi, b in enumerate(self.base):
            b.np_reduce_at(out[:, bi], inv, base[:, bi])
        return uniq, out

    # -- snapshot compatibility (runtime.snapshot reads/writes `stores`) ------
    @property
    def stores(self) -> Dict[str, Dict[tuple, np.ndarray]]:
        out: Dict[str, Dict[tuple, np.ndarray]] = {}
        for dur in self.durations:
            keys, base = self._local_rows(dur)
            out[dur] = {tuple(int(w) for w in keys[i]): base[i].copy()
                        for i in range(len(keys))}
        return out

    @stores.setter
    def stores(self, value: Dict[str, Dict[tuple, np.ndarray]]) -> None:
        with self._lock:
            for dur in self.durations:
                ds = self._dstores[dur]
                ds.alloc.restore({})
                ds.slab = ds.place(jnp.asarray(
                    np.tile(self._identities[:, None],
                            (1, self.bucket_capacity))))
                ds.dirty[:] = False
                mapping = value.get(dur) or {}
                if not mapping:
                    continue
                keys = np.array(list(mapping.keys()), np.int64)
                rows = np.stack([np.asarray(v, np.float64)
                                 for v in mapping.values()])
                cols = [np.ascontiguousarray(keys[:, i])
                        for i in range(keys.shape[1])]
                slots = ds.alloc.slots_for(cols)
                ds.scatter_rows(slots, rows.T)

    def snapshot_delta(self) -> Dict[str, Dict[tuple, np.ndarray]]:
        """Buckets written since the last snapshot baseline (per duration),
        as absolute rows; resets the baseline.  Keeps incremental persists
        proportional to CHANGE, not slab capacity."""
        out: Dict[str, Dict[tuple, np.ndarray]] = {}
        with self._lock:
            for dur in self.durations:
                ds = self._dstores[dur]
                idx = np.nonzero(ds.snap_dirty)[0]
                if not len(idx):
                    out[dur] = {}
                    continue
                ds.snap_dirty[:] = False
                slots, words = ds.decode_keys()
                live = np.isin(slots, idx)     # dirty AND currently bound
                if not live.any():
                    out[dur] = {}
                    continue
                slab = np.asarray(ds.slab)
                lslots, lwords = slots[live], words[live]
                rows = slab[:, lslots].T
                out[dur] = {tuple(int(x) for x in lwords[i]): rows[i].copy()
                            for i in range(len(lslots))}
        return out

    def apply_delta(self, value: Dict[str, Dict[tuple, np.ndarray]]) -> None:
        """Overwrite the given buckets with rows from an incremental
        snapshot (values are absolute, not diffs)."""
        with self._lock:
            for dur, mapping in (value or {}).items():
                ds = self._dstores.get(dur)
                if ds is None or not mapping:
                    continue
                keys = np.array(list(mapping.keys()), np.int64)
                rows = np.stack([np.asarray(v, np.float64)
                                 for v in mapping.values()])
                cols = [np.ascontiguousarray(keys[:, i])
                        for i in range(keys.shape[1])]
                slots = ds.alloc.slots_for(cols)
                ds.scatter_rows(slots, rows.T)

    def clear_snapshot_baseline(self) -> None:
        with self._lock:
            for ds in self._dstores.values():
                ds.snap_dirty[:] = False

    # -- @store backing tables (reference: AggregationParser table-per-
    #    duration + IncrementalExecutorsInitialiser.java:203) ----------------
    def _store_schema(self):
        from ..query_api.definition import StreamDefinition
        sdef = StreamDefinition(self.definition.id + "_STORE")
        sdef.attribute("SHARD_ID", "STRING")
        sdef.attribute("AGG_TIMESTAMP", "LONG")
        for n, t in zip(self.group_names, self.group_types):
            sdef.attribute(n, t)
        for i in range(len(self.base)):
            sdef.attribute(f"_b{i}", "DOUBLE")
        return sdef

    def _init_store_tables(self, store_ann) -> None:
        from ..io.store import connect_with_retry, create_store
        props = {k: v for k, v in (store_ann.elements or {}).items()
                 if k != "type"}
        sdef = self._store_schema()
        schema = ev.Schema(sdef, self.app.interner)
        for dur in self.durations:
            from ..query_api.definition import TableDefinition
            tdef = TableDefinition(f"{self.definition.id}_{dur}")
            st = create_store(store_ann.element("type"), tdef, schema, props)
            connect_with_retry(st, tdef.id)
            self._store_tables[dur] = st
        self.rebuild_from_store()

    def _row_decoders(self):
        dec = []
        for t in self.group_types:
            if t.upper() == "STRING":
                dec.append(self.app.interner.lookup)
            else:
                dec.append(None)
        return dec

    def flush_to_store(self) -> None:
        """Write this shard's live buckets through to the per-duration
        tables.  Rewrite is wholesale per shard but skipped entirely for
        durations with no writes since the last flush (dirty mask)."""
        dec = self._row_decoders()
        for dur, st in self._store_tables.items():
            ds = self._dstores[dur]
            if not ds.dirty.any():
                continue
            ds.dirty[:] = False
            keys, base = self._local_rows(dur)
            rows = []
            for i in range(len(keys)):
                gvals = []
                for gi, d in enumerate(dec):
                    bits = int(keys[i, gi])
                    if d is not None:
                        gvals.append(d(bits))
                    elif self.group_types[gi].upper() in ("FLOAT", "DOUBLE"):
                        gvals.append(float(
                            np.int64(bits).view(np.float64)))
                    else:
                        gvals.append(bits)
                rows.append(tuple([self.shard_id, int(keys[i, -1])] + gvals +
                                  [float(v) for v in base[i]]))
            stale = [r for r in st.read_all() if r[0] == self.shard_id]
            if stale:
                st.delete_rows(stale)
            if rows:
                st.add(rows)

    def _table_keyed_rows(self, per: str, include_own: bool
                          ) -> Tuple[np.ndarray, np.ndarray]:
        st = self._store_tables.get(per)
        ng = len(self.group_positions)
        if st is None:
            return (np.zeros((0, ng + 1), np.int64),
                    np.zeros((0, len(self.base))))
        keys, base = [], []
        for r in st.read_all():
            if (r[0] == self.shard_id) != include_own:
                continue
            gbits = []
            for gi, t in enumerate(self.group_types):
                v = r[2 + gi]
                tu = t.upper()
                if tu == "STRING":
                    gbits.append(self.app.interner.intern(v))
                elif tu in ("FLOAT", "DOUBLE"):
                    gbits.append(int(np.float64(v).view(np.int64)))
                else:
                    gbits.append(int(v))
            keys.append(gbits + [int(r[1])])
            base.append([float(x) for x in r[2 + ng:2 + ng + len(self.base)]])
        if not keys:
            return (np.zeros((0, ng + 1), np.int64),
                    np.zeros((0, len(self.base))))
        return np.array(keys, np.int64), np.array(base, np.float64)

    def _other_shard_rows(self, per: str):
        return self._table_keyed_rows(per, include_own=False)

    def rebuild_from_store(self) -> None:
        """Recreate this shard's in-memory slabs from its table rows
        (reference: IncrementalExecutorsInitialiser.java:203)."""
        with self._lock:
            for dur in self.durations:
                keys, base = self._table_keyed_rows(dur, include_own=True)
                if not len(keys):
                    continue
                ds = self._dstores[dur]
                cols = [np.ascontiguousarray(keys[:, i])
                        for i in range(keys.shape[1])]
                slots = ds.alloc.slots_for(cols)
                ds.scatter_rows(slots, base.T)
                ds.dirty[slots] = True

"""Columnar event model — the TPU-native replacement for the reference's
pooled linked-list event chunks.

Reference (what, not how): CORE/event/stream/StreamEvent.java:37,
CORE/event/ComplexEventChunk.java:32, CORE/event/Event.java. The reference
pushes one pooled Java object at a time through processor chains; here an
event micro-batch is a struct-of-arrays pytree with static shapes so each
query step jit-compiles once per batch bucket and runs fully on device.

Design:
  * EventBatch: timestamps i64[B], kind i32[B] (CURRENT/EXPIRED/TIMER/RESET),
    valid bool[B], and one fixed-dtype column per schema attribute.
  * Strings are dictionary-encoded to int32 ids by a host-side interner
    (per SiddhiManager), so string equality/group-by/partition-by are pure
    integer ops on device.
  * Batches are padded to bucket sizes (powers of 4) to bound the number of
    XLA compilations.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query_api.definition import AbstractDefinition

# Event kinds (reference: ComplexEvent.Type CURRENT/EXPIRED/TIMER/RESET)
CURRENT = 0
EXPIRED = 1
TIMER = 2
RESET = 3

KIND_NAMES = {CURRENT: "CURRENT", EXPIRED: "EXPIRED", TIMER: "TIMER", RESET: "RESET"}

# Attribute type -> on-device dtype.  DOUBLE maps to float32: TPU has no
# native f64; parity tests use tolerances (see SURVEY.md §7 hard part (f)).
# LONG is i64 (jax_enable_x64 is switched on in siddhi_tpu/__init__) because
# epoch-millisecond timestamps overflow i32; XLA:TPU emulates s64.
_DTYPES = {
    "STRING": jnp.int32,   # interned id; -1 == null
    "INT": jnp.int32,
    "LONG": jnp.int64,
    "FLOAT": jnp.float32,
    "DOUBLE": jnp.float32,
    "BOOL": jnp.bool_,
    "OBJECT": jnp.int32,   # host-side object registry id
}

NULL_ID = -1  # interned id representing null string
UUID_SENTINEL = -2  # UUID() marker id: decodes to a fresh uuid4 per cell

# In-band numeric nulls (reference: events carry boxed Java nulls,
# JoinProcessor emits them for unmatched outer-join rows).  Columnar numerics
# carry no side mask; instead one value per dtype is reserved as null —
# INT/LONG reserve their minimum (kdb-style), FLOAT/DOUBLE use NaN.  The
# reserved values round-trip to Python None at every host decode boundary.
# BOOL has no spare value: null bools decode as False (PARITY.md).
NULL_INT = int(np.iinfo(np.int32).min)
NULL_LONG = int(np.iinfo(np.int64).min)


def null_value(attr_type: str):
    """The encoded cell value representing null for this attribute type."""
    t = attr_type.upper()
    if t in ("STRING", "OBJECT"):
        return NULL_ID
    if t == "BOOL":
        return False
    if t in ("FLOAT", "DOUBLE"):
        return float("nan")
    if t == "INT":
        return NULL_INT
    return NULL_LONG


def null_mask(x, attr_type: str):
    """[B] bool mask of null cells; works on jnp arrays/tracers and np."""
    t = attr_type.upper()
    host = isinstance(x, np.ndarray)
    if t in ("STRING", "OBJECT"):
        # exactly NULL_ID: UUID_SENTINEL (-2) is a real pending value, not
        # null — `UUID() != 'x'` must stay true, isNull(UUID()) false
        return x == NULL_ID
    if t in ("FLOAT", "DOUBLE"):
        return np.isnan(x) if host else jnp.isnan(x)
    if t == "INT":
        return x == NULL_INT
    if t == "LONG":
        return x == NULL_LONG
    return (np.zeros if host else jnp.zeros)(np.shape(x), bool)


def decode_scalar(attr_type: str, v, interner, objects=None):
    """Encoded cell -> Python value at a host boundary: the ONE scalar
    decode rule (Events, on-demand results, script-function arguments all
    share it).  Reserved null values decode to None; UUID sentinels
    materialize a fresh id (reference: UUIDFunctionExecutor)."""
    t = attr_type.upper()
    if t == "STRING":
        iv = int(v)
        if iv == UUID_SENTINEL:
            import uuid
            return str(uuid.uuid4())
        return interner.lookup(iv)
    if t == "OBJECT":
        return objects.lookup(int(v)) if objects is not None else None
    if t == "BOOL":
        return bool(v)
    if t in ("FLOAT", "DOUBLE"):
        f = float(v)
        return None if f != f else f            # NaN is the float null
    iv = int(v)
    if iv == (NULL_INT if t == "INT" else NULL_LONG):
        return None
    return iv


def fill_uuid_cells(interner, col: "np.ndarray",
                    mask: "np.ndarray") -> "np.ndarray":
    """Replace masked cells with freshly interned uuid4 ids (copy-on-write).
    The single primitive behind every UUID_SENTINEL materialization site —
    one contract, one implementation."""
    import uuid
    if not mask.any():
        return col
    col = col.copy()
    col[mask] = [interner.intern(str(uuid.uuid4()))
                 for _ in range(int(mask.sum()))]
    return col


def materialize_uuid_sentinels(schema, valid_np, cols):
    """UUID() sentinels become real interned ids ONCE at a host boundary
    (query emission, table storage), so every consumer observes the same id
    per row (reference: CORE/executor/function/UUIDFunctionExecutor — one
    UUID per event, not per reader).  Returns [(position, new_col)] for the
    STRING columns that contained sentinels in valid rows."""
    changed = []
    for pos, t in enumerate(schema.types):
        if t.upper() != "STRING":
            continue
        col = np.asarray(cols[pos])
        mask = (col == UUID_SENTINEL) & valid_np
        if mask.any():
            changed.append((pos, fill_uuid_cells(schema.interner, col, mask)))
    return changed

_BUCKETS = (8, 32, 128, 512, 2048, 8192, 32768, 131072, 262144, 524288,
            1048576, 2097152)


def bucket_size(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"batch of {n} events exceeds max bucket {_BUCKETS[-1]}")


def dtype_of(attr_type: str):
    return _DTYPES[attr_type.upper()]


def typed_full(shape, value, dtype):
    """`jnp.full` with the fill typed on the HOST.  State is allocated
    eagerly, and under jax_enable_x64 a bare Python float fill (the 0.0 /
    NaN defaults below) reaches the device as an f64 scalar — a type the
    TPU does not have."""
    return jnp.full(shape, np.asarray(value, dtype))


def default_value(attr_type: str):
    t = attr_type.upper()
    if t in ("STRING", "OBJECT"):
        return NULL_ID
    if t == "BOOL":
        return False
    if t in ("FLOAT", "DOUBLE"):
        return 0.0
    return 0


class StringInterner:
    """Host-side dictionary encoder shared across an app's streams so ids are
    comparable across streams/tables/joins."""

    def __init__(self):
        self._lock = threading.Lock()
        self._to_id: Dict[str, int] = {}
        self._to_str: List[str] = []

    def intern(self, s: Optional[str]) -> int:
        if s is None:
            return NULL_ID
        got = self._to_id.get(s)
        if got is not None:
            return got
        with self._lock:
            got = self._to_id.get(s)
            if got is None:
                got = len(self._to_str)
                self._to_str.append(s)
                self._to_id[s] = got
            return got

    def lookup(self, i: int) -> Optional[str]:
        if i < 0 or i >= len(self._to_str):
            return None
        return self._to_str[i]

    def __len__(self):
        return len(self._to_str)


class ObjectRegistry:
    """Host-side registry giving OBJECT attributes a device-representable id."""

    def __init__(self):
        self._lock = threading.Lock()
        self._objs: List[Any] = []

    def register(self, o: Any) -> int:
        if o is None:
            return NULL_ID
        with self._lock:
            self._objs.append(o)
            return len(self._objs) - 1

    def lookup(self, i: int) -> Any:
        if i < 0 or i >= len(self._objs):
            return None
        return self._objs[i]


class Event:
    """Host-side event (reference: CORE/event/Event.java)."""

    __slots__ = ("timestamp", "data")

    def __init__(self, timestamp: int, data: Sequence[Any]):
        self.timestamp = int(timestamp)
        self.data = list(data)

    def __repr__(self):
        return f"Event({self.timestamp}, {self.data})"

    def __eq__(self, other):
        return (
            isinstance(other, Event)
            and self.timestamp == other.timestamp
            and self.data == other.data
        )


class Schema:
    """Runtime view of a definition: attribute order, dtypes, interner."""

    def __init__(self, definition: AbstractDefinition, interner: StringInterner,
                 objects: Optional[ObjectRegistry] = None):
        self.definition = definition
        self.id = definition.id
        self.names: Tuple[str, ...] = tuple(definition.attribute_names)
        self.types: Tuple[str, ...] = tuple(a.type for a in definition.attribute_list)
        self.dtypes = tuple(dtype_of(t) for t in self.types)
        self.interner = interner
        self.objects = objects or ObjectRegistry()

    def position(self, name: str) -> int:
        return self.names.index(name)

    def encode_value(self, attr_type: str, v: Any):
        t = attr_type.upper()
        if t == "STRING":
            return self.interner.intern(v) if isinstance(v, str) or v is None else int(v)
        if t == "OBJECT":
            return self.objects.register(v)
        if v is None:
            # reference events carry real nulls; numerics use the reserved
            # in-band value so None round-trips through the device
            return null_value(t)
        if t == "BOOL":
            return bool(v)
        if t in ("FLOAT", "DOUBLE"):
            return float(v)
        return int(v)

    def decode_value(self, attr_type: str, v):
        return decode_scalar(attr_type, v, self.interner, self.objects)


@jax.tree_util.register_pytree_node_class
class EventBatch:
    """Struct-of-arrays event micro-batch (static shape [B])."""

    def __init__(self, ts, kind, valid, cols: Tuple):
        self.ts = ts          # i64[B]
        self.kind = kind      # i32[B]
        self.valid = valid    # bool[B]
        self.cols = tuple(cols)

    # -- pytree protocol --
    def tree_flatten(self):
        return ((self.ts, self.kind, self.valid, self.cols), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        ts, kind, valid, cols = children
        return cls(ts, kind, valid, cols)

    @property
    def capacity(self) -> int:
        return self.ts.shape[0]

    def col(self, i: int):
        return self.cols[i]

    def with_cols(self, cols) -> "EventBatch":
        return EventBatch(self.ts, self.kind, self.valid, tuple(cols))

    def mask(self, keep) -> "EventBatch":
        return EventBatch(self.ts, self.kind, jnp.logical_and(self.valid, keep), self.cols)

    def with_kind(self, kind_value: int) -> "EventBatch":
        return EventBatch(
            self.ts, jnp.full_like(self.kind, kind_value), self.valid, self.cols
        )

    @staticmethod
    def empty(schema: Schema, capacity: int) -> "EventBatch":
        cols = tuple(
            typed_full((capacity,), default_value(t), d)
            for t, d in zip(schema.types, schema.dtypes)
        )
        return EventBatch(
            ts=jnp.zeros((capacity,), jnp.int64),
            kind=jnp.zeros((capacity,), jnp.int32),
            valid=jnp.zeros((capacity,), jnp.bool_),
            cols=cols,
        )


def np_dtype(attr_type: str):
    t = attr_type.upper()
    if t in ("STRING", "OBJECT", "INT"):
        return np.int32
    if t == "LONG":
        return np.int64
    if t == "FLOAT":
        return np.float32
    if t == "DOUBLE":
        return np.float32
    return np.bool_


class StagedBatch:
    """Host (numpy) staging of a batch: used for group-key/partition-key slot
    computation before the single host->device transfer."""

    __slots__ = ("ts", "kind", "valid", "cols", "n", "jprobe", "dev")

    def __init__(self, ts, kind, valid, cols, n):
        self.ts, self.kind, self.valid, self.cols, self.n = ts, kind, valid, cols, n
        # equi-join bucket slots, bound once at the fuse-offer edge and
        # replayed verbatim by drains/dispatch (core/runtime.py
        # JoinQueryRuntime._join_key_probe)
        self.jprobe = None
        # (schema, EventBatch, stager) prestaged by the serving
        # double-buffer (serving/staging.py): the H2D transfer started at
        # the junction accept edge; to_device adopts it instead of
        # re-transferring, and tells the stager it did
        self.dev = None

    def to_device(self, schema: Schema) -> EventBatch:
        dev = self.dev
        if dev is not None and (dev[0] is schema or
                                dev[0].dtypes == schema.dtypes):
            dev[2].adopted()
            return dev[1]
        cols = tuple(jnp.asarray(c).astype(d)
                     for c, d in zip(self.cols, schema.dtypes))
        return EventBatch(jnp.asarray(self.ts), jnp.asarray(self.kind),
                          jnp.asarray(self.valid), cols)


def encode_ts(ts: np.ndarray, n: int) -> Tuple[np.int64, np.ndarray]:
    """The timestamp wire of the sequential pattern programs: a staged
    `i64 [B]` column (`n` real rows first, padding after) ->
    `(base, delta)`, `base` the first real row's timestamp and `delta
    [B]` each real row's distance from it.  `delta` is int32 whenever the
    real rows' distances fit — every batch that spans under 2**31 ms
    (24.8 days) — so the timestamp plane's upload halves; int64 when they
    do not, and the same jitted step then specialises on that dtype.
    Padding rows (and an empty batch) encode as zeros: they decode to
    `base`, and no valid selection reads them."""
    if not n:
        return np.int64(0), np.zeros(ts.shape, np.int32)
    real = ts[:n]
    base = real[0]
    fits = int(real.max()) - int(base) < 2**31 and \
        int(real.min()) - int(base) >= -(2**31)
    # no i64 temporary: one buffered pass writes the narrowed distances
    delta = np.empty(ts.shape, np.int32 if fits else np.int64)
    np.subtract(real, base, out=delta[:n], casting="unsafe")
    delta[n:] = 0
    return base, delta


def decode_ts(base, delta):
    """`encode_ts`'s pair -> the `i64 [B]` timestamp column, on the
    device, inside the step that reads it."""
    return jnp.asarray(base, jnp.int64) + delta.astype(jnp.int64)


class StackedBatch:
    """K same-capacity staged micro-batches stacked into [K, B] host
    arrays for ONE fused device dispatch (core/fusion.py): one
    host->device transfer and one `lax.scan` execution replace K of
    each.  Capacity equality is the caller's contract (the fuse buffer
    keys its stack on the bucket size)."""

    __slots__ = ("ts", "kind", "valid", "cols", "k")

    def __init__(self, staged_list: Sequence["StagedBatch"]):
        self.k = len(staged_list)
        self.ts = np.stack([s.ts for s in staged_list])
        self.kind = np.stack([s.kind for s in staged_list])
        self.valid = np.stack([s.valid for s in staged_list])
        self.cols = tuple(
            np.stack([s.cols[j] for s in staged_list])
            for j in range(len(staged_list[0].cols)))

    def to_device(self, schema: Schema) -> EventBatch:
        """[K, B] EventBatch (EventBatch is shape-agnostic)."""
        cols = tuple(jnp.asarray(c).astype(d)
                     for c, d in zip(self.cols, schema.dtypes))
        return EventBatch(jnp.asarray(self.ts), jnp.asarray(self.kind),
                          jnp.asarray(self.valid), cols)


def pack_np(schema: Schema, events: Sequence[Event],
            kinds: Optional[Sequence[int]] = None,
            capacity: Optional[int] = None) -> StagedBatch:
    """Encode host events into padded numpy staging arrays."""
    n = len(events)
    cap = capacity if capacity is not None else bucket_size(max(n, 1))
    ts = np.zeros((cap,), np.int64)
    kind = np.zeros((cap,), np.int32)
    valid = np.zeros((cap,), np.bool_)
    raw_cols = [np.zeros((cap,), np_dtype(t)) for t in schema.types]
    for i, e in enumerate(events):
        ts[i] = e.timestamp
        valid[i] = True
        if kinds is not None:
            kind[i] = kinds[i]
        for j, (t, v) in enumerate(zip(schema.types, e.data)):
            raw_cols[j][i] = schema.encode_value(t, v)
    return StagedBatch(ts, kind, valid, raw_cols, n)


def pack(schema: Schema, events: Sequence[Event],
         kinds: Optional[Sequence[int]] = None,
         capacity: Optional[int] = None) -> EventBatch:
    """Encode host events into a padded columnar device batch."""
    return pack_np(schema, events, kinds, capacity).to_device(schema)


def timer_batch(schema: Schema, timestamp: int, capacity: int = 8) -> EventBatch:
    """A batch containing a single TIMER row (reference: Scheduler timer events,
    CORE/util/Scheduler.java:171)."""
    b = EventBatch.empty(schema, capacity)
    return EventBatch(
        b.ts.at[0].set(timestamp),
        b.kind.at[0].set(TIMER),
        b.valid.at[0].set(True),
        b.cols,
    )


def unpack(schema: Schema, batch: EventBatch,
           want_kinds: Tuple[int, ...] = (CURRENT,)) -> List[Tuple[int, Event]]:
    """Decode a device batch back to host [(kind, Event)] preserving order.
    Vectorized: one boolean reduction + per-column .tolist()."""
    kind = np.asarray(batch.kind)
    valid = np.asarray(batch.valid)
    keep = valid & (kind != TIMER) & (kind != RESET)
    if want_kinds is not None:
        sel = np.zeros_like(keep)
        for k in want_kinds:
            sel |= kind == k
        keep &= sel
    idx = np.nonzero(keep)[0]
    if idx.size == 0:
        return []
    ts_l = np.asarray(batch.ts)[idx].tolist()
    kind_l = kind[idx].tolist()
    col_np = [np.asarray(c)[idx] for c in batch.cols]
    col_ls = [c.tolist() for c in col_np]
    decoders = []

    def _str_decode(i, _lk=schema.interner.lookup):
        if i == UUID_SENTINEL:
            import uuid
            return str(uuid.uuid4())
        return _lk(i)

    for t, cnp in zip(schema.types, col_np):
        tu = t.upper()
        if tu == "STRING":
            decoders.append(_str_decode)
        elif tu == "OBJECT":
            decoders.append(schema.objects.lookup)
        elif cnp.size and null_mask(cnp, tu).any():
            # numeric nulls present: reserved values decode to None.  The
            # vectorized pre-check keeps null-free columns on the direct
            # (no per-cell call) path.
            nv = NULL_INT if tu == "INT" else NULL_LONG
            if tu in ("FLOAT", "DOUBLE"):
                decoders.append(lambda v: None if v != v else v)
            else:
                decoders.append(lambda v, _n=nv: None if v == _n else v)
        else:
            decoders.append(None)
    out: List[Tuple[int, Event]] = []
    for i in range(len(idx)):
        data = [c[i] if d is None else d(c[i])
                for c, d in zip(col_ls, decoders)]
        out.append((kind_l[i], Event(ts_l[i], data)))
    return out

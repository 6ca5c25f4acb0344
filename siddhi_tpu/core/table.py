"""In-memory tables: device-resident columnar event stores.

Reference behavior (what): CORE/table/InMemoryTable.java:58 +
IndexEventHolder (CORE/table/holder/IndexEventHolder.java:60 — primary key +
index maps), operators under CORE/util/collection/* (find/contains/update/
delete/update-or-insert with compiled conditions), and EventHolderPasser
(@PrimaryKey/@Index).

TPU-native design (how): a table is a fixed-capacity struct-of-arrays block
on device.  @PrimaryKey rows map to dense slots through the host
SlotAllocator (O(new keys) python, vectorized lookups), so keyed
insert/update/upsert are row scatters; conditions compile to masked [B, C]
broadcasts (stream rows x table rows) evaluated on device — the reference's
per-event TreeMap probes become one fused comparison kernel.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..query_api.definition import TableDefinition
from ..query_api.expression import Expression
from . import event as ev
from .executor import CompiledExpr, Scope, compile_expression
from .keyslots import SlotAllocator
from .table_index import AttributeIndex, IndexPlan, split_index_condition
from .steputil import jit_step


class TableCondition:
    """A compiled table condition + optional index plan (reference:
    CollectionExpressionParser.java splits a condition into an indexed probe
    and an exhaustive residual). `compiled` always holds the full dense
    condition (fallback + join path)."""

    def __init__(self, compiled: CompiledExpr,
                 plan: Optional[IndexPlan] = None,
                 rhs_fn=None, residual_fn=None):
        self.compiled = compiled
        self.plan = plan
        self.rhs_fn = rhs_fn
        self.residual_fn = residual_fn

    # CompiledExpr duck-typing for callers that pass this to match_matrix
    @property
    def fn(self):
        return self.compiled.fn

    @property
    def type(self):
        return self.compiled.type


class TableRuntime:
    def __init__(self, definition: TableDefinition, schema: ev.Schema,
                 capacity: int = 4096):
        self.definition = definition
        self.schema = schema
        cap_ann = definition.get_annotation("capacity")
        if cap_ann:
            capacity = int(cap_ann.element("rows", capacity))
        self.capacity = capacity
        self._lock = threading.RLock()

        pk = definition.get_annotation("PrimaryKey")
        self.pkey_positions: Optional[List[int]] = None
        self.allocator: Optional[SlotAllocator] = None
        if pk is not None:
            names = pk.positional_elements()
            self.pkey_positions = [schema.position(n) for n in names]
            self.allocator = SlotAllocator(capacity,
                                           name=f"table:{definition.id}")
        # @Index('a', 'b') declares one secondary index per attribute
        # (reference: IndexEventHolder.java:65-66, EventHolderPasser.java:48)
        self.indexes: Dict[int, AttributeIndex] = {}
        idx_ann = definition.get_annotation("Index")
        if idx_ann is not None:
            for n in idx_ann.positional_elements():
                p = schema.position(n)
                if self.pkey_positions == [p]:
                    continue  # the primary key is already an index
                self.indexes[p] = AttributeIndex(
                    capacity, ev.np_dtype(schema.types[p]),
                    name=f"{definition.id}.{n}")
        self.index_stats = {"indexed": 0, "dense": 0}
        # device state
        self.cols = tuple(
            ev.typed_full((capacity,), ev.default_value(t), d)
            for t, d in zip(schema.types, schema.dtypes))
        self.ts = jnp.zeros((capacity,), jnp.int64)
        self.valid = jnp.zeros((capacity,), jnp.bool_)
        self._append_ptr = 0  # non-keyed append position (host-tracked)
        self._free_rows: List[int] = []

        self._jit_write = jit_step(self._write_impl,
                                   owner=f"table:{definition.id}",
                                   role="table_write",
                                   donate_argnums=(0, 1, 2))
        self._jit_masked_delete = jit_step(self._masked_delete_impl,
                                          owner=f"table:{definition.id}",
                                          role="table_delete",
                                          donate_argnums=(0,))

    # -- row-slot resolution ---------------------------------------------------
    def _slots_for_batch(self, staged_cols: Sequence[np.ndarray],
                         valid: np.ndarray, insert: bool) -> np.ndarray:
        """Target row per batch event (primary-key tables)."""
        key_cols = [staged_cols[i] for i in self.pkey_positions]
        if insert:
            return self.allocator.slots_for(key_cols, valid)
        # lookup-only: unknown keys -> -1, nothing is allocated (reference:
        # find/contains never mutate, CORE/table/holder/IndexEventHolder.java)
        return self.allocator.slots_for(key_cols, valid, lookup_only=True)

    def _append_slots(self, n: int) -> np.ndarray:
        out = np.empty((n,), np.int32)
        for i in range(n):
            if self._free_rows:
                out[i] = self._free_rows.pop()
            else:
                if self._append_ptr >= self.capacity:
                    raise RuntimeError(
                        f"table {self.definition.id!r} capacity "
                        f"{self.capacity} exhausted; use "
                        f"@capacity(rows='...')")
                out[i] = self._append_ptr
                self._append_ptr += 1
        return out

    # -- device ops ------------------------------------------------------------
    @staticmethod
    def _write_impl(cols, ts, valid, new_cols, new_ts, slots, row_valid):
        tgt = jnp.where(row_valid, slots, jnp.iinfo(jnp.int32).max)
        # incoming batches may carry wider dtypes than the table column
        # (on-demand #sel stages ints as LONG): cast at the boundary
        cols = tuple(c.at[tgt].set(jnp.asarray(nc, c.dtype), mode="drop")
                     for c, nc in zip(cols, new_cols))
        ts = ts.at[tgt].set(new_ts, mode="drop")
        valid = valid.at[tgt].set(True, mode="drop")
        return cols, ts, valid

    @staticmethod
    def _masked_delete_impl(valid, kill):
        return jnp.logical_and(valid, jnp.logical_not(kill))

    # -- public API ------------------------------------------------------------
    def _materialize_uuids(self, batch: ev.EventBatch,
                           staged: ev.StagedBatch):
        """UUID() sentinels must become real interned strings at the storage
        boundary — a stored sentinel would decode to a different id on every
        read (reference: one UUID per event, UUIDFunctionExecutor)."""
        changed = ev.materialize_uuid_sentinels(
            self.schema, np.asarray(staged.valid), staged.cols)
        if not changed:
            return batch
        new_batch_cols = list(batch.cols)
        for pos, col in changed:
            scols = list(staged.cols)
            scols[pos] = col
            staged.cols = scols
            new_batch_cols[pos] = jnp.asarray(col).astype(
                batch.cols[pos].dtype)
        return batch.with_cols(new_batch_cols)

    def _materialize_uuid_col(self, val, hit):
        """`set T.s = UUID()` writes the sentinel; stored cells must hold
        REAL interned ids or every read mints a different uuid (same
        contract as _materialize_uuids on the insert path)."""
        vnp = np.asarray(val)
        mask = np.asarray(hit) & (vnp == ev.UUID_SENTINEL)
        if not mask.any():
            return val
        return jnp.asarray(
            ev.fill_uuid_cells(self.schema.interner, vnp, mask))

    def insert(self, batch: ev.EventBatch, staged: ev.StagedBatch) -> None:
        """Insert CURRENT rows (keyed: upsert on primary key; else append)."""
        with self._lock:
            n = int(np.sum(staged.valid))
            if n == 0:
                return
            batch = self._materialize_uuids(batch, staged)
            if self.pkey_positions is not None:
                slots = self._slots_for_batch(staged.cols, staged.valid, True)
            else:
                slots = np.full((staged.valid.shape[0],), -1, np.int32)
                slots[staged.valid] = self._append_slots(n)
            if self.indexes:
                mask = staged.valid & (slots >= 0)
                rows = slots[mask].astype(np.int64)
                for pos, idx in self.indexes.items():
                    idx.on_write(rows, np.asarray(staged.cols[pos])[mask])
            self.cols, self.ts, self.valid = self._jit_write(
                self.cols, self.ts, self.valid, batch.cols, batch.ts,
                jnp.asarray(slots), jnp.asarray(staged.valid))

    def compile_condition(self, cond: Expression, other_schema: ev.Schema,
                          other_key: str, interner) -> CompiledExpr:
        """Compile `on` condition over (stream rows [B,1], table rows [1,C])."""
        scope = Scope()
        scope.interner = interner
        scope.add_source(self.definition.id, self.schema)
        scope.add_source(other_key, other_schema)
        return compile_expression(cond, scope)

    def plan_condition(self, cond_expr: Expression, scope: Scope,
                       table_id: Optional[str] = None,
                       unqualified_is_table: bool = False,
                       ) -> TableCondition:
        """Compile a table condition with index-aware planning: if one AND-
        conjunct is `table.attr == <stream expr>` on an indexed attribute (or
        a single-column primary key), later matches probe that index instead
        of the dense [B, C] broadcast (reference:
        CollectionExpressionParser.java; IndexOperator.java).

        `table_id`/`unqualified_is_table` override the reference scoping for
        on-demand store queries (alias id, bare names bind to the store)."""
        compiled = compile_expression(cond_expr, scope)
        probe_positions = list(self.indexes)
        if self.pkey_positions is not None and len(self.pkey_positions) == 1:
            probe_positions.append(self.pkey_positions[0])
        plan = None
        if probe_positions:
            plan = split_index_condition(
                cond_expr, table_id or self.definition.id, self.schema,
                probe_positions, unqualified_is_table=unqualified_is_table)
        if plan is None:
            return TableCondition(compiled)
        if plan.kind == "range" and plan.pos not in self.indexes:
            return TableCondition(compiled)  # pkey has no sorted view
        rhs_fn = compile_expression(plan.rhs, scope).fn
        residual_fn = (compile_expression(plan.residual, scope).fn
                       if plan.residual is not None else None)
        return TableCondition(compiled, plan, rhs_fn, residual_fn)

    def _probe_candidates(self, pos: int, values: np.ndarray):
        """values [B] -> (cand [B, K] int32, ok [B, K] bool)."""
        values = np.asarray(values).astype(
            ev.np_dtype(self.schema.types[pos]))
        if pos in self.indexes:
            return self.indexes[pos].probe_eq(values)
        # single-column primary key: the slot allocator IS the index
        slots = self.allocator.slots_for(
            [np.ascontiguousarray(values)],
            np.ones(values.shape[0], bool), lookup_only=True)
        cand = slots.astype(np.int32)[:, None]
        return cand, cand >= 0

    def probe_rows(self, pos: int, values: np.ndarray):
        """Public index probe for the equi-join fast path (and tests):
        candidate row ids per value via the @Index lane table or the
        primary-key allocator — one vectorized lookup, no device work.
        Candidates narrow; the caller's full-condition re-check decides
        (exactly the `_match` contract)."""
        self.index_stats["indexed"] += 1
        return self._probe_candidates(pos, values)

    def _match(self, cond, other_key: str, batch: ev.EventBatch,
               staged: Optional[ev.StagedBatch] = None):
        """Unified match for delete/update paths.

        Returns (hit [C] bool, src [C] int last-matching-stream-row — device
        arrays on the dense path, host on the indexed path — and
        matched_any(), a thunk for the [B] per-stream-row hit mask so the
        dense path pays no device sync unless upsert needs it)."""
        C = self.capacity
        plan = cond.plan if isinstance(cond, TableCondition) else None
        if plan is None or plan.kind != "eq":
            self.index_stats["dense"] += 1
            m = self.match_matrix(cond, other_key, batch)      # [B, C]
            hit = jnp.any(m, axis=0)
            B = m.shape[0]
            rowid = jnp.arange(B)[:, None]
            src = jnp.max(jnp.where(m, rowid, -1), axis=0)
            return hit, src, lambda: np.asarray(jnp.any(m, axis=1))
        self.index_stats["indexed"] += 1
        # stream-side key values: [B] on host (staged cols when available,
        # else one small device read)
        if staged is not None:
            env_np = {other_key: tuple(staged.cols), "__ts__": staged.ts}
            vals = np.asarray(cond.rhs_fn(env_np))
        else:
            env_d = {other_key: batch.cols, "__ts__": batch.ts}
            vals = np.asarray(cond.rhs_fn(env_d))
        if vals.ndim == 0:
            vals = np.broadcast_to(vals, (batch.ts.shape[0],))
        cand, ok = self._probe_candidates(plan.pos, vals)       # [B, K]
        bvalid = np.asarray(batch.valid)
        ok = ok & bvalid[:, None]
        if ok.any():
            tvalid = np.asarray(self.valid)
            safe = np.clip(cand, 0, C - 1)
            ok = ok & tvalid[safe]
        if ok.any():
            # re-evaluate the FULL condition on the gathered candidates:
            # the hash probe only narrows, it never decides — this keeps
            # exact dense `==` semantics under dtype casts (LONG rhs vs INT
            # column) and hash-collision corner cases
            safe = jnp.asarray(np.clip(cand, 0, C - 1))
            env = {
                self.definition.id: tuple(c[safe] for c in self.cols),
                other_key: tuple(c[:, None] for c in batch.cols),
                "__ts__": batch.ts[:, None],
            }
            ok = ok & np.asarray(cond.compiled.fn(env)).astype(bool)
        hit = np.zeros(C, bool)
        src = np.full(C, -1, np.int64)
        rows = cand[ok]
        if rows.size:
            hit[rows] = True
            bs = np.broadcast_to(
                np.arange(ok.shape[0], dtype=np.int64)[:, None],
                ok.shape)[ok]
            np.maximum.at(src, rows, bs)
        return hit, src, lambda: ok.any(axis=1)

    def match_matrix(self, compiled: CompiledExpr, other_key: str,
                     batch: ev.EventBatch):
        """[B, C] boolean matches (pure; caller jits)."""
        env = {
            self.definition.id: tuple(c[None, :] for c in self.cols),
            other_key: tuple(c[:, None] for c in batch.cols),
            "__ts__": batch.ts[:, None],
        }
        m = compiled.fn(env)
        m = jnp.logical_and(m, self.valid[None, :])
        m = jnp.logical_and(m, batch.valid[:, None])
        return m

    def delete_where(self, compiled: CompiledExpr, other_key: str,
                     batch: ev.EventBatch, staged=None) -> None:
        with self._lock:
            kill, _, _ = self._match(compiled, other_key, batch, staged)
            self.valid = self._jit_masked_delete(self.valid,
                                                 jnp.asarray(kill))
            self._reclaim(np.asarray(kill))

    def _reclaim(self, kill) -> None:
        killed = np.nonzero(np.asarray(kill))[0]
        if self.pkey_positions is not None:
            if killed.size:
                self.allocator.purge(killed.tolist())
        else:
            self._free_rows.extend(int(x) for x in killed)
        if killed.size:
            for idx in self.indexes.values():
                idx.on_delete(killed)

    def update_where(self, compiled: CompiledExpr, other_key: str,
                     batch: ev.EventBatch,
                     set_fns: List[Tuple[int, Callable]],
                     upsert: bool = False,
                     staged: Optional[ev.StagedBatch] = None,
                     insert_map: Optional[List[int]] = None) -> None:
        """set_fns: [(table_col_pos, fn(env)->[B] value)], applied from the
        LAST matching stream row per table row (batch order semantics)."""
        with self._lock:
            hit, src, matched_any = self._match(
                compiled, other_key, batch, staged)
            hit = jnp.asarray(hit)                              # [C]
            src_c = jnp.clip(jnp.asarray(src), 0, batch.ts.shape[0] - 1)
            env = {
                other_key: tuple(c[src_c] for c in batch.cols),
                self.definition.id: self.cols,
                "__ts__": batch.ts[src_c],
            }
            new_cols = list(self.cols)
            # index maintenance needs host rows only when a set expression
            # actually writes an indexed column (the sync is not free)
            touches_index = any(pos in self.indexes for pos, _ in set_fns)
            hit_rows = (np.nonzero(np.asarray(hit))[0]
                        if touches_index else None)
            for pos, fn in set_fns:
                val = jnp.asarray(fn(env))
                if val.ndim == 0:        # constant set expressions are 0-d
                    val = jnp.broadcast_to(val, (self.capacity,))
                if self.schema.types[pos] == "STRING":
                    val = self._materialize_uuid_col(val, hit)
                new_cols[pos] = jnp.where(hit, val.astype(self.cols[pos].dtype),
                                          self.cols[pos])
                if pos in self.indexes and hit_rows is not None \
                        and hit_rows.size:
                    self.indexes[pos].on_write(
                        hit_rows, np.asarray(val)[hit_rows])
            self.cols = tuple(new_cols)
            if upsert and staged is not None:
                miss = staged.valid & ~matched_any()
                if miss.any():
                    sub_staged = ev.StagedBatch(
                        staged.ts, staged.kind, miss,
                        [staged.cols[i] for i in insert_map]
                        if insert_map else staged.cols, int(miss.sum()))
                    sub_batch = ev.EventBatch(
                        batch.ts, batch.kind, jnp.asarray(miss),
                        tuple(batch.cols[i] for i in insert_map)
                        if insert_map else batch.cols)
                    self.insert(sub_batch, sub_staged)

    def snapshot_rows(self) -> List[ev.Event]:
        with self._lock:
            batch = ev.EventBatch(self.ts, jnp.zeros_like(self.ts,
                                                          dtype=jnp.int32),
                                  self.valid, self.cols)
            return [e for _, e in ev.unpack(self.schema, batch)]

    # find for on-demand queries / joins
    def all_rows_batch(self) -> ev.EventBatch:
        return ev.EventBatch(self.ts,
                             jnp.zeros(self.ts.shape, jnp.int32),
                             self.valid, self.cols)


class RecordTableRuntime(TableRuntime):
    """`@store(type='...')` table: an external RecordTable store stays
    authoritative while its rows are mirrored into the device-resident
    columnar table, so joins/filters run on the TPU and writes flow through
    the store SPI (reference: AbstractRecordTable.java:449; cache layer
    CacheTable.java:62).

    The mirror is preloaded at startup (reference:
    AbstractQueryableRecordTable pre-load) and kept in sync write-through.
    """

    def __init__(self, definition, schema, store, interner,
                 cache=None, capacity: int = 4096):
        from ..io.store import connect_with_retry
        super().__init__(definition, schema, capacity)
        self.store = store
        self.cache = cache
        self._interner = interner
        connect_with_retry(store, definition.id)
        rows = store.read_all()
        if rows:
            self._mirror_insert(rows)

    # -- encode/decode ---------------------------------------------------------
    def _decode_row(self, vals) -> tuple:
        out = []
        for v, t in zip(vals, self.schema.types):
            if t == "STRING":
                out.append(self._interner.lookup(int(v)))
            elif t in ("INT", "LONG"):
                out.append(int(v))
            elif t in ("FLOAT", "DOUBLE"):
                out.append(float(v))
            elif t == "BOOL":
                out.append(bool(v))
            else:
                out.append(v)
        return tuple(out)

    def _decode_staged(self, staged) -> List[tuple]:
        idx = np.nonzero(staged.valid)[0]
        return [self._decode_row([c[i] for c in staged.cols])
                for i in idx]

    def _decode_mirror(self, mask: np.ndarray) -> List[tuple]:
        cols = [np.asarray(c) for c in self.cols]
        return [self._decode_row([c[i] for c in cols])
                for i in np.nonzero(mask)[0]]

    def _mirror_insert(self, rows: List[tuple]) -> None:
        """Load store rows into the device mirror without re-adding them."""
        enc_cols = []
        for j, t in enumerate(self.schema.types):
            vals = [r[j] for r in rows]
            if t == "STRING":
                vals = [self._interner.intern(v) for v in vals]
            enc_cols.append(np.asarray(vals, ev.np_dtype(t)))
        n = len(rows)
        staged = ev.StagedBatch(
            np.zeros(n, np.int64), np.zeros(n, np.int8),
            np.ones(n, bool), enc_cols, n)
        batch = ev.EventBatch(
            jnp.zeros(n, jnp.int64), jnp.zeros(n, jnp.int32),
            jnp.ones(n, jnp.bool_),
            tuple(jnp.asarray(c).astype(d)
                  for c, d in zip(enc_cols, self.schema.dtypes)))
        super().insert(batch, staged)

    # -- write-through ops -----------------------------------------------------
    def insert(self, batch, staged) -> None:
        rows = self._decode_staged(staged)
        if rows:
            self.store.add(rows)
            if self.cache is not None:
                self.cache.on_add(rows)
        super().insert(batch, staged)

    def delete_where(self, compiled, other_key, batch, staged=None) -> None:
        with self._lock:
            kill, _, _ = self._match(compiled, other_key, batch, staged)
            kill = np.asarray(kill)
            rows = self._decode_mirror(kill & np.asarray(self.valid))
            if rows:
                self.store.delete_rows(rows)
                if self.cache is not None:
                    self.cache.on_delete(rows)
            self.valid = self._jit_masked_delete(self.valid, jnp.asarray(kill))
            self._reclaim(kill)

    def update_where(self, compiled, other_key, batch, set_fns,
                     upsert=False, staged=None, insert_map=None) -> None:
        with self._lock:
            hit, _, _ = self._match(compiled, other_key, batch, staged)
            hit = np.asarray(hit) & np.asarray(self.valid)
            old_rows = self._decode_mirror(hit)
        super().update_where(compiled, other_key, batch, set_fns,
                             upsert=upsert, staged=staged,
                             insert_map=insert_map)
        with self._lock:
            new_rows = self._decode_mirror(hit)
        if old_rows:
            self.store.update_rows(old_rows, new_rows)
            if self.cache is not None:
                self.cache.on_update(old_rows, new_rows)


def _table_state(t: TableRuntime) -> Dict:
    """Host snapshot of a table's device state (reference: InMemoryTable
    state; record tables rebuild their mirror from the store on restore)."""
    if isinstance(t, RecordTableRuntime):
        return {"record": True}
    return {
        "record": False,
        "cols": [np.asarray(c) for c in t.cols],
        "ts": np.asarray(t.ts),
        "valid": np.asarray(t.valid),
        "append_ptr": t._append_ptr,
        "free_rows": list(t._free_rows),
        "slots": t.allocator.snapshot() if t.allocator else None,
    }


def _restore_table_state(t: TableRuntime, data: Dict) -> None:
    if data.get("record"):
        return
    with t._lock:
        t.cols = tuple(jnp.asarray(c).astype(d)
                       for c, d in zip(data["cols"], t.schema.dtypes))
        t.ts = jnp.asarray(data["ts"])
        t.valid = jnp.asarray(data["valid"])
        t._append_ptr = data["append_ptr"]
        t._free_rows = list(data["free_rows"])
        if data["slots"] is not None and t.allocator:
            t.allocator.restore(data["slots"])
        valid = np.asarray(t.valid)
        for pos, idx in t.indexes.items():
            idx.rebuild(np.asarray(t.cols[pos]), valid)

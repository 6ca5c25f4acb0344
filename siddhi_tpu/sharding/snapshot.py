"""Mesh-resize snapshot re-bucketing: key state moves between mesh sizes
as a pure permutation of state rows.

Reference (what): the reference's PersistenceStore snapshots are
layout-free object graphs — a restored app re-hydrates per-key state
maps whatever the thread count.  TPU design (how): our per-key state is
dense `[..., K]` slabs whose row order IS the mesh layout (`slot s` at
row `(s % n) * (K/n) + s // n`, sharding/router.py), so a snapshot taken
on an N-way mesh holds rows in N-way order and restoring it verbatim
onto an M-way mesh would scatter every key's state onto the wrong
device.  Each query snapshot therefore records its `layout`
(kind + shard count + capacity); restore compares it against the target
runtime's layout and permutes the key axis through
`ShardRouter.rebucket_index` — key->slot bindings are mesh-independent
(keyslots hashes key bytes), so the slot maps restore unchanged and only
the slot->row order moves.

Three state families carry a key-ordered axis:

- **pattern** (partitioned NFA): the HOST form of the packed state,
  `b32 [W32, K]` int32 and `b64 [W64, K]` int64 (key axis 1) — on the
  device the 64-bit part is two u32 planes (`StatePacker`:
  `(b32, lo64, hi64, scalars)`), joined into `b64` before a snapshot
  reaches this module and split again after it (`StatePacker.to_host` /
  `from_host`), so the on-disk format and this permutation know nothing
  of the planes — plus selector accumulator slabs `[K, ...]` (key axis
  0 — sharded patterns shard the selector with the same layout, see
  pattern_planner._shard_step's sspec);
- **plain** (windowless partitioned group-by): selector slabs
  `[G, ...]` over the group-slot space;
- **keyed** (windows inside partitions / session(gap, key)): the window
  state slab `[K, ...]`; its selector state stays replicated
  (planner._shard_keyed_step) and needs no permutation.

Join buffers ride GSPMD axis-0 row sharding with no key layout — a
restored join re-places through JoinQueryRuntime.place_state and needs
no re-bucketing (layout None).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from .router import ShardRouter, shard_count


def query_layout(qr) -> Optional[Dict[str, Any]]:
    """The key-state layout a query runtime's snapshot is written in:
    {'kind': 'pattern'|'plain'|'keyed', 'n': shards, 'capacity': rows},
    or None when the state has no key-ordered axis (single-key patterns,
    joins, unkeyed plain queries)."""
    p = qr.planned
    if qr._kind == "pattern":
        if not p.partition_positions:
            return None
        return {"kind": "pattern", "n": shard_count(p.mesh),
                "capacity": int(p.key_capacity)}
    if qr._kind == "join":
        return None
    if p.keyed_window:
        return {"kind": "keyed", "n": shard_count(p.keyed_mesh),
                "capacity": int(p.key_capacity)}
    if p.slot_allocator is not None:
        # n=1 for unsharded group-bys: the identity layout — recorded so
        # a snapshot from a SHARDED runtime re-buckets when restoring
        # onto an unsharded one (and vice versa)
        return {"kind": "plain", "n": shard_count(p.mesh),
                "capacity": int(p.slot_allocator.capacity)}
    return None


def needs_rebucket(old: Optional[Dict], new: Optional[Dict]) -> bool:
    """True when a snapshot written under `old` must be permuted to load
    into a runtime laid out as `new`.  Missing layouts (pre-round-8
    snapshots, or an unkeyed target) mean "restore verbatim" — exactly
    the old behavior."""
    if old is None or new is None:
        return False
    return int(old.get("n", 1)) != int(new.get("n", 1)) and \
        old.get("capacity") == new.get("capacity") and \
        old.get("kind") == new.get("kind")


def _perm(old: Dict, new: Dict) -> np.ndarray:
    cap = int(new["capacity"])
    return ShardRouter(int(new["n"]), cap).rebucket_index(
        ShardRouter(int(old["n"]), cap))


def _take(arr, src: np.ndarray, axis: int):
    a = np.asarray(arr)
    if a.ndim <= axis or a.shape[axis] != src.shape[0]:
        return arr
    return np.take(a, src, axis=axis)


def _sel_specs(planned):
    sel = planned.selector_exec
    bank = getattr(sel, "bank", None)
    return getattr(bank, "specs", None)


def _permute_selector(sel_state, specs, src: np.ndarray):
    """Permute slot-indexed selector slabs; leaves in a different slot
    space (pair refcounts via slot_src) or of a different length pass
    through untouched — same discrimination the partition purger's reset
    applies (runtime._reset_pattern_keys / _reset_selector_slots)."""
    if specs is None or len(specs) != len(sel_state):
        return sel_state
    return tuple(
        a if getattr(s, "slot_src", None) is not None
        else _take(a, src, 0)
        for a, s in zip(sel_state, specs))


def rebucket_state(host_state, old: Dict, new: Dict, planned):
    """Permute a host (numpy) query-state snapshot from mesh layout `old`
    into `new`.  Returns the state unchanged when the shapes don't match
    the declared layout (defensive: a mismatched snapshot fails later on
    upload exactly as it always did)."""
    src = _perm(old, new)
    kind = new["kind"]
    try:
        if kind == "pattern":
            (b32, b64, scalars), sel_state = host_state
            b32 = _take(b32, src, 1)
            b64 = _take(b64, src, 1)
            sel_state = _permute_selector(sel_state, _sel_specs(planned),
                                          src)
            return ((b32, b64, scalars), sel_state)
        if kind == "plain":
            wstate, astate = host_state
            astate = _permute_selector(astate, _sel_specs(planned), src)
            return (wstate, astate)
        if kind == "keyed":
            import jax
            wslab, astate = host_state
            wslab = jax.tree.map(lambda a: _take(a, src, 0), wslab)
            return (wslab, astate)
    except Exception:  # noqa: BLE001 — fall through to verbatim restore
        pass
    return host_state


def rebucket_selector(sel_state, old: Dict, new: Dict, planned):
    """Permute just a selector-state tuple between layouts (incremental
    pattern deltas ship the full selector tree next to per-row state
    columns)."""
    try:
        return _permute_selector(sel_state, _sel_specs(planned),
                                 _perm(old, new))
    except Exception:  # noqa: BLE001 — fall through to verbatim restore
        return sel_state


def rebucket_rows(rows: np.ndarray, old: Dict, new: Dict) -> np.ndarray:
    """Map state-ROW indices recorded under layout `old` (incremental
    snapshots store dirty rows, not slots) onto layout `new`."""
    cap = int(new["capacity"])
    old_r = ShardRouter(int(old["n"]), cap)
    new_r = ShardRouter(int(new["n"]), cap)
    return new_r.state_row(old_r.slot_of_row(np.asarray(rows)))

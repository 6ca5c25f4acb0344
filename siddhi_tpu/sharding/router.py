"""Key-space router: the single source of truth for how partition keys
map onto a device mesh.

Reference (what): SiddhiQL's `partition with (key of Stream)` declares a
key-scoped state clone per partition key (CORE/partition/
PartitionRuntimeImpl.java:75).  TPU design (how): keys become an explicit
state axis distributed over the mesh's `shard` axis.  Three places used
to hand-roll the same layout arithmetic — the pattern runtime's staging
grouping, the partition purger's reset remap, and the dirty-mask marking
for incremental snapshots — and snapshot/restore could not move state
between mesh sizes at all because no one owned the mapping.  This module
owns it:

- **shard assignment** is round-robin on the allocator slot
  (`slot % n_shards`), so sequential slot allocation spreads early keys
  across devices instead of parking them all on device 0;
- **state row** of slot `s` on an `n`-way mesh of capacity `C` is
  `(s % n) * (C // n) + s // n`: device `s % n` owns the contiguous
  global block `[d*C/n, (d+1)*C/n)` and stores the key at local row
  `s // n` — exactly the layout `jax.sharding.PartitionSpec('shard')`
  splits;
- **re-bucketing** between mesh sizes is therefore a pure permutation of
  state rows (`rebucket_index`), which is what lets a snapshot taken on
  an N-way mesh restore onto an M-way mesh (core/runtime.restore).

The allocator slot a key resolves to is mesh-independent (keyslots
hashes key bytes, not devices), so the key->slot binding in a snapshot
is portable across mesh sizes as-is; only the slot->state-row layout
changes, and that is this router's job.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np


class ShardRouter:
    """Layout arithmetic + staging-time grouping for one key space
    (`capacity` slots) over `n_shards` devices.  `capacity` must divide
    evenly — the planner rounds key capacities up to a mesh multiple at
    wiring time (runtime._add_partition)."""

    __slots__ = ("n_shards", "capacity", "block")

    def __init__(self, n_shards: int, capacity: int):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if capacity % n_shards != 0:
            raise ValueError(
                f"key capacity {capacity} is not divisible by "
                f"{n_shards} shards")
        self.n_shards = int(n_shards)
        self.capacity = int(capacity)
        self.block = self.capacity // self.n_shards

    # -- layout ---------------------------------------------------------------
    def shard_of(self, slots: np.ndarray) -> np.ndarray:
        """Mesh shard owning each allocator slot (round-robin)."""
        return np.asarray(slots) % self.n_shards

    def local_of(self, slots: np.ndarray) -> np.ndarray:
        """Local state row of each slot on its owning shard."""
        return np.asarray(slots) // self.n_shards

    def state_row(self, slots: np.ndarray) -> np.ndarray:
        """Global state row of each allocator slot under the sharded
        layout (the row PartitionSpec('shard') places on shard
        `slot % n`)."""
        s = np.asarray(slots)
        return (s % self.n_shards) * self.block + s // self.n_shards

    def slot_of_row(self, rows: np.ndarray) -> np.ndarray:
        """Inverse of state_row: the allocator slot stored at each global
        state row."""
        r = np.asarray(rows)
        return (r % self.block) * self.n_shards + r // self.block

    def rebucket_index(self, old: "ShardRouter") -> np.ndarray:
        """Permutation `src` moving key state between mesh layouts:
        `new_state[..., j] = old_state[..., src[j]]` for every global
        state row j.  Both routers must cover the same slot capacity."""
        if old.capacity != self.capacity:
            raise ValueError(
                f"cannot re-bucket between capacities {old.capacity} "
                f"and {self.capacity}")
        rows = np.arange(self.capacity, dtype=np.int64)
        return old.state_row(self.slot_of_row(rows))

    # -- staging-time grouping ------------------------------------------------
    def group(self, slots: np.ndarray, valid: np.ndarray
              ) -> Tuple[np.ndarray, ...]:
        """Arrange a batch's resolved slots into the sharded device
        layout: (key_idx [n, Kb] int32 local rows, sel [n, Kb, E] int32
        batch indices (-1 = padding), counts [n] int64 events routed to
        each shard) — and what the one grouping learned on the way, for
        the send's observers: (keys int32, the batch's distinct slots
        ascending; key_counts int32, the events of each).  Pad rows carry
        local sentinel `block` — the device scatter-back drops them as
        out-of-bounds (keyslots layout contract)."""
        from ..core.keyslots import group_events_by_shard
        return group_events_by_shard(np.asarray(slots), valid,
                                     self.n_shards, self.capacity)


# ---------------------------------------------------------------------------
# resolved accessors: the ONE place that maps a query runtime onto its
# key layout.  The meshes are the plan's own fields (`mesh`: plain /
# pattern shard mesh, `keyed_mesh`: keyed-window slab), the ones the step
# functions were built from; every plan declares both.
# ---------------------------------------------------------------------------

def shard_count(obj) -> int:
    """Devices in an app runtime's / mesh's shard axis (1 = unsharded)."""
    mesh = getattr(obj, "mesh", obj)
    if mesh is None:
        return 1
    devs = getattr(mesh, "devices", None)
    return int(devs.size) if devs is not None else 1


def router_for(qr) -> Optional[ShardRouter]:
    """ShardRouter of a query runtime's key-distributed state, or None
    when the query's state carries no sharded key axis (single-device
    plans, joins — whose buffers ride GSPMD row sharding with no key
    layout)."""
    p = qr.planned
    if qr._kind == "pattern":
        if not p.partition_positions or p.mesh is None:
            return None
        return ShardRouter(shard_count(p.mesh), int(p.key_capacity))
    if p.keyed_mesh is not None and p.keyed_window:
        return ShardRouter(shard_count(p.keyed_mesh), int(p.key_capacity))
    if p.mesh is not None and p.slot_allocator is not None:
        return ShardRouter(shard_count(p.mesh),
                           int(p.slot_allocator.capacity))
    return None


def group_router_for(qr) -> Optional[ShardRouter]:
    """Router of a plain query's GROUP-SLOT space (the selector slabs a
    windowless sharded group-by distributes), or None when those slabs
    are replicated — distinct from router_for, which resolves the KEY
    space (a keyed-window query has both: a sharded key slab and
    replicated selector state)."""
    p = qr.planned
    if p.mesh is None or qr._kind == "pattern" or p.slot_allocator is None:
        return None
    return ShardRouter(shard_count(p.mesh), int(p.slot_allocator.capacity))


def split_columns(cols: Sequence[np.ndarray], shard: np.ndarray,
                  n: int) -> List[List[np.ndarray]]:
    """Per-shard column split of a staged batch (diagnostics / per-shard
    snapshot export): returns n lists of column arrays."""
    return [[np.asarray(c)[shard == d] for c in cols] for d in range(n)]

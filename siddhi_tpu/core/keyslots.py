"""Host-side vectorized key -> dense slot allocation.

Replaces the reference's thread-local keyed state maps
(CORE/util/snapshot/state/PartitionStateHolder.java:43 — nested
Map<partitionKey, Map<groupByKey, State>> — and
CORE/query/selector/GroupByKeyGenerator.java:37's per-event string-concat
keys) with a batched design: group-by / partition keys are extracted from the
already-encoded integer columns, hashed to 128 bits, and resolved to dense
slot ids through an open-addressing table (linear probing).  Device state is
then plain [..., K] arrays indexed by slot, so aggregation is a segment op
and partitioning is an axis — no hash probing on the critical path on device.

Two backends share identical semantics and snapshot format:
- native (default): `native/staging.c` does the fused hash+probe+insert and
  the counting-sort grouping in C passes over numpy-owned buffers with an
  interleaved cell table (~75ms -> ~25ms per 524k-event batch on the 1-core
  driver host; `slots_and_group` fuses the count pass into the probe);
- numpy fallback when no C toolchain exists.

Slots are recycled through a free list on purge (reference: @purge idle-key
GC, PartitionRuntimeImpl.java:120-147).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..exceptions import CapacityExceededError
from ..native import LIB, ptr

_EMPTY = np.uint64(0)
_TOMB = np.uint64(1)
_FNV_OFF = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)
_MIX = np.uint64(0x9E3779B97F4A7C15)

if LIB is not None:
    import ctypes


def _hash_words(words: np.ndarray, seed) -> np.ndarray:
    """Fold [n, L8] u64 key words into one u64 per row (vectorized FNV-ish).
    Must match sg_slots_for's hash in native/staging.c."""
    h = np.full(words.shape[0], _FNV_OFF ^ np.uint64(seed), np.uint64)
    with np.errstate(over="ignore"):
        for j in range(words.shape[1]):
            h = (h ^ words[:, j]) * _FNV_PRIME
            h = (h ^ (h >> np.uint64(29))) * _MIX
        h ^= h >> np.uint64(32)
    return h


def _key_words(key_cols: Sequence[np.ndarray]) -> np.ndarray:
    """Pack key columns into [n, L8] u64 words (zero-padded bytes)."""
    n = len(key_cols[0])
    bs = []
    for c in key_cols:
        if c.dtype == np.bool_:
            b = c.astype(np.uint8).reshape(n, 1)
        else:
            b = np.ascontiguousarray(c).view(np.uint8).reshape(n, -1)
        bs.append(b)
    raw = np.concatenate(bs, axis=1) if len(bs) > 1 else bs[0]
    L = raw.shape[1]
    pad = (-L) % 8
    if pad:
        raw = np.concatenate(
            [raw, np.zeros((n, pad), np.uint8)], axis=1)
    return np.ascontiguousarray(raw).view(np.uint64)


class _JournalView:
    """List-shaped facade over the native journal buffer (runtime calls
    `.clear()` after full snapshots)."""

    def __init__(self, alloc: "SlotAllocator"):
        self._a = alloc

    def clear(self):
        self._a._meta[3] = 0
        self._a._meta[4] = 0

    def __len__(self):
        return int(self._a._meta[3]) + \
            (1 << 30 if self._a._meta[4] else 0)


class SlotAllocator:
    """Key->slot allocator over numpy buffers shared with the C kernels;
    snapshots read the buffers directly."""

    def __init__(self, capacity: int, name: str = "?"):
        self.capacity = capacity
        self.name = name
        self._lock = threading.Lock()
        self._cap2 = 1 << max(10, int(2 * capacity - 1).bit_length())
        self._mask = np.uint64(self._cap2 - 1)
        # interleaved probe cells [cap2, 3] = (h1, h2, slot): one cache line
        # per probe instead of three; h1 0=empty, 1=tombstone
        self._cells = np.zeros((self._cap2, 3), np.uint64)
        self._cell_by_slot = np.full(capacity, -1, np.int64)
        self._used = np.zeros(capacity, np.uint8)
        self._free = np.arange(capacity - 1, -1, -1, dtype=np.int32)
        # meta: [count, free_top, tombstones, journal_len, journal_overflow,
        #        journal_cap]
        jcap = min(2 * capacity, capacity + (1 << 20))
        self._journal = np.zeros(jcap, np.int32)
        self._meta = np.array([0, capacity, 0, 0, 0, jcap], np.int64)
        self._w8 = 0                    # key width in u64 words (fixed)
        self._arena = None              # [capacity, w8*8] u8
        # bumped whenever key->slot bindings change (insert/purge/restore):
        # callers memoizing resolved slot blocks key their cache on this
        self.version = 0
        # L2-resident direct-mapped probe cache (h1, h2, slot); cleared on
        # any unbinding mutation (purge/rebuild/restore)
        self._pcache = np.zeros((1 << 14, 3), np.uint64)
        self.journal = _JournalView(self)

    def __len__(self):
        return int(self._meta[0])

    def _ensure_arena(self, w8: int):
        if self._arena is None:
            self._w8 = w8
            self._arena = np.zeros((self.capacity, w8 * 8), np.uint8)
        elif w8 > self._w8:
            # an allocator shared across streams may see wider keys later:
            # zero-pad existing keys to the new width and re-hash the table
            # (hashes cover all w8 words, so every binding changes)
            wider = np.zeros((self.capacity, w8 * 8), np.uint8)
            wider[:, : self._w8 * 8] = self._arena
            self._arena = wider
            self._w8 = w8
            self._rebuild_table()

    # -- lookup/insert -------------------------------------------------------
    def slots_for(self, key_cols: Sequence[np.ndarray],
                  valid: Optional[np.ndarray] = None,
                  lookup_only: bool = False) -> np.ndarray:
        """Vectorized lookup/insert: key_cols are 1-D arrays of equal length.
        Returns int32 slot ids (-1 for invalid rows; with lookup_only also
        -1 for unknown keys, and nothing is allocated)."""
        out, _ = self._slots(key_cols, valid, lookup_only, group=None,
                             pad=0)
        return out

    def slots_and_group(self, key_cols: Sequence[np.ndarray],
                        valid: Optional[np.ndarray], pad: int):
        """Fused resolve + group: one C pass probes/inserts AND accumulates
        per-slot counts, then the fill pass emits the [Kb, E] device layout.
        Returns (slots, key_idx, sel)."""
        if LIB is None:
            slots = self.slots_for(key_cols, valid)
            v = np.ones(slots.shape[0], bool) if valid is None else valid
            key_idx, sel, _ = group_events_by_key(slots, v, pad=pad)
            return slots, key_idx, sel
        out, grouped = self._slots(key_cols, valid, False,
                                   group=_fill_groups, pad=pad)
        return out, grouped[0], grouped[1]

    def slots_and_tiers(self, key_cols: Sequence[np.ndarray],
                        valid: Optional[np.ndarray], pad: int):
        """`slots_and_group` with the layout sized by the batch's events:
        (slots, [(key_idx, sel), ...], the hottest key's count), the keys
        split by their count into a few [Kb, E] rectangles
        (`tier_events_by_key`) — one, and `slots_and_group`'s own, unless
        the keys' counts are far apart."""
        if LIB is None:
            slots = self.slots_for(key_cols, valid)
            v = np.ones(slots.shape[0], bool) if valid is None else valid
            return (slots,) + tier_events_by_key(slots, v, pad=pad)
        out, grouped = self._slots(key_cols, valid, False,
                                   group=_fill_tiers, pad=pad)
        return (out,) + grouped

    def _slots(self, key_cols, valid, lookup_only, group, pad: int):
        """`group`: None, or the fill (`_fill_groups` | `_fill_tiers`)
        the fused count pass feeds."""
        n = len(key_cols[0])
        if n == 0:
            return np.empty((0,), np.int32), None
        words = _key_words(key_cols)
        live = None if valid is None else \
            np.ascontiguousarray(valid, np.uint8)
        out = np.empty(n, np.int32)
        grouped = None
        with self._lock:
            count_before = int(self._meta[0])
            if self._arena is not None and words.shape[1] < self._w8:
                # narrower key than the arena width: zero-pad to match
                words = np.ascontiguousarray(np.concatenate(
                    [words, np.zeros((n, self._w8 - words.shape[1]),
                                     np.uint64)], axis=1))
            self._ensure_arena(words.shape[1])
            # purge churn turns EMPTY cells into tombstones; once EMPTY runs
            # out, probes for new keys could never terminate.  Rebuild
            # (clearing tombstones) past a load threshold.
            if (self._meta[0] + self._meta[2]) * 4 > self._cap2 * 3:
                self._rebuild_table()
            if LIB is not None:
                if group:
                    _group_scratch_lock.acquire()
                    cnt, rank, touched = _scratch(self.capacity)
                    gmeta = np.zeros(2, np.int64)
                    gargs = (ptr(cnt, ctypes.c_int32),
                             ptr(touched, ctypes.c_int32),
                             ptr(gmeta, ctypes.c_int64))
                else:
                    gargs = (None, None, None)
                try:
                    rc = LIB.sg_slots_for(
                        ptr(words, ctypes.c_uint64), n, self._w8,
                        None if live is None else ptr(live, ctypes.c_uint8),
                        ptr(self._cells, ctypes.c_uint64), self._cap2,
                        ptr(self._cell_by_slot, ctypes.c_int64),
                        ptr(self._arena, ctypes.c_uint8),
                        ptr(self._free, ctypes.c_int32),
                        ptr(self._journal, ctypes.c_int32),
                        ptr(self._used, ctypes.c_uint8),
                        ptr(self._meta, ctypes.c_int64),
                        1 if lookup_only else 0,
                        ptr(out, ctypes.c_int32), *gargs,
                        ptr(self._pcache, ctypes.c_uint64),
                        self._pcache.shape[0] - 1)
                    if rc < 0:
                        if group:
                            # re-zero count scratch the aborted pass touched
                            cnt[:] = 0
                        raise CapacityExceededError(
                            f"slot capacity {self.capacity} exhausted for "
                            f"{self.name!r}; raise via @capacity annotation")
                    if group:
                        grouped = group(out, live, n, cnt, rank,
                                        touched, int(gmeta[0]),
                                        int(gmeta[1]), pad)
                finally:
                    if group:
                        _group_scratch_lock.release()
            else:
                self._py_slots_for(words, live, lookup_only, out)
            if int(self._meta[0]) != count_before:
                self.version += 1
        if live is not None:
            out[live == 0] = -1
        return out, grouped

    # -- numpy fallback ------------------------------------------------------
    def _py_slots_for(self, words, live, lookup_only, out) -> None:
        n = words.shape[0]
        h1 = np.maximum(_hash_words(words, 0), np.uint64(2))
        h2 = _hash_words(words, 0xABCD)
        livemask = np.ones(n, bool) if live is None else live.astype(bool)
        slots, new = self._py_probe(h1, h2, livemask)
        if new.any() and not lookup_only:
            for r in np.nonzero(new)[0].tolist():
                # duplicate keys within the batch: re-probe before insert
                s = self._py_probe_one(int(h1[r]), int(h2[r]))
                if s >= 0:
                    slots[r] = s
                    continue
                if self._meta[1] <= 0:
                    raise CapacityExceededError(
                        f"slot capacity {self.capacity} exhausted for "
                        f"{self.name!r}; raise via @capacity annotation")
                self._meta[1] -= 1
                slot = int(self._free[self._meta[1]])
                self._cell_insert(int(h1[r]), int(h2[r]), slot)
                self._arena[slot] = words[r].view(np.uint8)
                self._used[slot] = 1
                self._meta[0] += 1
                if self._meta[3] < self._meta[5]:
                    self._journal[self._meta[3]] = slot
                    self._meta[3] += 1
                else:
                    self._meta[4] = 1
                slots[r] = slot
        elif new.any():
            slots[new] = -1
        out[:] = slots

    def _cell_insert(self, h1: int, h2: int, slot: int) -> None:
        j = h1 & (self._cap2 - 1)
        while self._cells[j, 0] > _TOMB:
            j = (j + 1) & (self._cap2 - 1)
        self._cells[j, 0] = np.uint64(h1)
        self._cells[j, 1] = np.uint64(h2)
        self._cells[j, 2] = np.uint64(np.uint32(slot))
        self._cell_by_slot[slot] = j

    def _py_probe_one(self, h1: int, h2: int) -> int:
        # bounded: cap2 probes visit every cell; when tombstones have eaten
        # the last EMPTY cell, exceeding the bound proves absence
        j = h1 & (self._cap2 - 1)
        for _ in range(self._cap2):
            c = int(self._cells[j, 0])
            if c == int(h1) and int(self._cells[j, 1]) == int(h2):
                return int(np.int32(np.uint32(self._cells[j, 2])))
            if c == 0:
                return -1
            j = (j + 1) & (self._cap2 - 1)
        return -1

    def _py_probe(self, h1, h2, live) -> Tuple[np.ndarray, np.ndarray]:
        n = h1.shape[0]
        out = np.full(n, -1, np.int32)
        new = np.zeros(n, bool)
        idx = (h1 & self._mask).astype(np.int64)
        unresolved = live.copy()
        for _ in range(self._cap2):
            uidx = np.nonzero(unresolved)[0]
            if uidx.size == 0:
                break
            ui = idx[uidx]
            ch, ch2 = self._cells[ui, 0], self._cells[ui, 1]
            cs = self._cells[ui, 2].astype(np.uint32).astype(np.int32)
            hit = (ch == h1[uidx]) & (ch2 == h2[uidx]) & (ch > _TOMB)
            empty = ch == _EMPTY
            out[uidx[hit]] = cs[hit]
            new[uidx[empty]] = True
            cont = ~(hit | empty)
            unresolved[uidx[~cont]] = False
            idx[uidx[cont]] = (ui[cont] + 1) & np.int64(self._cap2 - 1)
        return out, new

    def _rebuild_table(self) -> None:
        self._pcache[:] = 0
        self._meta[2] = 0
        if self._arena is None:
            self._cells[:] = 0
            self._cell_by_slot[:] = -1
            return
        if LIB is not None:
            LIB.sg_rebuild(
                ptr(self._cells, ctypes.c_uint64), self._cap2,
                ptr(self._cell_by_slot, ctypes.c_int64),
                ptr(self._arena, ctypes.c_uint8), self._w8,
                ptr(self._used, ctypes.c_uint8), self.capacity)
            return
        self._cells[:] = 0
        self._cell_by_slot[:] = -1
        for s in np.nonzero(self._used)[0].tolist():
            w = self._arena[s].view(np.uint64)[None, :]
            h1 = max(int(_hash_words(w, 0)[0]), 2)
            h2 = int(_hash_words(w, 0xABCD)[0])
            self._cell_insert(h1, h2, int(s))

    # -- lifecycle ------------------------------------------------------------
    def purge(self, slots: Sequence[int]) -> None:
        """Unbind `slots` (any sequence or array of ints; unknown, unused
        and repeated ones are skipped): a few numpy passes over them."""
        with self._lock:
            self.version += 1
            self._pcache[:] = 0
            s = np.unique(np.asarray(slots, np.int64))
            s = s[(s >= 0) & (s < self.capacity)]
            s = s[self._used[s] != 0]
            if not s.size:
                return
            self._used[s] = 0
            top = int(self._meta[1])
            self._free[top:top + s.size] = s
            self._meta[1] += s.size
            self._meta[0] -= s.size
            cells = self._cell_by_slot[s]
            cells = cells[cells >= 0]
            self._cells[cells, 0] = _TOMB
            self._cells[cells, 1] = _EMPTY
            self._cells[cells, 2] = np.uint64(0xFFFFFFFF)
            self._cell_by_slot[s] = -1
            self._meta[2] += cells.size

    def snapshot(self) -> Dict[bytes, int]:
        with self._lock:
            if self._arena is None:
                return {}
            return {self._arena[s].tobytes(): int(s)
                    for s in np.nonzero(self._used)[0]}

    def drain_journal(self) -> List[Tuple[bytes, int]]:
        """Insertions since the last drain (incremental snapshot delta).
        Slots purged since insertion are skipped (their arena bytes are
        stale).  On journal overflow, falls back to the full mapping — a
        superset of the delta, so restore stays correct."""
        with self._lock:
            if self._meta[4]:
                self._meta[3] = 0
                self._meta[4] = 0
                if self._arena is None:
                    return []
                return [(self._arena[s].tobytes(), int(s))
                        for s in np.nonzero(self._used)[0]]
            n = int(self._meta[3])
            self._meta[3] = 0
            return [(self._arena[s].tobytes(), int(s))
                    for s in self._journal[:n] if self._used[s]]

    def apply_journal(self, entries: List[Tuple[bytes, int]]) -> None:
        """Replay journal entries from an incremental snapshot.  A later
        entry re-binding an occupied slot wins (the source recycled it)."""
        with self._lock:
            for key, slot in entries:
                self._insert_exact(key, int(slot))
            # rebuild the free stack once for the whole batch
            free = np.nonzero(self._used == 0)[0][::-1].astype(np.int32)
            self._free[:free.shape[0]] = free
            self._meta[1] = free.shape[0]

    def _unbind(self, slot: int) -> None:
        self._pcache[:] = 0
        cell = int(self._cell_by_slot[slot])
        if cell >= 0:
            self._cells[cell, 0] = _TOMB
            self._cells[cell, 1] = _EMPTY
            self._cells[cell, 2] = np.uint64(0xFFFFFFFF)
            self._cell_by_slot[slot] = -1
            self._meta[2] += 1
        self._used[slot] = 0
        self._meta[0] -= 1

    def _insert_exact(self, key: bytes, slot: int) -> None:
        """Insert a key at a KNOWN slot (restore path).  Caller rebuilds the
        free stack afterwards."""
        if self._arena is None:
            self._w8 = len(key) // 8
            self._arena = np.zeros((self.capacity, len(key)), np.uint8)
        elif len(key) > self._w8 * 8:
            # source allocator widened after the base snapshot; mirror it
            self._ensure_arena(len(key) // 8)
        elif len(key) < self._w8 * 8:
            key = key + b"\x00" * (self._w8 * 8 - len(key))
        if self._used[slot]:
            if self._arena[slot].tobytes() == key:
                return
            self._unbind(slot)        # source recycled the slot to a new key
        w = np.frombuffer(key, np.uint64)[None, :]
        h1 = max(int(_hash_words(w, 0)[0]), 2)
        h2 = int(_hash_words(w, 0xABCD)[0])
        prev = self._py_probe_one(h1, h2)
        if prev >= 0:
            if prev == slot:
                self._arena[slot] = np.frombuffer(key, np.uint8)
                self._used[slot] = 1
                return
            self._unbind(prev)        # key moved to a different slot
        self._cell_insert(h1, h2, slot)
        self._arena[slot] = np.frombuffer(key, np.uint8)
        self._used[slot] = 1
        self._meta[0] += 1

    def restore(self, mapping: Dict[bytes, int]) -> None:
        with self._lock:
            self.version += 1
            self._used[:] = 0
            self._cell_by_slot[:] = -1
            self._cells[:] = 0
            self._meta[0] = 0
            self._meta[2] = 0
            self._meta[3] = 0
            self._meta[4] = 0
            if mapping:
                w = len(next(iter(mapping)))
                if self._arena is None or self._arena.shape[1] != w:
                    self._w8 = w // 8
                    self._arena = np.zeros((self.capacity, w), np.uint8)
                for key, slot in mapping.items():
                    self._arena[slot] = np.frombuffer(key, np.uint8)
                    self._used[slot] = 1
                self._meta[0] = len(mapping)
            free = np.nonzero(self._used == 0)[0][::-1].astype(np.int32)
            self._free[:free.shape[0]] = free
            self._meta[1] = free.shape[0]
            self._rebuild_table()


# scratch buffers for grouping, keyed by minimum capacity; RLock because
# group_events_by_key holds it across _scratch()+fill
_group_scratch: Dict[int, Tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
_group_scratch_lock = threading.RLock()


def _scratch(capacity: int):
    with _group_scratch_lock:
        for cap, bufs in _group_scratch.items():
            if cap >= capacity:
                return bufs
        cap = max(capacity, 1 << 16)
        bufs = (np.zeros(cap, np.int32), np.zeros(cap, np.int32),
                np.zeros(cap, np.int32))
        _group_scratch[cap] = bufs
        return bufs


def _fill_groups(slots, live, n, cnt, rank, touched, nu, maxc, pad):
    """Shared fill phase: bucket Kb/E, run sg_group_fill.  cnt holds counts
    from the count pass and is re-zeroed by the C fill."""
    if nu == 0:
        key_idx = np.full((1,), pad, np.int32)
        sel = np.full((1, 1), -1, np.int32)
        return key_idx, sel
    E = _bucket(maxc, _E_BUCKETS)
    Kb = _bucket(nu, _KB_BUCKETS)
    key_idx = np.empty(Kb, np.int32)
    sel = np.empty((Kb, E), np.int32)
    LIB.sg_group_fill(
        ptr(slots, ctypes.c_int32),
        None if live is None else ptr(live, ctypes.c_uint8), n,
        ptr(cnt, ctypes.c_int32), ptr(rank, ctypes.c_int32),
        ptr(touched, ctypes.c_int32), nu, Kb, E, pad,
        ptr(key_idx, ctypes.c_int32), ptr(sel, ctypes.c_int32))
    return key_idx, sel


def _count_and_fill(slots, valid, pad, fill):
    """The standalone C count pass over already-resolved `slots`, then
    `fill` (`_fill_groups` | `_fill_tiers` | a `_fill_shards`): (what
    `fill` returns, the number of distinct slots)."""
    n = slots.shape[0]
    slots = np.ascontiguousarray(slots, np.int32)
    live = np.ascontiguousarray(valid, np.uint8)
    with _group_scratch_lock:
        cnt, rank, touched = _scratch(
            max(pad, int(slots.max(initial=0)) + 1))
        maxc = np.zeros(1, np.int64)
        nu = int(LIB.sg_group_count(
            ptr(slots, ctypes.c_int32), ptr(live, ctypes.c_uint8), n,
            ptr(cnt, ctypes.c_int32), ptr(touched, ctypes.c_int32),
            ptr(maxc, ctypes.c_int64)))
        return fill(slots, live, n, cnt, rank, touched, nu, int(maxc[0]),
                    pad), nu


def _tier_plan(counts, nu: int, maxc: int):
    """How a batch whose `nu` keys have the per-key counts `counts()` (asked
    for only once the cheap tests have passed) is tiered:
    None for the one rectangle, else [(hi, Kb, E)] of the non-empty count
    classes, ascending (class i: the keys with _TIER_CUTS[i-1] < count <=
    _TIER_CUTS[i], the last one open above; hi = the class's own largest
    count, which tells the classes apart as well as the cut does).

    The one rectangle stays unless it has over twice the tiers' cells
    and over _TIER_MIN_CELLS: keys that all have one count are one class,
    counts a factor of two or three apart are not worth a dispatch a
    class, and neither is a rectangle whose columns are under a
    megabyte."""
    one = _bucket(nu, _KB_BUCKETS) * _bucket(maxc, _E_BUCKETS)
    if maxc <= _TIER_CUTS[0] or one <= _TIER_MIN_CELLS:
        return None
    counts = counts()
    cls = np.searchsorted(_TIER_CUTS, counts, side="left")
    plan = []
    for i in range(len(_TIER_CUTS) + 1):
        mine = counts[cls == i]
        if mine.size:
            hi = int(mine.max())
            plan.append((hi, _bucket(mine.size, _KB_BUCKETS),
                         _bucket(hi, _E_BUCKETS)))
    if len(plan) < 2 or one <= 2 * sum(kb * e for _, kb, e in plan):
        return None
    return plan


def _tier_buffers(plan):
    """The rectangles of a tier plan, end to end in one key_idx and one sel
    buffer: ([(key_idx view, sel view)], key_idx buffer, sel buffer, row
    offsets, cell offsets)."""
    key_off = np.zeros(len(plan) + 1, np.int64)
    sel_off = np.zeros(len(plan) + 1, np.int64)
    for t, (_, kb, e) in enumerate(plan):
        key_off[t + 1] = key_off[t] + kb
        sel_off[t + 1] = sel_off[t] + kb * e
    key_all = np.empty(int(key_off[-1]), np.int32)
    sel_all = np.empty(int(sel_off[-1]), np.int32)
    tiers = [(key_all[key_off[t]:key_off[t + 1]],
              sel_all[sel_off[t]:sel_off[t + 1]].reshape(kb, e))
             for t, (_, kb, e) in enumerate(plan)]
    return tiers, key_all, sel_all, key_off, sel_off


def _fill_tiers(slots, live, n, cnt, rank, touched, nu, maxc, pad):
    """`_fill_groups`, tiered: ([(key_idx, sel), ...], maxc) — the one
    rectangle of `_fill_groups` where `_tier_plan` keeps it."""
    plan = None if nu == 0 else \
        _tier_plan(lambda: cnt[touched[:nu]], nu, maxc)
    if plan is None:
        return [_fill_groups(slots, live, n, cnt, rank, touched, nu, maxc,
                             pad)], maxc
    tiers, key_all, sel_all, key_off, sel_off = _tier_buffers(plan)
    hi = np.array([h for h, _, _ in plan], np.int32)
    kbs = np.array([kb for _, kb, _ in plan], np.int64)
    es = np.array([e for _, _, e in plan], np.int64)
    LIB.sg_group_fill_tiers(
        ptr(slots, ctypes.c_int32),
        None if live is None else ptr(live, ctypes.c_uint8), n,
        ptr(cnt, ctypes.c_int32), ptr(rank, ctypes.c_int32),
        ptr(touched, ctypes.c_int32), nu,
        ptr(hi, ctypes.c_int32), len(plan),
        ptr(kbs, ctypes.c_int64), ptr(es, ctypes.c_int64),
        ptr(key_off, ctypes.c_int64), ptr(sel_off, ctypes.c_int64), pad,
        ptr(key_all, ctypes.c_int32), ptr(sel_all, ctypes.c_int32))
    return tiers, maxc


def tier_events_by_key(slots: np.ndarray, valid: np.ndarray,
                       pad: int = 2**30):
    """`group_events_by_key` with the layout sized by the batch's events,
    not by `distinct keys x hottest key's count`: ([(key_idx [Kb],
    sel [Kb, E]), ...], the hottest key's count), the keys split by
    their count (`_tier_plan`) into rectangles of their own, classes
    ascending.  Each rectangle keeps the layout contract of the one
    (slots ascending, a key's events along E in batch order, -1 / `pad`
    padding); a key is in exactly one.  A batch whose keys all have one
    count — and any batch the one rectangle serves within a factor of
    two — gives that one rectangle."""
    if LIB is not None and pad < 2**30:
        return _count_and_fill(slots, valid, pad, _fill_tiers)[0]
    idx = np.nonzero(valid & (slots >= 0))[0]
    plan, maxc = None, 0
    if idx.size:
        uniq, counts = np.unique(slots[idx], return_counts=True)
        maxc = int(counts.max())
        plan = _tier_plan(lambda: counts, len(uniq), maxc)
    if plan is None:
        return [group_events_by_key(slots, valid, pad)[:2]], maxc
    tiers, key_all, sel_all, _, _ = _tier_buffers(plan)
    key_all[:] = pad
    sel_all[:] = -1
    lo = 0
    for (hi, _, _), (key_idx, sel) in zip(plan, tiers):
        keys = uniq[(counts > lo) & (counts <= hi)]
        mine = idx[np.isin(slots[idx], keys)]
        order = np.argsort(slots[mine], kind="stable")
        row = np.searchsorted(keys, slots[mine][order])
        first = np.searchsorted(row, row, side="left")
        key_idx[:len(keys)] = keys
        sel[row, np.arange(len(row)) - first] = mine[order]
        lo = hi
    return tiers, maxc


def group_events_by_key(slots: np.ndarray, valid: np.ndarray,
                        pad: int = 2**30):
    """Arrange a batch into the per-key [Kb, E] device layout.

    Returns (key_idx [Kb] int32, sel [Kb, E] int32 original-batch indices
    (-1 = padding), kvalid [Kb, E] bool).  Kb/E are padded to buckets to
    bound recompilation.  Events of one key keep their batch order along E
    (sequential NFA semantics per key).

    Padding key rows get index `pad` (= state capacity): the device gather
    clamps them to a real row (their events are invalid, so the scan is a
    no-op there) and the scatter-back DROPS them as out-of-bounds — a pad row
    must never alias a live key's slot, or its stale state would clobber it."""
    if LIB is not None and pad < 2**30:
        (key_idx, sel), nu = _count_and_fill(slots, valid, pad,
                                             _fill_groups)
        if nu == 0:
            return key_idx, sel, np.zeros((1, 1), np.bool_)
        return key_idx, sel, sel >= 0
    vmask = valid & (slots >= 0)
    idx = np.nonzero(vmask)[0]
    if idx.size == 0:
        key_idx = np.full((1,), pad, np.int32)
        sel = np.full((1, 1), -1, np.int32)
        return key_idx, sel, np.zeros((1, 1), np.bool_)
    s = slots[idx]
    order = np.argsort(s, kind="stable")
    s_sorted = s[order]
    idx_sorted = idx[order]
    uniq, starts, counts = np.unique(s_sorted, return_index=True,
                                     return_counts=True)
    E = _bucket(int(counts.max()), _E_BUCKETS)
    Kb = _bucket(len(uniq), _KB_BUCKETS)
    key_idx = np.full((Kb,), pad, np.int32)
    key_idx[:len(uniq)] = uniq.astype(np.int32)
    within = np.arange(len(s_sorted)) - np.repeat(starts, counts)
    sel = np.full((Kb, E), -1, np.int32)
    group_rank = np.repeat(np.arange(len(uniq)), counts)
    sel[group_rank, within] = idx_sorted.astype(np.int32)
    return key_idx, sel, sel >= 0


def group_events_by_shard(slots: np.ndarray, valid: np.ndarray,
                          n_shards: int, capacity: int):
    """`group_events_by_key` for a key space of `capacity` slots laid
    round-robin over `n_shards` devices (sharding/router.py: slot s is
    local row `s // n_shards` of shard `s % n_shards`), in ONE grouping of
    the batch: (key_idx [n, Kb] int32 local rows, sel [n, Kb, E] int32,
    events [n] int64 routed to each shard, keys [distinct] int32 — the
    batch's distinct slots ascending — and counts [distinct] int32, the
    events of each).  Each shard's rectangle keeps the layout contract of
    the one (rows ascending, a key's events along E in batch order, -1 /
    `capacity // n_shards` padding); Kb is the fullest shard's bucket, E
    the hottest key's.  The ascending slots with `s % n_shards == d` ARE
    shard d's local rows ascending, so nothing is grouped a shard."""
    if LIB is not None and capacity < 2**30:
        return _count_and_fill(slots, valid, capacity,
                               _fill_shards(n_shards))[0]
    keys, by_key, kvalid = group_events_by_key(slots, valid, pad=capacity)
    live = keys < capacity
    keys, counts = keys[live], kvalid.sum(axis=1, dtype=np.int32)[live]
    shard = keys % n_shards
    mine = [np.flatnonzero(shard == d) for d in range(n_shards)]
    Kb = _bucket(max(m.size for m in mine), _KB_BUCKETS)
    key_idx = np.full((n_shards, Kb), capacity // n_shards, np.int32)
    sel = np.full((n_shards, Kb, by_key.shape[1]), -1, np.int32)
    events = np.zeros(n_shards, np.int64)
    for d, m in enumerate(mine):
        key_idx[d, :m.size] = keys[m] // n_shards
        sel[d, :m.size] = by_key[m]
        events[d] = counts[m].sum()
    return key_idx, sel, events, keys, counts


def _fill_shards(n_shards: int):
    """The fill of `group_events_by_shard` for `_count_and_fill` (whose
    `pad` is the key space's capacity here): sg_group_fill_shards."""
    def fill(slots, live, n, cnt, rank, touched, nu, maxc, pad):
        Kb = _bucket(int(np.bincount(touched[:nu] % n_shards,
                                     minlength=1).max()), _KB_BUCKETS)
        E = _bucket(maxc, _E_BUCKETS)
        key_idx = np.empty((n_shards, Kb), np.int32)
        sel = np.empty((n_shards, Kb, E), np.int32)
        counts = np.empty(nu, np.int32)
        events = np.empty(n_shards, np.int64)
        LIB.sg_group_fill_shards(
            ptr(slots, ctypes.c_int32), ptr(live, ctypes.c_uint8), n,
            ptr(cnt, ctypes.c_int32), ptr(rank, ctypes.c_int32),
            ptr(touched, ctypes.c_int32), nu, n_shards, Kb, E,
            pad // n_shards, ptr(key_idx, ctypes.c_int32),
            ptr(sel, ctypes.c_int32), ptr(counts, ctypes.c_int32),
            ptr(events, ctypes.c_int64))
        return key_idx, sel, events, touched[:nu].copy(), counts
    return fill


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    # beyond the table: next power of two (never clamp — a clamped bucket
    # would overflow the sel buffer in the C fill pass)
    return 1 << (n - 1).bit_length()


_KB_BUCKETS = (1, 8, 64, 512, 4096, 16384, 65536, 131072,
               262144, 524288, 1048576)
# powers of two up to 16,384: a key's E is under twice its count
_E_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096,
              8192, 16384)
# per-key count classes of a tiered grouping (`_tier_plan`): at most 4
# events of a batch, at most 32, more.  Fixed cuts, not cuts fitted to
# each batch's histogram: every distinct [Kb, E] is a compiled program,
# and cuts that follow the batch would meet new shapes for as long as the
# traffic runs.  Two cuts a factor 8 apart, as the Kb buckets are, hold
# whatever the skew (tests/test_keyslots_direct.py walks exponents 0.5 to
# 3 and key spaces of 4,096 to 16 M):
#   the scan's ticks, the sum of the E, stay under 36 + twice the
#   hottest key's count;
#   the two lower classes pay at most 4 and 32 cells a key, times the Kb
#   bucket's padding;
#   the open top class pays `its keys' Kb bucket x the hottest key's E`
#   — the known remainder: 20 keys of 33 to 1,500 events are a [64, 2048]
#   rectangle, ~36 cells an event of theirs.
# 4 is the E of a key that brings one visit of the 4-stage flagship
_TIER_CUTS = (4, 32)
_TIER_MIN_CELLS = 1 << 16


def valid_first_sel(valid: np.ndarray) -> np.ndarray:
    """[1, B] selection of an un-partitioned send whose bucket is not full
    (one key, so no slot to resolve): the valid rows' indices in order,
    FIRST, then -1 — never a hole between two valid rows.  The block
    step's linear form reads the slot after a valid event as the next
    valid event (pattern_block.py); the scan path sees the same events in
    the same order wherever the padding lies."""
    sel = np.full((1, valid.shape[0]), -1, np.int32)
    rows = np.flatnonzero(valid)
    sel[0, :rows.shape[0]] = rows
    return sel

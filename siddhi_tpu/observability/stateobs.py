"""State observatory: occupancy, key hotness, and high-water telemetry.

Reference (what): the reference engine's metrics/debugger surface reports
per-component statistics and lets an operator inspect live state
(SiddhiAppRuntimeImpl statistics + SiddhiDebugger state inspection).
Here every stateful operator runs against FIXED device shapes — keyed
window slabs, group-slot arenas, NFA blocks, join candidate lanes,
emission compaction blocks, serving rings — so the operational question
the reference never had is *utilization*: how full is each sized
structure, how hot is the key traffic, and what capacity would a
restart actually need.

TPU design (how): every sized device structure already has a HOST
mirror — `SlotAllocator` binds keys host-side before dispatch,
`JoinKeyTracker` mirrors per-bucket retention, `EmissionRing` counts
its own slots, emission demand is decoded from the header fetch that
delivery already pays — so the observatory is an always-on accumulator
over those mirrors, under the repo's never-fetch discipline: zero added
`jax.device_get` / `block_until_ready` anywhere.  The one device-side
quantity with no mirror (plain window-buffer fill, which lives inside
the jitted step state) is probed by a tiny sampled jitted reduction
whose scalar RIDES the delivery fetch that already happens
(`_deliver_output` packs it into the same `device_get` tuple).

Key hotness: staging already computes per-batch key sets (slot ids +
per-key row counts) to group events; the observatory folds them into a
count-min sketch (bounded memory, one-sided overestimates) plus a
space-saving top-K (the heavy hitters) plus an exact distinct bitmap
(slots are dense ints below the allocator capacity).  The feed is on
the served path of every keyed query at every statistics level, so a
batch is folded in WHOLE: a few O(n) numpy passes, and of the batch's
keys only those already tracked and the `_TOPK` heaviest others ever
reach the top-K table's Python dict (`KeyHotness`: the rule and the
guarantees it keeps).  Totals, distinct and the sketch stay exact sums —
nothing is sampled.  The derived
`hot_share` — the share of keyed traffic landing in the hottest 1% of
keys — is the measured input ROADMAP item 4's tiered key state needs.

High-water marks accumulate into a sizing-hints ledger that rides app
snapshots (`"sizing"` payload key), so a restarted app reports its
learned capacities from tick zero — the persistence half of ROADMAP
item 5's self-tuning controller.

Surfaces: `siddhi_state_occupancy` / `siddhi_state_high_water` /
`siddhi_key_hotset_share` in /metrics, a `utilization` node in EXPLAIN,
a `state` section in /healthz (near-capacity on a non-growable cap
flips `degraded`), /timeseries series, `runtime.state_report()`, and
REST `GET /siddhi-apps/<app>/state`.

Config: `state.obs.enabled` (default true; false reverts to the PR 13
baseline — the never-fetch guard test's control arm),
`state.obs.sample.every` (window-fill probe modulus, default 8, 0
disables the probe), `state.obs.near.capacity` (healthz near-capacity
threshold, default 0.9).
"""
from __future__ import annotations

import heapq
import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# canonical structure order — every surface lists structures in this
# order, not dict order (the phases.PHASES convention)
STRUCTURES = ("window_keys", "group_slots", "pattern_keys", "pair_slots",
              "join_keys", "join_lane", "join_window_left",
              "join_window_right", "window_fill", "emission_cap",
              "serve_ring")

# count-min sketch geometry: 4 rows x 1024 counters of int64 = 32 KiB
# per tracked query — error bound e*total/1024 per estimate, one-sided
_CMS_DEPTH = 4
_CMS_WIDTH = 1024
# odd multipliers of the per-row hashes (keys are dense non-negative slot
# ints)
_CMS_MULT = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE35, 0x27D4EB2F)
# the row hash is `(key + 1) * mult % 2**31 % _CMS_WIDTH`.  The width is a
# power of two dividing 2**31, so that is the low bits of the product,
# which only the low bits of `key + 1` reach: row d's counter for a key is
# a fixed permutation (the mults are odd) of ONE shared bucket,
# `(key + 1) & _CMS_MASK`.  A batch is therefore summed per bucket once,
# and `_CMS_FROM[d, j]` names the bucket whose sum row d's counter j takes.
# It also means the four rows split the keys the same way, so `estimate`
# is as sharp as one row: a hash on the product's HIGH bits would cure
# that and change every stored value, so it is not done in passing
_CMS_MASK = _CMS_WIDTH - 1
_CMS_FROM = np.stack([np.argsort((np.arange(_CMS_WIDTH) * m) & _CMS_MASK)
                      for m in _CMS_MULT])
_TOPK = 64


class KeyHotness:
    """Per-query key-traffic tracker: count-min sketch + space-saving
    top-K + exact distinct bitmap.  Fed from staging's already-computed
    per-batch key sets (DISTINCT slot ids + exact per-key row counts) —
    numpy only, never a device array.

    `total`, the distinct bitmap and the count-min rows are exact sums of
    what was fed.  The space-saving table takes a whole batch at once
    (`_ss_merge`): a batch is already an exact summary, so tracked keys
    in it add their counts, and of the untracked ones only the `_TOPK`
    largest go through the take-over rule (lightest first, so the
    heaviest are the last to come and the ones that stay) — the rest
    cannot end in the table and never touch it.  One `update` costs O(n)
    numpy passes and at most 2 * `_TOPK` Python steps, whatever n is.
    Whichever branch ran (one key, up to `_TOPK` keys one by one, or the
    merge), the table keeps:

    (a) a tracked key's count is never below its true count;
    (b) with the table full, an untracked key's true count is at most
        the table's minimum — so every key with more than
        `total / _TOPK` rows is tracked;
    (c) the table's counts sum to at most `total`;
    (d) below `_TOPK` distinct keys the table is exact.

    The merge raises the minimum once per key it takes over (at most
    `_TOPK` a batch), not once per untracked key of the batch as a
    key-by-key feed would, so wide uniform traffic inflates the floor
    far less and `hot_share` reads tighter."""

    __slots__ = ("_cms", "_seen", "_ss", "total")

    def __init__(self, capacity: int):
        self._cms = np.zeros((_CMS_DEPTH, _CMS_WIDTH), np.int64)
        self._seen = np.zeros(max(1, int(capacity)), bool)
        self._ss: Dict[int, int] = {}   # space-saving: key -> count
        self.total = 0

    def update(self, keys, counts) -> None:
        keys = np.asarray(keys, np.int64).ravel()
        counts = np.asarray(counts, np.int64).ravel()
        if keys.size < 2:
            # scalar path: single-key batches dominate interactive and
            # test sends, and even the few numpy calls below cost more
            # than the whole of `_update_one`
            if keys.size:
                self._update_one(int(keys[0]), int(counts[0]))
            return
        if int(keys.min()) < 0 or int(counts.min()) <= 0:
            live = (keys >= 0) & (counts > 0)
            if not live.any():
                return
            keys, counts = keys[live], counts[live]
        self.total += int(counts.sum())
        # exact distinct: slots are dense ints < allocator capacity
        seen = self._seen
        if int(keys.max()) < seen.shape[0]:
            seen[keys] = True
        else:
            seen[keys[keys < seen.shape[0]]] = True
        # CMS rows: one scatter-add into the shared buckets, then each
        # row takes the 1024 sums through its permutation (_CMS_FROM)
        buckets = np.zeros(_CMS_WIDTH, np.int64)
        np.add.at(buckets, (keys + 1) & _CMS_MASK, counts)
        self._cms += buckets[_CMS_FROM]
        self._ss_merge(keys, counts)

    def _update_one(self, k: int, c: int) -> None:
        if k < 0 or c <= 0:
            return
        self.total += c
        if k < self._seen.shape[0]:
            self._seen[k] = True
        kk = k + 1
        cms = self._cms
        for d in range(_CMS_DEPTH):
            cms[d, (kk * _CMS_MULT[d]) & _CMS_MASK] += c
        self._ss_feed(k, c)

    def _ss_merge(self, keys: np.ndarray, counts: np.ndarray) -> None:
        """Fold one batch (distinct live keys, exact counts) into the
        space-saving table."""
        ss = self._ss
        if keys.size > _TOPK:
            if ss:
                # the tracked keys of the batch add their counts in place
                tracked = np.sort(np.fromiter(ss, np.int64, len(ss)))
                at = np.searchsorted(tracked, keys)
                at[at == tracked.size] = 0
                hit = tracked[at] == keys
                if hit.any():
                    for k, c in zip(keys[hit].tolist(),
                                    counts[hit].tolist()):
                        ss[k] += c
                    miss = ~hit
                    keys, counts = keys[miss], counts[miss]
            if keys.size > _TOPK:
                # an untracked key with _TOPK others of its batch at or
                # above it cannot end in the table: their take-overs
                # alone lift the minimum past the old floor + its count
                top = np.argpartition(counts, keys.size - _TOPK)[-_TOPK:]
                keys, counts = keys[top], counts[top]
        late = []
        for k, c in zip(keys.tolist(), counts.tolist()):
            if k in ss:
                ss[k] += c
            elif len(ss) < _TOPK:
                ss[k] = c
            else:
                late.append((c, k))
        if late:
            # take-overs, lightest first: the heaviest of the batch come
            # last and are the ones still standing at the end
            late.sort()
            heap = [(c, k) for k, c in ss.items()]
            heapq.heapify(heap)
            for c, k in late:
                floor, victim = heap[0]
                heapq.heapreplace(heap, (floor + c, k))
                del ss[victim]
                ss[k] = floor + c

    def _ss_feed(self, k: int, c: int) -> None:
        # space-saving: exact for tracked keys; an untracked key takes
        # over the minimum tracked count (classic overestimate-in-place)
        ss = self._ss
        if k in ss:
            ss[k] += c
        elif len(ss) < _TOPK:
            ss[k] = c
        else:
            victim = min(ss, key=ss.get)
            floor = ss.pop(victim)
            ss[k] = floor + c

    @property
    def distinct(self) -> int:
        return int(self._seen.sum())

    def estimate(self, key: int) -> int:
        """CMS point estimate — never underestimates the true count."""
        kk = int(key) + 1
        return int(min(self._cms[d, (kk * _CMS_MULT[d]) & _CMS_MASK]
                       for d in range(_CMS_DEPTH)))

    def top(self, n: int = 10) -> List[Tuple[int, int]]:
        """Heavy hitters with tightened counts: the space-saving count
        and the CMS estimate are both one-sided upper bounds, so their
        min is a tighter upper bound — this keeps eviction inflation
        (space-saving's min-floor creep under uniform traffic) from
        masquerading as heat."""
        items = [(k, min(c, self.estimate(k)))
                 for k, c in self._ss.items()]
        return sorted(items, key=lambda kv: -kv[1])[:n]

    def hot_share(self, fraction: float = 0.01) -> float:
        """Share of total keyed traffic landing in the hottest
        ceil(distinct * fraction) keys (at least one key)."""
        if not self.total:
            return 0.0
        k = max(1, int(np.ceil(self.distinct * fraction)))
        hot = sum(c for _, c in self.top(k))
        return min(1.0, hot / self.total)

    def snapshot(self) -> Dict[str, Any]:
        return {
            "total": self.total,
            "distinct": self.distinct,
            "hot_share_1pct": round(self.hot_share(0.01), 4),
            "top": [[int(k), int(c)] for k, c in self.top(8)],
        }


class StateObservatory:
    """Always-on per-(query, structure) utilization accumulator.  One
    per StatisticsManager (i.e. per app runtime); `observe` is the
    single hot-path entry — a dict upsert under a short lock."""

    __slots__ = ("_lock", "_rec", "_hot")

    def __init__(self):
        self._lock = threading.Lock()
        # (query, structure) -> [occupancy, capacity, high_water,
        #                        growable, config_key]
        self._rec: Dict[tuple, list] = {}
        self._hot: Dict[str, KeyHotness] = {}

    def observe(self, query: str, structure: str,
                occupancy: Optional[int], capacity: int,
                growable: bool = True,
                config_key: Optional[str] = None) -> None:
        """Record one occupancy sample (high-water = running max).
        occupancy=None refreshes capacity/metadata only — the HWM a
        restore adopted survives untouched until real traffic beats
        it."""
        key = (query, structure)
        with self._lock:
            rec = self._rec.get(key)
            if rec is None:
                rec = self._rec[key] = [0, 0, 0, True, None]
            if occupancy is not None:
                occ = int(occupancy)
                rec[0] = occ
                if occ > rec[2]:
                    rec[2] = occ
            rec[1] = int(capacity)
            rec[3] = bool(growable)
            if config_key is not None:
                rec[4] = config_key

    def feed_keys(self, query: str, capacity: int, keys, counts) -> None:
        """Fold one staged batch's key set (slot ids + per-key row
        counts, both host numpy) into the query's hotness tracker."""
        with self._lock:
            hot = self._hot.get(query)
            if hot is None:
                hot = self._hot[query] = KeyHotness(capacity)
            hot.update(keys, counts)

    def hotness(self, query: str) -> Optional[KeyHotness]:
        with self._lock:
            return self._hot.get(query)

    def snapshot(self) -> Dict[str, Any]:
        """{"structures": {q: {s: {...}}}, "hotness": {q: {...}}} —
        structures in canonical order; scrape-safe shallow reads."""
        with self._lock:
            recs = {k: list(v) for k, v in self._rec.items()}
            hots = {q: h.snapshot() for q, h in self._hot.items()}
        structures: Dict[str, Dict] = {}
        for (q, s), (occ, cap, hwm, growable, ck) in recs.items():
            # utilization may exceed 1.0 for emission_cap: occupancy is
            # the batch's total row DEMAND while a partitioned pattern's
            # @emit cap is per-key — >1 reads as drop/growth pressure,
            # not arena fill
            structures.setdefault(q, {})[s] = {
                "occupancy": occ,
                "capacity": cap,
                "utilization": round(occ / cap, 4) if cap else 0.0,
                "high_water": hwm,
                "growable": growable,
                **({"config_key": ck} if ck else {}),
            }
        for q in structures:
            ordered = {s: structures[q][s] for s in STRUCTURES
                       if s in structures[q]}
            ordered.update({s: v for s, v in structures[q].items()
                            if s not in ordered})
            structures[q] = ordered
        return {"structures": structures, "hotness": hots}

    # -- sizing-hints ledger (snapshot persistence) ----------------------
    def ledger(self) -> Dict[str, Dict[str, Dict[str, int]]]:
        """{query: {structure: {"high_water", "capacity"}}} — the
        sizing-hints payload carried in app snapshots.

        `window_fill` is excluded: a sliding window trends to full by
        design (its capacity IS the configured length, nothing to
        learn), and the sampled probe rides the unfused delivery fetch
        — whether an entry exists depends on dispatch strategy, which
        would break the fused-vs-sequential snapshot byte-parity
        contract (tests/test_fused.py).  It stays a live surface
        (state_report/metrics/EXPLAIN), just not a persisted hint."""
        with self._lock:
            out: Dict[str, Dict] = {}
            for (q, s), (_, cap, hwm, _, _) in self._rec.items():
                if s == "window_fill":
                    continue
                out.setdefault(q, {})[s] = {"high_water": int(hwm),
                                            "capacity": int(cap)}
            return out

    def adopt_ledger(self, led: Dict) -> None:
        """Max-merge a restored sizing ledger: high-water marks survive
        the restart (a restarted app reports learned capacities from
        tick zero); live occupancy stays whatever this process saw."""
        if not isinstance(led, dict):
            return
        with self._lock:
            for q, structures in led.items():
                if not isinstance(structures, dict):
                    continue
                for s, hint in structures.items():
                    try:
                        hwm = int(hint.get("high_water", 0))
                        cap = int(hint.get("capacity", 0))
                    except Exception:  # noqa: BLE001 — bad blob: skip
                        continue
                    rec = self._rec.get((q, s))
                    if rec is None:
                        rec = self._rec[(q, s)] = [0, cap, 0, True, None]
                    rec[2] = max(rec[2], hwm)
                    if rec[1] == 0:
                        rec[1] = cap

    def reset(self) -> None:
        with self._lock:
            self._rec.clear()
            self._hot.clear()


# -- config memos (the phases.sample_every pattern) -------------------------

def obs_enabled(rt) -> bool:
    """`state.obs.enabled` (default true), memoized on the runtime —
    the hot path reads one dict slot, never the ConfigManager."""
    on = rt.__dict__.get("_stateobs_enabled")
    if on is None:
        on = True
        try:
            cm = getattr(rt, "config_manager", None)
            v = cm.extract_property("state.obs.enabled") \
                if cm is not None else None
            if v is not None:
                on = str(v).strip().lower() not in ("false", "0", "no")
        except Exception:  # noqa: BLE001 — observability must not throw
            on = True
        rt.__dict__["_stateobs_enabled"] = on
    return on


def obs_sample_every(rt) -> int:
    """`state.obs.sample.every` — window-fill probe modulus (default 8,
    0 disables the sampled probe entirely), memoized like obs_enabled."""
    every = rt.__dict__.get("_stateobs_sample_every")
    if every is None:
        every = 8
        try:
            cm = getattr(rt, "config_manager", None)
            v = cm.extract_property("state.obs.sample.every") \
                if cm is not None else None
            if v is not None:
                every = max(0, int(v))
        except Exception:  # noqa: BLE001 — observability must not throw
            every = 8
        rt.__dict__["_stateobs_sample_every"] = every
    return every


def near_capacity_threshold(rt) -> float:
    """`state.obs.near.capacity` — /healthz degraded threshold over
    non-growable structures (default 0.9)."""
    th = rt.__dict__.get("_stateobs_near_capacity")
    if th is None:
        th = 0.9
        try:
            cm = getattr(rt, "config_manager", None)
            v = cm.extract_property("state.obs.near.capacity") \
                if cm is not None else None
            if v is not None:
                th = min(1.0, max(0.0, float(v)))
        except Exception:  # noqa: BLE001 — observability must not throw
            th = 0.9
        rt.__dict__["_stateobs_near_capacity"] = th
    return th


# tiny test fixtures legitimately run 100%-full 4-key allocators; below
# this capacity a full arena is sizing noise, not an incident
_NEAR_CAPACITY_MIN_CAP = 16

# a sliding length/time window runs 100% full at steady state — that is
# its job, not an incident — and emission-cap "occupancy" is per-batch
# row demand (legitimately >cap for partitioned patterns, and already
# surfaced by drop counters + adaptive growth); only arenas where
# "full" means "next new key raises" count toward the near-capacity
# verdict
_NEAR_CAPACITY_EXEMPT = frozenset({"window_fill", "emission_cap"})


# -- pull collection over the host mirrors ----------------------------------

def collect(rt) -> None:
    """Refresh the observatory from every query's HOST mirrors: slot
    allocators (len/capacity attribute reads), the join tracker's lane
    demand, emission-cap plan metadata, serve-ring facts.  Pure host
    object walk — scrape surfaces call this under the monkeypatched
    never-fetch bomb and must survive."""
    if not obs_enabled(rt):
        return
    obs = rt.stats.stateobs
    for qname, qr in list(getattr(rt, "query_runtimes", {}).items()):
        try:
            _collect_query(obs, qname, qr)
        except Exception:  # noqa: BLE001 — metrics must not throw
            pass


def _collect_query(obs: StateObservatory, qname: str, qr) -> None:
    p = qr.planned
    wk = p.window_key_allocator
    if wk is not None:
        obs.observe(qname, "window_keys", len(wk), wk.capacity,
                    growable=False, config_key="@capacity(keys='N')")
    ga = p.slot_allocator
    if ga is not None and qr.slot_allocator is not ga:
        obs.observe(qname, "group_slots", len(ga), ga.capacity,
                    growable=False, config_key="@capacity(groups='N')")
    pairs = p.pair_allocs
    if pairs:
        obs.observe(qname, "pair_slots",
                    max(len(a) for a, _ in pairs),
                    max(a.capacity for a, _ in pairs),
                    growable=False, config_key="@capacity(groups='N')")
    # pattern slab allocator lives on the runtime, not the plan
    pa = qr.slot_allocator
    if pa is not None:
        obs.observe(qname, "pattern_keys", len(pa), pa.capacity,
                    growable=False, config_key="@capacity(keys='N')")
    jk_alloc = p.join_key_allocator
    if jk_alloc is not None:
        obs.observe(qname, "join_keys", len(jk_alloc), jk_alloc.capacity,
                    growable=False, config_key="@capacity(keys='N')")
    jk = qr._jk
    if jk is not None:
        obs.observe(qname, "join_lane", jk.needed_k(),
                    p.lane_k, growable=True,
                    config_key="auto (lane grows via replan)")
        # a `window.time` side kept as a ring: rows held over its bound
        for rows, cap, kind, which in zip(jk.rows(), p.ring_caps,
                                          p.index_kind, ("left", "right")):
            if kind == "chain":
                obs.observe(qname, f"join_window_{which}", rows, cap,
                            growable=False,
                            config_key=f"@capacity(window.{which}='N')")
    cap = p.compact_rows
    if cap is not None:
        obs.observe(qname, "emission_cap", None, cap,
                    growable=not p.emit_explicit,
                    config_key="@emit(rows='N')")
    ring = qr._serve_ring
    if ring is not None:
        obs.observe(qname, "serve_ring", ring.occupancy(), ring.capacity,
                    growable=True, config_key="serving.ring.capacity")


# -- window-fill probe (sampled; the scalar rides the delivery fetch) -------

def _alive_leaves(state) -> List:
    """`alive` masks of every window Buffer inside a state pytree —
    a host-side container walk (NamedTuple fields), no device reads."""
    out: List = []

    def walk(node):
        if isinstance(node, tuple):
            fields = getattr(node, "_fields", None)
            if fields is not None and "alive" in fields:
                out.append(node.alive)
            for sub in node:
                walk(sub)
        elif isinstance(node, (list,)):
            for sub in node:
                walk(sub)
        elif isinstance(node, dict):
            for sub in node.values():
                walk(sub)

    walk(state)
    return out


_PROBE_FN = None


def _probe_fn():
    """ONE process-wide jitted fill reduction, shared by every query
    and runtime: jax's jit cache keys on (function object, avals), so a
    module-level function re-uses compiles across queries — and across
    the many short-lived runtimes a test session creates — for every
    repeated window shape.  A per-query closure here recompiled the
    identical reduction once per runtime, which dominated the probe's
    cost under pytest."""
    global _PROBE_FN
    if _PROBE_FN is None:
        import jax
        from ..core.steputil import jit_step

        def _probe(ls):
            return jax.numpy.stack(
                [jax.numpy.sum(a.astype(jax.numpy.int32)) for a in ls])

        _PROBE_FN = jit_step(_probe, owner="stateobs:fill_probe",
                             role="fill_probe")
    return _PROBE_FN


def arm_fill_probe(qr) -> None:
    """Every Nth dispatch, dispatch ONE tiny jitted reduction over the
    query state's window `alive` masks and stash the lazy [n] fill
    vector on the runtime — `_deliver_output` packs it into the
    `device_get` it already performs (zero added fetches; the probe is
    dispatch-only).  No-op when the state holds no Buffer windows
    (keyed slabs mirror through their allocator instead)."""
    rt = qr.app
    if qr._stateobs_probe_off:
        return
    if not obs_enabled(rt):
        return
    every = obs_sample_every(rt)
    if every <= 0:
        return
    n = qr._stateobs_tick = qr._stateobs_tick + 1
    if n % every:
        return
    leaves = _alive_leaves(qr.state)
    if not leaves:
        # no Buffer windows in this state shape — never will be; stop
        # walking the pytree on every Nth dispatch
        qr._stateobs_probe_off = True
        return
    try:
        qr._stateobs_probe = _probe_fn()(leaves)
        qr._stateobs_probe_caps = [int(np.prod(a.shape)) for a in leaves]
    except Exception:  # noqa: BLE001 — observability must not throw
        qr._stateobs_probe = None


def take_fill_probe(qr):
    """Pop the pending lazy fill vector (or None) — the delivery path
    appends it to its existing fetch tuple."""
    probe, qr._stateobs_probe = qr._stateobs_probe, None
    return probe


def record_fill(qr, fills) -> None:
    """Fold a fetched fill vector back into the observatory (summed
    across the query's window buffers; capacity is the buffers' total
    row capacity from shape metadata)."""
    if fills is None:
        return
    caps = qr._stateobs_probe_caps or []
    try:
        per_buffer = np.asarray(fills)
        if any(int(f) >= c for f, c in zip(per_buffer, caps)):
            # a full slab: a time window past this point drops its OLDEST
            # rows without expiring them out of the aggregates — counted
            # beside the emission drop counters (sampled, so a floor)
            qr.app.stats.counter_inc(f"{qr.name}.window_full")
        fill = int(per_buffer.sum())
        cap = int(sum(caps)) or 1
        qr.app.stats.stateobs.observe(
            qr.name, "window_fill", fill, cap, growable=False,
            config_key="window length/time capacity")
    except Exception:  # noqa: BLE001 — observability must not throw
        pass


# -- reports ----------------------------------------------------------------

def near_capacity(rt, snap: Optional[Dict] = None) -> List[Dict]:
    """Non-growable structures at/over the near-capacity threshold —
    the /healthz degraded trigger and the STATE003 lint input."""
    if snap is None:
        snap = rt.stats.stateobs.snapshot()
    th = near_capacity_threshold(rt)
    out: List[Dict] = []
    for q, structures in snap["structures"].items():
        for s, rec in structures.items():
            if rec["growable"] or s in _NEAR_CAPACITY_EXEMPT \
                    or rec["capacity"] < _NEAR_CAPACITY_MIN_CAP:
                continue
            if rec["occupancy"] >= th * rec["capacity"]:
                out.append({"query": q, "structure": s,
                            "occupancy": rec["occupancy"],
                            "capacity": rec["capacity"],
                            "utilization": rec["utilization"],
                            **({"config_key": rec["config_key"]}
                               if rec.get("config_key") else {})})
    return out


def state_report(rt) -> Dict:
    """Full observatory report for one app: per-structure utilization
    and high-water marks, key hotness, near-capacity verdicts, and the
    sizing-hints ledger a snapshot would carry.  Host-side reads only —
    safe to call on a live app."""
    enabled = obs_enabled(rt)
    if enabled:
        collect(rt)
    obs = rt.stats.stateobs
    snap = obs.snapshot()
    return {
        "app": rt.name,
        "enabled": enabled,
        "sample_every": obs_sample_every(rt),
        "structures": snap["structures"],
        "hotness": snap["hotness"],
        "near_capacity": near_capacity(rt, snap) if enabled else [],
        "sizing_hints": obs.ledger(),
        "state_rows": state_rows(rt.stats.counters()),
        # what each pattern's last drain read off its slab
        # (runtime.PatternQueryRuntime.note_nfa_facts)
        "nfa": {q: dict(qr._nfa_facts) for q, qr in sorted(
            getattr(rt, "query_runtimes", {}).items()) if qr._nfa_facts},
        # what each bucket join's retention mirror holds
        # (runtime.JoinQueryRuntime.join_facts)
        "join": {q: qr.join_facts() for q, qr in sorted(
            getattr(rt, "query_runtimes", {}).items())
            if qr._kind == "join" and qr._jk is not None},
    }


def state_rows(counters: Dict[str, int]) -> Dict[str, Dict]:
    """{query: {keys, blocks, keys_per_block}} from the row-mover's
    counters (`runtime._count_state_rows`; statistics BASIC and above):
    live keys whose state rows the gather-path pattern step moved, the
    distinct 128-key blocks they lay in, and their ratio — the block
    mover's hit share, 1 for scattered keys, 128 for a contiguous run."""
    out: Dict[str, Dict] = {}
    for name, keys in counters.items():
        if name.endswith(".state_row_keys"):
            q = name[:-len(".state_row_keys")]
            blocks = counters.get(q + ".state_row_blocks", 0)
            out[q] = {"keys": keys, "blocks": blocks,
                      "keys_per_block": keys / blocks if blocks else 0.0}
    return out

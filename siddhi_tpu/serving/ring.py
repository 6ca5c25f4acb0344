"""On-device emission rings: the send path becomes dispatch-only.

Reference behavior (what): the reference decouples producers from
consumers host-side with its Disruptor-backed async StreamJunction
(CORE/stream/StreamJunction.java:276) — a producer never blocks on a
consumer; it writes into a preallocated ring and moves on.

TPU design (how): in blocking delivery the sender's thread waits for
the device step inside the emission's header fetch, and @pipeline/@fuse
only *amortize* that `device_get`.  This module does the Disruptor
decoupling *across the host-device boundary*: a query's emission is
appended to a persistent DEVICE ring buffer (one jitted
`dynamic_update_index_in_dim` dispatch, `siddhi:dispatch
step=ring_append`, no fetch) and stays in HBM until the drainer thread
(serving/drain.py) takes it.  The producer thread never calls
`jax.device_get` — tests guard this with a monkeypatched fetch.

Ring layout: a stacked pytree — EVERY leaf of the query's emission
gains a leading [S] slot axis, preallocated once (so the ring's bytes
are static state: MEM001/state-bytes/audit account for them).  A flat
emission stacks its `(n_valid, n_dropped, ts, kind, valid, cols)`; a
partitioned pattern's `BandedEmission` (a registered pytree) stacks,
per tier, the header `(n_valid, n_dropped, ranks_used)` and BOTH u32
buffers of every band of `band_edges(R)` — the bands above
`ranks_used` too: which bands a send used is known only on the device,
so an append writes and a read copies all of them, and only then does
the drainer fetch the headers and `_EmissionRows` the bands below
`ranks_used`.  Appends and reads are slot-indexed jitted programs
shared across slots (the index rides as a traced scalar: ONE compile
per emission signature, not one per slot).  For mesh-sharded queries
the ring leaves preserve the output's NamedSharding with a replicated
slot axis, so each shard hosts its own ring segment and the drain
fetches per-shard buffers independently.

Overflow follows the emission-cap grow-via-replan pattern
(`_grow_emission_cap`): a full ring doubles in one jump, gated by
admission's state ceilings (`admit_growth`); a denied growth degrades
to bounded blocking backpressure on the producer — never a silent
drop.  A change of the emission's signature — emission-cap growth
replans the step; a pattern send of another `[Kb, E]` bucket, or laid
out as other tiers, has other band shapes — seals the current ring
generation and opens a fresh one; sealed generations drain FIFO before
newer entries, so delivery order per query is exactly send order.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from ..observability import phases as _phases
from ..observability import stateobs as _stateobs

jnp = jax.numpy
log = logging.getLogger("siddhi_tpu")

# ring capacity ceiling mirrors the emission-cap growth budget: past
# this the producer blocks (bounded-lag watermark) instead of growing
RING_CAP_MAX = 1 << 10


def _aval_key(out) -> Tuple:
    """Hashable (shape, dtype) signature of an output pytree — the ring
    generation key: entries with one signature share one buffer + one
    compiled append/read pair."""
    return tuple((tuple(x.shape), str(x.dtype))
                 for x in jax.tree_util.tree_leaves(out))


def _alloc_like(x, slots: int):
    """([S, ...] zeros for one output leaf, placement fell back?).
    Sharded leaves keep their NamedSharding with a replicated slot axis:
    each mesh device holds its own segment of every ring slot (per-shard
    rings — the drain transfers each shard's buffer independently).  A
    refused sharded placement leaves the leaf on the default device —
    the whole ring on chip 0 — which still delivers but is not the
    layout asked for, so the caller counts it and it is logged at ERROR
    (`facts()["placement_fallbacks"]`; chip_smoke.py requires zero)."""
    z = jnp.zeros((slots,) + tuple(x.shape), x.dtype)
    sh = getattr(x, "sharding", None)
    spec = getattr(sh, "spec", None)
    mesh = getattr(sh, "mesh", None)
    if spec is None or mesh is None or \
            all(p is None for p in tuple(spec)):
        return z, False
    from jax.sharding import NamedSharding, PartitionSpec
    try:
        return jax.device_put(
            z, NamedSharding(mesh, PartitionSpec(None, *tuple(spec)))), False
    except Exception:  # noqa: BLE001 — delivery continues, unsharded
        log.exception(
            "emission ring leaf %s%s could not be placed with spec %s; "
            "left on the default device", x.dtype, tuple(z.shape), spec)
        return z, True


class _Generation:
    """One ring buffer: a stacked [S, ...] pytree plus FIFO head/tail.
    Appends go to the NEWEST generation only; sealed (older) generations
    drain to empty and are dropped, so a signature change never reorders
    delivery."""

    __slots__ = ("qr", "state", "slots", "head", "tail", "count", "key",
                 "placement_fallbacks", "_set", "_read")

    def __init__(self, out, slots: int, qr):
        from ..core.steputil import jit_step
        owner = qr.name
        self.qr = qr
        self.slots = slots
        self.head = 0          # next write slot
        self.tail = 0          # next read slot
        self.count = 0         # occupied slots
        self.key = _aval_key(out)
        leaves, treedef = jax.tree.flatten(out)
        placed = [_alloc_like(x, slots) for x in leaves]
        self.state = jax.tree.unflatten(treedef, [z for z, _ in placed])
        self.placement_fallbacks = sum(fell for _, fell in placed)

        def _set(state, o, i):
            return jax.tree.map(
                lambda b, x: jax.lax.dynamic_update_index_in_dim(
                    b, x, i, 0), state, o)

        def _read(state, i):
            return jax.tree.map(
                lambda b: jax.lax.dynamic_index_in_dim(
                    b, i, 0, keepdims=False), state)

        # slot index rides as a traced scalar: one compile per output
        # signature.  The buffer is donated — XLA updates the ring in
        # place instead of copying S slots per append.
        self._set = jit_step(_set, owner=f"serve:{owner}",
                             role="ring_append", donate_argnums=(0,))
        self._read = jit_step(_read, owner=f"serve:{owner}:read",
                              role="ring_read")

    def append(self, out, occupancy: int) -> int:
        """Dispatch the slot write; `occupancy`: the ring's entries once
        this one is in (the span says it)."""
        slot = self.head
        with _phases.phase(self.qr.app.stats, self.qr.name, "dispatch",
                           step="ring_append", occupancy=occupancy):
            self.state = self._set(self.state, out, slot)
        self.head = (slot + 1) % self.slots
        self.count += 1
        return slot

    def read_tail(self):
        """Dispatch the device read of the oldest slot (lazy arrays, no
        fetch) and free it.  Device execution order guarantees the read
        completes before any later append overwrites the slot."""
        with _phases.phase(self.qr.app.stats, self.qr.name, "dispatch",
                           step="ring_read"):
            out = self._read(self.state, self.tail)
        self.tail = (self.tail + 1) % self.slots
        self.count -= 1
        return out

    def nbytes(self) -> int:
        from ..observability.memory import tree_nbytes
        try:
            return tree_nbytes(self.state)
        except Exception:  # noqa: BLE001 — metrics must not throw
            return 0


class EmissionRing:
    """Per-runtime device emission ring.

    `append` is the producer edge (runs under the query lock, zero
    fetches); `take` is the drainer edge (dispatches slot reads, the
    blocking fetch happens downstream in serving/drain.py).  All
    bookkeeping is guarded by the ring's own condition lock so the
    drainer never needs the query lock — a full-ring producer blocking
    for space cannot deadlock against the thread that frees it.
    """

    def __init__(self, qr, capacity: int = 8,
                 on_highwater=None):
        self.qr = qr
        self.capacity = max(1, int(capacity))
        self._cond = threading.Condition()
        self._gens: List[_Generation] = []
        # (generation, now, ingest_ns, trace_token, append_ns) in send
        # order, across generations: the token is the dispatch thread's
        # handoff (observability/phases.handoff: the send's batch number
        # and its DETAIL trace) so the drainer's delivery spans carry the
        # one and join the other; append_ns
        # stamps ring entry for the `ring_wait` phase (take - append)
        self._meta: "list" = []
        self._on_highwater = on_highwater
        self.appends_total = 0
        self.grows_total = 0
        self.generation = 0
        self.placement_fallbacks = 0

    def _open_generation(self, out, slots: int) -> "_Generation":
        gen = _Generation(out, slots, self.qr)
        self._gens.append(gen)
        self.generation += 1
        self.placement_fallbacks += gen.placement_fallbacks
        return gen

    # -- producer edge (query lock held; never fetches) ---------------------
    def append(self, out, now: int, ingest_ns=None, trace=None) -> None:
        append_ns = time.perf_counter_ns()
        with self._cond:
            gen = self._gens[-1] if self._gens else None
            if gen is None or gen.key != _aval_key(out):
                # emission signature changed (an emission-cap replan,
                # a pattern send of another [Kb, E] bucket or tiering):
                # seal the old generation — it keeps draining FIFO —
                # and open a fresh buffer at the configured capacity
                gen = self._open_generation(out, self.capacity)
            if gen.count >= gen.slots:
                gen = self._make_room(gen, out)
            occ = len(self._meta) + 1
            gen.append(out, occ)
            self._meta.append((gen, now, ingest_ns, trace, append_ns))
            self.appends_total += 1
            kick = occ >= self._high_water()
        if _stateobs.obs_enabled(self.qr.app):
            # serve-ring depth high-water for the sizing ledger (host
            # counter read — the producer edge stays fetch-free)
            self.qr.app.stats.stateobs.observe(
                self.qr.name, "serve_ring", occ, self.capacity,
                growable=self.capacity < RING_CAP_MAX,
                config_key="serving.ring.capacity")
        if kick and self._on_highwater is not None:
            # bounded-lag watermark: occupancy crossed high-water, wake
            # the drainer NOW instead of waiting out its interval
            self._on_highwater()

    def _high_water(self) -> int:
        return max(1, (self.capacity * 3) // 4)

    def _make_room(self, gen: "_Generation", out) -> "_Generation":
        """Full ring: grow 2x (admission-gated, the emission-cap
        grow-via-replan pattern) or block as bounded backpressure until
        the drainer frees a slot.  Called with the cond lock held."""
        new_cap = min(self.capacity * 2, RING_CAP_MAX)
        adm = self.qr.app.admission
        grown = False
        if new_cap > self.capacity and (
                adm is None or adm.admit_growth(
                    self.qr.name, (new_cap - self.capacity) *
                    max(1, gen.nbytes() // max(1, gen.slots)))):
            log.warning(
                "%s: emission ring full at %d slots; growing to %d "
                "(serving.ring.capacity pre-sizes and silences this)",
                self.qr.name, self.capacity, new_cap)
            self.capacity = new_cap
            stats = self.qr.app.stats
            if stats.enabled:
                stats.counter_inc(f"{self.qr.name}.ring_grows")
            self.grows_total += 1
            gen = self._open_generation(out, new_cap)
            grown = True
        if grown:
            return gen
        # growth denied (state ceiling) or at RING_CAP_MAX: block until
        # the drainer frees a slot — backpressure, never a silent drop
        if self._on_highwater is not None:
            self._on_highwater()
        waited = 0.0
        while gen.count >= gen.slots:
            if not self._cond.wait(timeout=0.05):
                waited += 0.05
                if waited >= 30.0:
                    raise RuntimeError(
                        f"{self.qr.name}: emission ring full for 30s "
                        f"with no drain progress (drainer dead?)")
                if self._on_highwater is not None:
                    self._on_highwater()
        return gen

    # -- drainer edge --------------------------------------------------------
    def take(self, max_n: Optional[int] = None) -> List[Tuple]:
        """Pop up to `max_n` pending entries in send order, dispatching
        each slot's device read (lazy arrays — the caller does ONE
        batched blocking fetch for everything it took).  Each item is
        (qr, out, now, ingest_ns, trace_token, ring_wait_ns)."""
        out: List[Tuple] = []
        with self._cond:
            take_ns = time.perf_counter_ns()  # under the lock: after appends
            n = len(self._meta) if max_n is None else \
                min(max_n, len(self._meta))
            for _ in range(n):
                gen, now, ingest_ns, trace, append_ns = self._meta.pop(0)
                with _phases.adopt(trace):   # the read's span: its send's
                    slot = gen.read_tail()
                out.append((self.qr, slot, now, ingest_ns, trace,
                            take_ns - append_ns))
            # drop fully-drained sealed generations (their buffers free)
            while len(self._gens) > 1 and self._gens[0].count == 0:
                self._gens.pop(0)
            if out:
                self._cond.notify_all()
        return out

    # -- introspection (host-side reads only) --------------------------------
    def occupancy(self) -> int:
        return len(self._meta)

    def nbytes(self) -> int:
        with self._cond:
            return sum(g.nbytes() for g in self._gens)

    def state_leaves(self):
        """Current generations' device buffers (metadata walks only —
        observability/memory.py counts the ring under `serve_ring`)."""
        return [g.state for g in self._gens]

    def programs(self) -> List[Tuple[str, Any]]:
        """(role, jitted fn) of every live generation's append/read pair
        — the ring's share of `runtime.compiled_steps`."""
        with self._cond:
            gens = list(self._gens)
        out: List[Tuple[str, Any]] = []
        for gi, gen in enumerate(gens):
            out += [(f"ring_append[{gi}]", gen._set),
                    (f"ring_read[{gi}]", gen._read)]
        return out

    def facts(self) -> Dict[str, Any]:
        """EXPLAIN / healthz node for this ring."""
        return {
            "capacity": self.capacity,
            "occupancy": self.occupancy(),
            "high_water": self._high_water(),
            "appends_total": self.appends_total,
            "overflow_grows": self.grows_total,
            "generation": self.generation,
            "placement_fallbacks": self.placement_fallbacks,
            "nbytes": self.nbytes(),
        }

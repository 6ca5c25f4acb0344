"""Async ring drainer: the only place serving emissions cross D2H.

One thread per app pulls every registered ring's pending segments and
blocks on the transfers HERE — `jax.device_get` / `block_until_ready`
never run in the send path (the producer merely dispatched a slot
write).  Delivery re-enters `_emit_output_sync`, so batch callbacks,
table ops, rate limiting, sink publication, breaker/error-store
routing, and the `<q>:e2e` histogram behave exactly as a blocking
fetch would — the serving loop changes WHEN the fetch happens, never
what delivery does.

Cadence: the thread wakes every `serving.drain.interval.ms` (bounded
lag for a quiet ring) and immediately on a high-water kick from any
ring (bounded occupancy under load).  Each cycle takes every ring's
entries (one `siddhi:dispatch step=ring_read` an entry) and pays ONE
batched `device_get` for what `runtime._header_of` names of each — the
same amortization as `_EmissionDrainer._run`: of a partitioned
pattern's `BandedEmission` the per-tier headers `(n_valid, n_dropped,
ranks_used)`, of a flat headed emission (joins, @fuse stacks, the
block step) `(n_valid, n_dropped)`, and a plain query's window-
capacity-bounded output whole.  That fetch is where the drainer waits
for the device step; its span (`siddhi:fetch what=ring`) says how many
sends it served (`items`) and how long they had sat in the ring
(`ring_wait_us`, append -> take, summed).  A headed emission's rows
stay on the device until the subscriber reads them
(`runtime._EmissionRows`: of a banded one only the bands below
`ranks_used`).

`drain_all()` is the synchronous edge for flush/quiesce/shutdown: it
runs a cycle on the CALLER'S thread under the same delivery lock the
thread uses, so quiesce can drain rings to empty without racing the
drainer and snapshot never sees a non-empty ring.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import List


log = logging.getLogger("siddhi_tpu")

# a drainer that hasn't ticked for this many intervals while work is
# pending is considered stalled (healthz flips `degraded`, not `live`:
# producers fall back to backpressure, the app still processes)
STALL_INTERVALS = 10.0


class ServingDrainer:
    """Per-app serving drain thread (lazy-started on first ring)."""

    def __init__(self, app, interval_ms: float = 2.0):
        self.app = app
        self.interval_ms = float(interval_ms)
        self._rings: List = []
        self._cv = threading.Condition()
        # serializes delivery cycles: thread ticks and caller-side
        # drain_all never interleave, so per-ring delivery order is
        # exactly take order (which is exactly send order)
        self._deliver_lock = threading.Lock()
        self._thread = None
        self._started = False
        self._running = False
        self._kicked = False
        self.last_tick_ns = time.monotonic_ns()
        self.drains_total = 0
        self.drained_outputs_total = 0

    # -- registration --------------------------------------------------------
    def register(self, ring) -> None:
        with self._cv:
            if ring not in self._rings:
                self._rings.append(ring)
        self.start()

    def start(self) -> None:
        with self._cv:
            if self._started:
                return
            self._started = True
            self._running = True
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="siddhi-serve-drain")
            # see StreamJunction workers: internal threads bypass the
            # ingress gate so quiesce doesn't deadlock on its own drain
            self._thread._siddhi_internal = True
            self._thread.start()

    def kick(self) -> None:
        """High-water wakeup from a ring (bounded-lag watermark)."""
        with self._cv:
            self._kicked = True
            self._cv.notify_all()

    def stop(self) -> None:
        with self._cv:
            if not self._started:
                return
            self._running = False
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        self.drain_all()   # anything dispatched after the final tick

    # -- introspection -------------------------------------------------------
    def pending(self) -> int:
        """Ring entries accepted but not yet delivered (the serving
        analog of `_EmissionDrainer.pending`)."""
        return sum(r.occupancy() for r in list(self._rings))

    def depth(self) -> int:
        return self.pending()

    def alive(self) -> bool:
        t = self._thread
        return (not self._started) or (t is not None and t.is_alive())

    def stalled(self) -> bool:
        """Work pending but no tick within the stall budget — /healthz
        flips `degraded` on this (the app still processes; producers
        degrade to ring backpressure)."""
        if not self._started or self.pending() == 0:
            return False
        idle_ns = time.monotonic_ns() - self.last_tick_ns
        budget_ns = max(self.interval_ms, 1.0) * 1e6 * STALL_INTERVALS
        return idle_ns > budget_ns or not self.alive()

    # -- drain ---------------------------------------------------------------
    def drain_all(self) -> int:
        """Synchronous full drain on the caller's thread (flush /
        quiesce / shutdown).  Loops until every ring reads empty so
        snapshot state never includes an occupied ring."""
        total = 0
        for _ in range(64):
            n = self._cycle()
            total += n
            if n == 0 and self.pending() == 0:
                break
        return total

    def _cycle(self) -> int:
        with self._deliver_lock:
            items = []
            for ring in list(self._rings):
                items.extend(ring.take())
            if not items:
                return 0
            self._deliver(items)
            self.drains_total += 1
            self.drained_outputs_total += len(items)
            return len(items)

    def _deliver(self, items) -> None:
        import traceback
        from ..core.runtime import _emit_fetched, _header_of
        from ..observability import phases as _phases
        # phase accounting: each item's ring residency (append -> take,
        # stamped by ring.take) plus this cycle's batched fetch wall —
        # charged per item, exactly as each item's e2e sample counts it.
        # ONE blocking fetch for every entry taken this cycle: a headed
        # emission's header, a plain one whole; the span carries the
        # batch of the first send it serves, how many it serves and
        # their summed residency
        st = self.app.stats
        try:
            with _phases.adopt(items[0][4]):
                fetched = _phases.fetch(
                    st, tuple(it[0].name for it in items), "ring", [
                        _header_of(out) for _, out, _, _, _, _ in items],
                    items=len(items),
                    ring_wait_us=sum(it[5] for it in items) // 1000)
        except Exception:  # noqa: BLE001 — drainer must survive
            traceback.print_exc()
            fetched = [None] * len(items)
        per_q = {}
        loop_t0 = time.perf_counter_ns()
        for (qr, out, now, t_in, trace, wait_ns), fetch_h in \
                zip(items, fetched):
            # in-batch wait: deliveries run serially, so a later item's
            # e2e contains every predecessor's demux/sink wall — that
            # residency is drainer wait, charged here so the phase sum
            # keeps tracking e2e (attribution rule in phases.py)
            _phases.waited(st, qr.name, loop_t0, wait_ns)
            try:
                if fetch_h is None:
                    continue
                with _phases.adopt(trace):
                    _emit_fetched(qr, out, fetch_h, now, t_in)
                per_q[qr] = per_q.get(qr, 0) + 1
            except Exception as exc:  # noqa: BLE001 — drainer survives
                # same fault routing as _EmissionDrainer._run: overflow
                # and callback failures reach the exception listener
                log.error("serving drain error in %s: %s",
                          qr.name, exc)
                listener = qr.app.exception_listener
                if listener is not None:
                    try:
                        listener(exc)
                    except Exception:  # noqa: BLE001
                        traceback.print_exc()
                else:
                    traceback.print_exc()
        for qr, n in per_q.items():
            st = qr.app.stats
            if st.enabled:
                st.counter_inc(f"{qr.name}.ring_drains", n)

    def _run(self) -> None:
        while True:
            with self._cv:
                if not self._running:
                    return
                if not self._kicked:
                    self._cv.wait(timeout=max(self.interval_ms, 0.1) / 1e3)
                self._kicked = False
                if not self._running:
                    return
            self.last_tick_ns = time.monotonic_ns()
            try:
                self._cycle()
            except Exception:  # noqa: BLE001 — drainer must survive
                import traceback
                traceback.print_exc()

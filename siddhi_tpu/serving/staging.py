"""Double-buffered H2D staging: batch N+1 uploads while N computes.

In the blocking path a batch's host->device transfer starts inside
`process_staged` (StagedBatch.to_device), AFTER the junction has
waited on the query lock and resolved group slots — the upload
serializes behind host staging work, and its latency lands in the send
path.

The stager moves the upload to the junction's ACCEPT edge: the moment
a staged batch enters dispatch (sync path) or the @async ingress queue
(async path), its columns are cast host-side and `jax.device_put`
starts — non-blocking, so by the time `to_device` runs the transfer
has overlapped slot resolution, lock wait, and (because dispatch is
asynchronous) the previous batch's device compute.  `to_device` then
adopts the prestaged arrays instead of re-transferring.  The junction
stages only where a subscriber calls `to_device` on the batch it was
given (`adopts_staged`; `StreamJunction._serve_stage`): the pattern
runtimes upload their own columns — grouped by the host, stacked, a
shard's share — and an upload for them would be a second one, for
nothing.

Ownership is donation-discipline: the stager's device buffers are
handed to exactly ONE step dispatch and never touched host-side again
(mirrors `jit_step(donate_argnums=(0,))` on state) — the pipeline
keeps at most `depth` uploads in flight, so a slow device backpressures
staging instead of accumulating transfers.
"""
from __future__ import annotations

import collections
import logging
import threading

import jax
import numpy as np

jnp = jax.numpy
log = logging.getLogger("siddhi_tpu")


class DoubleBufferedStager:
    """Per-app H2D staging pipeline (default depth 2: the classic
    double buffer — one upload in flight while one batch computes)."""

    def __init__(self, depth: int = 2):
        self.depth = max(1, int(depth))
        self._lock = threading.Lock()
        # refs to in-flight uploads; bounded so a stalled device holds
        # at most `depth` staged transfers alive
        self._inflight = collections.deque(maxlen=self.depth)
        self.staged_total = 0
        self.adopted_total = 0
        self.fallback_total = 0

    def stage(self, staged, schema) -> None:
        """Start the non-blocking upload of one StagedBatch's arrays and
        attach them for `to_device` adoption.  Idempotent per batch; a
        failure leaves the batch unstaged (to_device transfers as
        before) — staging is an overlap optimization, never a
        correctness dependency — but the lost overlap is counted
        (`facts()["fallback_total"]`) and logged, so a measurement can
        refuse to run on the downgraded path."""
        if staged.dev is not None:
            return
        try:
            from ..core.event import EventBatch
            cols = tuple(
                jnp.asarray(np.asarray(c).astype(d, copy=False))
                for c, d in zip(staged.cols, schema.dtypes))
            batch = EventBatch(jnp.asarray(staged.ts),
                               jnp.asarray(staged.kind),
                               jnp.asarray(staged.valid), cols)
        except Exception:  # noqa: BLE001 — fall back to in-path transfer
            with self._lock:
                self.fallback_total += 1
            log.exception("accept-edge H2D staging failed; the batch "
                          "transfers in the dispatch path instead")
            return
        # the stager rides along so `to_device` can count the adoption
        staged.dev = (schema, batch, self)
        with self._lock:
            self._inflight.append(batch)
            self.staged_total += 1

    def adopted(self) -> None:
        """A step took the prestaged arrays instead of re-uploading
        (StagedBatch.to_device): staged_total - adopted_total uploads
        were made twice, or for nothing."""
        with self._lock:
            self.adopted_total += 1

    def facts(self) -> dict:
        with self._lock:
            return {
                "depth": self.depth,
                "in_flight": len(self._inflight),
                "staged_total": self.staged_total,
                "adopted_total": self.adopted_total,
                "fallback_total": self.fallback_total,
            }

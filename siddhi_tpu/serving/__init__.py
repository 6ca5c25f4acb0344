"""Device-resident serving loop (ROADMAP open item 2).

Three pieces, one invariant — the SEND PATH NEVER FETCHES:

- ring.py      on-device emission rings: emissions append into a
               persistent device buffer (dispatch-only send path)
- drain.py     per-app async drainer: the only thread that blocks on
               D2H, feeding the unchanged delivery machinery
- staging.py   double-buffered H2D staging: batch N+1 uploads while
               batch N computes — for subscribers whose step takes
               the staged batch as it is; a junction of pattern
               runtimes (they upload columns of their own) stages
               nothing, so every batch goes up once

Enablement: `@serve` on a query / input stream / `@app:serve`
(core/plan_facts.serve_enabled), or app-wide via the config property
`serving.enabled: 'true'`.  Ring sizing and drain cadence read
`serving.ring.capacity` (slots, default plan_facts.SERVE_RING_SLOTS)
and `serving.drain.interval.ms` (default 2 ms); both are overridable
per query with @serve(ring.capacity=).
"""
from __future__ import annotations

from ..core.plan_facts import SERVE_RING_SLOTS
from .drain import ServingDrainer
from .ring import EmissionRing
from .staging import DoubleBufferedStager

__all__ = ["EmissionRing", "ServingDrainer", "DoubleBufferedStager",
           "serving_config", "ensure_ring", "ring_append",
           "SERVE_RING_SLOTS"]

_TRUE = ("true", "1", "yes", "on")
DEFAULT_DRAIN_INTERVAL_MS = 2.0


def serving_config(rt) -> dict:
    """App-level serving settings from the manager config (memoized on
    the runtime: config cannot change under a live manager)."""
    cfg = rt.__dict__.get("_serving_config")
    if cfg is not None:
        return cfg
    enabled = False
    capacity = SERVE_RING_SLOTS
    interval_ms = DEFAULT_DRAIN_INTERVAL_MS
    try:
        cm = getattr(rt, "config_manager", None)
        if cm is not None:
            v = cm.extract_property("serving.enabled")
            if v is not None:
                enabled = str(v).lower() in _TRUE
            v = cm.extract_property("serving.ring.capacity")
            if v:
                capacity = max(1, int(v))
            v = cm.extract_property("serving.drain.interval.ms")
            if v:
                interval_ms = max(0.0, float(v))
    except Exception:  # noqa: BLE001 — malformed config reads as default
        pass
    cfg = {"enabled": enabled, "ring_capacity": capacity,
           "drain_interval_ms": interval_ms}
    rt.__dict__["_serving_config"] = cfg
    return cfg


def ensure_ring(qr) -> EmissionRing:
    """The query's emission ring, created on first serving emission and
    registered with the app drainer (which lazy-starts its thread)."""
    ring = qr._serve_ring
    if ring is None:
        app = qr.app
        cfg = serving_config(app)
        # @serve(ring.capacity=) stashed at wiring time (runtime.py sets
        # `serve_ring_capacity` next to `serve_emit`); 0 = use config
        drainer = app._serve_drainer
        ring = qr._serve_ring = EmissionRing(
            qr, capacity=qr.serve_ring_capacity or cfg["ring_capacity"],
            on_highwater=drainer.kick)
        drainer.register(ring)
    return ring


def ring_append(qr, out, now: int, ingest_ns=None, trace=None) -> None:
    """Producer edge of the serving loop: dispatch the ring append and
    return — zero host<->device synchronization (core/runtime.py
    `_emit_output` routes here for serve-enabled runtimes).  `trace` is
    the dispatch thread's handoff token (phases.handoff): it rides the
    ring so the drainer's delivery spans carry the send's batch number
    and join its DETAIL trace."""
    ensure_ring(qr).append(out, now, ingest_ns, trace)

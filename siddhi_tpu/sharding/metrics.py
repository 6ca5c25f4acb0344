"""Shard-aware observability: per-shard state bytes, routing balance,
and collective-op introspection.

Scrape-path invariant (same as observability/exposition.py): everything
here reads host-side metadata only — `leaf.sharding.shard_shape` is
layout arithmetic, never a device fetch — so /metrics and /healthz stay
device-silent on sharded apps too.  The one exception,
`step_collectives`, compiles a step's HLO to list its collectives; it is
called only from EXPLAIN's deep mode (an on-demand diagnostic, never the
scrape path).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..observability.memory import leaf_nbytes
from .router import router_for, shard_count

# collective-op HLO tokens asserted by dryrun_multichip and reported by
# EXPLAIN's sharding node — one list, two consumers
COLLECTIVE_TOKENS = ("all-gather", "all-reduce", "collective-permute",
                     "all-to-all", "reduce-scatter")


def _leaf_shard_bytes(leaf) -> int:
    """Bytes of one leaf RESIDENT PER DEVICE: sharded leaves report their
    shard slice, replicated leaves (and host numpy) their full size."""
    nb = leaf_nbytes(leaf)
    sh = getattr(leaf, "sharding", None)
    shape = getattr(leaf, "shape", None)
    if sh is None or shape is None:
        return nb
    try:
        per = 1
        for d in sh.shard_shape(tuple(shape)):
            per *= int(d)
        return per * int(np.dtype(leaf.dtype).itemsize)
    except Exception:  # noqa: BLE001 — metrics must not throw
        return nb


def tree_shard_bytes(tree) -> int:
    try:
        import jax
        return sum(_leaf_shard_bytes(leaf)
                   for leaf in jax.tree_util.tree_leaves(tree))
    except Exception:  # noqa: BLE001 — metrics must not throw
        return 0


def shard_state_bytes(rt) -> Dict[int, int]:
    """{shard index: resident state bytes} for one app runtime.  The
    layout is uniform by construction (PartitionSpec splits evenly), so
    every shard reports the same residency — the value operators watch is
    that it stays ~1/n of the unsharded total as the mesh grows."""
    n = shard_count(rt)
    if n < 2:
        return {}
    per = 0
    for qr in getattr(rt, "query_runtimes", {}).values():
        per += tree_shard_bytes(getattr(qr, "state", None))
    for nw in getattr(rt, "named_windows", {}).values():
        per += tree_shard_bytes(getattr(nw, "state", None))
    for agg in getattr(rt, "aggregations", {}).values():
        for store in getattr(agg, "_dstores", {}).values():
            per += tree_shard_bytes(getattr(store, "slab", None))
    return {d: per for d in range(n)}


def shard_events(rt) -> Dict[int, int]:
    """{shard index: events routed} summed over the app's sharded
    queries, from the statistics registry (host counters)."""
    n = shard_count(rt)
    out = {d: 0 for d in range(n)} if n >= 2 else {}
    snap = rt.stats.exposition_snapshot() if rt.stats.enabled else {}
    for _q, per_shard in snap.get("shard_events", {}).items():
        for d, c in enumerate(per_shard):
            if d in out:
                out[d] += int(c)
    return out


def shard_report(rt) -> Optional[Dict[str, Any]]:
    """/healthz `shards` section for one app: per-shard residency +
    routed-event balance with a skew verdict (max/mean of routed events;
    a shard at 0 while others flow reads `idle` — the PART002 lint
    hazard observed live)."""
    n = shard_count(rt)
    if n < 2:
        return None
    ev = shard_events(rt)
    by = shard_state_bytes(rt)
    total = sum(ev.values())
    mean = total / n if n else 0.0
    shards = {}
    for d in range(n):
        e = ev.get(d, 0)
        if total and e == 0:
            status = "idle"
        elif mean and e > 2.0 * mean:
            status = "hot"
        else:
            status = "ok"
        shards[str(d)] = {"events_total": e,
                          "state_bytes": by.get(d, 0),
                          "status": status}
    skew = (max(ev.values()) / mean) if total and mean else None
    report: Dict[str, Any] = {
        "devices": n,
        "layout": "round_robin(slot % n_shards)",
        "balanced": all(s["status"] == "ok" for s in shards.values()),
        "event_skew_max_over_mean":
            round(skew, 3) if skew is not None else None,
        "per_shard": shards,
    }
    # serving emission rings (serving/ring.py): ring slots carry the
    # producing step's sharding with a replicated slot axis, so each
    # device hosts its own segment of every buffered output — report the
    # per-shard resident bytes next to occupancy so operators can see
    # drain lag per device
    rings = {}
    for q, ring in (rt.serve_rings().items()
                    if hasattr(rt, "serve_rings") else ()):
        try:
            rings[q] = {
                "occupancy": ring.occupancy(),
                "capacity": ring.capacity,
                "shard_bytes": sum(tree_shard_bytes(s)
                                   for s in ring.state_leaves()),
            }
        except Exception:  # noqa: BLE001 — metrics must not throw
            continue
    if rings:
        report["serve_rings"] = rings
    return report


def hlo_collectives(compiled) -> List[str]:
    """Sorted collective-op kinds present in a compiled step's HLO text.
    THE one token scan — step_collectives (EXPLAIN) and step_cost's
    collectives mode (the plan auditor) both report it, so a new
    collective appearing in a plan is the same string everywhere."""
    try:
        hlo = compiled.as_text()
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return []
    return sorted({tok for tok in COLLECTIVE_TOKENS if tok in hlo})


def step_collectives(fn, specs=None) -> Optional[List[str]]:
    """Collective ops in a jitted step's compiled HLO at its last-traced
    signature — or, when it never traced, at synthesized `specs`
    (analysis/signatures.py).  None = no signature available / backend
    refused.  Compiles — EXPLAIN deep mode only, memoized upstream."""
    holder = getattr(fn, "_siddhi_argspec", None)
    traced = holder.get("argspecs") if holder else None
    if traced is not None:
        specs = traced
    if specs is None:
        return None
    try:
        from ..observability.recompile import RECOMPILES
        with RECOMPILES.suppress():
            return hlo_collectives(fn.lower(*specs).compile())
    except Exception:  # noqa: BLE001 — diagnostics must not throw
        return None


def explain_node(qr, kind: str, deep: bool = False) -> Optional[Dict]:
    """EXPLAIN `sharding` section for one query runtime: the shard
    layout its state lives in, per-shard residency, and (deep) the
    collectives its compiled step carries."""
    from .snapshot import query_layout
    p = qr.planned
    mesh = p.mesh or p.keyed_mesh
    n = shard_count(mesh) if mesh is not None else 1
    if n < 2:
        # GSPMD-placed joins have no key router but ARE sharded
        if kind != "join" or shard_count(qr.app.mesh) < 2:
            return None
        n = shard_count(qr.app.mesh)
    node: Dict[str, Any] = {
        "devices": n,
        "per_shard_state_bytes": tree_shard_bytes(qr.state),
    }
    router = router_for(qr)
    if router is not None:
        node["layout"] = "round_robin(slot % n_shards)"
        node["key_capacity"] = router.capacity
        node["keys_per_shard"] = router.block
    layout = query_layout(qr)
    if layout is not None:
        node["snapshot_layout"] = layout
    if deep:
        colls: Dict[str, List[str]] = {}
        from ..observability.explain import _steps_of
        for role, fn in _steps_of(qr, kind):
            c = step_collectives(fn)
            if c:
                colls[role] = c
        node["collectives"] = colls
    return node

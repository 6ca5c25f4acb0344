"""State-memory accounting: nbytes per device-state component.

Reference (what): the reference's SiddhiMemoryUsageMetric walks the query
object graph and reports retained heap per query.  TPU design (how): our
state is device pytrees — window buffers, pattern NFA slot blocks, key
slots, tables, fused stack buffers — so the accounting walks each
runtime's pytrees and sums nbytes PER COMPONENT, computed purely from
shape × dtype metadata.  This is the scrape path (`siddhi_state_bytes`
in /metrics, plus the explain report), so the invariant from
exposition.py applies verbatim: **no `device_get`, no array
materialization** — a Prometheus poll must never pay a device sync or a
D2H transfer.  `leaf_nbytes` therefore reads only `.shape`/`.dtype`
(host-side metadata on both numpy and jax arrays) and never the buffer.

Component naming follows the recompile-owner convention so the two
metric families join naturally in dashboards: queries by name with a
sub-component label, shared objects as `table:<id>` / `window:<id>` /
`agg:<id>`.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def leaf_nbytes(x) -> int:
    """nbytes of one pytree leaf from metadata only (no device access)."""
    try:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            # host scalar / python object leaf
            return int(np.asarray(x).nbytes) if np.isscalar(x) else 0
        n = 1
        for d in shape:
            n *= int(d)
        return n * int(np.dtype(dtype).itemsize)
    except Exception:  # noqa: BLE001 — metrics must not throw
        return 0


def tree_nbytes(tree) -> int:
    """Total nbytes of a pytree, metadata-only."""
    try:
        import jax
        return sum(leaf_nbytes(leaf) for leaf in
                   jax.tree_util.tree_leaves(tree))
    except Exception:  # noqa: BLE001 — metrics must not throw
        return 0


def _kind_components(qr) -> Dict[str, int]:
    """Split a query runtime's state tuple into named components.  The
    state layouts are (window, selector) for planned single queries,
    ((b32, lo64, hi64, scalars), selector) for patterns (the NFA's i64
    leaves as two u32 planes on the device: the same bytes as the one
    int64 `b64` blob a host snapshot holds), and the join's
    (left window, right window, selector...) tuple; anything that doesn't
    match falls back to positional names so the total always adds up."""
    mg = qr._merged
    if mg is not None:
        # merged member (optimizer/mqo.py): report only this query's
        # EXCLUSIVE bytes — the shared window buffer is accounted ONCE,
        # under the group owner (component_bytes adds `merged:<group>`),
        # never per member (the MEM001 double-count fix)
        return mg.member_components(qr)
    state = qr.state
    p = qr.planned
    names = None
    if qr._kind == "pattern":
        names = ("pattern_slots", "selector")
    elif qr._kind == "join":
        names = ("window_left", "window_right", "selector")
    elif isinstance(state, tuple) and len(state) == 2:
        names = ("window", "selector")
    out: Dict[str, int] = {}
    if isinstance(state, tuple) and names is not None and \
            len(state) <= len(names) + 1:
        for i, part in enumerate(state):
            label = names[i] if i < len(names) else f"state[{i}]"
            out[label] = tree_nbytes(part)
    else:
        out["state"] = tree_nbytes(state)
    # @fuse stack buffers hold K-1 staged host batches awaiting dispatch
    fb = qr._fuse
    if fb is not None and fb.items:
        total = 0
        for args in fb.items:
            for a in args:
                staged = a if hasattr(a, "cols") else None
                if staged is not None:
                    total += leaf_nbytes(staged.ts) + \
                        leaf_nbytes(staged.kind) + leaf_nbytes(staged.valid)
                    total += sum(leaf_nbytes(c) for c in staged.cols)
        if total:
            out["fuse_stack"] = total
    # serving emission ring (serving/ring.py): device-resident output
    # slots awaiting the async drainer — metadata-only walk of the
    # ring's generation buffers
    ring = qr._serve_ring
    if ring is not None:
        try:
            total = sum(tree_nbytes(s) for s in ring.state_leaves())
        except Exception:  # noqa: BLE001 — metrics must not throw
            total = 0
        if total:
            out["serve_ring"] = total
    return out


def query_component_bytes(qr) -> Dict[str, int]:
    """{component: nbytes} for one query runtime (metadata-only walk)."""
    try:
        return _kind_components(qr)
    except Exception:  # noqa: BLE001 — metrics must not throw
        return {}


def component_bytes(rt) -> Dict[str, Dict[str, int]]:
    """{owner: {component: nbytes}} across an app: every query runtime
    plus shared tables, named windows, and aggregations."""
    out: Dict[str, Dict[str, int]] = {}
    for name, qr in list(getattr(rt, "query_runtimes", {}).items()):
        comps = query_component_bytes(qr)
        if comps:
            out[name] = comps
    for gid, mg in list(getattr(rt, "merged_groups", {}).items()):
        try:
            comps = mg.shared_components()
        except Exception:  # noqa: BLE001 — metrics must not throw
            comps = {}
        if comps:
            out[f"merged:{gid}"] = comps
    for tid, t in list(getattr(rt, "tables", {}).items()):
        n = sum(leaf_nbytes(c) for c in getattr(t, "cols", ())) + \
            leaf_nbytes(getattr(t, "ts", None)) + \
            leaf_nbytes(getattr(t, "valid", None))
        if n:
            out[f"table:{tid}"] = {"rows": n}
    for wid, nw in list(getattr(rt, "named_windows", {}).items()):
        n = tree_nbytes(getattr(nw, "state", None))
        if n:
            out[f"window:{wid}"] = {"buffer": n}
    for aid, agg in list(getattr(rt, "aggregations", {}).items()):
        # one device slab per declared duration (_DurationStore.slab)
        comps = {}
        for dur, store in getattr(agg, "_dstores", {}).items():
            n = tree_nbytes(getattr(store, "slab", None))
            if n:
                comps[dur] = n
        if comps:
            out[f"agg:{aid}"] = comps
    return out


def total_bytes(rt) -> int:
    return sum(n for comps in component_bytes(rt).values()
               for n in comps.values())

"""What the runtime's `siddhi:route_keys` spans SAY of a bucket join's
windows, from a run's own profiler trace.

Since PR 57 the join's retention mirror (siddhi_tpu/core/join.py
`JoinKeyTracker`) knows, before a send is dispatched, the rows each side's
window holds after it, the rows a full `window.time` side has lost, and how
deep a probe walks a ring side's same-key chain; `_join_key_probe`'s span
carries them beside `lane_k` / `lane_need`: `window_rows_l`, `window_rows_r`,
`window_dropped`, `probe_depth`.  Read here over the spans that start inside
the slice `trace_reduce.reduce_trace` takes.  A program whose spans lack the
stats (the parent of the PR that added them) gives None, and every reader
built on this returns None.
"""
from __future__ import annotations

from . import trace_reduce as tr

STATS = ("window_rows_l", "window_rows_r", "window_dropped", "probe_depth")


def read_windows(path: str) -> dict | None:
    """Over the slice's `siddhi:route_keys` spans that carry the stats:
    `rows_resident` (both windows' rows after the slice's LAST send, and the
    least and most over its sends), `window_dropped` (the counter's last
    reading: rows lost since the app started), `probe_depth` (the deepest
    walk planned)."""
    _devices, spans = tr.read_planes(path)
    found = tr.slice_of(spans)
    if found is None:
        return None
    lo, hi, _sends = found
    seen = []
    for evs in spans.values():
        for name, s, _e, stats in evs:
            if name == "siddhi:route_keys" and lo <= s < hi \
                    and STATS[0] in stats:
                seen.append((s, {k: int(stats[k]) for k in STATS}))
    if not seen:
        return None
    seen.sort(key=lambda x: x[0])
    rows = [st["window_rows_l"] + st["window_rows_r"] for _, st in seen]
    last = seen[-1][1]
    return {"spans": len(seen), "rows_resident": rows[-1],
            "rows_resident_min": min(rows), "rows_resident_max": max(rows),
            "rows_l": last["window_rows_l"], "rows_r": last["window_rows_r"],
            "window_dropped": max(st["window_dropped"] for _, st in seen),
            "probe_depth": max(st["probe_depth"] for _, st in seen)}


def windows(run: dict) -> dict | None:
    """The run's window facts, computed once and kept on the run record."""
    if "join_windows" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = read_windows(tr.newest_xplane(run["trace_dir"]))
        run["join_windows"] = out
        if out is not None:
            print(f"join windows over the slice: {out}", flush=True)
    return run["join_windows"]

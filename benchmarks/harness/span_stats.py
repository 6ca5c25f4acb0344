"""Read what the runtime's `siddhi:route_keys` spans SAY about a send's
device layout, from a run's own profiler trace.

Since the pattern path lays a skewed send out as a few [Kb, E] tiers, its
`route_keys` span carries `tiers` (rectangles the send was split into),
`cells` (their cells, padding included), `max_e` (the hottest key's events)
and `ticks` (the sum of the rectangles' E: the scan ticks the send costs);
`siddhi:send` carries `events`.  Summed here over the spans that start
inside the slice `trace_reduce` / `program_spans` reduce.  A program whose
spans lack the stats (the parent of the PR that added them) gives None, and
every reader built on this returns None.
"""
from __future__ import annotations

from . import trace_reduce as tr

LAYOUT = ("tiers", "cells", "max_e", "ticks")


def read_layout(path: str) -> dict | None:
    """One pass over the host plane: the slice from the harness's `bench:*`
    spans (as `trace_reduce.reduce_trace` takes it), the stats from the
    runtime's `siddhi:send` / `siddhi:route_keys` spans that start in it."""
    import jax
    first_send, last_end, seen = None, 0.0, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = float(ev.start_ns)
                if ev.name.startswith(tr.SPAN_PREFIX):
                    last_end = max(last_end, s + float(ev.duration_ns))
                    if ev.name == tr.SEND_SPAN and \
                            (first_send is None or s < first_send):
                        first_send = s
                elif ev.name in ("siddhi:send", "siddhi:route_keys"):
                    seen.append((ev.name, s, dict(ev.stats)))
    if first_send is None:
        return None
    out = dict.fromkeys(LAYOUT, 0)
    out.update(spans=0, sends=0, events=0)
    for name, s, stats in seen:
        if not first_send <= s < last_end:
            continue
        if name == "siddhi:send":
            out["sends"] += 1
            out["events"] += int(stats.get("events", 0))
        elif "cells" in stats:
            out["spans"] += 1
            for key in LAYOUT:
                out[key] += int(stats[key])
    return out if out["spans"] and out["sends"] else None


def layout(run: dict) -> dict | None:
    """The run's layout sums, computed once and kept on the run record."""
    if "route_layout" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = read_layout(tr.newest_xplane(run["trace_dir"]))
        run["route_layout"] = out
        if out is not None:
            print(f"route_keys layout over the slice: {out}", flush=True)
    return run["route_layout"]

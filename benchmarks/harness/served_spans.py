"""Read what the runtime's spans SAY about a served (`@serve`) send, from a
run's own profiler trace.

Under `@serve` a send's emission is appended to a device ring on the
sender's thread and delivered by the drainer's: the drain cycle's one
`siddhi:fetch what=ring` span carries `items` (the sends it serves) and
`ring_wait_us` (their append -> take residency, summed), the ring append's
`siddhi:dispatch step=ring_append` carries `occupancy` (the ring's entries
once it is in), and every `siddhi:h2d` span carries the `bytes` it uploads.
Summed here over the spans that start inside the slice `trace_reduce` /
`program_spans` reduce (start of the first `bench:send_columns` span -> end
of the last `bench:*` span).  A program whose spans lack a stat (the parent
of the PR that added it) gives None for what is built on it.
"""
from __future__ import annotations

from . import trace_reduce as tr

WANTED = ("siddhi:send", "siddhi:h2d", "siddhi:fetch", "siddhi:dispatch")


def read_served(path: str) -> dict | None:
    """One pass over the host plane.  {sends, h2d_spans, h2d_bytes,
    ring_fetches, items, ring_wait_us, occupancy_max}: `h2d_bytes`, `items`
    / `ring_wait_us` and `occupancy_max` are None where no span in the
    slice carries the stat.  None where the slice holds no send."""
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
                elif ev.name in WANTED:
                    seen.append((ev.name, s, dict(ev.stats)))
    if first_send is None:
        return None
    out = {"sends": 0, "h2d_spans": 0, "h2d_bytes": None, "ring_fetches": 0,
           "items": None, "ring_wait_us": None, "occupancy_max": None}

    def add(key, value):
        out[key] = (out[key] or 0) + int(value)

    for name, s, stats in seen:
        if not first_send <= s < last_end:
            continue
        if name == "siddhi:send":
            out["sends"] += 1
        elif name == "siddhi:h2d":
            out["h2d_spans"] += 1
            if "bytes" in stats:
                add("h2d_bytes", stats["bytes"])
        elif name == "siddhi:fetch" and stats.get("what") == "ring":
            out["ring_fetches"] += 1
            if "items" in stats:
                add("items", stats["items"])
                add("ring_wait_us", stats["ring_wait_us"])
        elif name == "siddhi:dispatch" and "occupancy" in stats:
            out["occupancy_max"] = max(out["occupancy_max"] or 0,
                                       int(stats["occupancy"]))
    return out if out["sends"] else None


def served(run: dict) -> dict | None:
    """The run's served-path sums, computed once and kept on the run
    record; the first computation prints one line."""
    if "served_spans" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = read_served(tr.newest_xplane(run["trace_dir"]))
        run["served_spans"] = out
        if out is not None:
            print(f"served path over the slice: {out}", flush=True)
    return run["served_spans"]


def per_send(run: dict, key: str, scale: float = 1.0):
    """`key`'s sum over the slice per `siddhi:send` span in it; None where
    no span carries the stat."""
    out = served(run)
    if out is None or out[key] is None:
        return None
    return out[key] * scale / out["sends"]


def returned_stamps(run: dict):
    """[(stamp, delivery of the send's last row, s)] of the window's sends
    by the harness's clock, or None unless every timed send returned and
    was delivered (then `latency_ms` lines up with `stamps`)."""
    stamps, lat = run["stamps"], run["latency_ms"]
    if not stamps or len(lat) != len(stamps) or \
            any("returned" not in st for st in stamps):
        return None
    return [(st, st["due"] + ms / 1e3) for st, ms in zip(stamps, lat)]

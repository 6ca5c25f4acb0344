"""Reduce the runtime's own spans in a run's profiler trace.

The program opens a `jax.profiler.TraceAnnotation("siddhi:<name>", q=,
batch=, ...)` at every hot-path boundary (siddhi_tpu/observability/
phases.py): send, stage, route_keys, obs_feed, h2d, dispatch, fetch, demux,
sink, compile, timer.  With a profiler session live they are host spans on
the same timeline as the device's `XLA Ops` and the harness's `bench:*`
spans, so the one number `send_to_delivery_ms_per_send` splits into layers
without a statistics switch that would slow the send.

What is read: the `siddhi:*` events of the host plane of the run's own
`.xplane.pb`, over the SAME slice as `trace_reduce.reduce_trace` (start of
the first `bench:send_columns` span -> end of the last `bench:*` span) and
with the SAME skew on the device's stamps.  Per span name:

- `count`: spans that start inside the slice;
- `wall_s`: their total length, clipped to the slice;
- `self_s`: wall minus the `siddhi:*` spans nested in them on the same
  thread (the harness's `bench:subscriber` runs inside `siddhi:sink` and
  stays in it), summed over threads;
- `idle_s`: the device-idle time of the slice (complement of the union of
  the device-op intervals, averaged over the device planes) that falls
  under the span's self time — why the chip waited while the host was
  there.

A program without the spans (the parent of the PR that added them) gives
no `siddhi:send` in the slice: `reduce_spans` returns None and every reader
built on it returns None.
"""
from __future__ import annotations

import json

from . import trace_reduce as tr

PREFIX = "siddhi:"
SEND = PREFIX + "send"
# idle because the step was not yet submitted / because the host is still
# delivering the previous one
PRE_DISPATCH = ("stage", "route_keys", "obs_feed", "h2d", "dispatch")
POST_STEP = ("fetch", "demux", "sink")


def read_program_spans(path: str) -> dict:
    """{thread: [(name, start, end)]} of the `siddhi:*` events of the host
    plane; times in ns, one key per profiler line (thread)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    spans = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = float(ev.start_ns)
                    spans.setdefault((plane.name, i), []).append(
                        (ev.name, s, s + float(ev.duration_ns)))
    return spans


def reduce_intervals(spans: dict, busy_by_device: list, lo: float,
                     hi: float) -> dict | None:
    """The arithmetic, on plain intervals: `spans` {thread: [(name, s, e)]},
    `busy_by_device` one list of [s, e) device-op intervals per device
    plane (already skew-corrected), the slice [lo, hi).  None when no
    `siddhi:send` starts inside the slice."""
    names = sorted({n for evs in spans.values() for n, _, _ in evs})
    count = {n: 0 for n in names}
    wall = {n: 0.0 for n in names}
    self_s = {n: 0.0 for n in names}
    selfs = {n: [] for n in names}        # union over threads, for idle
    send_wall = []
    for evs in spans.values():
        inside = [x for x in evs if x[2] > lo and x[1] < hi]
        for n, s, e in inside:
            count[n] += lo <= s < hi
            wall[n] += min(e, hi) - max(s, lo)
            if n == SEND:
                send_wall.append([max(s, lo), min(e, hi)])
        for n, iv in tr.self_intervals(inside).items():
            iv = tr.clip(iv, lo, hi)
            self_s[n] += tr.total(iv)
            selfs[n] = tr.union(selfs[n] + iv)
    if not count.get(SEND):
        return None
    n_dev = max(1, len(busy_by_device))
    gaps_by_device = [
        tr.complement(tr.clip(tr.union(ops), lo, hi), lo, hi)
        for ops in busy_by_device] or [[[lo, hi]]]
    sends = tr.union(send_wall)

    def idle_under(merged) -> float:
        return sum(tr.overlap(merged, gs, ge)
                   for gaps in gaps_by_device for gs, ge in gaps) / n_dev

    return {
        "sends": count[SEND],
        "slice_s": (hi - lo) / 1e9,
        "idle_s": sum(tr.total(g) for g in gaps_by_device) / n_dev / 1e9,
        "idle_in_send_s": idle_under(sends) / 1e9,
        "spans": {
            n[len(PREFIX):]: {
                "count": int(count[n]), "wall_s": wall[n] / 1e9,
                "self_s": self_s[n] / 1e9,
                "idle_s": idle_under(selfs[n]) / 1e9}
            for n in names},
    }


def reduce_spans(path: str, skew_s: float) -> dict | None:
    """`reduce_intervals` over one trace file: the slice and the device
    planes as `trace_reduce.reduce_trace` takes them, device stamps shifted
    by the skew it found."""
    devices, bench = tr.read_planes(path)
    sends = [s for evs in bench.values() for n, s, _ in evs
             if n == tr.SEND_SPAN]
    if not sends:
        return None
    lo = min(sends)
    hi = max(e for evs in bench.values() for _, _, e in evs)
    skew = skew_s * 1e9
    busy = [[[s + skew, e + skew] for _, s, e in rec["ops"]]
            for rec in devices.values()]
    return reduce_intervals(read_program_spans(path), busy, lo, hi)


def program_spans(run: dict) -> dict | None:
    """The run's reduced program spans, computed once and kept on the run
    record; the first computation prints one line.  None without a trace,
    without a send in its slice, or without the spans."""
    if "program_spans" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = reduce_spans(tr.newest_xplane(run["trace_dir"]),
                               red.get("skew_s", 0.0))
        run["program_spans"] = out
        if out is not None:
            print(f"program spans: {json.dumps(out)}", flush=True)
    return run["program_spans"]


# -- what the readers in layer_metrics/ share ------------------------------------

def self_ms_per_send(run: dict, name: str):
    """Self time of `siddhi:<name>` per send in the slice, ms (0.0 when the
    slice has sends but the path never opens that span)."""
    red = program_spans(run)
    if red is None:
        return None
    return red["spans"].get(name, {"self_s": 0.0})["self_s"] * 1e3 \
        / red["sends"]


def count_per_send(run: dict, name: str):
    red = program_spans(run)
    if red is None:
        return None
    return red["spans"].get(name, {"count": 0})["count"] / red["sends"]


def idle_ms_per_send(run: dict, names):
    """Device-idle time under the self time of the named spans, per send."""
    red = program_spans(run)
    if red is None:
        return None
    return sum(red["spans"].get(n, {"idle_s": 0.0})["idle_s"]
               for n in names) * 1e3 / red["sends"]

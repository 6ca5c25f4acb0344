"""Reduce a jax.profiler trace (.xplane.pb) to the benchmark's numbers.

What is read (jax.profiler.ProfileData, nothing but jax):

- device planes (`/device:TPU:<n>`): the line `XLA Ops` holds one event per
  operation that ran on the chip, `XLA Modules` one per executed program
  (jitted step).  Busy time is the UNION of the op intervals; an op belongs
  to the module whose interval holds its start.
- the host plane (`/host:CPU`): the harness's own `bench:*` spans
  (jax.profiler.TraceAnnotation), one line per thread.  The slice that is
  reduced runs from the start of the first `bench:send_columns` span to the end of
  the last span, so the profiler's own start-up and write-out are outside it.

Idle gaps — the complement of busy time inside the slice — are attributed to
the `bench:*` span whose SELF time (the span minus the spans nested in it)
covers most of the gap, or to `no_span` when none is open: what the host was
doing while the chip waited.  Device and host events share the profiler's
clock to within a millisecond or two: on the v5e a device op can be stamped
a little BEFORE the host span that launched it.  The harness starts the
profiler right before the first send of the slice, so any op stamped before
that send's span is that skew: device times are shifted forward by it (only
when it is under MAX_SKEW_NS — more is work that really ran earlier).

Where there is no device plane (a CPU rehearsal), ops are the host-plane
events that carry an `hlo_op` stat: the same arithmetic runs, and the caller
never prints the result as a device metric.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
SEND_SPAN = "bench:send_columns"
MAX_SKEW_NS = 5e6


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals):
    """Merge [start, end) intervals; returns the merged, sorted list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def complement(merged, lo, hi):
    """Gaps of a merged interval list inside [lo, hi)."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def overlap(merged, lo, hi) -> float:
    """Length of [lo, hi) covered by a merged, sorted interval list."""
    i = bisect.bisect_left(merged, [lo, lo]) - 1
    i = max(i, 0)
    got = 0.0
    while i < len(merged) and merged[i][0] < hi:
        got += max(0.0, min(merged[i][1], hi) - max(merged[i][0], lo))
        i += 1
    return got


def self_intervals(spans):
    """{name: merged self intervals} for one thread's spans (name, start,
    end): each span's interval minus the spans nested inside it."""
    out = {}
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    for idx, (name, s, e) in enumerate(spans):
        kids = [[cs, ce] for _, cs, ce in spans[idx + 1:]
                if cs >= s and ce <= e and (cs, ce) != (s, e)]
        mine = complement(union(kids), s, e)
        out.setdefault(name, []).extend(mine)
    return {n: union(v) for n, v in out.items()}


def _module_name(raw: str) -> str:
    """`jit_step_w(1234567890)` -> `jit_step_w`: the id changes per run."""
    return re.sub(r"\(\d+\)$", "", raw)


def read_planes(path: str):
    """(device planes, host spans): device planes as {name: {"ops": [(name,
    s, e)], "modules": [(name, s, e)]}}, host spans as {thread: [(name, s,
    e)]} for the `bench:*` annotations.  Times in ns."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host_ops, spans = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    key = "ops"
                elif line.name == MODULES_LINE:
                    key = "modules"
                else:
                    continue
                for ev in line.events:
                    rec[key].append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
            if rec["ops"]:
                devices[plane.name] = rec
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.setdefault(line.name, []).append(
                            (ev.name, s, e))
                    elif ev.duration_ns > 0:
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            host_ops.append(
                                (ev.name, s, e,
                                 str(stats.get("hlo_module", "?"))))
    if not devices and host_ops:
        # CPU rehearsal: XLA:CPU ops stand in, modules from their stat
        devices["host-ops (rehearsal)"] = {
            "ops": [(n, s, e) for n, s, e, _ in host_ops],
            "modules": []}
        devices["host-ops (rehearsal)"]["op_module"] = {
            (s, e): mod for _, s, e, mod in host_ops}
    return devices, spans


def reduce_trace(path: str, top: int = 10) -> dict:
    """The numbers the per-layer metrics and the result line read:
    window_s, busy_s (union of op intervals, averaged over the device
    planes), sends_in_slice, by_module [[module, seconds]], idle_gaps
    [[span, seconds]], longest_gap_s."""
    devices, spans = read_planes(path)
    sends = [(s, e) for evs in spans.values() for n, s, e in evs
             if n == SEND_SPAN]
    if not sends or not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "sends_in_slice": 0,
                "devices": len(devices), "by_module": [], "idle_gaps": [],
                "longest_gap_s": 0.0, "ops": 0, "skew_s": 0.0}
    lo = min(s for s, _ in sends)
    hi = max(e for evs in spans.values() for _, _, e in evs)
    first_op = min(s for rec in devices.values() for _, s, _ in rec["ops"])
    skew = lo - first_op if 0 < lo - first_op < MAX_SKEW_NS else 0.0
    if skew:
        for rec in devices.values():
            for key in ("ops", "modules"):
                rec[key] = [(n, s + skew, e + skew) for n, s, e in rec[key]]
            if "op_module" in rec:
                rec["op_module"] = {(s + skew, e + skew): m for (s, e), m
                                    in rec["op_module"].items()}
    selfs = {}
    for evs in spans.values():
        for name, iv in self_intervals(
                [x for x in evs if x[2] > lo and x[1] < hi]).items():
            selfs[name] = union(selfs.get(name, []) + iv)
    busy_total, by_module, gap_by, longest, n_ops = 0.0, {}, {}, 0.0, 0
    for rec in devices.values():
        ops = [(n, s, e) for n, s, e in rec["ops"] if e > lo and s < hi]
        n_ops += len(ops)
        merged = clip(union([[s, e] for _, s, e in ops]), lo, hi)
        busy_total += total(merged)
        mods = sorted((s, e, n) for n, s, e in rec["modules"])
        starts = [m[0] for m in mods]
        op_module = rec.get("op_module")
        for name, s, e in ops:
            if op_module is not None:
                mod = op_module.get((s, e), "?")
            else:
                i = bisect.bisect_right(starts, s) - 1
                mod = (_module_name(mods[i][2])
                       if i >= 0 and s < mods[i][1] else "no_module")
            dur = min(e, hi) - max(s, lo)
            by_module[mod] = by_module.get(mod, 0.0) + dur
        for gs, ge in complement(merged, lo, hi):
            longest = max(longest, ge - gs)
            best, best_cov = "no_span", 0.0
            for name, iv in selfs.items():
                cov = overlap(iv, gs, ge)
                if cov > best_cov:
                    best, best_cov = name, cov
            gap_by[best] = gap_by.get(best, 0.0) + (ge - gs)
    n_dev = len(devices)

    def ranked(d):
        return [[k, v / n_dev / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "sends_in_slice": len(sends),
        "devices": n_dev,
        "ops": n_ops,
        "by_module": ranked(by_module),
        "idle_gaps": ranked(gap_by),
        "longest_gap_s": longest / 1e9,
        "skew_s": skew / 1e9,
    }

"""Reduce a jax.profiler trace (.xplane.pb) to the benchmark's numbers.

What is read (jax.profiler.ProfileData, nothing but jax):

- device planes (`/device:TPU:<n>`): the line `XLA Ops` holds one event per
  operation that ran on the chip, `XLA Modules` one per executed program
  (jitted step).  Busy time is the UNION of the op intervals; an op belongs
  to the module whose interval holds its start.
- the host plane (`/host:CPU`): the harness's own `bench:*` spans and the
  program's `siddhi:*` spans (jax.profiler.TraceAnnotation), one list per
  thread.  The slice that is reduced runs from the start of the first
  `bench:send_columns` span to the end of the last `bench:*` span, so the
  profiler's own start-up and write-out are outside it.

Idle time — the complement of busy time inside the slice — is SPLIT over what
the host was doing under each part of it (`split_idle`): the innermost
`siddhi:*` span open on any thread (its self time: the span minus the
`siddhi:*` spans nested in it), else the innermost `bench:*` span, else
`no_span`; where spans of several threads are open at once (a sender's and a
drainer's) the time is divided equally among them, never counted twice.  So
`idle_gaps` adds up to `window_s - busy_s`, `siddhi:send` stands for its
unspanned self time alone and `bench:send_columns` for what of the call lies
outside `siddhi:send`; the harness's subscriber runs inside `siddhi:sink`
and stays in it, as in `program_spans`.

Device and host events share the profiler's clock to within a millisecond
or two: on the v5e a device op can be stamped a little BEFORE the host span
that launched it.  The harness starts the
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

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
PROGRAM_PREFIX = "siddhi:"
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


def idle_before(gaps_by_device):
    """F(t) -> device-idle time before `t` (ns, a numpy array for an array),
    the mean over the device planes; `gaps_by_device` holds one sorted list
    of disjoint idle gaps [s, e) a plane.  The idle time inside [a, b) is
    F(b) - F(a)."""
    tables = []
    for gaps in gaps_by_device:
        g = np.asarray(gaps, dtype=float).reshape(-1, 2)
        if len(g):
            lens = g[:, 1] - g[:, 0]
            tables.append((g[:, 0], lens,
                           np.concatenate([[0.0], np.cumsum(lens)])))

    def before(t):
        t = np.asarray(t, dtype=float)
        out = np.zeros(t.shape)
        for starts, lens, cum in tables:
            i = np.searchsorted(starts, t, side="right") - 1
            j = np.maximum(i, 0)
            out += np.where(
                i >= 0, cum[j] + np.clip(t - starts[j], 0.0, lens[j]), 0.0)
        return out / max(1, len(gaps_by_device))
    return before


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


def self_times(events, lo: float, hi: float):
    """[(metadata_id, self ns, index of the enclosing event or -1)] of one
    line's events (metadata_id, s, e), clipped to [lo, hi): an event's
    interval minus the events nested in it.  Events of a device line nest
    properly or not at all, and their self times add up to the union of
    their intervals; where two only overlap (host ops of several threads in
    a CPU rehearsal, whose numbers are no metric) the overlap is taken from
    the earlier one."""
    evs = sorted(((max(s, lo), min(e, hi), mid) for mid, s, e in events
                  if e > lo and s < hi), key=lambda x: (x[0], -x[1]))
    out, stack = [], []               # stack: indices into `out`, open events
    ends = []
    for s, e, mid in evs:
        while stack and ends[stack[-1]] <= s:
            stack.pop()
        parent = stack[-1] if stack else -1
        if parent >= 0:
            out[parent][1] -= min(e, ends[parent]) - s
        out.append([mid, e - s, parent])
        ends.append(e)
        stack.append(len(out) - 1)
    return out


def _module_name(raw: str) -> str:
    """`jit_step_w(1234567890)` -> `jit_step_w`: the id changes per run."""
    return re.sub(r"\(\d+\)$", "", raw)


def split_idle(spans: dict, before, lo: float, hi: float) -> dict:
    """{name: idle ns} over the slice [lo, hi): every instant of device-idle
    time (`before`: `idle_before`'s F) goes to the innermost `siddhi:*` span
    open under it on any thread, else to the innermost `bench:*` span, else
    to `no_span`; spans of several threads open at once share it equally.
    The values add up to the slice's idle time."""
    tiers = {PROGRAM_PREFIX: [], SPAN_PREFIX: []}
    for evs in spans.values():
        inside = [x for x in evs if x[2] > lo and x[1] < hi]
        for prefix, threads in tiers.items():
            mine = [x[:3] for x in inside if x[0].startswith(prefix)]
            segs = sorted((s, e, name)
                          for name, iv in self_intervals(mine).items()
                          for s, e in clip(iv, lo, hi))
            if segs:
                threads.append(segs)
    cuts = sorted({lo, hi}.union(
        t for threads in tiers.values() for segs in threads
        for s, e, _ in segs for t in (s, e)))
    idle = np.diff(before(cuts))
    out = {}
    for a, b, ns in zip(cuts, cuts[1:], idle.tolist()):
        if ns <= 0.0:
            continue
        mid, names = (a + b) / 2, []
        for threads in tiers.values():
            for segs in threads:
                i = bisect.bisect_right(segs, (mid, float("inf"), "")) - 1
                if i >= 0 and segs[i][1] > mid:
                    names.append(segs[i][2])
            if names:
                break
        for name in names or ["no_span"]:
            out[name] = out.get(name, 0.0) + ns / max(1, len(names))
    return out


def read_planes(path: str):
    """(device planes, host spans): device planes as {name: {"ops": [(name,
    s, e)], "modules": [(name, s, e)]}}, host spans as {thread: [(name, s,
    e, stats)]} for the `bench:*` and `siddhi:*` annotations, one key per
    profiler line.  Times in ns."""
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
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    s = float(ev.start_ns)
                    e = s + float(ev.duration_ns)
                    if ev.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        spans.setdefault((plane.name, i), []).append(
                            (ev.name, s, e, dict(ev.stats)))
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


def slice_of(spans: dict):
    """(lo, hi, sends): the reduced slice — start of the first
    `bench:send_columns` span to the end of the last `bench:*` span — and
    how many sends it holds; None without a send."""
    bench = [x for evs in spans.values() for x in evs
             if x[0].startswith(SPAN_PREFIX)]
    sends = [x[1] for x in bench if x[0] == SEND_SPAN]
    if not sends:
        return None
    return min(sends), max(x[2] for x in bench), len(sends)


def reduce_trace(path: str, top: int = 10) -> dict:
    """The numbers the per-layer metrics and the result line read:
    window_s, busy_s (union of op intervals, averaged over the device
    planes), sends_in_slice, by_module [[module, seconds of its ops' SELF
    time]: they add up to busy_s where the list is whole], idle_gaps
    [[span, seconds]] (`split_idle`; they add up to window_s - busy_s),
    longest_gap_s."""
    devices, spans = read_planes(path)
    found = slice_of(spans)
    if found is None or not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "sends_in_slice": 0,
                "devices": len(devices), "by_module": [], "idle_gaps": [],
                "longest_gap_s": 0.0, "ops": 0, "skew_s": 0.0}
    lo, hi, n_sends = found
    first_op = min(s for rec in devices.values() for _, s, _ in rec["ops"])
    skew = lo - first_op if 0 < lo - first_op < MAX_SKEW_NS else 0.0
    if skew:
        for rec in devices.values():
            for key in ("ops", "modules"):
                rec[key] = [(n, s + skew, e + skew) for n, s, e in rec[key]]
            if "op_module" in rec:
                rec["op_module"] = {(s + skew, e + skew): m for (s, e), m
                                    in rec["op_module"].items()}
    n_dev = len(devices)
    busy_total, by_module, gaps_by_device, n_ops = 0.0, {}, [], 0
    for rec in devices.values():
        ops = [(n, s, e) for n, s, e in rec["ops"] if e > lo and s < hi]
        n_ops += len(ops)
        merged = clip(union([[s, e] for _, s, e in ops]), lo, hi)
        busy_total += total(merged)
        mods = sorted((s, e, n) for n, s, e in rec["modules"])
        starts = [m[0] for m in mods]
        op_module = rec.get("op_module")
        # SELF times, as `step_sections` books them: a `while` minus the
        # body ops it runs, so a plane's entries add up to its busy time
        for i, ns, _parent in self_times(
                ((i, s, e) for i, (_, s, e) in enumerate(ops)), lo, hi):
            _, s, e = ops[i]
            if op_module is not None:
                mod = op_module.get((s, e), "?")
            else:
                m = bisect.bisect_right(starts, s) - 1
                mod = (_module_name(mods[m][2])
                       if m >= 0 and s < mods[m][1] else "no_module")
            by_module[mod] = by_module.get(mod, 0.0) + ns / n_dev
        gaps_by_device.append(complement(merged, lo, hi))
    gap_by = split_idle(spans, idle_before(gaps_by_device), lo, hi)

    def ranked(d, rest=False):
        """The `top` largest as [[name, seconds]]; with `rest`, what lies
        beyond them is summed as `other`, so the list still adds up."""
        best = sorted(d.items(), key=lambda kv: -kv[1])
        if rest and len(best) > top:
            best = best[:top - 1] + [
                ("other", sum(v for _, v in best[top - 1:]))]
        return [[k, v / 1e9] for k, v in best[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "sends_in_slice": n_sends,
        "devices": n_dev,
        "ops": n_ops,
        "by_module": ranked(by_module),
        "idle_gaps": ranked(gap_by, rest=True),
        "longest_gap_s": max((e - s for gaps in gaps_by_device
                              for s, e in gaps), default=0.0) / 1e9,
        "skew_s": skew / 1e9,
    }

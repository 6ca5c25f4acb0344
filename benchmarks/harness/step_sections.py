"""Device time of a run's traced slice BY SECTION of the pattern programs.

The sequential pattern programs (siddhi_tpu/core/pattern_planner.py) put
every op under a `jax.named_scope` section — SECTIONS below — inside one
outermost scope a program that names its `[Kb, E]` rectangle,
`rect_<Kb>x<E>`.  Scopes are op-name metadata: the compiled program is the
same with and without them, and in a device trace they arrive as the
`tf_op` stat of each op's EVENT METADATA (`jit(pattern_step)/rect_2048x4/
nfa_advance/while/body/...:`), beside `program_id` (one per compiled
executable, so one per rectangle) and `hlo_category`.  `harness/xspace.py`
reads those; nothing here knows an op or an instruction by name.

What is computed, over the SAME slice and skew as `trace_reduce.
reduce_trace` and `program_spans.reduce_spans` (start of the first
`bench:send_columns` span -> end of the last `bench:*` span; device stamps
shifted by the skew `reduce_trace` found), per device plane and then
averaged over the planes:

- the SELF time of every `XLA Ops` event — the event minus the events
  nested in its interval (a `while` minus the body ops it runs), so the
  self times of a plane add up to the union of its op intervals, which is
  `reduce_trace`'s `busy_s`;
- a PATTERN PROGRAM is a `program_id` some op of which names a section or
  a rectangle.  Its events are booked to the section their `tf_op` names
  (the outermost path component that is one; a fusion carries its root
  instruction's, so a section's edge is exact to a fusion); an event that
  names none — an empty `tf_op`, or one that is only a parameter's name:
  the copies the compiler puts in — takes the section of the innermost
  event that encloses it, and what then still has none is `unscoped`, kept
  by `hlo_category`.  The rectangle is the program's (one scope a
  program);
- every other program's time goes to `other_modules`, by module name (the
  `XLA Modules` line names each `program_id`): the ring's two programs
  under `@serve`, the small converts and slices jax runs around a fetch.

So sections + `unscoped` + `other_modules` = `busy_s`; the line printed
says how closely (`closure`).  A trace with no device plane (the CPU
rehearsal: host events that carry `hlo_op` and no `tf_op`) or a program
none of whose ops names a section gives None, and every reader built on
this returns None.
"""
from __future__ import annotations

import json
import re
import time

from . import trace_reduce as tr
from . import xspace

SECTIONS = ("event_load", "state_load", "nfa_advance", "state_store",
            "match_rows", "selector", "emission_compaction",
            "emission_bands", "mesh_reduce")
UNSCOPED = "unscoped"
RECT = re.compile(r"rect_(\d+)x(\d+)$")
NO_RECT = "no_rect"        # a pattern program older than the rect_* scopes


def named(tf_op: str):
    """(section, rectangle) a `tf_op` names — the outermost path component
    that is a section, the one that is a `rect_<Kb>x<E>` — each None where
    it names none.  An op the compiler merged from several carries their
    names joined by `;`: the first one's decides."""
    section = rect = None
    for part in (tf_op or "").split(";")[0].rstrip(":").split("/"):
        if section is None and part in SECTIONS:
            section = part
        elif rect is None and RECT.match(part):
            rect = part
    return section, rect


def slice_of(host) -> tuple | None:
    """(lo, hi, sends): `trace_reduce.slice_of` over the host plane's
    `bench:*` events."""
    names = {mid: name for mid, (name, _) in host.metadata.items()
             if name.startswith(tr.SPAN_PREFIX)}
    return tr.slice_of({
        i: [(names[mid], s, e) for mid, s, e in line.events()
            if mid in names] for i, line in enumerate(host.lines)})


def reduce_plane(plane, lo: float, hi: float, skew: float):
    """One device plane's slice, ns: ({(rectangle, section): the pattern
    programs' time}, {hlo_category: what of it names no section}, {module:
    every other program's time}); None where the plane has no `XLA Ops`
    line."""
    lines = {line.name: line for line in plane.lines}
    if tr.OPS_LINE not in lines:
        return None
    meta = plane.metadata
    module_of = {}                    # program_id -> module name
    if tr.MODULES_LINE in lines:
        for mid, _s, _e in lines[tr.MODULES_LINE].events():
            hit = re.match(r"(.*)\((\d+)\)$", meta[mid][0])
            if hit:
                module_of[int(hit.group(2))] = hit.group(1)
    says = {}        # metadata id -> (section, program_id, hlo_category)
    rect_of = {}     # program_id of a pattern program -> its rectangle
    for mid, (_name, stats) in meta.items():
        if "program_id" in stats:
            section, rect = named(stats.get("tf_op", ""))
            pid = stats["program_id"]
            says[mid] = (section, pid, stats.get("hlo_category", "?"))
            if rect or section:
                rect_of[pid] = rect or rect_of.get(pid, NO_RECT)
    selfs = tr.self_times(((mid, s + skew, e + skew) for mid, s, e in
                        lines[tr.OPS_LINE].events()), lo, hi)
    said = [says.get(mid, (None, None, "?")) for mid, _, _ in selfs]
    sections = resolve([own for own, _, _ in said],
                       [ns for _, ns, _ in selfs],
                       [parent for _, _, parent in selfs])
    cells, unscoped, others = {}, {}, {}
    for (_mid, ns, _parent), (_own, pid, category), section in zip(
            selfs, said, sections):
        if pid not in rect_of:        # no op of the program names a scope
            key, book = module_of.get(pid, f"program_{pid}"), others
        else:
            key, book = (rect_of[pid], section or UNSCOPED), cells
            if section is None:
                unscoped[category] = unscoped.get(category, 0.0) + ns
        book[key] = book.get(key, 0.0) + ns
    return cells, unscoped, others


def resolve(own, self_ns, parents) -> list:
    """The section each event is booked to, events in `self_times`' order
    (an enclosing event before what it encloses): its own; else the
    innermost enclosing event's; else — a loop the compiler rebuilt, its
    own `tf_op` empty — the section that holds most of the time of what it
    encloses; and what it encloses and names none takes that in turn."""
    out = list(own)
    for i, parent in enumerate(parents):
        if out[i] is None and parent >= 0:
            out[i] = out[parent]
    inside = [None] * len(out)        # per event: {section: ns nested in it}
    for i in range(len(out) - 1, -1, -1):
        if out[i] is None and inside[i]:
            out[i] = max(inside[i], key=inside[i].get)
        if parents[i] >= 0 and out[i] is not None:
            held = inside[parents[i]] = inside[parents[i]] or {}
            held[out[i]] = held.get(out[i], 0.0) + self_ns[i] + \
                sum((inside[i] or {}).values())
    for i, parent in enumerate(parents):
        if out[i] is None and parent >= 0:
            out[i] = out[parent]
    return out


def hot_rect(rects) -> str | None:
    """The rectangle with the largest E (then the most keys) of those that
    ran: a tiered send's hot tier; the one rectangle of any other send."""
    sized = [(int(m.group(2)), int(m.group(1)), r) for r in rects
             for m in [RECT.match(r)] if m]
    return max(sized)[2] if sized else None


def reduce_sections(path: str, skew_s: float) -> dict | None:
    """The slice's device time by section, rectangle and module, seconds,
    the mean over the device planes; None without a device plane, a send
    in the slice or a pattern program that names a section."""
    planes = xspace.read(path)
    host = [p for p in planes if p.name.startswith("/host:CPU")]
    found = slice_of(host[0]) if host else None
    if found is None:
        return None
    lo, hi, sends = found
    per_plane = [red for p in planes if p.name.startswith("/device:TPU:")
                 for red in [reduce_plane(p, lo, hi, skew_s * 1e9)]
                 if red is not None]
    if not any(cells for cells, _, _ in per_plane):
        return None
    n = len(per_plane)

    def mean(i):
        out = {}
        for red in per_plane:
            for k, ns in red[i].items():
                out[k] = out.get(k, 0.0) + ns / n / 1e9
        return out

    cells, unscoped, others = mean(0), mean(1), mean(2)
    sections, rects, rect_sections = {}, {}, {}
    for (rect, section), sec in cells.items():
        sections[section] = sections.get(section, 0.0) + sec
        rects[rect] = rects.get(rect, 0.0) + sec
        rect_sections.setdefault(rect, {})[section] = sec
    return {
        "sends": sends, "devices": n,
        "sections_s": sections,
        "rects_s": rects,
        "rect_sections_s": rect_sections,
        "hot_rect": hot_rect(rects),
        "unscoped_by_category_s": unscoped,
        "other_modules_s": others,
        "pattern_s": sum(cells.values()),
        "total_s": sum(cells.values()) + sum(others.values()),
    }


def step_sections(run: dict) -> dict | None:
    """The run's device time by section, computed once and kept on the run
    record; the first computation prints one line, with the closure
    against `trace_reduce`'s `busy_s`."""
    if "step_sections" not in run:
        red = run.get("trace_reduced")
        out, t0 = None, time.perf_counter()
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = reduce_sections(tr.newest_xplane(run["trace_dir"]),
                                  red.get("skew_s", 0.0))
        if out is not None:
            out["closure"] = {"busy_s": red["busy_s"],
                              "ratio": out["total_s"] / red["busy_s"]
                              if red["busy_s"] else None}
            # what this reader itself cost the traced run, on the host
            out["reader_s"] = time.perf_counter() - t0
            print(f"step sections: {json.dumps(out)}", flush=True)
        run["step_sections"] = out
    return run["step_sections"]


# -- what the readers in layer_metrics/ share ------------------------------------

def section_ms_per_send(run: dict, *names: str):
    """Device time of the named sections of the pattern programs per send
    in the slice, ms (0.0 where the programs ran and no op names one)."""
    out = step_sections(run)
    if out is None:
        return None
    return sum(out["sections_s"].get(n, 0.0) for n in names) * 1e3 \
        / out["sends"]


def hot_rect_ms_per_send(run: dict):
    """Device time of the executions whose rectangle has the largest E of
    those in the slice, per send; None on a program without `rect_*`."""
    out = step_sections(run)
    if out is None or out["hot_rect"] is None:
        return None
    return out["rects_s"][out["hot_rect"]] * 1e3 / out["sends"]

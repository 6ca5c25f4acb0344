"""Device time of a run's traced slice BY SECTION of the plain query step.

`jit_plain_step` (siddhi_tpu/core/planner.py: filter chain -> window ->
selector, one program a send) puts every op under a `jax.named_scope`
section — SECTIONS below, each named in the code that owns the work
(`planner.stage_body`, `window.LengthBatchWindow.process`, `window.
sort_rows`, `selector.AggregatorBank.process`, `selector.SelectorExec.
process`).  None of them is one of `step_sections.SECTIONS` and the program
carries no `rect_*`, so `step_sections` books the plain step under its
`other_modules` and no pattern reader sees it.

This is `step_sections`' reduction with other names: the same slice and
skew, the same SELF times (`trace_reduce.self_times`: an op minus the ops
nested in it) and the same borrowing (`step_sections.resolve`: an op that
names no section takes its enclosing op's, a loop the compiler rebuilt its
body's), per device plane and averaged over the planes:

- a PLAIN PROGRAM is a `program_id` some op of which names a section; its
  events are booked to the section their `tf_op` names (the outermost path
  component that is one; of a merged op's `;`-joined names the first), what
  then has none is `unscoped`, kept by `hlo_category`;
- every other program's time goes to `other_modules`, by module name.

So sections + `unscoped` + `other_modules` = `busy_s`; the line printed
says how closely (`closure`).  A trace with no device plane (the CPU
rehearsal) or a program none of whose ops names a section (a tree older
than the scopes) gives None, and every reader built on this returns None.
"""
from __future__ import annotations

import json
import re
import time

from . import step_sections as ss
from . import trace_reduce as tr
from . import xspace

SECTIONS = ("plain_chain", "window_fill", "window_state", "window_order",
            "agg_layout", "agg_scan", "project")
UNSCOPED = "unscoped"


def named(tf_op: str):
    """The section a `tf_op` names: the outermost path component of its
    first `;`-joined name that is one, else None."""
    for part in (tf_op or "").split(";")[0].rstrip(":").split("/"):
        if part in SECTIONS:
            return part
    return None


def reduce_plane(plane, lo: float, hi: float, skew: float):
    """One device plane's slice, ns: ({section: the plain programs' time,
    `unscoped` among them}, {hlo_category: what of it names no section},
    {module: every other program's time}); None where the plane has no
    `XLA Ops` line."""
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
    plain = set()    # program_ids some op of which names a section
    for mid, (_name, stats) in meta.items():
        if "program_id" in stats:
            section = named(stats.get("tf_op", ""))
            says[mid] = (section, stats["program_id"],
                         stats.get("hlo_category", "?"))
            if section:
                plain.add(stats["program_id"])
    selfs = tr.self_times(((mid, s + skew, e + skew) for mid, s, e in
                           lines[tr.OPS_LINE].events()), lo, hi)
    said = [says.get(mid, (None, None, "?")) for mid, _, _ in selfs]
    booked = ss.resolve([own for own, _, _ in said],
                        [ns for _, ns, _ in selfs],
                        [parent for _, _, parent in selfs])
    sections, unscoped, others = {}, {}, {}
    for (_mid, ns, _parent), (_own, pid, category), section in zip(
            selfs, said, booked):
        if pid not in plain:
            key, book = module_of.get(pid, f"program_{pid}"), others
        else:
            key, book = section or UNSCOPED, sections
            if section is None:
                unscoped[category] = unscoped.get(category, 0.0) + ns
        book[key] = book.get(key, 0.0) + ns
    return sections, unscoped, others


def reduce_sections(path: str, skew_s: float) -> dict | None:
    """The slice's device time by section and module, seconds, the mean
    over the device planes; None without a device plane, a send in the
    slice or a plain program that names a section."""
    planes = xspace.read(path)
    host = [p for p in planes if p.name.startswith("/host:CPU")]
    found = ss.slice_of(host[0]) if host else None
    if found is None:
        return None
    lo, hi, sends = found
    per_plane = [red for p in planes if p.name.startswith("/device:TPU:")
                 for red in [reduce_plane(p, lo, hi, skew_s * 1e9)]
                 if red is not None]
    if not any(sections for sections, _, _ in per_plane):
        return None
    n = len(per_plane)

    def mean(i):
        out = {}
        for red in per_plane:
            for k, ns in red[i].items():
                out[k] = out.get(k, 0.0) + ns / n / 1e9
        return out

    sections, unscoped, others = mean(0), mean(1), mean(2)
    return {
        "sends": sends, "devices": n,
        "sections_s": sections,
        "unscoped_by_category_s": unscoped,
        "other_modules_s": others,
        "plain_s": sum(sections.values()),
        "total_s": sum(sections.values()) + sum(others.values()),
    }


def plain_sections(run: dict) -> dict | None:
    """The run's device time by section of the plain step, computed once
    and kept on the run record; the first computation prints one line,
    with the closure against `trace_reduce`'s `busy_s`."""
    if "plain_sections" not in run:
        red = run.get("trace_reduced")
        out, t0 = None, time.perf_counter()
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = reduce_sections(tr.newest_xplane(run["trace_dir"]),
                                  red.get("skew_s", 0.0))
        if out is not None:
            out["closure"] = {"busy_s": red["busy_s"],
                              "ratio": out["total_s"] / red["busy_s"]
                              if red["busy_s"] else None}
            out["unscoped_share"] = \
                out["sections_s"].get(UNSCOPED, 0.0) / out["plain_s"]
            # what this reader itself cost the traced run, on the host
            out["reader_s"] = time.perf_counter() - t0
            print(f"plain step sections: {json.dumps(out)}", flush=True)
        run["plain_sections"] = out
    return run["plain_sections"]


def section_ms_per_send(run: dict, *names: str):
    """Device time of the named sections of the plain step per send in the
    slice, ms (0.0 where the program ran and no op names one)."""
    out = plain_sections(run)
    if out is None:
        return None
    return sum(out["sections_s"].get(n, 0.0) for n in names) * 1e3 \
        / out["sends"]

"""Device time of a run's traced slice BY SECTION of the join step, and
what the runtime's `siddhi:route_keys` spans say of the candidate lanes.

A join query's two side programs (siddhi_tpu/core/join.py `make_step`:
`jit_join_left`, `jit_join_right`) put their ops under `jax.named_scope`
sections — SECTIONS below, named in `make_step.step` around the calls and
blocks that are there:

- `join_window`   the arriving side's window with the side's pre-filters:
                  `this.window.process`, which for a `length` window hands
                  over R = 2 B rows a send, B CURRENT and B EXPIRED — or,
                  since PR 48, where no EXPIRED row joins (a CURRENT-only
                  projection join over a window whose CURRENT rows are its
                  arrivals), `window.admit`, the state update alone, the B
                  staged rows being the R = B trigger rows;
- `join_lanes`    `_bucket_lanes`: the `[buckets, K]` lane table re-derived
                  from the OTHER window's slot column, every dispatch;
- `join_probe`    the `[R, K]` candidate gather, the ON re-check, the masks
                  (the grid and the table-probe forms take the same name);
- `join_pairs`    the N = R x K candidate pairs.  A join that keeps every
                  pair row (an aggregator, a `having`, an `order by`): the
                  expansion to N pair rows — pair indices, both sides'
                  gathers over them, the joined `Rows`.  A projection-only
                  join (since PR 43, where cap < N): the N candidate FLAGS,
                  then — after `join_compact` has taken the cap's order
                  from them — the pair indices of the `cap` rows alone and
                  one gather a column over `cap` rows;
- `join_select`   the selector (`sel.process`): over the N pair rows, or the
                  projection over the `cap` rows of a projection-only join;
- `join_compact`  the emission cap's stable valid-first argsort over the N
                  flags — taken FIRST in a projection-only join, which then
                  gathers nothing of N rows — and the header; where every
                  pair row is kept, also a gather a column to the cap.

The names and what the readers add up are the same on both sides of those
choices; only which ops stand under a name differs.

None of them is one of `step_sections.SECTIONS` or `plain_sections.
SECTIONS`, so those readers book a join program under `other_modules`.
Sections the shared code names inside (`window_order` in `window.
sort_rows`, `agg_scan` / `project` in the selector) count for the join
section around them: the OUTERMOST component that is a section wins.

This is `plain_sections`' reduction with other names: the same slice and
skew, the same SELF times (`trace_reduce.self_times`) and the same
borrowing (`step_sections.resolve`), per device plane and averaged over the
planes.  A JOIN PROGRAM is a `program_id` some op of which names a section
(so both side programs are in); what of it names none is `unscoped`, kept
by `hlo_category`; every other program's time goes to `other_modules`.
Sections + `unscoped` + `other_modules` = `busy_s`; the line printed says
how closely (`closure`).  A program none of whose ops names a section (a
tree older than the scopes) gives None, and every reader built on this
returns None.

Where there is no device plane (a CPU rehearsal) the host plane's XLA:CPU op
events stand in, as they do in `trace_reduce`: an event says its instruction
(`hlo_op`) and its module (`hlo_module`), and the trace's `/host:metadata`
plane keeps every module's `HloProto`, whose instructions carry the
`op_name` a device op's `tf_op` would (`host_ops`).  The same arithmetic
runs, so a rehearsal walks this reader end to end; the caller never prints
the result as a device metric.
"""
from __future__ import annotations

import json
import re
import time
import types

from . import step_sections as ss
from . import trace_reduce as tr
from . import xspace

SECTIONS = ("join_window", "join_lanes", "join_probe", "join_pairs",
            "join_select", "join_compact")
UNSCOPED = "unscoped"
LANE_STATS = ("lane_k", "lane_need")


def named(tf_op: str):
    """The section a `tf_op` names: the outermost path component of its
    first `;`-joined name that is one, else None."""
    for part in (tf_op or "").split(";")[0].rstrip(":").split("/"):
        if part in SECTIONS:
            return part
    return None


def reduce_plane(plane, lo: float, hi: float, skew: float):
    """One device plane's slice, ns: ({section: the join programs' time,
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
    joins = set()    # program_ids some op of which names a section
    for mid, (_name, stats) in meta.items():
        if "program_id" in stats:
            section = named(stats.get("tf_op", ""))
            says[mid] = (section, stats["program_id"],
                         stats.get("hlo_category", "?"))
            if section:
                joins.add(stats["program_id"])
    selfs = tr.self_times(((mid, s + skew, e + skew) for mid, s, e in
                           lines[tr.OPS_LINE].events()), lo, hi)
    said = [says.get(mid, (None, None, "?")) for mid, _, _ in selfs]
    booked = ss.resolve([own for own, _, _ in said],
                        [ns for _, ns, _ in selfs],
                        [parent for _, _, parent in selfs])
    sections, unscoped, others = {}, {}, {}
    for (_mid, ns, _parent), (_own, pid, category), section in zip(
            selfs, said, booked):
        if pid not in joins:
            key, book = module_of.get(
                pid, pid if isinstance(pid, str) else f"program_{pid}"), \
                others
        else:
            key, book = section or UNSCOPED, sections
            if section is None:
                unscoped[category] = unscoped.get(category, 0.0) + ns
        book[key] = book.get(key, 0.0) + ns
    return sections, unscoped, others


def hlo_op_names(buf: bytes) -> dict:
    """{instruction name: its metadata's `op_name`} of a serialized
    `HloProto` (xla's hlo.proto: HloProto.hlo_module = 1, HloModuleProto.
    computations = 3, HloComputationProto.instructions = 2,
    HloInstructionProto.name = 1 and .metadata = 7, OpMetadata.op_name = 2),
    read off the wire as `xspace` reads the trace."""
    def sub(span, field):
        return [v for f, wt, v in xspace._fields(buf, *span)
                if f == field and wt == 2]
    out = {}
    for module in sub((0, len(buf)), 1):
        for computation in sub(module, 3):
            for instruction in sub(computation, 2):
                name = [xspace._text(buf, v) for v in sub(instruction, 1)]
                said = [xspace._text(buf, w) for v in sub(instruction, 7)
                        for w in sub(v, 2)]
                if name:
                    out[name[0]] = said[0] if said else ""
    return out


def host_ops(path: str, planes):
    """A CPU rehearsal's stand-in for a device plane, in `reduce_plane`'s
    terms: one `XLA Ops` line of the host plane's XLA:CPU op events, and for
    each (module, instruction) an event metadata whose `tf_op` is the
    instruction's `op_name` in the module's `HloProto` and whose
    `program_id` is the module's name."""
    import jax
    said = {}                         # module -> {instruction: op_name}
    for plane in planes:
        if plane.name == "/host:metadata":
            for raw, stats in plane.metadata.values():
                if "Hlo Proto" in stats:
                    said.setdefault(tr._module_name(raw), {}).update(
                        hlo_op_names(stats["Hlo Proto"]))
    metadata, events, ids = {}, [], {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats) if ev.duration_ns > 0 else {}
                if "hlo_op" not in stats:
                    continue
                module = str(stats.get("hlo_module", "?"))
                key = (module, str(stats["hlo_op"]))
                if key not in ids:
                    ids[key] = len(ids) + 1
                    metadata[ids[key]] = (key[1], {
                        "tf_op": said.get(module, {}).get(key[1], ""),
                        "program_id": module, "hlo_category": "?"})
                s = float(ev.start_ns)
                events.append((ids[key], s, s + float(ev.duration_ns)))
    ops = types.SimpleNamespace(name=tr.OPS_LINE,
                                events=lambda: iter(events))
    return types.SimpleNamespace(name="host-ops (rehearsal)",
                                 metadata=metadata, lines=[ops])


def reduce_sections(path: str, skew_s: float) -> dict | None:
    """The slice's device time by section and module, seconds, the mean
    over the device planes (a CPU rehearsal's host ops where there is
    none); None without a send in the slice or a join program that names a
    section."""
    planes = xspace.read(path)
    host = [p for p in planes if p.name.startswith("/host:CPU")]
    found = ss.slice_of(host[0]) if host else None
    if found is None:
        return None
    lo, hi, sends = found
    devices = [p for p in planes if p.name.startswith("/device:TPU:")] \
        or [host_ops(path, planes)]
    per_plane = [red for p in devices
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
        "join_s": sum(sections.values()),
        "total_s": sum(sections.values()) + sum(others.values()),
    }


def join_sections(run: dict) -> dict | None:
    """The run's device time by section of the join step, computed once
    and kept on the run record; the first computation prints one line,
    with the closure against `trace_reduce`'s `busy_s`."""
    if "join_sections" not in run:
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
                out["sections_s"].get(UNSCOPED, 0.0) / out["join_s"]
            # what this reader itself cost the traced run, on the host
            out["reader_s"] = time.perf_counter() - t0
            print(f"join step sections: {json.dumps(out)}", flush=True)
        run["join_sections"] = out
    return run["join_sections"]


def section_ms_per_send(run: dict, *names: str):
    """Device time of the named sections of the join step per send in the
    slice, ms (0.0 where the program ran and no op names one)."""
    out = join_sections(run)
    if out is None:
        return None
    return sum(out["sections_s"].get(n, 0.0) for n in names) * 1e3 \
        / out["sends"]


# -- the candidate lanes, from the runtime's own spans ----------------------

def read_lanes(path: str) -> dict | None:
    """`lane_k` (the planned lane width) and `lane_need` (the fullest lane
    of either window's retention ring, `JoinKeyTracker.needed_k()`) summed
    over the runtime's `siddhi:route_keys` spans that start in the slice
    `trace_reduce.reduce_trace` takes and carry them.  None where no span
    does (a program older than the stats, a query off the bucket path)."""
    _devices, spans = tr.read_planes(path)
    found = tr.slice_of(spans)
    if found is None:
        return None
    lo, hi, _sends = found
    out = dict.fromkeys(LANE_STATS, 0)
    out.update(spans=0, lane_need_max=0)
    for evs in spans.values():
        for name, s, _e, stats in evs:
            if name == "siddhi:route_keys" and lo <= s < hi \
                    and LANE_STATS[0] in stats:
                out["spans"] += 1
                for key in LANE_STATS:
                    out[key] += int(stats[key])
                out["lane_need_max"] = max(out["lane_need_max"],
                                           int(stats["lane_need"]))
    return out if out["spans"] and out["lane_k"] else None


def lanes(run: dict) -> dict | None:
    """The run's lane sums, computed once and kept on the run record."""
    if "join_lanes" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = read_lanes(tr.newest_xplane(run["trace_dir"]))
        run["join_lanes"] = out
        if out is not None:
            print(f"join lanes over the slice: {out}", flush=True)
    return run["join_lanes"]

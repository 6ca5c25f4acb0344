"""Device time of a run's traced slice BY OP: every `XLA Ops` event of every
program booked under (program, section, part, primitive).

The three section readers (`step_sections`, `plain_sections`,
`join_sections`) stop at a SECTION — the outermost `jax.named_scope` of an
op's `tf_op`.  The same `tf_op` says more: `jit(plain_step)/agg_layout/
to_sorted/gather:` is the section, then (since PR 53, in the sections that
own a step — siddhi_tpu/observability/phases.py lists them) a PART, the
second scope level, which names what a group of ops is for, and last the jax
PRIMITIVE the op was lowered from.  `hlo_category` calls every gather and
scatter `custom fusion`; the primitive tells them apart.  Beside `tf_op` the
op's event metadata holds `bytes_accessed`, `shape_with_layout` and `source`
(file:line), which are kept for the costliest op of a key.

This is the section readers' reduction one level down and over EVERY
program: the same slice and skew, the same SELF times
(`trace_reduce.self_times`) and the same borrowing
(`step_sections.resolve` for the section; part and primitive an op's own,
else its enclosing op's), per device plane and averaged over the planes.

- section: the outermost path component of `tf_op` that is one of the three
  readers' SECTIONS (of a merged op's `;`-joined names the first), resolved
  exactly as they resolve it, so a section's keys add up to the section's
  total in its reader; `unscoped` for what names none in a program that has
  sections, `""` in a program that has none (the readers' `other_modules`);
- part: the component right after the section where it is a scope the
  program named — a plain identifier that is neither the primitive nor one
  of jax's own words (`while`, `body`, `jit(...)`, ...); `""` where there is
  none: an old recording, a section without parts, an op a later edit put
  outside them.  A section nested in another (`join_select/project`) reads
  as the outer one's part;
- primitive: the last component, `:` stripped (`gather`, `sort`,
  `scatter-max`); `<hlo_category>` for an op with no name of its own that
  nothing encloses.

Per key: ops, self time and `bytes_accessed` (of ops that enclose no other:
a loop's bytes are its body's) — and per program its module's name, its
`program_id`, its rectangle where it has one, its executions in the slice.
Two programs of one module (`jit_plain_step` at the timer's 8 rows and at
the send's 8,192) are two rows.

None without a device plane (the CPU rehearsal) or a send in the slice.
"""
from __future__ import annotations

import json
import re
import time

from . import join_sections as js
from . import plain_sections as ps
from . import step_sections as ss
from . import trace_reduce as tr
from . import xspace

SECTIONS = frozenset(ss.SECTIONS) | frozenset(ps.SECTIONS) \
    | frozenset(js.SECTIONS)
UNSCOPED = "unscoped"
TOP = 12                   # keys a program the printed line lists
SCOPE = re.compile(r"[A-Za-z_][A-Za-z0-9_]*$")
JAX_WORDS = re.compile(
    r"(while|body|cond|branch_\d+_fun|closed_call|core_call|checkpoint|"
    r"remat|custom_jvp_call|custom_vjp_call|shard_map|pallas_call)$")
# the section readers whose result, where a cell's own readers have left it
# on the run record, the printed line is held against
READERS = ("step_sections", "plain_sections", "join_sections")


def named(tf_op: str):
    """(section, part, primitive) a `tf_op` names: section None where no
    component is one, primitive `""` where it names no op (empty, or only a
    parameter's name)."""
    comps = (tf_op or "").split(";")[0].rstrip(":").split("/")
    prim = comps[-1] if len(comps) > 1 else ""
    for i, comp in enumerate(comps):
        if comp in SECTIONS:
            if i + 1 == len(comps):          # a scope's name and no op
                return comp, "", ""
            after = comps[i + 1] if i + 2 < len(comps) else ""
            scope = SCOPE.match(after) and not JAX_WORDS.match(after)
            return comp, after if scope else "", prim
    return None, "", prim


def family(primitive: str, name: str) -> bool:
    """Whether `primitive` is `name` or one of its variants (`scatter`:
    `scatter-add`, `scatter_max`, ...)."""
    return primitive == name or primitive.startswith((name + "-", name + "_"))


def reduce_plane(plane, lo: float, hi: float, skew: float):
    """One device plane's slice: ({(program_id, section, part, primitive):
    [ops, self ns, bytes, (ns of its costliest op, that op's source, its
    shape)]}, {program_id: [module, rectangle or None, executions]}); None
    where the plane has no `XLA Ops` line."""
    lines = {line.name: line for line in plane.lines}
    if tr.OPS_LINE not in lines:
        return None
    meta = plane.metadata
    programs = {}                     # program_id -> [module, rect, runs]
    if tr.MODULES_LINE in lines:
        for mid, s, e in lines[tr.MODULES_LINE].events():
            hit = re.match(r"(.*)\((\d+)\)$", meta[mid][0])
            if hit:
                rec = programs.setdefault(int(hit.group(2)),
                                          [hit.group(1), None, 0])
                rec[2] += e + skew > lo and s + skew < hi
    says = {}        # metadata id -> (names, program_id, primitive, bytes)
    wrote = {}       # metadata id -> (source, shape)
    scoped = set()   # program_ids some op of which names a section
    for mid, (_name, stats) in meta.items():
        if "program_id" not in stats:
            continue
        pid, tf_op = stats["program_id"], stats.get("tf_op", "")
        section, part, prim = named(tf_op)
        says[mid] = ((section, part, prim) if section else None, pid,
                     prim or f"<{stats.get('hlo_category', '?')}>",
                     stats.get("bytes_accessed", 0))
        wrote[mid] = (stats.get("source", ""),
                      stats.get("shape_with_layout", ""))
        rec = programs.setdefault(pid, [f"program_{pid}", None, 0])
        if section:
            scoped.add(pid)
        if rec[1] is None:
            rec[1] = ss.named(tf_op)[1]
    selfs = tr.self_times(((mid, s + skew, e + skew) for mid, s, e in
                           lines[tr.OPS_LINE].events()), lo, hi)
    said = [says.get(mid, (None, None, "<?>", 0)) for mid, _, _ in selfs]
    parents = [parent for _, _, parent in selfs]
    # the section exactly as the section readers resolve it ...
    sections = ss.resolve([own and own[0] for own, _, _, _ in said],
                          [ns for _, ns, _ in selfs], parents)
    encloses = set(parents)
    names = []       # ... the names an op's own, else its enclosing op's
    keys = {}        # key -> [ops, self ns, bytes]
    by_op = {}       # (key, metadata id) -> self ns
    for i, ((mid, ns, parent), (own, pid, prim, nbytes), section) in \
            enumerate(zip(selfs, said, sections)):
        name = own or (names[parent] if parent >= 0 else None)
        names.append(name)
        part = ""
        if section is None:
            section = UNSCOPED if pid in scoped else ""
        elif name is not None and name[0] == section:
            part, prim = name[1], name[2] or prim
        key = (pid, section, part, prim)
        rec = keys.setdefault(key, [0, 0.0, 0])
        rec[0] += 1
        rec[1] += ns
        if i not in encloses:
            rec[2] += nbytes
        by_op[key, mid] = by_op.get((key, mid), 0.0) + ns
    best = {}        # key -> (ns of its costliest op, its source, its shape)
    for (key, mid), ns in by_op.items():
        best[key] = max(best.get(key, (-1.0, "", "")),
                        (ns,) + wrote.get(mid, ("", "")))
    return {key: rec + [best[key]] for key, rec in keys.items()}, programs


def reduce_ops(path: str, skew_s: float) -> dict | None:
    """The slice's device ops by key, the mean over the device planes:
    `rows` [{program_id, section, part, primitive, ops, self_s, bytes,
    source, shape}] (costliest first), `programs` {program_id: {module,
    rect, executions, self_s}}, `sections_s`, `total_s`.  None without a
    device plane or a send in the slice."""
    planes = xspace.read(path)
    host = [p for p in planes if p.name.startswith("/host:CPU")]
    found = ss.slice_of(host[0]) if host else None
    if found is None:
        return None
    lo, hi, sends = found
    per_plane = [red for p in planes if p.name.startswith("/device:TPU:")
                 for red in [reduce_plane(p, lo, hi, skew_s * 1e9)]
                 if red is not None]
    if not per_plane:
        return None
    n = len(per_plane)
    rows, programs = {}, {}
    for keys, progs in per_plane:
        for key, (ops, ns, nbytes, best) in keys.items():
            row = rows.setdefault(key, dict(
                zip(("program_id", "section", "part", "primitive"), key),
                ops=0.0, self_s=0.0, bytes=0.0, best=(-1.0, "", "")))
            row["ops"] += ops / n
            row["self_s"] += ns / n / 1e9
            row["bytes"] += nbytes / n
            row["best"] = max(row["best"], best)
        for pid, (module, rect, runs) in progs.items():
            rec = programs.setdefault(pid, {
                "module": module, "rect": rect, "executions": 0.0,
                "self_s": 0.0})
            rec["executions"] += runs / n
            rec["rect"] = rec["rect"] or rect
    sections = {}
    for row in rows.values():
        _ns, row["source"], row["shape"] = row.pop("best")
        programs[row["program_id"]]["self_s"] += row["self_s"]
        sections[row["section"]] = \
            sections.get(row["section"], 0.0) + row["self_s"]
    return {
        "sends": sends, "devices": n,
        "rows": sorted(rows.values(), key=lambda r: -r["self_s"]),
        "programs": {pid: rec for pid, rec in programs.items()
                     if rec["self_s"] or rec["executions"]},
        "sections_s": sections,
        "total_s": sum(sections.values()),
    }


def _short(source: str) -> str:
    """`/root/repo/siddhi_tpu/core/window.py:86` -> `core/window.py:86`."""
    return source.rsplit("siddhi_tpu/", 1)[-1]


def printed(out: dict, run: dict) -> dict:
    """What the one printed line holds: per program the TOP costliest keys
    (per send; GB/s = bytes_accessed / self time) and the rest summed, the
    sections' sums, and how far they stand from the section readers' that
    are on the run record."""
    sends = out["sends"]
    programs = []
    for pid, rec in sorted(out["programs"].items(),
                           key=lambda kv: -kv[1]["self_s"]):
        mine = [r for r in out["rows"] if r["program_id"] == pid]
        programs.append({
            "module": rec["module"], "program_id": pid,
            **({"rect": rec["rect"]} if rec["rect"] else {}),
            "executions": rec["executions"],
            "ms_per_execution": rec["self_s"] * 1e3 / rec["executions"]
            if rec["executions"] else None,
            "ms_per_send": rec["self_s"] * 1e3 / sends,
            "keys": ["section", "part", "primitive", "ops_per_send",
                     "ms_per_send", "bytes_per_send", "GB_per_s", "source",
                     "shape"],
            "top": [[r["section"], r["part"], r["primitive"],
                     r["ops"] / sends, r["self_s"] * 1e3 / sends,
                     r["bytes"] / sends,
                     r["bytes"] / r["self_s"] / 1e9 if r["self_s"] else None,
                     _short(r["source"]), r["shape"][:64]]
                    for r in mine[:TOP]],
            "rest": {"keys": len(mine[TOP:]), "ms_per_send": sum(
                r["self_s"] for r in mine[TOP:]) * 1e3 / sends}})
    against = {}
    for name in READERS:
        theirs = run.get(name)
        if theirs:
            # their sections (`unscoped` among them) one by one; what they
            # book by module is this table's section ""
            pairs = [(sec, out["sections_s"].get(section, 0.0))
                     for section, sec in theirs["sections_s"].items()]
            pairs.append((sum(theirs["other_modules_s"].values()),
                          out["sections_s"].get("", 0.0)))
            against[name] = {
                "sections": len(pairs) - 1,
                "max_diff_s": max(abs(a - b) for a, b in pairs),
                "total_diff_s": abs(theirs["total_s"] - out["total_s"])}
    return {"sends": sends, "devices": out["devices"],
            "programs": programs,
            "sections_ms_per_send": {
                k: v * 1e3 / sends for k, v in out["sections_s"].items()},
            "against": against, "total_s": out["total_s"],
            "closure": out["closure"], "reader_s": out["reader_s"]}


def section_ops(run: dict) -> dict | None:
    """The run's device ops by key, computed once and kept on the run
    record; the first computation prints one line, with the closure
    against `trace_reduce`'s `busy_s`."""
    if "section_ops" not in run:
        red = run.get("trace_reduced")
        out, t0 = None, time.perf_counter()
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = reduce_ops(tr.newest_xplane(run["trace_dir"]),
                             red.get("skew_s", 0.0))
        if out is not None:
            out["closure"] = {"busy_s": red["busy_s"],
                              "ratio": out["total_s"] / red["busy_s"]
                              if red["busy_s"] else None}
            # what this reader itself cost the traced run, on the host
            out["reader_s"] = time.perf_counter() - t0
            print(f"section ops: {json.dumps(printed(out, run))}",
                  flush=True)
        run["section_ops"] = out
    return run["section_ops"]


# -- what the readers in layer_metrics/ share ---------------------------------

def primitive_ms_per_send(run: dict, name: str):
    """Self time of the device ops whose primitive is `name` or a variant
    of it (`family`), every program of the slice, per send, ms; 0.0 where
    programs ran and hold no such op."""
    out = section_ops(run)
    if out is None:
        return None
    return sum(r["self_s"] for r in out["rows"]
               if family(r["primitive"], name)) * 1e3 / out["sends"]


def part_ms_per_send(run: dict, section: str, part: str):
    """Self time of the device ops under `part` of `section`, per send,
    ms; 0.0 where programs ran and no op names it."""
    out = section_ops(run)
    if out is None:
        return None
    return sum(r["self_s"] for r in out["rows"]
               if (r["section"], r["part"]) == (section, part)) * 1e3 \
        / out["sends"]

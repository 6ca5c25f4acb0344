"""Device time of a run's traced slice by the PARTS a section names below its
own first level.

`section_ops` reads a part as the path component right after the section
(`jit(plain_step)/agg_layout/to_sorted/gather:`).  The pattern programs'
`nfa_advance` is one `lax.scan`, so what its body names stands below the
loop's own words — `jit(pattern_dense)/rect_131072x4/nfa_advance/while/body/
fork_spawn/...` — and `section_ops` books it as part `""`.  This reads the
same `tf_op` for every scope an op stands under at ANY depth below its
section (siddhi_tpu/observability/phases.py lists them: `count_capture`,
`fork_spawn` inside `nfa_advance`; `last_capture` inside `match_rows`).

Same slice, skew and SELF times as the section readers (`trace_reduce.
self_times`); an op with no name of its own (the copies the compiler puts in)
takes its enclosing op's scopes; per device plane, averaged over the planes.
A fusion carries its root instruction's name, so a part's edge is exact to a
fusion.  None without a device plane or a send in the slice; 0.0 where
programs ran and no op names the part (a tree older than the parts, a query
with no count atom).
"""
from __future__ import annotations

from . import section_ops as so
from . import step_sections as ss
from . import trace_reduce as tr
from . import xspace


def scopes(tf_op: str):
    """(section, the scopes the program named below it) of a `tf_op`;
    (None, ()) where it names no section."""
    comps = (tf_op or "").split(";")[0].rstrip(":").split("/")
    for i, comp in enumerate(comps):
        if comp in so.SECTIONS:
            return comp, tuple(
                c for c in comps[i + 1:-1]
                if so.SCOPE.match(c) and not so.JAX_WORDS.match(c))
    return None, ()


def reduce_plane(plane, lo: float, hi: float, skew: float):
    """{(section, scope): self ns} of one device plane's slice; None where
    it has no `XLA Ops` line."""
    lines = {line.name: line for line in plane.lines}
    if tr.OPS_LINE not in lines:
        return None
    says = {mid: scopes(stats.get("tf_op", ""))
            for mid, (_name, stats) in plane.metadata.items()
            if "program_id" in stats}
    selfs = tr.self_times(((mid, s + skew, e + skew) for mid, s, e in
                           lines[tr.OPS_LINE].events()), lo, hi)
    out, named = {}, []
    for mid, ns, parent in selfs:
        own = says.get(mid, (None, ()))
        if own[0] is None and parent >= 0:
            own = named[parent]
        named.append(own)
        for scope in own[1]:
            out[own[0], scope] = out.get((own[0], scope), 0.0) + ns
    return out


def nested_parts(run: dict):
    """{"sends", "parts_s": {(section, scope): seconds}} of the run's
    traced slice, computed once and kept on the run record."""
    if "nested_parts" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            planes = xspace.read(tr.newest_xplane(run["trace_dir"]))
            host = [p for p in planes if p.name.startswith("/host:CPU")]
            found = ss.slice_of(host[0]) if host else None
            if found is not None:
                lo, hi, sends = found
                per_plane = [r for p in planes
                             if p.name.startswith("/device:TPU:")
                             for r in [reduce_plane(
                                 p, lo, hi, red.get("skew_s", 0.0) * 1e9)]
                             if r is not None]
                if per_plane:
                    parts = {}
                    for r in per_plane:
                        for key, ns in r.items():
                            parts[key] = parts.get(key, 0.0) + \
                                ns / len(per_plane) / 1e9
                    out = {"sends": sends, "parts_s": parts}
                    print("nested parts (ms a send): " + ", ".join(
                        f"{s}/{p} {v * 1e3 / sends:.4f}"
                        for (s, p), v in sorted(parts.items())), flush=True)
        run["nested_parts"] = out
    return run["nested_parts"]


def nested_part_ms_per_send(run: dict, section: str, part: str):
    """Self time of the device ops that stand under `part` at any depth
    below `section`, per send, ms."""
    out = nested_parts(run)
    if out is None:
        return None
    return out["parts_s"].get((section, part), 0.0) * 1e3 / out["sends"]

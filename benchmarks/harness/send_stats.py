"""Read what the runtime's `siddhi:send` spans SAY about the sending thread,
from a run's own profiler trace.

While a session records it, `siddhi:send` carries `minflt`: the minor page
faults the sending thread took inside the call (`getrusage(RUSAGE_THREAD)`
before and after).  A send makes and frees its staging arrays; memory the
kernel hands out fresh faults some 250 times a MB, memory the allocator
re-uses does not.  It was added to tell the mesh cell's two allocator modes
apart (~10 ms a send with no line changed) and reads 0 in both: the slow
mode is not fault time (PERF.md, PR 35).  Summed here over the `siddhi:send` spans that start inside the
slice `trace_reduce` / `program_spans` reduce, from the host plane alone
(`harness/xspace.py` never walks the device's events for it).  A program
whose spans lack the stat (the parent of the PR that added it, a platform
without `RUSAGE_THREAD`) gives None, and every reader built on this returns
None.
"""
from __future__ import annotations

from . import trace_reduce as tr
from . import xspace
from .step_sections import slice_of

SEND = tr.PROGRAM_PREFIX + "send"


def read_sends(path: str) -> dict | None:
    """{sends, with_minflt, minflt} over the slice (start of the first
    `bench:send_columns` span -> end of the last `bench:*` span); None
    where it holds no send or none that says its faults."""
    host = [p for p in xspace.read(path) if p.name.startswith("/host:CPU")]
    found = slice_of(host[0]) if host else None
    if found is None:
        return None
    lo, hi, _ = found
    send_ids = {mid for mid, (name, _) in host[0].metadata.items()
                if name == SEND}
    out = {"sends": 0, "with_minflt": 0, "minflt": 0}
    for line in host[0].lines:
        for mid, s, _e, stats in line.events(stats=True):
            if mid in send_ids and lo <= s < hi:
                out["sends"] += 1
                if "minflt" in stats:
                    out["with_minflt"] += 1
                    out["minflt"] += int(stats["minflt"])
    return out if out["with_minflt"] else None


def sends(run: dict) -> dict | None:
    """The run's send sums, computed once and kept on the run record; the
    first computation prints one line."""
    if "send_stats" not in run:
        red = run.get("trace_reduced")
        out = None
        if run.get("trace_dir") and red and red.get("sends_in_slice"):
            out = read_sends(tr.newest_xplane(run["trace_dir"]))
        run["send_stats"] = out
        if out is not None:
            print(f"sends over the slice: {out}", flush=True)
    return run["send_stats"]

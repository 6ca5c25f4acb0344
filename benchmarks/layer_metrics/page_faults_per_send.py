"""served path: minor page faults the sending thread takes inside one send
call — `siddhi:send`'s `minflt` (a `getrusage(RUSAGE_THREAD)` pair) summed
over the sends of the traced slice, divided by them.  Memory the
kernel hands the send's staging arrays fresh faults some 250 times a MB,
memory the allocator re-uses does not; on the v5e hosts every cell reads 0
so far, in either allocator mode of the mesh cell (PERF.md, PR 35).  None
on a program whose span lacks the stat."""
from benchmarks.harness.send_stats import sends


def read(run):
    out = sends(run)
    return None if out is None else out["minflt"] / out["with_minflt"]

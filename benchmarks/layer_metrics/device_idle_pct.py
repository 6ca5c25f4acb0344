"""device: 1 - busy / slice, as a percentage (profiler trace).  The result
line's `breakdown.idle_gaps` says what the host was doing in the gaps."""
from benchmarks.harness.readers import trace_slice


def read(run):
    red = trace_slice(run)
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])

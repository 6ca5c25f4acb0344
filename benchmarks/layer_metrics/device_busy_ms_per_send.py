"""device step: union of the device-op intervals in the traced slice, per
send in the slice (profiler trace, harness/trace_reduce.py)."""
from benchmarks.harness.readers import trace_slice


def read(run):
    red = trace_slice(run)
    if red is None:
        return None
    return red["busy_s"] * 1e3 / red["sends_in_slice"]

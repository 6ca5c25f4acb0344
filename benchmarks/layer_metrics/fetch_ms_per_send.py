"""emission: self time of `siddhi:fetch`, all kinds (header, rows, ring),
per send in the traced slice.  In blocking delivery the header fetch holds
the wait for the device step, so this is never under the device's busy time
per send."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "fetch")

"""dispatch: self time of `siddhi:dispatch` per send in the traced slice —
the jitted step call up to SUBMIT (async dispatch: no device time in it)."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "dispatch")

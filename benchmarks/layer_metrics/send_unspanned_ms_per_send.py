"""served path: self time of `siddhi:send` per send in the traced slice —
what of the call no child span covers (the gate, admission, the junction's
loop, locks, the playback clock).  Large means a boundary is missing."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "send")

"""delivery: self time of `siddhi:demux` (header decode, ts-order restore,
unpack to events) per send in the traced slice; the fetches and the sink
nested in it are their own metrics."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "demux")

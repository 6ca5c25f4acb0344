"""emission: `siddhi:fetch` spans (device_get calls on the delivery path)
per send in the traced slice: the header, then what the subscriber reads."""
from benchmarks.harness.program_spans import count_per_send


def read(run):
    return count_per_send(run, "fetch")

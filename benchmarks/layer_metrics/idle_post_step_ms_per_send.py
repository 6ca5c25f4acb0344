"""device: device-idle time that falls under the self time of the spans
after the step (fetch, demux, sink), per send in the traced slice: the chip
waits because the host is still delivering."""
from benchmarks.harness.program_spans import POST_STEP, idle_ms_per_send


def read(run):
    return idle_ms_per_send(run, POST_STEP)

"""device: device-idle time that falls under the self time of the spans
before the step is submitted (stage, route_keys, obs_feed, h2d, dispatch),
per send in the traced slice: the chip waits because the host has not yet
given it the step."""
from benchmarks.harness.program_spans import PRE_DISPATCH, idle_ms_per_send


def read(run):
    return idle_ms_per_send(run, PRE_DISPATCH)

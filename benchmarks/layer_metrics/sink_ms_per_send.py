"""delivery: self time of `siddhi:sink` (batch and event callbacks, table op,
rate limiter, downstream publish) per send in the traced slice.  The
harness's subscriber runs inside it, so it reads `subscriber_ms_per_send`
(taken from outside, over the whole window) less the payload fetches the
subscriber triggers, which are `fetch_ms_per_send`'s."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "sink")

"""host staging: self time of `siddhi:route_keys` (key -> slot routing, the
[Kb, E] grouping and its block memo, the ts-wire fit check and delta build)
per send in the traced slice."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "route_keys")

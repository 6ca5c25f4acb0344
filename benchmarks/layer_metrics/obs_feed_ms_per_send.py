"""host staging: self time of `siddhi:obs_feed` per send in the traced slice
— what the always-on state observatory (key hotness, liveness touch, dirty
marks, emission-cap demand) costs on the hot path with statistics OFF."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "obs_feed")

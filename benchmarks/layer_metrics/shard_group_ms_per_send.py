"""host staging: self time of `siddhi:shard_group` (the key-space router's
regroup of a sharded send into the [n, Kb, E] device layout, nested in
`siddhi:route_keys`) per send in the traced slice.  0.0 where the app runs
on one shard (the span is never opened); None on a program without it."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "shard_group")

"""H2D: bytes uploaded per send — the `bytes` of every `siddhi:h2d` span
that starts in the traced slice over the `siddhi:send` spans in it.  Beside
`h2d_ms_per_send`: what the uploads carried, not how long the calls took;
a batch that goes up twice reads double."""
from benchmarks.harness.served_spans import per_send


def read(run):
    return per_send(run, "h2d_bytes")

"""emission: sends served by one drain cycle — the `items` of the
`siddhi:fetch what=ring` spans in the traced slice over their count.  1.0:
every send is fetched by a cycle of its own; more: the drainer is behind
and batches.  None on a program whose ring fetch does not say it."""
from benchmarks.harness.served_spans import served


def read(run):
    out = served(run)
    if out is None or out["items"] is None:
        return None
    return out["items"] / out["ring_fetches"]

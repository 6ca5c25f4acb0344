"""host staging: cells of the [Kb, E] device layout a send was put into
(`siddhi:route_keys`' `cells`, padding included, all its tiers) per event
sent (`siddhi:send`'s `events`), over the traced slice.  1.0 is a layout
with no padding; one rectangle of `distinct keys x hottest key's count`
reads 2,048 under Zipf(1.2) keys.  None on a program without the stat."""
from benchmarks.harness.span_stats import layout


def read(run):
    lay = layout(run)
    if lay is None or not lay["events"]:
        return None
    return lay["cells"] / lay["events"]

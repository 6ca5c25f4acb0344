"""device step: events of a send's hottest key (`siddhi:route_keys`'
`max_e`), mean over the sends of the traced slice: a key's events are
sequential, so this is the deployment's own critical path in scan ticks.
None on a program without the stat."""
from benchmarks.harness.span_stats import layout


def read(run):
    lay = layout(run)
    if lay is None:
        return None
    return lay["max_e"] / lay["sends"]

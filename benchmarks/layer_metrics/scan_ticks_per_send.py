"""device step: scan ticks a send costs — the sum of its tiers' E
(`siddhi:route_keys`' `ticks`), the steps the sequential NFA scan walks on
the chip — per send in the traced slice.  Beside
`hot_key_events_per_send` (what the hottest key alone needs): their ratio is
the padding left on the critical path.  None on a program without the
stat."""
from benchmarks.harness.span_stats import layout


def read(run):
    lay = layout(run)
    if lay is None:
        return None
    return lay["ticks"] / lay["sends"]

"""host staging: how much of the join step's [R, K] candidate rectangle can
ever match — 100 x `lane_need` (the fullest lane of either window's retention
ring, `JoinKeyTracker.needed_k()`) over `lane_k` (the planned lane width),
summed over the `siddhi:route_keys` spans of the traced slice that carry
them. Low: the rectangle is mostly padding; 100: the next denser send grows
the lanes (a recompile). None on a program whose spans lack the stats."""
from benchmarks.harness.join_sections import lanes


def read(run):
    out = lanes(run)
    if out is None:
        return None
    return 100.0 * out["lane_need"] / out["lane_k"]

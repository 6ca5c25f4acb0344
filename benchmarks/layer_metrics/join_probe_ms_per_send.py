"""device step: device time of the join step's `join_lanes` (`_bucket_lanes`:
the [buckets, K] lane table re-derived from the other window's slot column,
every dispatch) and `join_probe` (the [R, K] candidate gather, the ON
re-check, the masks) sections per send in the traced slice. From each device
op's `tf_op` (harness/join_sections.py); None on a program without the
sections."""
from benchmarks.harness.join_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "join_lanes", "join_probe")

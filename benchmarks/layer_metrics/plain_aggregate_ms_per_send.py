"""device step: device time of the plain step's `agg_scan` (the aggregators'
contributions, the segmented associative scans, the carry to the next send)
and `project` (the select list over the running values, having, the valid
mask) sections per send in the traced slice. From each device op's `tf_op`
(harness/plain_sections.py); None on a program without the sections."""
from benchmarks.harness.plain_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "agg_scan", "project")

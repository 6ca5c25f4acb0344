"""device step: device time of the join step's ops that stand in NO section
per send in the traced slice — the instrument's own honesty check: copies and
format changes the compiler puts in for parameters and results are the
expected remainder (the `join step sections:` line lists it by `hlo_category`
and as a share of the step). From each device op's `tf_op`
(harness/join_sections.py); None on a program without the sections."""
from benchmarks.harness.join_sections import UNSCOPED, section_ms_per_send


def read(run):
    return section_ms_per_send(run, UNSCOPED)

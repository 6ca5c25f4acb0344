"""device step: device time of the plain step's ops that stand in NO section
per send in the traced slice — the instrument's own honesty check: copies and
format changes the compiler puts in for parameters and results are the
expected remainder (the `plain step sections:` line lists it by
`hlo_category` and as a share of the step). From each device op's `tf_op`
(harness/plain_sections.py); None on a program without the sections."""
from benchmarks.harness.plain_sections import UNSCOPED, section_ms_per_send


def read(run):
    return section_ms_per_send(run, UNSCOPED)

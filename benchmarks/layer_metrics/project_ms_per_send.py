"""device step: device time of the plain step's `project` section alone per
send in the traced slice — the select list over the aggregates' running
values, `having` and the valid mask (`selector.SelectorExec.process`). From
each device op's `tf_op` (harness/plain_sections.py); None on a program
without the sections."""
from benchmarks.harness.plain_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "project")

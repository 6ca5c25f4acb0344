"""device step: device time of the pattern programs' `nfa_advance` section per
send in the traced slice — the sequential scan over E, its loop's own time
included. From each device op's `tf_op` (harness/step_sections.py); None on a
program without the sections."""
from benchmarks.harness.step_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "nfa_advance")

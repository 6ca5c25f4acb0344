"""device step: device time of the pattern programs' `state_store` section per
send in the traced slice — packing the advanced state and writing the key rows
back into the three planes (in place as one slice, or row by key index). From
each device op's `tf_op` (harness/step_sections.py); None on a program without
the sections."""
from benchmarks.harness.step_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "state_store")

"""device step: device time of the pattern programs' `state_load` section per
send in the traced slice — the key rows read out of the three state planes
(one slice, or row by key index) and their unpacking to 64-bit leaves. From
each device op's `tf_op` (harness/step_sections.py); None on a program without
the sections."""
from benchmarks.harness.step_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "state_load")

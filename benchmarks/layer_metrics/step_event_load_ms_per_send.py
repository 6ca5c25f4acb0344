"""device step: device time of the pattern programs' `event_load` section per
send in the traced slice — the timestamp decode, the grouped reshape or (the
sharded step) the one lookup of the staged columns by row index, the casts and
the transposes that feed the scan. From each device op's `tf_op`
(harness/step_sections.py); None on a program without the sections."""
from benchmarks.harness.step_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "event_load")

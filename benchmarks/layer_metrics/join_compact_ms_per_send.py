"""device step: device time of the join step's `join_compact` section per
send in the traced slice — the emission cap's stable valid-first argsort over
the N pair rows, a gather a column to the cap, the header. From each device
op's `tf_op` (harness/join_sections.py); None on a program without the
sections."""
from benchmarks.harness.join_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "join_compact")

"""device step: device time of the join step's `join_pairs` (the expansion to
N = R x K pair rows: pair indices, both sides' gathers over them, the joined
rows) and `join_select` (the selector over the N pair rows) sections per send
in the traced slice. From each device op's `tf_op`
(harness/join_sections.py); None on a program without the sections."""
from benchmarks.harness.join_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "join_pairs", "join_select")

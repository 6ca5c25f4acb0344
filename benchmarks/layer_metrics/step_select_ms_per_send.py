"""device step: device time of the pattern programs' `match_rows` and
`selector` sections per send in the traced slice — the scan's emissions
flattened into the selector's rows and capture env, the wake reduction, the
selector. From each device op's `tf_op` (harness/step_sections.py); None on a
program without the sections."""
from benchmarks.harness.step_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "match_rows", "selector")

"""device step: device time of the pattern programs' `emission_compaction` and
`emission_bands` sections per send in the traced slice — the per-key rank
compaction of the selector's rows and their cut into rank bands on the u32
wire. From each device op's `tf_op` (harness/step_sections.py); None on a
program without the sections."""
from benchmarks.harness.step_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "emission_compaction", "emission_bands")

"""device step: device time of the sharded step's `mesh_reduce` section per
send in the traced slice, the mean over the chips — the collectives that
make the header's counts, the scalar counters and the next wake-up one value
over the mesh. A chip that finishes its share early waits in them and the
trace books the wait as busy, so this is where skew between the chips shows.
From each device op's `tf_op` (harness/step_sections.py); None on a program
without the sections."""
from benchmarks.harness.step_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "mesh_reduce")

"""device step: self time of the device ops whose jax primitive is `gather`,
every program of the traced slice, per send in the slice — the last path
component of each op's `tf_op` (harness/section_ops.py). 0.0 where programs
ran and hold no gather; None without a device plane."""
from benchmarks.harness.section_ops import primitive_ms_per_send


def read(run):
    return primitive_ms_per_send(run, "gather")

"""device step: self time of the device ops whose jax primitive is `scatter`
or one of its variants (`scatter-add`, `scatter_max`, ...), every program of
the traced slice, per send in the slice — the last path component of each
op's `tf_op` (harness/section_ops.py). 0.0 where programs ran and hold no
scatter; None without a device plane."""
from benchmarks.harness.section_ops import primitive_ms_per_send


def read(run):
    return primitive_ms_per_send(run, "scatter")

"""device step: self time of the device ops under part `to_sorted` of the
selector's `agg_layout` section — every `x[order]`: the
layout's four gathers and `vals[order]` a spec —
(`selector.AggregatorBank.process`) per send in the traced slice, from each
op's `tf_op` (harness/section_ops.py). 0.0 where programs ran and no op names
the part (a tree older than the parts, a query with one slot); None without a
device plane."""
from benchmarks.harness.section_ops import part_ms_per_send


def read(run):
    return part_ms_per_send(run, "agg_layout", "to_sorted")

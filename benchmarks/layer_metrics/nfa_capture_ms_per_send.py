"""device step: self time of the device ops under part `count_capture` of the
pattern programs' `nfa_advance` section — a count atom's capture write, one of
its D rows picked by the slot's count, a select over `[P, D, K]` a column a
tick — per send in the traced slice, from each op's `tf_op` at any depth
below the section (harness/nested_parts.py). 0.0 where programs ran and no op
names the part (a tree older than the part, a pattern with no count atom);
None without a device plane."""
from benchmarks.harness.nested_parts import nested_part_ms_per_send


def read(run):
    return nested_part_ms_per_send(run, "nfa_advance", "count_capture")

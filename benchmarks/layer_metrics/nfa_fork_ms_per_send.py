"""device step: self time of the device ops under part `fork_spawn` of the
pattern programs' `nfa_advance` section — `PatternExec._spawn`: fork and seed
candidates ranked against the key's free slots, every leaf of the slab pulled
by a one-hot contraction over the candidates, the D-deep capture planes among
them — per send in the traced slice, from each op's `tf_op` at any depth
below the section (harness/nested_parts.py). 0.0 where programs ran and no op
names the part (a tree older than the part); None without a device plane."""
from benchmarks.harness.nested_parts import nested_part_ms_per_send


def read(run):
    return nested_part_ms_per_send(run, "nfa_advance", "fork_spawn")

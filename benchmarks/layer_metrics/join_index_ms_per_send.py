"""device step: device time of the join step's `join_lanes` section ALONE per
send in the traced slice — how the other side's rows are found by key: for a
`window.time` side kept as a ring the upkeep of its kept index (the arrivals
linked into their keys' chains, a head a key slot: B rows a send), for a
`length` side the `[buckets, K]` lane table re-derived from the buffer. From
each device op's `tf_op` (harness/join_sections.py); None on a program
without the sections."""
from benchmarks.harness.join_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "join_lanes")

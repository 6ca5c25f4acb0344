"""device step: device time of the plain step's `agg_layout` section alone per
send in the traced slice — the selector's `sorted` layout, which a query with
a group slot takes: the argsort by (slot, reset epoch), the gathers into that
order and the permutation back (`selector.AggregatorBank.process`; a query
with one slot lays nothing out and reads 0 here). From each device op's
`tf_op` (harness/plain_sections.py); None on a program without the
sections."""
from benchmarks.harness.plain_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "agg_layout")

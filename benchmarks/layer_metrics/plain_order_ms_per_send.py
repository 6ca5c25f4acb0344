"""device step: device time of every sort and unsort of the plain step per
send in the traced slice — `window_order` (`window.sort_rows`: the argsort by
sequence number and the gathers by it) and `agg_layout` (the selector's
argsort by (slot, reset epoch), the gathers into that order and back). From
each device op's `tf_op` (harness/plain_sections.py); None on a program
without the sections."""
from benchmarks.harness.plain_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "window_order", "agg_layout")

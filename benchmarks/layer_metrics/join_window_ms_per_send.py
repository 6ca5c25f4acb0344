"""device step: device time of the join step's `join_window` section per send
in the traced slice — the arriving side's window (`this.window.process`: a
`length` window hands over 2 B rows a send, B CURRENT and B EXPIRED, its
`window_order` sort among them) and the side's pre-filters. From each device
op's `tf_op` (harness/join_sections.py); None on a program without the
sections."""
from benchmarks.harness.join_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "join_window")

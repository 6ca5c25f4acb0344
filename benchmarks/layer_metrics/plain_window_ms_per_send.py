"""device step: device time of the plain step's `plain_chain` (the filter /
stream-function chain), `window_fill` (the window building the rows a send
emits: CURRENT, the EXPIRED replay, RESET) and `window_state` (the buffers it
keeps) sections per send in the traced slice. From each device op's `tf_op`
(harness/plain_sections.py); None on a program without the sections."""
from benchmarks.harness.plain_sections import section_ms_per_send


def read(run):
    return section_ms_per_send(run, "plain_chain", "window_fill",
                               "window_state")

"""dispatch: `siddhi:dispatch` spans per send in the traced slice.  One
today; a timer pile-up (one more timer step each send) shows here."""
from benchmarks.harness.program_spans import count_per_send


def read(run):
    return count_per_send(run, "dispatch")

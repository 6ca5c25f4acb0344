"""dispatch: `siddhi:timer` spans per send in the traced slice — the timer
steps the scheduler fired (under @app:playback: from the `timer_drain` of the
send whose first row's clock made them due).  At most one a send for a
sliding time window whose runtime holds one wake-up; a pile-up (one more each
send) shows here and in `dispatches_per_send`.  None without the spans."""
from benchmarks.harness.program_spans import count_per_send


def read(run):
    return count_per_send(run, "timer")

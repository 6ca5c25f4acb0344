"""host staging: device-idle time under the self time of `siddhi:obs_feed`,
per send in the traced slice — what of the observatory's feed the chip waits
for.  A feed that runs after the step's dispatch lies under the step: ~0; one
that runs before it reads about its whole `obs_feed_ms_per_send`."""
from benchmarks.harness.program_spans import idle_ms_per_send


def read(run):
    return idle_ms_per_send(run, ("obs_feed",))

"""served path: mean over the window's sends of (delivery of the send's
last row) - (the call's return), by the harness's own clock: how long after
the producer got its thread back the subscriber had the results — ring
residency, the drainer's wake, the wait for the step inside its header
fetch, the rows.  None where a send was delivered inside its call (blocking
delivery: the lag would be the negative of `after_delivery_ms_per_send`)."""
from benchmarks.harness.served_spans import returned_stamps


def read(run):
    pairs = returned_stamps(run)
    if pairs is None or \
            any(st["subscriber_end"] is not None for st, _ in pairs):
        return None
    return sum(done - st["returned"] for st, done in pairs) * 1e3 \
        / len(pairs)

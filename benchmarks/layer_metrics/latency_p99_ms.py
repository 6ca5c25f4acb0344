"""served path: 99th percentile of event-time latency (delivery of a send's
last result minus the send's due time) over every send of the traced run's
window, where it holds the thousand sends a p99 wants.  A record of the
tail, not a judged number: it carries no bound because on a one-chip machine
it is set by whole-process stalls of ~0.1 s that come a few times a minute
(PERF.md section 2).  Every run, traced or not, also prints it."""
from benchmarks.harness import numeric


def read(run):
    lat = run["latency_ms"]
    if not numeric.supports(len(lat), 0.99):
        return None
    return numeric.percentile(lat, 0.99)

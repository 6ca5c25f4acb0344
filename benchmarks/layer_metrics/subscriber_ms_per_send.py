"""served path: time inside the subscriber's batch callback, which reads
every selected column (the payload's device->host fetch is in it), by the
harness's own clock, statistics OFF."""
from benchmarks.harness.readers import served_path_ms


def read(run):
    return served_path_ms(run, "subscriber")

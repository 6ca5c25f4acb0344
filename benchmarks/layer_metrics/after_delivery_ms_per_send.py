"""served path: from the subscriber's last return to the return of the
blocking send call — what the runtime still does after the results are
delivered — by the harness's own clock, statistics OFF."""
from benchmarks.harness.readers import served_path_ms


def read(run):
    return served_path_ms(run, "post")

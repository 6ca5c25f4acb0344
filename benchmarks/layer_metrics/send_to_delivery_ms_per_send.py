"""served path: the blocking send call up to the subscriber and between its
callbacks — staging, upload, dispatch, the device step, the fetch and the
demux together — by the harness's own clock, statistics OFF."""
from benchmarks.harness.readers import served_path_ms


def read(run):
    return served_path_ms(run, "pre")

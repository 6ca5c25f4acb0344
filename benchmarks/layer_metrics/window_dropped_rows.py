"""state: rows a full join window has LOST for capacity since the app
started, as the last `siddhi:route_keys` span of the traced slice says
(`window_dropped`; harness/join_windows.py). Each is a missing match, and
the runtime reports it as an error: 0 in a correct run. None on a program
whose spans lack the stat."""
from benchmarks.harness.join_windows import windows


def read(run):
    out = windows(run)
    return None if out is None else out["window_dropped"]

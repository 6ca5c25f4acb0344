"""device step: how many rows of one key a probe of a ring side walks — the
deepest `probe_depth` the traced slice's `siddhi:route_keys` spans say
(harness/join_windows.py): a power of two at or above the probed side's
fullest key; the candidate rectangle of a send is `[rows, depth]`. None on a
program whose spans lack the stat."""
from benchmarks.harness.join_windows import windows


def read(run):
    out = windows(run)
    return None if out is None else out["probe_depth"]

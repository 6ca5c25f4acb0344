"""entry (generator): how late the generator issued a send, send issued
minus send due by the harness's own clock — so that a starved generator is
not read as a fast system.  99th percentile, where the window holds enough
sends for one."""
from benchmarks.harness import numeric


def read(run):
    late = run["gen_late_ms"]
    if not numeric.supports(len(late), 0.99):
        return None
    return numeric.percentile(late, 0.99)

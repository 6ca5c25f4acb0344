"""emission (kernels): device time of the ring's two programs per send in
the traced slice — the ops of the modules `jit_ring_append` (the emission
into its ring slot, on the sender's dispatch) and `jit_ring_read` (the slot
out again, on the drainer's), from the device trace
(`trace_reduce.reduce_trace`'s `by_module`, which lists a run's ten busiest
modules: this cell runs three).  None where neither module ran."""
from benchmarks.harness.readers import trace_slice

MODULES = ("jit_ring_append", "jit_ring_read")


def ring_copy_s(run):
    """Seconds the ring's modules were busy in the slice, or None."""
    red = trace_slice(run)
    if red is None:
        return None
    got = [s for mod, s in red["by_module"] if mod in MODULES]
    return sum(got) if got else None


def read(run):
    busy = ring_copy_s(run)
    if busy is None:
        return None
    return busy * 1e3 / run["trace_reduced"]["sends_in_slice"]

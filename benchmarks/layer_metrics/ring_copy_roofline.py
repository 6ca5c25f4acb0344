"""emission (kernels): the bytes the ring's append and read must move for
one send (the configuration's `ring_least_bytes`, from shapes: the send's
emission read and written once by each) over what the chip's HBM could move
in the time the two programs' ops were busy for one send, as a percentage.
Bound: bytes (both are copies).  All device time of `jit_ring_append` and
`jit_ring_read` in the slice is in the denominator."""
from benchmarks.harness import loader
from benchmarks.layer_metrics.ring_copy_ms_per_send import ring_copy_s


def read(run):
    cell = run["cell"]
    least = getattr(cell.model, "ring_least_bytes", None)
    busy = ring_copy_s(run)
    peaks = cell.peaks
    if not peaks and cell.rehearse:
        # a rehearsal walks the arithmetic with the table's chip; run.py
        # withholds the number
        peaks = loader.load_peaks("TPU v5 lite")
    if least is None or not busy or not peaks:
        return None
    per_send = busy / run["trace_reduced"]["sends_in_slice"]
    return 100.0 * least(cell.traffic, cell.sizes, cell.config) / (
        per_send * peaks["hbm_bytes_per_s"])

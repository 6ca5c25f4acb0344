"""device step (kernel): the bytes the ALGORITHM needs for one send (the
configuration's `least_bytes`, from shapes) over what the chip's HBM could
move in the time its ops were busy for one send, as a percentage.  Bound:
bytes (the steps are gathers, scatters, sorts and compares — no matmul).
All device time of the slice is in the denominator, so no part of the work
is left out of it."""
from benchmarks.harness.readers import trace_slice


def read(run):
    red = trace_slice(run)
    peaks = run["cell"].peaks
    if red is None or not peaks or red["busy_s"] <= 0:
        return None
    busy_per_send = red["busy_s"] / red["sends_in_slice"]
    return 100.0 * run["least_bytes"] / (
        busy_per_send * peaks["hbm_bytes_per_s"])

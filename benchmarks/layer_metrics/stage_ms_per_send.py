"""host staging: self time of `siddhi:stage` (pad/adopt of the sent columns
into a StagedBatch; `pack_np` on the row path) per send in the traced slice
(profiler trace, harness/program_spans.py)."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "stage")

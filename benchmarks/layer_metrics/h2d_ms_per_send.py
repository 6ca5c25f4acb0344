"""H2D: self time of `siddhi:h2d` per send in the traced slice — the HOST
wall of the upload calls (`jnp.asarray` of the batch's columns, timestamps,
selection and key index), not the transfer's own time on the link."""
from benchmarks.harness.program_spans import self_ms_per_send


def read(run):
    return self_ms_per_send(run, "h2d")

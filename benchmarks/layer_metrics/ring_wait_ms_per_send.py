"""emission: how long a send's emission sat in the device ring, append ->
take — the `ring_wait_us` of the drain cycles' `siddhi:fetch what=ring`
spans in the traced slice, per `siddhi:send` span in it.  None on a program
whose ring fetch does not say it."""
from benchmarks.harness.served_spans import per_send


def read(run):
    return per_send(run, "ring_wait_us", 1e-3)

"""emission: how much of what delivery pulled off the device was a row — 100
x the rows the traced slice's sends were owed (the stamps' `owed`) over the
row SLOTS their `siddhi:fetch` spans fetched: the fetches' `bytes`
(`fetch_bytes_per_send`'s reduction of the slice; a banded emission's rows are
the bands below `ranks_used`, `ranks` x K slots a send, filler included; the
20-byte header a send rides along) over the model's `SLOT_BYTES`, the u32
words one slot takes on the wire.  Low: the per-key rank rectangle is mostly
filler — one key with many rows sets `ranks_used` for all.  None on a run
without a trace, on a model that states no `SLOT_BYTES`, or where no fetch
says its bytes."""
from benchmarks.layer_metrics import fetch_bytes_per_send


def read(run):
    slot_bytes = getattr(run["cell"].model, "SLOT_BYTES", None)
    if not slot_bytes or fetch_bytes_per_send.read(run) is None:
        return None
    got = run["fetch_bytes"]
    owed = sum(st["owed"] for st in run["stamps"][:got["sends"]])
    print(f"emission fill over the slice: {got['sends']} sends owed {owed} "
          f"rows, {got['bytes']} bytes fetched = "
          f"{got['bytes'] // slot_bytes} slots of {slot_bytes} B", flush=True)
    return 100.0 * owed * slot_bytes / got["bytes"]

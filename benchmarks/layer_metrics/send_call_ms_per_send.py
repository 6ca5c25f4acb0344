"""served path: mean wall of the `send_columns` call over the window's
sends, by the harness's own clock (`returned` - `issued`): what the producer
pays.  In blocking delivery the call holds the step, the fetches and the
subscriber; under `@serve` it ends at the ring append — the number `@serve`
exists to lower."""
from benchmarks.harness.served_spans import returned_stamps


def read(run):
    pairs = returned_stamps(run)
    if pairs is None:
        return None
    return sum(st["returned"] - st["issued"] for st, _ in pairs) * 1e3 \
        / len(pairs)

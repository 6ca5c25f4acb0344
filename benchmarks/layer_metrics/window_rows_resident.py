"""state: the rows both join windows hold after the traced slice's last send,
as the runtime's retention mirror says them on its `siddhi:route_keys` span
(`window_rows_l` + `window_rows_r`; harness/join_windows.py) — what the
configuration's window range keeps of the stream. None on a program whose
spans lack the stats."""
from benchmarks.harness.join_windows import windows


def read(run):
    out = windows(run)
    return None if out is None else out["rows_resident"]

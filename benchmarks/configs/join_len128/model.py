"""join_len128: traffic, plain reference and comparison — the inner join of
two `window.length(128)` streams on a symbol, sends alternating between the
two streams.

    from L#window.length(128) join R#window.length(128) on L.symbol == R.symbol
    select L.symbol as s, L.price as p, R.qty as v

A row that arrives on one side is paired with every row of the OTHER side's
window that has its symbol, as that window stood before the send; then the
side's own window keeps its last 128 rows.  A send is far wider than the
window, so only its last 128 rows are ever met by the other side.  The first
send of the app meets an empty window and owes no rows.

Numpy only, nothing of siddhi_tpu: it is the yardstick the program is held
to.  Symbols and payloads are carried, never computed: every comparison is
exact.
"""
from __future__ import annotations

import numpy as np

from benchmarks.harness.numeric import to_bf16

SIDES = ("L", "R")
PAYLOAD = {"L": np.float32, "R": np.int32}      # price, qty
# bytes one event needs on the wire: long symbol, a 4-byte payload, long
# timestamp; and one pair: long symbol, f32 price, i32 qty, long timestamp
EVENT_BYTES = 8 + 4 + 8
ROW_BYTES = 8 + 4 + 4 + 8


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What the generator keeps between sends: the symbols each side's
    window holds (sends are made in the order they are sent), so that a
    send knows how many pairs it is owed."""
    return {"window_length": int(sizes["window_length"]),
            "held": {side: np.zeros(0, np.int64) for side in SIDES}}


def events_per_send(traffic: dict) -> int:
    return int(traffic["events_per_send"])


def clock_step_ms(traffic: dict) -> int:
    return 1


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """The i-th send goes to L where i is even, to R where it is odd: one
    rng call a column, every column new in every send.  What it is owed
    comes from a count of the other window's symbols, never from an
    [events, window] compare: a send made inside the closed loop costs far
    less than a send."""
    n, n_sym = events_per_send(traffic), int(traffic["symbols"])
    side, other = SIDES[i % 2], SIDES[1 - i % 2]
    symbol = rng.integers(0, n_sym, n, np.int64)
    value = rng.random(n, np.float32) if side == "L" else \
        rng.integers(1, int(traffic["qty_hi"]) + 1, n, np.int32)
    held = plan_["held"]
    rows = int(np.bincount(held[other], minlength=n_sym)[symbol].sum())
    held[side] = np.concatenate(
        [held[side], symbol])[-plan_["window_length"]:]
    return {"stream": side, "cols": [symbol, value],
            "ts": np.full(n, clock_ms, np.int64), "events": n, "rows": rows}


def expected_rows(send: dict) -> int:
    return send["rows"]


def reference(sends: list, plan_: dict) -> list:
    """The plain thing: each side's last `window_length` rows, and for
    every send, in order, its rows paired with the other side's window as
    it stands."""
    w = plan_["window_length"]
    window = {side: (np.zeros(0, np.int64), np.zeros(0, PAYLOAD[side]))
              for side in SIDES}
    out = []
    for send in sends:
        side = send["stream"]
        symbol, value = send["cols"]
        other_symbol, other_value = window[SIDES[1 - SIDES.index(side)]]
        j, k = np.nonzero(symbol[:, None] == other_symbol[None, :])
        p, v = (value[j], other_value[k]) if side == "L" else \
            (other_value[k], value[j])
        out.append({"s": symbol[j], "p": p, "v": v})
        window[side] = (np.concatenate([window[side][0], symbol])[-w:],
                        np.concatenate([window[side][1], value])[-w:])
    return out


def canonical(rows: dict) -> dict:
    """By (s, p, v): the program may emit a send's pairs in any order."""
    order = np.lexsort((rows["v"], rows["p"], rows["s"]))
    return {n: a[order] for n, a in rows.items()}


class Attribution:
    """Result row -> the send in flight: delivery is blocking with one send
    outstanding, and a pair carries nothing that names the send that made
    it.  A row delivered while a send that owes none is in flight is
    unexpected there."""

    def __init__(self, plan_: dict):
        self.sid = -1

    def on_issue(self, sid: int, send: dict) -> None:
        self.sid = sid

    def attribute(self, rows: dict) -> np.ndarray:
        return np.full(rows["s"].shape[0], self.sid, np.int64)


LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered pairs against the reference's, both canonical:
    the counts' difference, and with the count right the pairs that differ
    in any column (a pair dropped and another doubled shift the sorted
    rows between them, so they differ)."""
    n_got, n_want = got["s"].shape[0], want["s"].shape[0]
    differing = 0
    if n_got == n_want:
        bad = np.zeros(n_want, bool)
        for n in want:
            bad |= got[n] != want[n]
        differing = int(bad.sum())
    return {"rows_missing": max(n_want - n_got, 0),
            "rows_unexpected": max(n_got - n_want, 0),
            "rows_differing": differing}


def control_rows(want: dict) -> dict:
    """The reference's pairs with the f32 payload `p` carried as bfloat16:
    the nearest precision below the configuration's, which no exact
    comparison passes."""
    return dict(want, p=to_bf16(want["p"]))


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """From shapes: the send's events in, the pairs they are owed out
    (events x window / symbols: each event meets window / symbols rows of
    its symbol), both windows read and one written."""
    n, w = events_per_send(traffic), int(sizes["window_length"])
    pairs = n * w // int(traffic["symbols"])
    return n * EVENT_BYTES + pairs * ROW_BYTES + 3 * w * EVENT_BYTES

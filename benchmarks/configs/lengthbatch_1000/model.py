"""lengthbatch_1000: traffic, plain reference and comparison.

The deployment is `app.siddhi` beside this file:
    from StockStream#window.lengthBatch(1000) select avg(price) as ap
A batch is 1,000 consecutive events of the stream, whatever send brought
them.  When its 1,000th event arrives the query emits one row for EACH of
its events, in arrival order: `ap` of the j-th is the mean of the batch's
first j prices (the selector's running value, `tests/test_window.py`
`TestLengthBatchWindow`).  Events of a batch that is not full yet wait for
the send that fills it.

Everything here is numpy and imports nothing of siddhi_tpu: it is the
yardstick the program is held to, so it must not move when the program
does.  Unlike every configuration before it, the answers are COMPUTED, the
rows carry no key, and their order IS the semantics — so the reference is a
float64 cumulative sum, a row belongs to the send in flight, and rows are
compared in delivery order, untouched.
"""
from __future__ import annotations

import numpy as np

from benchmarks.harness.numeric import to_bf16

# bytes one event needs on the wire: long symbol, f32 price, i32 volume,
# long timestamp; and one result row: f32 ap, long timestamp
EVENT_BYTES = 8 + 4 + 4 + 8
ROW_BYTES = 4 + 8
# one event-time millisecond per this many events of a send
EVENTS_PER_MS = 128

# `ap` is held to the float64 mean within this RELATIVE tolerance.  The
# device accumulates in float32 (its DOUBLE, core/event.py): any order of
# float32 accumulation of n positive terms stays within n * u of the exact
# sum, n = 1,000, u = 2**-24 = 5.96e-8 -> 6e-5, and the division and the
# reference's own cast add 2 u (measured on the CPU: 3.3e-7, the scan is a
# tree).  bfloat16 anywhere — a price, the sum, the result — is off by up
# to 2**-8 = 3.9e-3 (eight bits of significand), and by more than 2e-4 on
# nine rows of ten.  2e-4 stands three times over the first bound and
# twenty times under the second.
AP_RTOL = 2e-4


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What the generator keeps between sends: how many events of the
    batch being filled the stream already holds (sends are made in the
    order they are sent), and the event-time offsets within a send."""
    return {"window_length": int(sizes["window_length"]), "fill": 0,
            "ts_offsets": np.arange(events_per_send(traffic),
                                    dtype=np.int64) // EVENTS_PER_MS}


def events_per_send(traffic: dict) -> int:
    return int(traffic["events_per_send"])


def clock_step_ms(traffic: dict) -> int:
    return max(1, events_per_send(traffic) // EVENTS_PER_MS)


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """One send, every column new: one rng call a column (a send made
    inside the closed loop has to cost well under a send's time).  It
    records the batch's fill before it and the rows it completes."""
    n = events_per_send(traffic)
    w, fill = plan_["window_length"], plan_["fill"]
    plan_["fill"] = (fill + n) % w
    price = rng.random(n, np.float32)
    price *= np.float32(traffic["price_span"])
    price += np.float32(traffic["price_lo"])
    return {
        "cols": [rng.integers(0, int(traffic["symbols"]), n, np.int64),
                 price,
                 rng.integers(1, int(traffic["volume_hi"]) + 1, n, np.int32)],
        "ts": clock_ms + plan_["ts_offsets"],
        "events": n,
        "fill": fill,
        "rows": (fill + n) // w * w,
    }


def expected_rows(send: dict) -> int:
    """1,000 rows for every batch whose last event is in this send."""
    return send["rows"]


def reference(sends: list, plan_: dict) -> list:
    """The plain thing: every price since the app started, in arrival
    order, cut into batches of `window_length`; within a batch the float64
    cumulative sum over 1 ... window_length, as float32; a batch's rows go
    to the send that holds its last event."""
    w = plan_["window_length"]
    prices = np.concatenate([s["cols"][1] for s in sends])
    n_b = prices.shape[0] // w
    ap = (np.cumsum(prices[:n_b * w].astype(np.float64).reshape(n_b, w),
                    axis=1) / np.arange(1, w + 1)).astype(np.float32)
    ap = ap.reshape(-1)
    ends = np.cumsum([s["events"] for s in sends])
    return [{"ap": ap[(end - s["events"]) // w * w:end // w * w]}
            for s, end in zip(sends, ends.tolist())]


def canonical(rows: dict) -> dict:
    """Delivery order, untouched: a running average means nothing in any
    other."""
    return rows


class Attribution:
    """Result row -> the send that completes it: the send in flight.  A
    row carries nothing that names a send; delivery is blocking with one
    send outstanding and every row of a send is delivered before the call
    returns (config.json `guarantees`), so this is exact — and a row that
    comes during another send's call is unexpected there and missing from
    its own."""

    def __init__(self, plan_: dict):
        self.sid = -1

    def on_issue(self, sid: int, send: dict) -> None:
        self.sid = sid

    def attribute(self, rows: dict) -> np.ndarray:
        return np.full(rows["ap"].shape[0], self.sid, np.int64)


# each number compared, with its limit.  The counts are exact; a row
# differs when it is outside AP_RTOL, and no row may
LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows against the reference's, both in delivery
    order: {number: value}, each held to LIMITS.  With the count right, row
    j is compared with row j; a NaN differs."""
    g, w = got["ap"], want["ap"]
    differing = 0
    if g.shape[0] == w.shape[0]:
        w64 = w.astype(np.float64)
        near = np.abs(g.astype(np.float64) - w64) <= AP_RTOL * np.abs(w64)
        differing = int(w.shape[0] - near.sum())
    return {"rows_missing": max(w.shape[0] - g.shape[0], 0),
            "rows_unexpected": max(g.shape[0] - w.shape[0], 0),
            "rows_differing": differing}


def control_rows(want: dict) -> dict:
    """What the nearest lower precision would deliver: the reference's
    `ap` carried as bfloat16."""
    return {"ap": to_bf16(want["ap"])}


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """Bytes the ALGORITHM needs to move through HBM for one send, from
    shapes: the events in, a row out for each (a send completes as many
    rows as it has events, give or take a batch), and the window's two
    buffers (the batch being filled, the batch before it) read and
    written.  Not what today's program moves."""
    n = events_per_send(traffic)
    return (n * EVENT_BYTES + n * ROW_BYTES +
            2 * 2 * int(sizes["window_length"]) * EVENT_BYTES)

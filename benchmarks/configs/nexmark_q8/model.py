"""nexmark_q8: traffic, plain reference and comparison — NEXmark query 8,
"Monitor New Users":

    SELECT Rstream(P.id, P.name, A.reserve)
    FROM Person [RANGE 12 HOUR] P, Auction [RANGE 12 HOUR] A
    WHERE P.id = A.seller

as the SiddhiQL join of two sliding `window.time(12 hours)` on
`P.id == A.seller`.  An arriving auction is paired with the person of its
seller iff that person arrived before it and less than the window (by stamp)
before it; an arriving person with each auction that named its id ahead of
time (the generator's `PERSON_ID_LEAD`) and is still less than the window
old.  Nothing else makes a row.

The generator is the public NEXmark generator's (Apache Beam
`sdks/java/testing/nexmark`), written here from what is known of it — every
constant recalled rather than read is listed in config.json `assumed` — and
seeded from `--seed`: events are numbered, event `n` is a person where
`n % 50 == 0`, an auction where it is 1, 2 or 3 (the 46 bids are another
app's); a connector drains the two topics in sends of `rows_per_send` rows,
`Person, Auction, Auction, Auction` a round, and stamps each row at arrival.

Numpy only, nothing of siddhi_tpu: it is the yardstick the program is held
to.  Ids and payloads are carried, never computed: every comparison is exact.
"""
from __future__ import annotations

import numpy as np

# -- the generator's constants (Beam GeneratorConfig / PersonGenerator /
#    AuctionGenerator; recalled, see config.json `assumed`) -----------------
PROPORTION_DENOMINATOR = 50       # 1 person : 3 auctions : 46 bids
AUCTIONS_PER_PERSON = 3
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10
NUM_CATEGORIES = 5
HOT_SELLER_RATIO = 100            # the hot seller: (lastPerson / 100) * 100
HOT_SELLER_SHARE = 4              # nextInt(4) > 0: 3 of 4 auctions
PERSON_ID_LEAD = 10
FIRST_NAMES, LAST_NAMES = 11, 9   # name = one of 99 pairs
US_CITIES, US_STATES = 10, 6
EVENT_US = 100                    # 10,000 events/s: the event clock
BASE_TIME_MS = 1_436_918_400_000  # the generator's base time (2015-07-15)
STAMP0_MS = 1_000_000             # the arrival clock's first stamp
STRING_IDS = 1 << 31              # a random string, as its interner id

STREAMS = ("Person", "Auction")
# bytes a row needs on the wire: its columns and its long stamp
PERSON_BYTES = 8 + 5 * 4 + 8 + 8
AUCTION_BYTES = 7 * 8 + 2 * 4 + 8
ROW_BYTES = 8 + 4 + 8 + 8         # id, name, reserve, stamp


def window_ms(sizes: dict) -> int:
    return int(sizes["window_hours"]) * 3_600_000


def events_per_send(traffic: dict) -> int:
    return int(traffic["rows_per_send"])


def clock_step_ms(traffic: dict) -> int:
    """The harness's own clock is not read: a row's stamp is its place in
    the stream (`stamps`)."""
    return 1


def plan(seed: int, traffic: dict, sizes: dict) -> dict:
    """What the generator keeps between sends: its place in the stream (the
    harness restarts a traffic's index after `prefill`; the stream goes on),
    and the auctions that named a person not yet arrived."""
    first = int(traffic["first_event"])
    assert first % PROPORTION_DENOMINATOR == 0
    return {"window_ms": window_ms(sizes),
            "active": int(sizes["active_people"]),
            "first_epoch": first // PROPORTION_DENOMINATOR,
            "sent": 0,                       # sends made so far
            "ahead": {}}                     # person base0 id -> [stamps]


def stamps(row0: int, n: int, traffic: dict) -> np.ndarray:
    """The arrival stamps of rows `row0 ... row0 + n` of the stream: one
    every `stamp_us`, in whole milliseconds, never stepping back."""
    r = row0 + np.arange(n, dtype=np.int64)
    return STAMP0_MS + r * int(traffic["stamp_us"]) // 1000


def person_row(b, plan_: dict, n: int):
    """The stream row at which person `b` (base-0 id) arrives; -1 for one
    from before the app started."""
    k = np.asarray(b, np.int64) - plan_["first_epoch"]
    return np.where(k >= 0, (k // n) * 4 * n + k % n, -1)


def make_send(rng, i: int, traffic: dict, plan_: dict, clock_ms: int) -> dict:
    """The next send of the stream: a person send, then three auction sends
    with the auctions of the same `n` epochs.  `rows` — what it is owed — is
    the generator's own bookkeeping: a person's arrival row is arithmetic."""
    n = events_per_send(traffic)
    k = plan_["sent"]
    plan_["sent"] = k + 1
    rnd, phase = divmod(k, 4)
    ts = stamps(k * n, n, traffic)
    e0 = plan_["first_epoch"] + rnd * n
    w = plan_["window_ms"]
    if phase == 0:
        epoch = e0 + np.arange(n, dtype=np.int64)
        cols = [
            FIRST_PERSON_ID + epoch,
            (rng.integers(0, FIRST_NAMES, n) * LAST_NAMES +
             rng.integers(0, LAST_NAMES, n)).astype(np.int32),
            rng.integers(0, STRING_IDS, n, np.int32),        # emailAddress
            rng.integers(0, STRING_IDS, n, np.int32),        # creditCard
            rng.integers(0, US_CITIES, n, np.int32),
            rng.integers(0, US_STATES, n, np.int32),
            BASE_TIME_MS + epoch * PROPORTION_DENOMINATOR * EVENT_US // 1000,
        ]
        rows, ahead = 0, plan_["ahead"]
        for b in [b for b in ahead if e0 <= b < e0 + n]:
            rows += sum(t + w > ts[b - e0] for t in ahead.pop(b))
        return {"stream": "Person", "cols": cols, "ts": ts, "events": n,
                "rows": int(rows)}
    q = (phase - 1) * n + np.arange(n, dtype=np.int64)
    epoch, j = e0 + q // AUCTIONS_PER_PERSON, q % AUCTIONS_PER_PERSON
    event = epoch * PROPORTION_DENOMINATOR + 1 + j
    date_time = BASE_TIME_MS + event * EVENT_US // 1000
    people = epoch + 1                       # persons created so far
    active = np.minimum(people, plan_["active"])
    seller = np.where(
        rng.integers(0, HOT_SELLER_SHARE, n) > 0,
        epoch // HOT_SELLER_RATIO * HOT_SELLER_RATIO,
        people - active + (rng.random(n) * (active + PERSON_ID_LEAD)
                           ).astype(np.int64))
    initial = price(rng, n)
    cols = [
        FIRST_AUCTION_ID + epoch * AUCTIONS_PER_PERSON + j,
        rng.integers(0, STRING_IDS, n, np.int32),            # itemName
        rng.integers(0, STRING_IDS, n, np.int32),            # description
        initial, initial + price(rng, n), date_time,
        date_time + 1 + rng.integers(0, int(traffic["auction_ms_hi"]), n),
        FIRST_PERSON_ID + seller,
        FIRST_CATEGORY_ID + rng.integers(0, NUM_CATEGORIES, n),
    ]
    row_a = k * n + np.arange(n, dtype=np.int64)
    row_p = person_row(seller, plan_, n)
    here = (row_p >= 0) & (row_p < row_a)
    seen = here & (STAMP0_MS + np.where(here, row_p, 0) *
                   int(traffic["stamp_us"]) // 1000 + w > ts)
    for b, t in zip(seller[row_p > row_a].tolist(),
                    ts[row_p > row_a].tolist()):
        plan_["ahead"].setdefault(b, []).append(t)
    return {"stream": "Auction", "cols": cols, "ts": ts, "events": n,
            "rows": int(seen.sum())}


def price(rng, n: int) -> np.ndarray:
    """The generator's `nextPrice`: 10 ** (6 u) dollars, in cents."""
    return np.round(10.0 ** (rng.random(n) * 6.0) * 100.0).astype(np.int64)


def expected_rows(send: dict) -> int:
    return send["rows"]


class _Rows:
    """Columns that grow at the end and are cut at the front."""

    def __init__(self, dtypes):
        self.cols = [np.empty(1 << 16, d) for d in dtypes]
        self.lo = self.hi = 0

    def append(self, *arrays) -> None:
        n = arrays[0].shape[0]
        if self.hi + n > self.cols[0].shape[0]:
            size = max(2 * (self.hi - self.lo + n), 1 << 16)
            for i, c in enumerate(self.cols):
                grown = np.empty(size, c.dtype)
                grown[:self.hi - self.lo] = c[self.lo:self.hi]
                self.cols[i] = grown
            self.hi, self.lo = self.hi - self.lo, 0
        for c, a in zip(self.cols, arrays):
            c[self.hi:self.hi + n] = a
        self.hi += n

    def view(self):
        return [c[self.lo:self.hi] for c in self.cols]


def reference(sends: list, plan_: dict) -> list:
    """The plain thing: every send since the app started, in order, over the
    persons by id and the auctions by seller with their stamps.  An auction
    looks its seller up among the persons that have arrived (ids kept
    sorted) and takes the one whose stamp is less than the window before its
    own; a person looks its id up among the auctions still less than the
    window old.  Then the send's rows join their side."""
    w = plan_["window_ms"]
    persons = _Rows((np.int64, np.int32, np.int64))     # id, name, stamp
    auctions = _Rows((np.int64, np.int64, np.int64))    # seller, reserve, ts
    out = []
    for send in sends:
        ts, cols = send["ts"], send["cols"]
        if send["stream"] == "Auction":
            seller, reserve = cols[7], cols[4]
            p_id, p_name, p_ts = persons.view()
            at = np.minimum(np.searchsorted(p_id, seller), p_id.size - 1) \
                if p_id.size else np.zeros(seller.shape, np.int64)
            hit = (p_id[at] == seller) & (p_ts[at] + w > ts) \
                if p_id.size else np.zeros(seller.shape, bool)
            out.append({"id": seller[hit], "name": p_name[at[hit]],
                        "reserve": reserve[hit]})
            auctions.append(seller, reserve, ts)
            continue
        ids, names = cols[0], cols[1]
        a_seller, a_reserve, a_ts = auctions.view()
        # auctions a window old by this send's first stamp are gone for good
        auctions.lo += int(np.searchsorted(a_ts, ts[0] - w, side="right"))
        a_seller, a_reserve, a_ts = auctions.view()
        near = np.nonzero((a_seller >= ids.min()) &
                          (a_seller <= ids.max()))[0]
        order = np.argsort(ids, kind="stable")
        at = order[np.minimum(np.searchsorted(ids[order], a_seller[near]),
                              ids.size - 1)]
        hit = (ids[at] == a_seller[near]) & (a_ts[near] + w > ts[at])
        out.append({"id": ids[at[hit]], "name": names[at[hit]],
                    "reserve": a_reserve[near[hit]]})
        p_id = persons.view()[0]
        if p_id.size and ids.min() <= p_id[-1] or (np.diff(ids) <= 0).any():
            # ids out of order: keep the persons sorted by id the plain way
            p_id, p_name, p_ts = persons.view()
            merged = np.argsort(np.concatenate([p_id, ids]), kind="stable")
            cat = [np.concatenate(pair)[merged] for pair in
                   ((p_id, ids), (p_name, names), (p_ts, ts))]
            persons.lo = persons.hi = 0
            persons.append(*cat)
        else:
            persons.append(ids, names, ts)
    return out


def brute_force(sends: list, window: int) -> list:
    """The same answers by an O(n^2) nested comparison (the model's own
    test holds `reference` to it on a small stream)."""
    p_id, p_name, p_ts = (np.zeros(0, np.int64), np.zeros(0, np.int32),
                          np.zeros(0, np.int64))
    a_seller, a_reserve, a_ts = (np.zeros(0, np.int64),) * 3
    out = []
    for send in sends:
        ts, cols = send["ts"], send["cols"]
        if send["stream"] == "Auction":
            j, k = np.nonzero((cols[7][:, None] == p_id[None, :]) &
                              (p_ts[None, :] + window > ts[:, None]))
            out.append({"id": p_id[k], "name": p_name[k],
                        "reserve": cols[4][j]})
            a_seller = np.concatenate([a_seller, cols[7]])
            a_reserve = np.concatenate([a_reserve, cols[4]])
            a_ts = np.concatenate([a_ts, ts])
        else:
            j, k = np.nonzero((cols[0][:, None] == a_seller[None, :]) &
                              (a_ts[None, :] + window > ts[:, None]))
            out.append({"id": cols[0][j], "name": cols[1][j],
                        "reserve": a_reserve[k]})
            p_id = np.concatenate([p_id, cols[0]])
            p_name = np.concatenate([p_name, cols[1]])
            p_ts = np.concatenate([p_ts, ts])
    return out


def canonical(rows: dict) -> dict:
    """By (id, name, reserve): the program may emit a send's rows in any
    order."""
    order = np.lexsort((rows["reserve"], rows["name"], rows["id"]))
    return {n: np.asarray(a)[order] for n, a in rows.items()}


class Attribution:
    """Result row -> the send in flight: delivery is blocking with one send
    outstanding, and a row carries nothing that names the send that made
    it.  A row delivered while a send that owes none is in flight is
    unexpected there."""

    def __init__(self, plan_: dict):
        self.sid = -1

    def on_issue(self, sid: int, send: dict) -> None:
        self.sid = sid

    def attribute(self, rows: dict) -> np.ndarray:
        return np.full(rows["id"].shape[0], self.sid, np.int64)


LIMITS = {"rows_missing": 0, "rows_unexpected": 0, "rows_differing": 0}


def compare(got: dict, want: dict) -> dict:
    """One send's delivered rows against the reference's, both canonical:
    the counts' difference, and with the count right the rows that differ in
    any column."""
    n_got, n_want = got["id"].shape[0], want["id"].shape[0]
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
    """The reference's rows with the long `reserve` carried through float32:
    the nearest precision below the configuration's, which loses the cents of
    every reserve above 2**24 and which no exact comparison passes."""
    return dict(want, reserve=want["reserve"].astype(np.float32)
                .astype(np.int64))


def least_bytes(traffic: dict, sizes: dict, config: dict) -> int:
    """From shapes, one send of the P, A, A, A round on average: the rows in
    and once more into their window; for each trigger row its key's head
    word; for each row owed the candidate's key and stamp (what the ON
    condition and the window's time must read) and the payload it
    contributes; the rows owed out."""
    n = events_per_send(traffic)
    owed = float(config["rows_per_event"]) * n
    rows_in = n * (PERSON_BYTES + 3 * AUCTION_BYTES) / 4
    return int(2 * rows_in + 4 * n + owed * (8 + 8 + 8) + owed * ROW_BYTES)

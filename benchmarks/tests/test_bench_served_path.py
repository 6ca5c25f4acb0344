"""`readers.served_path_ms` tells "results are delivered on another thread"
from "this send owed nothing" (PR 50): None iff some timed send OWED rows
and its subscriber did not run inside its call; a send that owed none counts
with `subscriber` 0, `post` 0 and `pre` the whole call.

For every stamp set an accepted cell can make — all its timed sends deliver
inside their calls, or all are served — the three entries that read it
(`send_to_delivery_` / `subscriber_` / `after_delivery_ms_per_send`) give
BIT FOR BIT what the expression before PR 50 gave, kept below as
`served_path_ms_before_pr50`: on handmade stamps and on the stamps a
rehearsal of each of the nine ACCEPTED cells records (`adding_pr.
ACCEPTED_CELLS`, a literal: a cell a later PR adds is a case of the same
test and is held to the general rule alone, so that one whose `having`
passes nothing in some sends, or a second served one, trips nothing —
`check_the_stamps_split_every_timed_send`, which `test_bench_adding_pr.py`
runs on the scratch adding PR's cells).  The one run whose reading changes
is the one no accepted cell makes: a blocking run with zero-row sends among
its timed sends (the two-stream fixture), None before, a number now, its
parts adding up to the mean of `returned - issued`."""
import importlib

import pytest

from adding_pr import ACCEPTED_CELLS, rehearse_here
from benchmarks.harness import loader, readers
from test_bench_two_streams import fixture_cell, run

PARTS = ("pre", "subscriber", "post")
READERS = {"pre": "send_to_delivery_ms_per_send",
           "subscriber": "subscriber_ms_per_send",
           "post": "after_delivery_ms_per_send"}
CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]
SERVED = "pattern_1m.served_paced"


def served_path_ms_before_pr50(run: dict, part: str):
    """`harness/readers.py` `served_path_ms` as PR 48's tree has it."""
    parts = []
    for st in run["stamps"]:
        if "returned" not in st:
            continue
        if st["subscriber_end"] is None:
            return None
        whole = st["returned"] - st["issued"]
        post = st["returned"] - st["subscriber_end"]
        split = {"subscriber": st["subscriber_s"], "post": post,
                 "pre": whole - st["subscriber_s"] - post}
        parts.append(split[part] * 1e3)
    if not parts:
        return None
    return sum(parts) / len(parts)


def read(run, part):
    return importlib.import_module(
        f"benchmarks.layer_metrics.{READERS[part]}").read(run)


def stamp(i, owed, inline, returned=True):
    """The i-th send's stamp: a call of 5 + i ms at irrational offsets, its
    subscriber 1.7 ms ending 0.3 ms before the return where it ran."""
    issued = 10.0 + i * 0.0123457
    st = {"due": issued - 1e-4, "owed": owed, "issued": issued,
          "subscriber_s": 0.0017 if inline else 0.0,
          "subscriber_end": issued + 0.0047 + i * 1e-3 if inline else None}
    if returned:
        st["returned"] = issued + 0.005 + i * 1e-3
    return st


def stamps(owed, inline, n=7):
    return {"stamps": [stamp(i, o, inl) for i, (o, inl) in
                       enumerate(zip(owed, inline))][:n]}


BLOCKING = stamps([655, 640, 700, 1, 3, 650, 9], [True] * 7)
ALL_SERVED = stamps([2048] * 7, [False] * 7)
LAST_NOT_BACK = {"stamps": BLOCKING["stamps"][:6] +
                 [stamp(6, 9, True, returned=False)]}


@pytest.mark.parametrize("run_", [BLOCKING, ALL_SERVED, LAST_NOT_BACK,
                                  {"stamps": []}],
                         ids=["blocking", "served", "last_not_back", "none"])
@pytest.mark.parametrize("part", PARTS)
def test_what_an_accepted_cell_stamps_reads_as_before(run_, part):
    got = readers.served_path_ms(run_, part)
    assert got == served_path_ms_before_pr50(run_, part)
    assert read(run_, part) == got
    assert (got is None) == (run_ is ALL_SERVED or not run_["stamps"])


def test_served_means_an_owed_send_whose_subscriber_did_not_run():
    # one served send among blocking ones: results came on another thread
    mixed = stamps([5, 5, 5], [True, False, True])
    # a served run some of whose sends owe nothing is served all the same
    served_with_nothing_owed = stamps([2048, 0, 2048], [False] * 3)
    for run_ in (mixed, served_with_nothing_owed):
        for part in PARTS:
            assert readers.served_path_ms(run_, part) is None
            assert served_path_ms_before_pr50(run_, part) is None


def test_a_zero_row_send_counts_with_the_whole_call_before_delivery():
    run_ = stamps([5, 0, 7, 0], [True, False, True, False])
    assert served_path_ms_before_pr50(run_, "pre") is None
    got = {p: readers.served_path_ms(run_, p) for p in PARTS}
    calls = [(st["returned"] - st["issued"]) * 1e3 for st in run_["stamps"]]
    assert got["subscriber"] == pytest.approx(1.7 * 2 / 4, rel=1e-12)
    assert got["post"] == pytest.approx(0.3 * 2 / 4, rel=1e-9)
    assert sum(got.values()) == pytest.approx(sum(calls) / 4, rel=1e-12)
    # "per send" is every timed send: the zero-row ones are in the mean
    assert got["pre"] == pytest.approx(
        (calls[0] - 2.0 + calls[1] + calls[2] - 2.0 + calls[3]) / 4,
        rel=1e-12)
    # a zero-row send whose subscriber DID run (another send's rows, an
    # empty batch) is split like any other
    ran = stamps([0, 4], [True, True])
    for p in PARTS:
        assert readers.served_path_ms(ran, p) == \
            served_path_ms_before_pr50(ran, p) is not None
    # nothing owed, nothing delivered, in every send: the whole call
    none_owed = stamps([0, 0, 0], [False] * 3)
    assert readers.served_path_ms(none_owed, "subscriber") == 0.0
    assert readers.served_path_ms(none_owed, "post") == 0.0


# -- recorded stamps: a rehearsal of each cell of the table -----------------------------

def check_the_stamps_split_every_timed_send(cell, done):
    """What the three entries read from the stamps of a rehearsal of `cell`.

    ANY cell, accepted or added later: None iff some timed send owed rows
    and its subscriber did not run inside its call; otherwise three numbers
    that add up to the mean of `returned - issued` over every timed send —
    whatever the sends owe, and whichever thread delivers.

    One of the nine ACCEPTED cells, besides: every timed send owes rows, all
    are delivered inside their calls (or, the served cell, none is), and the
    three read bit for bit what the expression before PR 50 read.  That is
    held of those nine names and of no other — a later deployment's `having`
    may pass nothing, a later cell may be served."""
    run_ = done.run
    assert len(run_["stamps"]) == run_["attempted"] >= 5
    timed = [st for st in run_["stamps"] if "returned" in st]
    owed = [st["owed"] for st in timed]
    assert all(isinstance(n, int) and n >= 0 for n in owed), owed
    inline = [st["subscriber_end"] is not None for st in timed]
    off_thread = any(n and not inl for n, inl in zip(owed, inline))
    got = {p: readers.served_path_ms(run_, p) for p in PARTS}
    for part in PARTS:
        assert read(run_, part) == got[part]
        assert (got[part] is None) == off_thread, (part, got)
    if not off_thread:
        calls = [(st["returned"] - st["issued"]) * 1e3 for st in timed]
        assert sum(got.values()) == pytest.approx(sum(calls) / len(calls),
                                                  rel=1e-9)
        assert all(v >= 0.0 for v in got.values()), got
    if cell in ACCEPTED_CELLS:
        assert all(n > 0 for n in owed), owed
        assert all(inline) if cell != SERVED else not any(inline)
        for part in PARTS:
            assert got[part] == served_path_ms_before_pr50(run_, part), part


def test_the_nine_accepted_cells_are_among_the_cases():
    assert set(ACCEPTED_CELLS) <= set(CELLS) and SERVED in ACCEPTED_CELLS
    assert len(ACCEPTED_CELLS) == 9


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_stamps_split_every_send_and_the_nine_read_as_before(
        monkeypatch, capsys, cell):
    check_the_stamps_split_every_timed_send(
        cell, rehearse_here(monkeypatch, capsys, cell, 0))


def test_a_blocking_run_with_zero_row_sends_reads_a_number():
    """The two-stream fixture: about one timed send in four owes nothing
    and its subscriber never runs.  Before PR 50 the three entries read
    None for the whole run."""
    out, said = run(fixture_cell())
    assert out["correct"] is True, said
    timed = out["stamps"]
    nothing = [st for st in timed if st["owed"] == 0]
    assert 0 < len(nothing) < len(timed) and len(timed) >= 20
    assert all(st["subscriber_end"] is None and st["subscriber_s"] == 0.0
               for st in nothing)
    assert all(st["subscriber_end"] is not None
               for st in timed if st["owed"])
    assert served_path_ms_before_pr50(out, "pre") is None
    got = {p: read(out, p) for p in PARTS}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    calls = [(st["returned"] - st["issued"]) * 1e3 for st in timed]
    assert sum(got.values()) == pytest.approx(sum(calls) / len(calls),
                                              rel=1e-9)
    assert got["subscriber"] > 0.0 and len(timed) == out["attempted"]

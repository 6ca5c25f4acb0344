"""BENCHMARK.json's `per_layer` table: one entry a quantity and loop kind.

`x.sat` moves `events_per_s` and lists closed-loop cells, `x.paced` moves
`latency_p50_ms` and lists open-loop ones, what moves `setup_s` has no
suffix, and a quantity only one cell has keeps its single-cell name.  A new
cell joins lists; it adds an entry only for a quantity no cell had.

`data/per_layer_parent.json` holds every (cell, quantity) pair the table
reported when it had one entry a cell (128 entries, PR 33's tree): each is
still reported in its cell, by the same reader file, exactly once.
`data/per_layer_pr48_names.json` holds the 89 names of PR 48's tree, which
stand first; its first 60 are the names PR 34 merged those pairs into.

Every pin is ONE-SIDED: it holds what was accepted — those pairs, those
names, the five cells of PR 34 in the lists they were in — and lets a later
PR add a cell to a list or an entry to the table (at most 128).  The checks
of a whole table are the `check_*(bench)` functions, which
`test_bench_adding_pr.py` runs with every other file's on conftest.py's
scratch adding PR; the last test here runs this file's on it."""
import json
import os

import pytest

from benchmarks.harness import loader
from adding_pr import NEW_CELLS, NEW_CLOSED, NEW_ENTRIES, NEW_OPEN

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the cells PR 34's table was merged for, by loop kind
CLOSED = ["pattern_1m.saturated", "pattern_32m.mesh4_saturated"]
OPEN = ["pattern_1m.paced", "pattern_16m_zipf.paced",
        "pattern_1m.served_paced"]
with open(os.path.join(DATA, "per_layer_parent.json")) as fh:
    PARENT = json.load(fh)
with open(os.path.join(DATA, "per_layer_pr48_names.json")) as fh:
    PR48_NAMES = json.load(fh)     # the 89 an adding PR appends behind
PR34_NAMES = PR48_NAMES[:60]       # the lists PR 34 wrote stay whole
# the one thing a cell gained: a reading its reader already served, left out
# of PR 33 for the cap alone
GAINED = {(cell, "obs_feed_idle_ms_per_send") for cell in OPEN}
SPAN_QUANTITIES = (
    "stage_ms_per_send", "route_keys_ms_per_send", "obs_feed_ms_per_send",
    "h2d_ms_per_send", "dispatch_ms_per_send", "fetch_ms_per_send",
    "demux_ms_per_send", "sink_ms_per_send", "send_unspanned_ms_per_send",
    "dispatches_per_send", "fetches_per_send",
    "idle_pre_dispatch_ms_per_send", "idle_post_step_ms_per_send")


class Table:
    """A BENCHMARK.json and what the harness resolves from it."""

    def __init__(self, bench):
        self.bench = bench
        self.entries = {e["name"]: e for e in bench["per_layer"]}
        self.cells = [w["name"] for w in bench["workloads"]]
        self._resolved, self._loops = {}, None

    def resolved(self, cell):
        """[(quantity, entry, reader module)] as the harness resolves the
        cell."""
        if cell not in self._resolved:
            self._resolved[cell] = [
                (e["name"].split(".", 1)[0], e, read.__module__)
                for e, read in loader.resolve(cell).per_layer]
        return self._resolved[cell]

    def reports(self, cell):
        return {e["name"] for e in self.bench["end_to_end"]
                if "workloads" not in e or cell in e["workloads"]}

    def of_kind(self, loop):
        if self._loops is None:
            self._loops = {c: loader.resolve(c).traffic["loop"]
                           for c in self.cells}
        return [c for c in self.cells if self._loops[c] == loop]

    def in_order(self, cells):
        return cells == [c for c in self.cells if c in cells]


TABLE = Table(loader.load_benchmark())


def check_pair(t, pair):
    hits = [(e, mod) for q, e, mod in t.resolved(pair["cell"])
            if q == pair["quantity"]]
    assert len(hits) == 1, (pair, [e["name"] for e, _ in hits])
    (entry, module), = hits
    # by the same reader file, moving the same end-to-end metric
    assert module == "bench_layer_" + pair["quantity"]
    assert entry["moves"] == pair["moves"]
    # under x.sat, x.paced, its un-suffixed name or its single-cell name
    q, old, new = pair["quantity"], pair["name"], entry["name"]
    kind = ".sat" if pair["moves"] == "events_per_s" else ".paced"
    assert new in (old, q, q + kind, q + ".traced"), (old, new)
    # the two blocking `pattern_1m` cells keep every name they had
    if pair["cell"] in ("pattern_1m.saturated", "pattern_1m.paced"):
        assert new == old


def check_room(t):
    assert len(PARENT) == 131          # 128 entries, three of two cells
    assert (PR34_NAMES[0], PR34_NAMES[-1]) == (
        "gen_late_ms_p99", "obs_feed_idle_ms_per_send.paced")
    names = [e["name"] for e in t.bench["per_layer"]]
    assert len(names) == len(set(names)) <= 128
    # PR 34's 60 are all there, first and in their order; so are the 89 of
    # PR 48's tree (PR 50): an adding PR appends, it inserts nothing
    assert names[:89] == PR48_NAMES and len(set(PR48_NAMES)) == 89


def check_cell(t, cell):
    quantities = [q for q, _, _ in t.resolved(cell)]
    assert len(quantities) == len(set(quantities))
    had = {p["quantity"] for p in PARENT if p["cell"] == cell}
    assert had <= set(quantities)
    assert {(cell, q) for q in set(quantities) - had} >= \
        {g for g in GAINED if g[0] == cell}


def check_entry(t, name):
    e = t.entries[name]
    assert t.in_order(e["workloads"])
    for cell in e["workloads"]:
        assert e["moves"] in t.reports(cell), (name, cell)
    held = name in PR34_NAMES          # the lists PR 34 wrote stay whole
    # the suffix is the loop kind, which is the metric moved
    if name.endswith(".sat"):
        assert e["moves"] == "events_per_s"
        assert set(e["workloads"]) <= set(t.of_kind("closed"))
        assert not held or set(CLOSED) <= set(e["workloads"])
    if name.endswith(".paced"):
        assert e["moves"] == "latency_p50_ms"
        assert set(e["workloads"]) <= set(t.of_kind("open"))
    if e["moves"] == "setup_s":
        assert "." not in name
        assert set(CLOSED + OPEN) <= set(e["workloads"])
    if len(e["workloads"]) == 1 and "." in name:
        suffix = name.rsplit(".", 1)[1]
        assert suffix in e["workloads"][0], name


def check_fetch_bytes(t, cell):
    got = {q: e for q, e, _ in t.resolved(cell)}
    new, old = got["fetch_bytes_per_send"], got["fetch_ms_per_send"]
    assert new["workloads"] == old["workloads"]
    assert new["moves"] == old["moves"] and new["layer"] == old["layer"]
    assert (new["source"], new["better"], new["unit"]) == \
        ("program_span", "lower", "bytes")


def check_obs_feed_idle(t, kind, cells):
    e = t.entries["obs_feed_idle_ms_per_send" + kind]
    twin = t.entries["obs_feed_ms_per_send" + kind]
    assert {k: v for k, v in e.items() if k != "name"} == \
        {k: v for k, v in twin.items() if k != "name"}
    assert set(cells) <= set(e["workloads"])
    assert e["layer"] == "host staging"


def check_span_quantities(t, cell):
    """A cell reads the span quantities BENCHMARK.json lists it for: a cell
    that opens no `route_keys` / `obs_feed` span is not asked for them; the
    five cells of PR 34 read all thirteen."""
    got = {q: e for q, e, _ in t.resolved(cell)}
    kind = ".sat" if cell in t.of_kind("closed") else ".paced"
    for q in SPAN_QUANTITIES:
        if cell not in t.entries[q + kind]["workloads"]:
            assert cell not in CLOSED + OPEN and q not in got, (cell, q)
            continue
        e = got[q]
        assert e["name"] == q + kind
        assert e["source"] == "program_span" and e["better"] == "lower"


OWN_SETS = [
    ("pattern_1m.served_paced", ".served", (
        "send_call_ms_per_send", "delivery_lag_ms_per_send",
        "ring_wait_ms_per_send", "sends_per_drain", "h2d_bytes_per_send",
        "ring_copy_ms_per_send", "ring_copy_roofline")),
    ("pattern_32m.mesh4_saturated", ".mesh4", (
        "shard_group_ms_per_send", "compiles_in_window"))]


def check_own_set(t, cell, suffix, want):
    # the cell's own: it stands first in their lists (a later cell may join)
    own = {n for n, e in t.entries.items() if e["workloads"][:1] == [cell]}
    assert own >= {n + suffix for n in want}
    moves = "latency_p50_ms" if cell in OPEN else "events_per_s"
    assert all(t.entries[n + suffix]["moves"] == moves for n in want)


# -- the same checks as functions of a whole table -------------------------------------

def check_every_pair_the_parent_reported(bench):
    t = Table(bench)
    for pair in PARENT:
        check_pair(t, pair)


def check_the_tables_names_and_room(bench):
    check_room(Table(bench))


def check_every_cell_of_the_table(bench):
    t = Table(bench)
    for cell in t.cells:
        check_cell(t, cell)
        check_fetch_bytes(t, cell)
        check_span_quantities(t, cell)


def check_every_entry_of_the_table(bench):
    t = Table(bench)
    for entry in t.entries:
        check_entry(t, entry)
    for kind, cells in ((".sat", CLOSED), (".paced", OPEN)):
        check_obs_feed_idle(t, kind, cells)


def check_the_own_sets(bench):
    t = Table(bench)
    for own in OWN_SETS:
        check_own_set(t, *own)


@pytest.mark.parametrize(
    "pair", PARENT, ids=[f"{p['cell']}-{p['name']}" for p in PARENT])
def test_every_pair_the_parent_reported_is_still_reported_once(pair):
    check_pair(TABLE, pair)


def test_the_table_keeps_pr34s_names_and_has_room():
    check_room(TABLE)


@pytest.mark.parametrize("cell", TABLE.cells)
def test_no_cell_resolves_one_quantity_twice_or_loses_one(cell):
    check_cell(TABLE, cell)


@pytest.mark.parametrize("name", sorted(TABLE.entries))
def test_an_entry_moves_a_metric_every_cell_of_its_list_reports(name):
    check_entry(TABLE, name)


# -- what the per-file pins held, one case each ----------------------------------------

@pytest.mark.parametrize("cell", TABLE.cells)
def test_fetch_bytes_stands_beside_fetch_ms_in_every_cell(cell):
    check_fetch_bytes(TABLE, cell)


@pytest.mark.parametrize("kind,cells", [(".sat", CLOSED), (".paced", OPEN)])
def test_obs_feed_idle_is_its_twins_entry_but_for_the_name(kind, cells):
    check_obs_feed_idle(TABLE, kind, cells)


@pytest.mark.parametrize("cell", TABLE.cells)
def test_every_cell_reads_the_span_quantities_it_is_listed_for(cell):
    check_span_quantities(TABLE, cell)


@pytest.mark.parametrize("cell,suffix,want", OWN_SETS,
                         ids=[c for c, _, _ in OWN_SETS])
def test_a_cells_own_quantities_keep_their_single_cell_entries(
        cell, suffix, want):
    check_own_set(TABLE, cell, suffix, want)


def test_a_seventh_cell_in_the_sat_lists_trips_no_pin(adding_pr):
    t = Table(adding_pr)
    assert t.cells[-4:] == list(NEW_CELLS)
    assert len(t.cells) == len(TABLE.cells) + 4
    assert NEW_CLOSED in t.entries["stage_ms_per_send.sat"]["workloads"]
    assert NEW_OPEN in t.entries["stage_ms_per_send.paced"]["workloads"]
    assert list(t.entries)[-3:] == [e[0] for e in NEW_ENTRIES]
    check_every_pair_the_parent_reported(adding_pr)
    check_the_tables_names_and_room(adding_pr)
    check_the_own_sets(adding_pr)
    check_every_cell_of_the_table(adding_pr)
    check_every_entry_of_the_table(adding_pr)

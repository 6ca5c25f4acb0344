"""`harness/section_ops.py`: a traced slice's device ops booked by (program,
section, part, primitive), and the ten `per_layer` entries PR 53 appended
that read it (`ops_sort_` / `ops_gather_` / `ops_scatter_ms_per_send` in all
ten cells, the four `agg_layout_<part>_ms_per_send.paced` of
`timewindow_256sym.paced`).

On hand-made events (names, families, two programs of one module), on the
four TPU recordings the section readers are tested with — `tiny_plain` and
`tiny_join` and `tiny_sections` and `tiny_served`, all older than the parts,
so every part reads `""` and the primitives still read — and on
`data/tiny_timewindow.xplane.pb.gz` (`record_section_ops.py`: two sends and
their timer steps of this tree's time-window cell at rehearsal sizes, on the
v5e), whose ops name their parts: a section's keys add up to the section's
total in its own reader, the whole to `total_s` and to the slice's busy
time.  A program that names nothing reads 0.0; without a device plane every
reader gives None.

The pins of the TABLE and of a RUN are `check_*(bench)` / `check_*(cell,
done)` functions, one-sided, as conftest.py's rule asks."""
import json
import os
import types

import pytest

from benchmarks.harness import join_sections as js
from benchmarks.harness import loader, plain_sections as ps
from benchmarks.harness import section_ops as so
from benchmarks.harness import step_sections as ss
from benchmarks.harness import trace_reduce as tr
from test_bench_step_sections import DATA, reader, recorded_run

BENCH = loader.load_benchmark()
TW = "timewindow_256sym.paced"
PRIMITIVES = ("ops_sort_ms_per_send", "ops_gather_ms_per_send",
              "ops_scatter_ms_per_send")
LAYOUT_PARTS = ("order", "invert", "to_sorted", "from_sorted")
APPENDED = [q + kind for q in PRIMITIVES for kind in (".sat", ".paced")] + [
    f"agg_layout_{part}_ms_per_send.paced" for part in LAYOUT_PARTS]
# recording -> (the section reader of its programs, its key on the run)
RECORDED = {
    "tiny_plain": (ps, "plain_sections"),
    "tiny_join": (js, "join_sections"),
    "tiny_sections": (ss, "step_sections"),
    "tiny_served": (ss, "step_sections"),
    "tiny_timewindow": (ps, "plain_sections"),
}


# the one recording this file brings; until `record_section_ops.py` has run
# on the chip the cases that read it say so and skip
needs_the_recording = pytest.mark.skipif(
    not os.path.exists(os.path.join(DATA, "tiny_timewindow.xplane.pb.gz")),
    reason="data/tiny_timewindow.xplane.pb.gz is not recorded yet: run "
           "benchmarks/tests/record_section_ops.py on the chip")


# -- names ---------------------------------------------------------------------------

@pytest.mark.parametrize("tf_op,want", [
    ("jit(plain_step)/agg_layout/to_sorted/gather:",
     ("agg_layout", "to_sorted", "gather")),
    ("jit(plain_step)/agg_layout/order/jit(argsort)/sort:",
     ("agg_layout", "order", "sort")),
    ("jit(plain_step)/agg_scan/store/scatter-max:",
     ("agg_scan", "store", "scatter-max")),
    # no part: the primitive, a jitted helper or one of jax's own words
    # stands right after the section
    ("jit(plain_step)/window_order/gather:", ("window_order", "", "gather")),
    ("jit(plain_step)/window_order/jit(argsort)/sort:",
     ("window_order", "", "sort")),
    ("jit(pattern_step)/rect_8x256/nfa_advance/while/body/closed_call/"
     "jit(_where)/select_n:", ("nfa_advance", "", "select_n")),
    # the outermost section; one nested in it reads as its part
    ("jit(join_left)/join_window/window_order/to_sorted/gather:",
     ("join_window", "window_order", "gather")),
    ("jit(join_left)/join_select/project/mul:",
     ("join_select", "project", "mul")),
    # of a merged op's names the first
    ("jit(plain_step)/agg_scan/scan/add:;jit(plain_step)/project/div:",
     ("agg_scan", "scan", "add")),
    # no section; a parameter's name or nothing names no op at all
    ("jit(plain_step)/convert_element_type:",
     (None, "", "convert_element_type")),
    ("args[0]", (None, "", "")), ("", (None, "", "")), (None, (None, "", "")),
])
def test_a_tf_op_names_its_section_its_part_and_its_primitive(tf_op, want):
    assert so.named(tf_op) == want
    # the section is the one the section readers take
    mine = [n for n in (ps.named(tf_op), js.named(tf_op),
                        ss.named(tf_op)[0]) if n]
    assert want[0] in mine if mine else want[0] is None


def test_a_primitive_family_takes_its_variants_and_nothing_else():
    for prim in ("scatter", "scatter-add", "scatter-max", "scatter_mul",
                 "scatter_min"):
        assert so.family(prim, "scatter")
    for prim in ("gather", "dynamic_update_slice", "scatters", ""):
        assert not so.family(prim, "scatter")
    assert so.family("sort", "sort") and not so.family("argsort", "sort")
    assert so.family("gather", "gather") and \
        not so.family("dynamic_slice", "gather")


def plane_of(metadata, ops, modules):
    """A device plane as `section_ops.reduce_plane` reads one."""
    def line(name, events):
        return types.SimpleNamespace(name=name, events=lambda: iter(events))
    return types.SimpleNamespace(
        name="/device:TPU:0", metadata=metadata,
        lines=[line(tr.OPS_LINE, ops), line(tr.MODULES_LINE, modules)])


def test_two_programs_of_one_module_are_two_rows_and_an_unnamed_op_borrows():
    def op(pid, tf_op, nbytes=8, category="loop fusion"):
        return ("%op", {"program_id": pid, "tf_op": tf_op,
                        "hlo_category": category, "bytes_accessed": nbytes,
                        "source": f"/x/siddhi_tpu/core/a.py:{pid}",
                        "shape_with_layout": "u32[4]"})
    meta = {
        1: op(11, "jit(plain_step)/agg_layout/to_sorted/gather:", 100),
        2: op(22, "jit(plain_step)/agg_layout/to_sorted/gather:", 7),
        3: op(11, "jit(plain_step)/agg_scan/scan/while:", 1000),
        4: op(11, "", 64, "data formatting"),       # a copy in the loop
        5: op(11, "jit(plain_step)/agg_scan/scan/add:", 32),
        6: op(11, "", 5, "data formatting"),        # one nothing encloses
        7: op(33, "jit(convert_element_type)/convert_element_type:", 4),
        91: ("jit_plain_step(11)", {}), 92: ("jit_plain_step(22)", {}),
        93: ("jit_convert_element_type(33)", {}),
    }
    ops = [(1, 0.0, 10.0), (3, 10.0, 40.0), (4, 12.0, 15.0),
           (5, 20.0, 30.0), (6, 40.0, 42.0), (2, 50.0, 53.0),
           (7, 60.0, 61.0), (1, 70.0, 74.0),
           (1, 500.0, 600.0)]                        # past the slice
    modules = [(91, 0.0, 42.0), (92, 50.0, 53.0), (93, 60.0, 61.0),
               (91, 70.0, 74.0), (91, 500.0, 600.0)]
    keys, programs = so.reduce_plane(plane_of(meta, ops, modules),
                                     0.0, 100.0, 0.0)
    assert programs == {11: ["jit_plain_step", None, 2],
                        22: ["jit_plain_step", None, 1],
                        33: ["jit_convert_element_type", None, 1]}
    got = {k: tuple(v[:3]) for k, v in keys.items()}
    assert got == {
        # the same name in two programs: two rows
        (11, "agg_layout", "to_sorted", "gather"): (2, 14.0, 200),
        (22, "agg_layout", "to_sorted", "gather"): (1, 3.0, 7),
        # the loop's self time; its bytes are its body's, not counted twice;
        # the copy inside it names nothing and takes the loop's names
        (11, "agg_scan", "scan", "while"): (2, 20.0, 64),
        (11, "agg_scan", "scan", "add"): (1, 10.0, 32),
        # nothing encloses it: unscoped, under its hlo_category
        (11, so.UNSCOPED, "", "<data formatting>"): (1, 2.0, 5),
        # a program none of whose ops names a section
        (33, "", "", "convert_element_type"): (1, 1.0, 4),
    }
    # a key's costliest op says where it was written
    assert keys[11, "agg_layout", "to_sorted", "gather"][3] == \
        (14.0, "/x/siddhi_tpu/core/a.py:11", "u32[4]")
    assert so._short("/x/siddhi_tpu/core/a.py:11") == "core/a.py:11"


# -- the recordings ------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """{recording: (run record with `section_ops` computed, what it
    printed)} — each reduced once for the whole file."""
    cache = {}

    def get(name, capsys):
        if name not in cache:
            run = recorded_run(tmp_path_factory.mktemp(name),
                               name + ".xplane.pb.gz")
            capsys.readouterr()
            module, key = RECORDED[name]
            getattr(module, key)(run)       # the cell's own reader, first
            capsys.readouterr()
            so.section_ops(run)
            cache[name] = (run, capsys.readouterr().out)
        return cache[name]
    return get


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=needs_the_recording) if n == "tiny_timewindow"
    else n for n in sorted(RECORDED)])
def test_a_sections_keys_add_up_to_the_section_readers_total(
        name, recorded, capsys):
    run, said = recorded(name, capsys)
    out, theirs = run["section_ops"], run[RECORDED[name][1]]
    assert so.section_ops(run) is out and capsys.readouterr().out == ""
    red = run["trace_reduced"]
    assert (out["sends"], out["devices"]) == \
        (theirs["sends"], theirs["devices"]) == (red["sends_in_slice"], 1)
    # section by section, `unscoped` among them: the same self times and the
    # same borrowing, so equal to float rounding
    assert set(theirs["sections_s"]) | {""} >= set(out["sections_s"])
    for section, sec in theirs["sections_s"].items():
        assert out["sections_s"].get(section, 0.0) == \
            pytest.approx(sec, rel=1e-9, abs=1e-15), section
    # what the readers book by module is this table's section ""
    assert out["sections_s"].get("", 0.0) == pytest.approx(
        sum(theirs["other_modules_s"].values()), rel=1e-9, abs=1e-15)
    by_module = {}
    for row in out["rows"]:
        if row["section"] == "":
            mod = out["programs"][row["program_id"]]["module"]
            by_module[mod] = by_module.get(mod, 0.0) + row["self_s"]
    for mod, sec in theirs["other_modules_s"].items():
        assert by_module.get(mod, 0.0) == pytest.approx(sec, abs=1e-15), mod
    # the whole: the rows, the sections, the readers' total, the busy time
    assert sum(r["self_s"] for r in out["rows"]) == \
        pytest.approx(out["total_s"], rel=1e-9)
    assert out["total_s"] == pytest.approx(theirs["total_s"], rel=1e-9)
    assert out["total_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert out["closure"]["ratio"] == pytest.approx(1.0, rel=1e-9)
    assert sum(p["self_s"] for p in out["programs"].values()) == \
        pytest.approx(out["total_s"], rel=1e-9)
    # ONE printed line, which says the same of itself
    (line,) = [ln for ln in said.splitlines()
               if ln.startswith("section ops: ")]
    assert said.count("section ops: ") == 1
    shown = json.loads(line[len("section ops: "):])
    held = shown["against"][RECORDED[name][1]]
    assert held["sections"] == len(theirs["sections_s"])
    assert held["max_diff_s"] < 1e-12 and held["total_diff_s"] < 1e-12
    assert sum(shown["sections_ms_per_send"].values()) == \
        pytest.approx(out["total_s"] * 1e3 / out["sends"])
    assert shown["reader_s"] > 0 and shown["closure"] == out["closure"]
    for prog in shown["programs"]:
        assert len(prog["top"]) <= so.TOP
        assert all(len(row) == len(prog["keys"]) for row in prog["top"])
        assert sum(row[4] for row in prog["top"]) + \
            prog["rest"]["ms_per_send"] == pytest.approx(prog["ms_per_send"])
        if prog["executions"]:
            assert prog["ms_per_execution"] * prog["executions"] == \
                pytest.approx(prog["ms_per_send"] * out["sends"])


def rows_of(out, **want):
    return [r for r in out["rows"]
            if all(r[k] == v for k, v in want.items())]


def ms(rows, out):
    return sum(r["self_s"] for r in rows) * 1e3 / out["sends"]


def test_a_recording_older_than_the_parts_reads_no_part_and_every_primitive(
        recorded, capsys):
    run, _ = recorded("tiny_plain", capsys)
    out = run["section_ops"]
    assert {r["part"] for r in out["rows"]} == {""}
    # the movers, not the sorts: what PRs 44 and 52 found by micro-timings
    # stands in a trace recorded before either
    order = "window_order"
    sort, gather = (ms(rows_of(out, section=order, primitive=p), out)
                    for p in ("sort", "gather"))
    assert 0 < sort < 0.02 and gather > 10 * sort
    assert ms(rows_of(out, section="window_state", primitive="scatter"),
              out) > ms(rows_of(out, section="window_state"), out) * 0.9
    assert ms(rows_of(out, section="agg_scan", primitive="scatter-max"),
              out) > 0
    # a key says where its costliest op was written, and what it moved
    top = rows_of(out, section="agg_layout", primitive="gather")[0]
    assert "siddhi_tpu/core/selector.py:" in top["source"]
    assert top["shape"].startswith("u32[") and top["bytes"] > 0
    assert top["ops"] == 30.0                  # 15 a send, whole numbers
    # the readers: three families over every program, one part of none
    got = {q: reader(q)(run) for q in PRIMITIVES}
    assert 0 < got["ops_sort_ms_per_send"] == pytest.approx(
        ms(rows_of(out, primitive="sort"), out))
    assert got["ops_scatter_ms_per_send"] == pytest.approx(ms(
        rows_of(out, primitive="scatter") +
        rows_of(out, primitive="scatter-max"), out))
    assert sum(got.values()) < reader("device_busy_ms_per_send")(run)
    for part in LAYOUT_PARTS:
        assert reader(f"agg_layout_{part}_ms_per_send")(run) == 0.0
    assert so.part_ms_per_send(run, "agg_layout", "") == pytest.approx(
        reader("agg_layout_ms_per_send")(run))


def test_the_join_and_pattern_recordings_read_their_programs(recorded,
                                                             capsys):
    run, _ = recorded("tiny_join", capsys)
    out = run["section_ops"]
    mods = sorted(p["module"] for p in out["programs"].values())
    assert {"jit_join_left", "jit_join_right"} <= set(mods)
    # a section nested in another reads as its part
    assert {r["part"] for r in rows_of(out, section="join_window")} == \
        {"", "window_order"}
    assert {r["part"] for r in rows_of(out, section="join_select")} == \
        {"project"}
    assert reader("ops_gather_ms_per_send")(run) > \
        ms(rows_of(out, section="join_pairs", primitive="gather"), out) > 0
    run, _ = recorded("tiny_sections", capsys)
    out = run["section_ops"]
    # three rectangles of one module: three rows, each with its rectangle
    rects = sorted(p["rect"] for p in out["programs"].values()
                   if p["module"] == "jit_pattern_step")
    assert rects == ["rect_512x4", "rect_64x32", "rect_8x256"]
    assert all(p["executions"] == 2.0 for p in out["programs"].values())
    assert reader("ops_sort_ms_per_send")(run) == 0.0     # no sort: 0, not None
    assert reader("ops_gather_ms_per_send")(run) > 0


@needs_the_recording
def test_the_time_windows_recording_names_its_parts(recorded, capsys):
    run, said = recorded("tiny_timewindow", capsys)
    out = run["section_ops"]
    # ONE module, TWO programs: the send's step and the timer's
    steps = [p for p in out["programs"].values()
             if p["module"] == "jit_plain_step"]
    assert len(steps) == 2 and all(p["executions"] >= 1 for p in steps)
    shown = json.loads(said.split("section ops: ", 1)[1].splitlines()[0])
    assert [p["module"] for p in shown["programs"][:2]] == \
        ["jit_plain_step"] * 2
    # every op of the two sections stands under one of its parts
    parts = {s: {r["part"] for r in rows_of(out, section=s)}
             for s in ("agg_layout", "agg_scan")}
    assert parts == {"agg_layout": {"keys", *LAYOUT_PARTS},
                     "agg_scan": {"scan", "store"}}
    # ... and the sections without parts read none
    assert {r["part"] for r in out["rows"] if r["section"] in
            ("window_fill", "window_state", "project", so.UNSCOPED, "")} \
        == {""}
    # which primitive does a part's work
    for part, prim in (("order", "sort"), ("invert", "scatter"),
                       ("to_sorted", "gather"), ("from_sorted", "gather")):
        mine = rows_of(out, section="agg_layout", part=part)
        assert max(mine, key=lambda r: r["self_s"])["primitive"] == prim, \
            part
    assert rows_of(out, section="agg_scan", part="store",
                   primitive="scatter-max")
    # the four entries and `keys` are the section
    got = {p: reader(f"agg_layout_{p}_ms_per_send")(run)
           for p in LAYOUT_PARTS}
    assert all(v > 0 for v in got.values())
    keys = so.part_ms_per_send(run, "agg_layout", "keys")
    assert keys > 0 and sum(got.values()) + keys == pytest.approx(
        reader("agg_layout_ms_per_send")(run))
    assert so.part_ms_per_send(run, "agg_scan", "scan") + \
        so.part_ms_per_send(run, "agg_scan", "store") == pytest.approx(
            ps.section_ms_per_send(run, "agg_scan"))
    # the movers, not the sort
    assert got["order"] < min(got["invert"], got["to_sorted"],
                              got["from_sorted"])
    families = {q: reader(q)(run) for q in PRIMITIVES}
    assert all(v > 0 for v in families.values())
    assert sum(families.values()) < reader("device_busy_ms_per_send")(run)


def test_a_rehearsals_host_ops_name_the_parts_of_this_trees_step(
        monkeypatch, capsys):
    """No chip in it: a traced CPU rehearsal of the time window's cell,
    its XLA:CPU op events named through the trace's own `HloProto`s
    (`join_sections.host_ops`, the stand-in the join reader walks a
    rehearsal with) and booked by `reduce_plane` — THIS tree's step says
    its parts to the reducer.  Its times are no metric."""
    from adding_pr import rehearse_here
    done = rehearse_here(monkeypatch, capsys, TW, 1, seconds=1.5)
    path = tr.newest_xplane(done.run["trace_dir"])
    planes = so.xspace.read(path)
    host = next(p for p in planes if p.name.startswith("/host:CPU"))
    lo, hi, _sends = ss.slice_of(host)
    keys, programs = so.reduce_plane(js.host_ops(path, planes), lo, hi, 0.0)
    assert "jit_plain_step" in programs
    parts = {}
    for (pid, section, part, _prim), rec in keys.items():
        if pid == "jit_plain_step":
            parts.setdefault(section, set()).add(part)
            assert rec[0] > 0
    assert parts["agg_layout"] == {"keys", *LAYOUT_PARTS}
    assert parts["agg_scan"] == {"scan", "store"}
    assert parts["window_fill"] == parts["window_state"] == {""}
    prims = {part: {k[3] for k in keys if k[1:3] == ("agg_layout", part)}
             for part in LAYOUT_PARTS}
    assert "sort" in prims["order"] and "scatter" in prims["invert"]
    assert "gather" in prims["to_sorted"] and "gather" in prims["from_sorted"]
    assert ("jit_plain_step", "agg_scan", "store", "scatter-max") in keys


def spans_run(tmp_path):
    """`tiny_spans.xplane.pb` (three sends of a `jit__lambda` on the v5e,
    older than every scope) as a run record."""
    path = tmp_path / "plugins" / "profile" / "1"
    path.mkdir(parents=True)
    with open(f"{DATA}/tiny_spans.xplane.pb", "rb") as src:
        (path / "t.xplane.pb").write_bytes(src.read())
    return {"trace_dir": str(tmp_path),
            "trace_reduced": tr.reduce_trace(str(path / "t.xplane.pb"))}


def test_a_program_that_names_nothing_reads_zero_not_none(tmp_path):
    run = spans_run(tmp_path)
    out = so.section_ops(run)
    (row,) = out["rows"]
    assert (row["section"], row["part"], row["primitive"]) == ("", "", "add")
    assert out["total_s"] == pytest.approx(run["trace_reduced"]["busy_s"])
    for name in APPENDED:
        assert reader(name.split(".")[0])(run) == 0.0, name


def test_without_a_device_plane_every_reader_gives_none(tmp_path,
                                                        monkeypatch):
    # what a CPU rehearsal's trace holds: host planes alone (the run check
    # below holds a real one, through `test_bench_adding_pr.py`)
    read = so.xspace.read
    monkeypatch.setattr(so.xspace, "read", lambda path: [
        p for p in read(path) if not p.name.startswith("/device:")])
    run = spans_run(tmp_path)
    assert so.section_ops(run) is None and run["section_ops"] is None
    for name in APPENDED:
        assert reader(name.split(".")[0])(run) is None, name
    assert so.section_ops({}) is None                 # an untraced run


# -- the table, one-sided ------------------------------------------------------------

def check_the_ten_appended_entries(bench):
    """The ten entries stay, together and in order, behind what stood before
    them; the primitive families list every cell `device_busy_ms_per_send`
    lists (a later cell joins both), the four parts list the time window's
    cell first; each resolves to its own reader file."""
    names = [e["name"] for e in bench["per_layer"]]
    at = names.index(APPENDED[0])
    assert names[at:at + len(APPENDED)] == APPENDED
    assert 98 <= at and len(names) <= 128
    by_name = {e["name"]: e for e in bench["per_layer"]}
    for n in APPENDED:
        e = by_name[n]
        assert (e["unit"], e["better"], e["source"], e["layer"]) == \
            ("ms", "lower", "device_trace", "device step"), n
        assert e["moves"] == ("events_per_s" if n.endswith(".sat")
                              else "latency_p50_ms")
    for q in PRIMITIVES:
        for kind in (".sat", ".paced"):
            busy = by_name["device_busy_ms_per_send" + kind]["workloads"]
            mine = by_name[q + kind]["workloads"]
            assert len(busy) >= 5 and mine[:5] == busy[:5]
            assert set(mine) <= set(busy)
    for part in LAYOUT_PARTS:
        e = by_name[f"agg_layout_{part}_ms_per_send.paced"]
        assert e["workloads"][:1] == [TW]
    # every cell of the table that reports a device step reads the three
    # families, under its loop's suffix, from their own files
    cells = {c for kind in (".sat", ".paced") for c in
             by_name["device_busy_ms_per_send" + kind]["workloads"][:5]}
    assert len(cells) == 10
    for cell in sorted(cells):
        got = {e["name"].split(".")[0]: read.__module__
               for e, read in loader.resolve(cell).per_layer
               if e["name"] in APPENDED}
        want = set(PRIMITIVES) | ({
            f"agg_layout_{p}_ms_per_send" for p in LAYOUT_PARTS}
            if cell == TW else set())
        assert set(got) == want, cell
        assert all(mod == "bench_layer_" + q for q, mod in got.items())


def test_the_ten_appended_entries():
    check_the_ten_appended_entries(BENCH)


# -- a run, one-sided ----------------------------------------------------------------

def check_a_cpu_rehearsal_books_no_device_op(cell, done):
    """A rehearsal has no device plane: `section_ops` gives None, prints no
    line, and none of its entries is among the metrics computed."""
    if not done.trace:
        return
    assert "section ops: " not in done.out
    assert done.run.get("section_ops", None) is None
    withheld = next(ln for ln in done.out.splitlines() if "withheld" in ln)
    assert "ops_sort" not in withheld and "agg_layout_order" not in withheld

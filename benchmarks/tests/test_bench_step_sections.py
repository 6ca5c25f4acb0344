"""Device time by section of the pattern programs (`harness/step_sections.py`),
the `XSpace` wire reader under it (`harness/xspace.py`), the send's page
faults (`harness/send_stats.py`) and the `per_layer` entries PR 35 added (18,
less `step_select_ms_per_send.sat` / `.paced`, retired in PR 41).

On hand-made events (a loop's body is not counted twice, an op with no
`tf_op` takes its enclosing op's section, a loop the compiler rebuilt takes
its body's), on `data/tiny_served.xplane.pb.gz` as it is — a v5e recording
of the PARENT's scopes: three of its four sections are found, the rows'
reads and writes stand `unscoped` and there is no rectangle — on
`data/tiny_sections.xplane.pb.gz` (`record_sections.py`: a tiered send of
this tree on the v5e: sections add up to the slice's busy time, three
rectangles are told apart), on a CPU trace (no device plane: None from every
section reader), and the wire reader against a generated `xplane_pb2`
wherever one can be imported."""
import gzip
import importlib
import os
import re
import shutil

import pytest

from adding_pr import NEW_CLOSED, NEW_OPEN
from benchmarks.harness import loader, send_stats, step_sections as ss, xspace
from benchmarks.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDINGS = ("tiny_served.xplane.pb.gz", "tiny_sections.xplane.pb.gz")
BENCH = loader.load_benchmark()
ENTRIES = {e["name"]: e for e in BENCH["per_layer"]}
CLOSED = ["pattern_1m.saturated", "pattern_32m.mesh4_saturated"]
OPEN = ["pattern_1m.paced", "pattern_16m_zipf.paced",
        "pattern_1m.served_paced"]
SECTION_QUANTITIES = {
    "step_event_load_ms_per_send": ("event_load",),
    "step_state_load_ms_per_send": ("state_load",),
    "step_scan_ms_per_send": ("nfa_advance",),
    "step_state_store_ms_per_send": ("state_store",),
    "step_compact_ms_per_send": ("emission_compaction", "emission_bands"),
    "step_unscoped_ms_per_send": (ss.UNSCOPED,),
}
# two scopes with no entry since PR 41 (`step_select_ms_per_send` read 0.0 in
# every cell by construction: no fusion's root stands there); they stay in
# the program and in the printed `step sections:` line
RETIRED = ("match_rows", "selector")
MESH_ONLY = "step_mesh_reduce_ms_per_send"
HOT, FAULTS = "hot_tier_busy_ms_per_send", "page_faults_per_send"


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def recorded_run(tmp_path, name):
    """A run record whose trace is a recorded file, as run.py leaves it."""
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    path = str(d / "t.xplane.pb")
    with gzip.open(os.path.join(DATA, name)) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return {"trace_dir": str(tmp_path), "trace_reduced": tr.reduce_trace(path)}


# -- the arithmetic, on hand-made events -------------------------------------------

def test_a_loops_body_is_not_counted_twice():
    # a loop [0, 100) running two body ops, one op after it, one before the
    # slice and one cut by its end
    events = [(1, 0.0, 100.0), (2, 10.0, 40.0), (3, 50.0, 90.0),
              (4, 100.0, 130.0), (5, -50.0, -10.0), (6, 140.0, 190.0)]
    got = tr.self_times(events, -5.0, 150.0)
    assert [(mid, ns) for mid, ns, _ in got] == \
        [(1, 30.0), (2, 30.0), (3, 40.0), (4, 30.0), (6, 10.0)]
    assert [parent for _, _, parent in got] == [-1, 0, 0, -1, -1]
    # the self times add up to the union of the intervals in the slice
    assert sum(ns for _, ns, _ in got) == tr.total(tr.clip(
        tr.union([[s, e] for _, s, e in events]), -5.0, 150.0)) == 140.0


def test_an_op_that_names_no_section_takes_its_enclosing_ops():
    # loop A (names `nfa_advance`) > copy (names none) ; loop B the compiler
    # rebuilt (names none) > two ops of `state_store`, one of `state_load`,
    # one that names none ; a top-level op that names none
    own = ["nfa_advance", None, None, "state_store", "state_store",
           "state_load", None, None]
    self_ns = [5.0, 3.0, 2.0, 10.0, 10.0, 4.0, 1.0, 7.0]
    parents = [-1, 0, -1, 2, 2, 2, 2, -1]
    assert ss.resolve(own, self_ns, parents) == [
        "nfa_advance", "nfa_advance", "state_store", "state_store",
        "state_store", "state_load", "state_store", None]


def test_a_tf_op_names_its_outermost_section_and_its_rectangle():
    assert ss.named("jit(pattern_step)/rect_64x2048/nfa_advance/while/body/"
                    "closed_call/jit(_where)/select_n:") == \
        ("nfa_advance", "rect_64x2048")
    assert ss.named("jit(pattern_step)/nfa_advance/while:") == \
        ("nfa_advance", None)
    assert ss.named("jit(pattern_step_sharded)/rect_131072x4/shard_map/"
                    "mesh_reduce/psum:") == ("mesh_reduce", "rect_131072x4")
    # merged by the compiler: the first name decides
    assert ss.named("jit(f)/rect_8x4/emission_compaction/reshape;jit(f)/"
                    "rect_8x4/match_rows/reshape:") == \
        ("emission_compaction", "rect_8x4")
    assert ss.named("packed[0]:") == ss.named("") == ss.named(None) == \
        (None, None)
    # the two scopes whose entry is retired are still sections: a fusion
    # rooted there would show in the printed line
    assert set(RETIRED) <= set(ss.SECTIONS)
    assert ss.named("jit(pattern_step)/rect_8x4/selector/add:") == \
        ("selector", "rect_8x4")
    # a scope's name inside another word is not the scope
    assert ss.named("jit(selector_step)/my_rect_2x2/add:") == (None, None)
    assert ss.hot_rect(["rect_4096x4", "rect_64x2048", "rect_512x32",
                        ss.NO_RECT]) == "rect_64x2048"
    assert ss.hot_rect([ss.NO_RECT]) is None


# -- the parent's scopes, as recorded on the v5e ------------------------------------------

def test_the_parents_recording_reads_its_old_sections_and_a_large_unscoped(
        tmp_path, capsys):
    run = recorded_run(tmp_path, "tiny_served.xplane.pb.gz")
    out = ss.step_sections(run)
    assert "step sections: {" in capsys.readouterr().out
    assert ss.step_sections(run) is out              # computed once
    assert capsys.readouterr().out == ""
    red = run["trace_reduced"]
    assert (out["sends"], out["devices"]) == (4, 1)
    # PR 24 / 31's scopes, and nothing PR 35 added
    assert set(out["sections_s"]) == {
        "nfa_advance", "emission_compaction", "emission_bands", ss.UNSCOPED}
    assert out["sections_s"]["nfa_advance"] == pytest.approx(67.2e-6, rel=.01)
    assert out["sections_s"]["emission_bands"] == pytest.approx(8.6e-6,
                                                                rel=.01)
    # the key rows' reads and writes carried no scope there
    assert out["sections_s"][ss.UNSCOPED] > 0.5 * out["pattern_s"]
    assert {"custom fusion", "data formatting", "custom-call"} <= \
        set(out["unscoped_by_category_s"])
    assert sum(out["unscoped_by_category_s"].values()) == \
        pytest.approx(out["sections_s"][ss.UNSCOPED])
    assert out["rects_s"] == {ss.NO_RECT: pytest.approx(out["pattern_s"])}
    assert out["hot_rect"] is None
    # what ran outside the pattern program, by module
    assert set(out["other_modules_s"]) == {
        "jit_ring_append", "jit_ring_read", "jit_convert_element_type"}
    mods = dict(red["by_module"])
    for mod, s in out["other_modules_s"].items():
        assert s == pytest.approx(mods[mod], rel=1e-3)
    # `by_module` books self times too (a loop minus its body's ops), so
    # the pattern program reads there what it reads here
    assert out["pattern_s"] == pytest.approx(mods["jit_pattern_step"],
                                             rel=1e-9)
    assert out["pattern_s"] == pytest.approx(
        red["busy_s"] - sum(out["other_modules_s"].values()))
    # closure: to the last digit, both sides sum whole nanoseconds
    assert out["total_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert out["closure"]["ratio"] == pytest.approx(1.0, rel=1e-9)
    # the readers: six section quantities a number, no rectangle, no faults
    for q in SECTION_QUANTITIES:
        assert isinstance(reader(q)(run), float), q
    assert reader("step_scan_ms_per_send")(run) == \
        pytest.approx(67.2e-3 / 4, rel=.01)
    assert reader("step_state_store_ms_per_send")(run) == 0.0
    assert reader(HOT)(run) is None
    assert reader(FAULTS)(run) is None


# -- this tree's scopes: a tiered send, recorded on the v5e -------------------------------

@pytest.fixture(scope="module")
def tiered(tmp_path_factory):
    run = recorded_run(tmp_path_factory.mktemp("tiered"),
                       "tiny_sections.xplane.pb.gz")
    return run, ss.step_sections(run)


def test_sections_add_up_to_the_slices_busy_time(tiered):
    run, out = tiered
    red = run["trace_reduced"]
    assert out["sends"] == red["sends_in_slice"] == 2
    assert out["total_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert out["pattern_s"] + sum(out["other_modules_s"].values()) == \
        pytest.approx(out["total_s"])
    assert sum(out["rects_s"].values()) == pytest.approx(out["pattern_s"])
    per_send = sum(reader(q)(run) for q in SECTION_QUANTITIES) + \
        reader(MESH_ONLY)(run) + ss.section_ms_per_send(run, *RETIRED)
    assert ss.section_ms_per_send(run, *RETIRED) == 0.0
    others = sum(out["other_modules_s"].values()) * 1e3 / out["sends"]
    busy = reader("device_busy_ms_per_send")(run)
    assert per_send + others == pytest.approx(busy, rel=1e-9)
    # every section this tree's one-chip step has, each with time in it
    for section in ("event_load", "state_load", "nfa_advance", "state_store",
                    "emission_compaction", "emission_bands"):
        assert out["sections_s"][section] > 0, section
    assert "mesh_reduce" not in out["sections_s"]
    assert reader(MESH_ONLY)(run) == 0.0
    # the honesty check: what stands in no section is the smaller part
    assert out["sections_s"][ss.UNSCOPED] < 0.25 * out["pattern_s"]


def test_three_rectangles_are_told_apart(tiered):
    run, out = tiered
    rects = sorted(out["rects_s"], key=lambda r: int(
        re.match(r"rect_\d+x(\d+)", r).group(1)))
    assert len(rects) == 3 and ss.NO_RECT not in rects
    assert [re.match(r"rect_(\d+)x(\d+)", r) is not None for r in rects] == \
        [True] * 3
    assert out["hot_rect"] == rects[-1]
    # every rectangle ran every section of the step
    for rect in rects:
        assert {"state_load", "nfa_advance", "state_store"} <= \
            set(out["rect_sections_s"][rect]), rect
        assert sum(out["rect_sections_s"][rect].values()) == \
            pytest.approx(out["rects_s"][rect])
    # the hot tier is the longest scan, and the reader's number
    scans = [out["rect_sections_s"][r]["nfa_advance"] for r in rects]
    assert scans[-1] == max(scans)
    assert reader(HOT)(run) == pytest.approx(
        out["rects_s"][rects[-1]] * 1e3 / 2)
    assert reader(HOT)(run) < reader("device_busy_ms_per_send")(run)


def test_the_send_span_says_its_page_faults(tiered, capsys):
    run, _ = tiered
    got = send_stats.sends(run)
    assert "sends over the slice: {" in capsys.readouterr().out
    assert got["sends"] == got["with_minflt"] == 2 and got["minflt"] >= 0
    assert reader(FAULTS)(run) == got["minflt"] / 2
    assert send_stats.sends(run) is got and capsys.readouterr().out == ""


# -- no device plane: the CPU rehearsal ----------------------------------------------

def test_a_cpu_trace_reads_none_in_every_section_reader(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation as span
    step = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((64, 64), jnp.float32)
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(2):
        with span("bench:send_columns"):
            with span("siddhi:send", events=1):
                step(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.newest_xplane(str(tmp_path))
    run = {"trace_dir": str(tmp_path), "trace_reduced": tr.reduce_trace(path)}
    assert run["trace_reduced"]["sends_in_slice"] == 2
    assert ss.reduce_sections(path, 0.0) is None
    for q in (*SECTION_QUANTITIES, MESH_ONLY, HOT):
        assert reader(q)(run) is None, q
    # spans that say no `minflt` (a program older than the stat): None too
    assert reader(FAULTS)(run) is None
    # and a run with no trace at all
    for q in (*SECTION_QUANTITIES, MESH_ONLY, HOT, FAULTS):
        assert reader(q)({"trace_dir": None, "trace_reduced": None}) is None


# -- the wire reader against a generated xplane_pb2 -------------------------------------

def generated_xplane_pb2():
    for name in ("tensorflow.tsl.profiler.protobuf.xplane_pb2",
                 "tsl.profiler.protobuf.xplane_pb2",
                 "xprof.protobuf.xplane_pb2",
                 "tensorboard_plugin_profile.protobuf.xplane_pb2"):
        try:
            return importlib.import_module(name)
        except Exception:  # noqa: BLE001 — any import failure: try the next
            continue
    return None


@pytest.mark.parametrize("name", RECORDINGS)
def test_the_wire_reader_agrees_with_a_generated_xplane_pb2(name):
    pb2 = generated_xplane_pb2()
    if pb2 is None:
        pytest.skip("no generated xplane_pb2 can be imported here")
    path = os.path.join(DATA, name)
    space = pb2.XSpace()
    with gzip.open(path) as fh:
        space.ParseFromString(fh.read())

    def value(stat, names):
        kind = stat.WhichOneof("value")
        got = getattr(stat, kind)
        return names[got].name if kind == "ref_value" else got

    planes = xspace.read(path)
    assert [p.name for p in planes] == [p.name for p in space.planes]
    n_events = 0
    for mine, ref in zip(planes, space.planes):
        names = ref.stat_metadata
        assert mine.stat_names == {k: v.name for k, v in names.items()}
        assert mine.metadata == {
            k: (v.name, {names[s.metadata_id].name: value(s, names)
                         for s in v.stats})
            for k, v in ref.event_metadata.items()}
        assert [ln.name for ln in mine.lines] == [ln.name for ln in ref.lines]
        for line, ref_line in zip(mine.lines, ref.lines):
            got = list(line.events(stats=True))
            assert len(got) == len(ref_line.events)
            assert [e[:3] for e in got] == list(line.events())
            for (mid, s, e, stats), ev in zip(got, ref_line.events):
                n_events += 1
                assert mid == ev.metadata_id
                assert s == ref_line.timestamp_ns + ev.offset_ps // 1000
                assert e == s + ev.duration_ps // 1000
                assert stats == {names[x.metadata_id].name: value(x, names)
                                 for x in ev.stats}
    assert n_events > 1000


@pytest.mark.parametrize("name", RECORDINGS)
def test_the_wire_reader_gives_profile_datas_times(name, tmp_path):
    """... which is what lets the sections close against `trace_reduce`."""
    path = recorded_run(tmp_path, name)["trace_dir"]
    path = tr.newest_xplane(path)
    devices, _spans = tr.read_planes(path)
    (plane,) = [p for p in xspace.read(path) if p.name in devices]
    (line,) = [ln for ln in plane.lines if ln.name == tr.OPS_LINE]
    assert [(s, e) for _, s, e in line.events()] == \
        [(s, e) for _, s, e in devices[plane.name]["ops"]]


# -- the 16 entries (18 less the two retired in PR 41) --------------------------------

def check_the_entries_pr35_added(bench):
    entries = {e["name"]: e for e in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    want = {}
    for q in SECTION_QUANTITIES:
        want[q + ".sat"] = ("ms", "device_trace", "device step",
                            "events_per_s", CLOSED)
        want[q + ".paced"] = ("ms", "device_trace", "device step",
                              "latency_p50_ms", OPEN)
    want[MESH_ONLY + ".mesh4"] = ("ms", "device_trace", "device step",
                                  "events_per_s", CLOSED[1:])
    want[HOT + ".paced"] = ("ms", "device_trace", "device step",
                            "latency_p50_ms", OPEN)
    want[FAULTS + ".sat"] = ("faults", "program_span", "served path",
                             "events_per_s", CLOSED)
    want[FAULTS + ".paced"] = ("faults", "program_span", "served path",
                               "latency_p50_ms", OPEN)
    assert len(want) == 16
    for name, (unit, source, layer, moves, held) in want.items():
        e = entries[name]
        assert (e["unit"], e["source"], e["layer"], e["moves"],
                e["better"]) == (unit, source, layer, moves, "lower"), name
        # the cells it was accepted with stay; a later cell may join, in
        # BENCHMARK.json's order
        assert set(held) <= set(e["workloads"]), name
        assert e["workloads"] == [c for c in cells if c in e["workloads"]]
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # appended: PR 34's 60 entries come first, in their order
    names = [e["name"] for e in bench["per_layer"]]
    assert names[60:76] == list(want) and len(names) >= 76
    assert names[59] == "obs_feed_idle_ms_per_send.paced"
    assert not any(n.startswith("step_select_ms_per_send") for n in names)


def test_the_entries_pr35_added_obey_the_tables_naming_rule():
    check_the_entries_pr35_added(BENCH)


def test_a_seventh_cell_in_their_lists_trips_no_pin(adding_pr):
    lists = {e["name"]: e["workloads"] for e in adding_pr["per_layer"]}
    assert NEW_CLOSED in lists[FAULTS + ".sat"][-2:]
    assert NEW_OPEN in lists[FAULTS + ".paced"][-2:]
    assert NEW_OPEN in lists["step_scan_ms_per_send.paced"][-2:]
    check_the_entries_pr35_added(adding_pr)


@pytest.mark.parametrize("cell", CLOSED + OPEN)
def test_every_cell_resolves_the_new_quantities_to_their_reader_files(cell):
    got = {e["name"].split(".", 1)[0]: read.__module__
           for e, read in loader.resolve(cell).per_layer}
    new = set(SECTION_QUANTITIES) | {FAULTS}
    if cell in OPEN:
        new.add(HOT)
    if cell == CLOSED[1]:
        new.add(MESH_ONLY)
    for q in new:
        assert got[q] == "bench_layer_" + q
    assert (HOT in got) == (cell in OPEN)
    assert (MESH_ONLY in got) == (cell == CLOSED[1])


def test_no_new_benchmark_file_knows_an_op_by_name():
    """Sections and rectangles come from the trace's own `tf_op`: the files
    PR 35 added match on scope names and stat names only."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(here, "harness", f) for f in (
        "step_sections.py", "xspace.py", "send_stats.py")]
    files += [os.path.join(here, "layer_metrics", q + ".py") for q in (
        *SECTION_QUANTITIES, MESH_ONLY, HOT, FAULTS)]
    ops = re.compile(r"\b(fusion|scatter|gather|dynamic[-_](update[-_])?"
                     r"slice|all[-_]reduce|psum|pmax|pmin|copy[-_]start|"
                     r"custom[-_]call|jit_\w+)\b")
    for path in files:
        with open(path) as fh:
            code = "\n".join(
                ln for ln in fh.read().split('"""')[2::2])  # not docstrings
        assert not ops.search(code), (path, ops.search(code).group(0))

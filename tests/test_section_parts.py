"""PARTS — the second `jax.named_scope` level inside the sections that own a
step (PR 53; `observability/phases.py` lists them, `benchmarks/harness/
section_ops.py` books a traced slice's device ops by them).

Of the programs that carry them — the time-window cell's plain step, the
length-batch cell's, the join cell's two sides, each deployed through the
harness's own `Deployment` at rehearsal sizes — this holds three things:

- the lowered text WITH debug info names every part of the program's
  sections under its section (`jit(plain_step)/agg_layout/to_sorted/gather`);
- no op of a section that has parts stands outside one: the section's
  remainder is nothing, so an op a later edit adds unnamed shows as part
  `""` in the trace's table and trips this;
- parts are metadata only: the text WITHOUT debug info is, byte for byte,
  the text the same program lowers to with `jax.named_scope` patched to do
  nothing (`tests/test_accepted_cells_text.py` holds the same text to the
  parent's).
"""
import contextlib
import os
import re
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the parts of every section that has them, as phases.py's docstring lists
PARTS = {
    "agg_layout": ("keys", "order", "invert", "to_sorted", "from_sorted"),
    "agg_scan": ("scan", "store"),
    "window_order": ("order", "to_sorted"),
    "join_pairs": ("index", "take_this", "take_other"),
}
# program -> (cell, role, {section: the parts this program's ops name})
PROGRAMS = {
    "timewindow_step": ("timewindow_256sym.paced", "step", {
        "agg_layout": PARTS["agg_layout"], "agg_scan": PARTS["agg_scan"]}),
    # one group slot: the `in_order` layout sorts, permutes and gathers
    # nothing, so `agg_layout` holds its keys alone; the window sorts
    "lengthbatch_step": ("lengthbatch_1000.saturated", "step", {
        "agg_layout": ("keys",), "agg_scan": PARTS["agg_scan"],
        "window_order": PARTS["window_order"]}),
    "join_left": ("join_len128.saturated", "step[left]", {
        "join_pairs": PARTS["join_pairs"]}),
    "join_right": ("join_len128.saturated", "step[right]", {
        "join_pairs": PARTS["join_pairs"]}),
}
_LOC = re.compile(r'^(#loc\d+) = loc\("([^"]+)"', re.M)
_GATHER = re.compile(r'stablehlo\.gather.*: \(tensor<([^>]*)>.* loc\((#loc\d+)\)$',
                     re.M)


def lowered_texts(cell_name):
    """{role: (lowered text, lowered text with debug info)} of the programs
    `cell_name` runs through its prefill and four sends at rehearsal
    sizes."""
    from benchmarks.harness import loader, runner
    cell = loader.resolve(cell_name, rehearse=True)
    dep = runner.Deployment(cell, 7, annotate=False)
    out = {}
    try:
        pre = cell.traffic.get("prefill")
        if pre:
            dep.run_untimed(pre, int(pre["sends"]), "prefill")
        dep.run_untimed(cell.traffic, 4, "warm-up")
        dep.flush()
        for role, fn, specs in dep.rt.compiled_steps(cell.config["query"]):
            if specs is not None:
                lowered = fn.lower(*specs)
                out[role] = (lowered.as_text(),
                             lowered.as_text(debug_info=True))
    finally:
        dep.close()
    return out


@pytest.fixture(scope="module")
def texts():
    cache = {}

    def get(cell):
        if cell not in cache:
            cache[cell] = lowered_texts(cell)
        return cache[cell]
    return get


def op_names(named_text):
    """Every op name of a lowered text with debug info, as path
    components."""
    return [name.split("/") for _loc, name in _LOC.findall(named_text)
            if "/" in name]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_program_names_every_part_under_its_section(program, texts):
    cell, role, sections = PROGRAMS[program]
    names = op_names(texts(cell)[role][1])
    for section, parts in sections.items():
        under = {c[c.index(section) + 1] for c in names if section in c[:-1]}
        assert set(parts) <= under, (program, section, sorted(under))


def gathers(named_text):
    """Every `stablehlo.gather` of a lowered text with debug info, as (the
    op's name, its operand's type)."""
    name = dict(_LOC.findall(named_text))
    return [(name[loc], operand)
            for operand, loc in _GATHER.findall(named_text)]


def test_the_sorted_layout_moves_its_rows_once_each_way(texts):
    """The time-window cell's query — seven specs (`sum:` / `cnt:` of
    `sum(price)`, of `avg(price)` and of the `having`'s `total`, and
    `count:`), one layout, one wave: ONE gather under `to_sorted`, of the
    layout's own three columns and the seven contributions as u32 planes,
    and ONE under `from_sorted`, of the seven scans (PR 54; the parent made
    eleven and seven).  The length-batch cell's `in_order` layout gathers
    nothing (its whole text is the parent's:
    `tests/test_accepted_cells_text.py`)."""
    B = 10240                       # the rehearsal's rows a step
    mine = [(n.split("/", 1)[1], t) for n, t in gathers(
        texts("timewindow_256sym.paced")["step"][1]) if "/agg_layout/" in n]
    assert mine == [("agg_layout/to_sorted/gather", f"16x{B}xui32"),
                    ("agg_layout/from_sorted/gather", f"11x{B}xui32")]
    assert not [n for n, _t in gathers(
        texts("lengthbatch_1000.saturated")["step"][1]) if "/agg_layout/" in n]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_no_op_of_a_section_with_parts_stands_outside_one(program, texts):
    cell, role, _sections = PROGRAMS[program]
    seen = 0
    for c in op_names(texts(cell)[role][1]):
        for i, comp in enumerate(c[:-1]):          # c[-1]: the primitive
            if comp in PARTS:
                seen += 1
                assert i + 2 < len(c) and c[i + 1] in PARTS[comp], \
                    (program, "/".join(c))
                break
    assert seen > 20, (program, seen)


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_parts_move_no_op(program, texts, monkeypatch):
    cell, role, _sections = PROGRAMS[program]
    with_scopes = texts(cell)[role][0]
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    bare, named = lowered_texts(cell)[role]
    for section in PARTS:                 # the patch took: nothing is named
        assert f"/{section}/" not in named
    assert bare == with_scopes

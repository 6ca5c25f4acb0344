"""The sequential pattern programs from inside: every op under one named
SECTION and the program's `rect_<Kb>x<E>` scope (core/pattern_planner.py).

Scopes are `jax.named_scope`s: op-name metadata that a device trace carries
as each op's `tf_op`, which is what `benchmarks/harness/step_sections.py`
turns into device time by section.  Checked here on the COMPILED text of the
three roles the benchmark's cells run — the instructions that execute as ops
(the entry computation and the loop bodies; not the insides of a fusion,
which the trace shows as one op under its root's name): each one that
carries an `op_name` of the program names exactly one section and one
rectangle.  What the compiler puts in itself — copies, the loops it expands
an op into — carries no `op_name` at all and is reported, not judged: a
reader books it to the op that encloses it, else as `unscoped`
(`tests/test_state_planes.py` judges the same on the v5e's own compile).
"""
import collections
import dataclasses
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability.explain import compiled_steps

SECTIONS = ("event_load", "state_load", "nfa_advance", "state_store",
            "match_rows", "selector", "emission_compaction",
            "emission_bands", "mesh_reduce")
RECT = re.compile(r"rect_\d+x\d+$")
# what the device runs no op for
NOT_OPS = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")

QL = """
@app:playback
define stream T (key long, price float, volume int);
partition with (key of T)
begin
  @capacity(keys='2048', slots='4') @emit(rows='128') @info(name='q')
  from every e1=T[volume == 1] -> e2=T[volume == 2 and price >= e1.price]
       -> e3=T[volume == 3] -> e4=T[volume == 4 and price >= e3.price]
  select e1.key as k, e1.price as p1, e2.price as p2, e4.price as p4
  insert into M;
end;
"""         # the benchmark's flagship query (benchmarks/configs/pattern_1m)
T0 = 1_760_000_000_000


def rows(keys, vol, ts):
    k = np.asarray(list(keys), np.int64)
    return ([k, np.full(k.shape, 10.0, np.float32),
             np.zeros(k.shape, np.int32) + np.asarray(vol, np.int32)],
            np.asarray(ts, np.int64) + np.zeros(k.shape, np.int64))


def all_stages(keys, ts):
    k = np.repeat(np.asarray(list(keys), np.int64), 4)
    return rows(k, np.tile([1, 2, 3, 4], k.size // 4), ts)


def deploy(mesh=None):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(QL, mesh=mesh) if mesh is not None \
        else m.create_siddhi_app_runtime(QL)
    errors = []
    rt.set_exception_listener(errors.append)
    rt.add_batch_callback("q", lambda ts, b: None)
    rt.start()
    return m, rt, errors


def send(rt, batch):
    c, ts = batch
    rt.get_input_handler("T").send_columns(c, timestamps=ts)
    rt.flush()


def executed(hlo_text):
    """[(opcode, op_name)] of the instructions that run as device ops: the
    entry computation's and those of every computation a `while`, `call`
    or `conditional` of theirs reaches."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{") and not line.startswith(" "):
            head = re.match(r"(ENTRY )?%?([\w.\-]+) \(", line)
            if head:
                cur = head.group(2)
                comps[cur] = []
                entry = cur if head.group(1) else entry
            continue
        if line.startswith("}"):
            cur = None
        elif cur and re.match(r"\s+(ROOT )?%?[\w.\-]+ = ", line):
            comps[cur].append(line)
    reached, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in reached:
            continue
        reached.add(comp)
        for line in comps[comp]:
            if re.search(r" (while|call|conditional)\(", line):
                todo += re.findall(
                    r"(?:body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)", line)
                for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                        line):
                    todo += [c.strip().lstrip("%") for c in group.split(",")]
    out = []
    for comp in reached:
        for line in comps[comp]:
            opcode = re.search(r" ([a-z][a-z\-]*)\(", line.split(" = ", 1)[1])
            name = re.search(r'op_name="([^"]*)"', line)
            if opcode and opcode.group(1) not in NOT_OPS:
                out.append((opcode.group(1), name.group(1) if name else ""))
    return out


def judged(hlo_text, role):
    """(instructions of the program by what their `op_name` names, those
    that name no section or no rectangle, the compiler's own by opcode)."""
    named, short, compilers = collections.Counter(), [], \
        collections.Counter()
    for opcode, op_name in executed(hlo_text):
        # an instruction the compiler merged from several carries their
        # names joined by `;`: the first one's decides, as in the reader
        parts = op_name.split(";")[0].split("/")
        if parts[0] != f"jit({role})":
            compilers[opcode] += 1        # no op_name, or a parameter's
            continue
        sections = [p for p in parts if p in SECTIONS]
        rects = [p for p in parts if RECT.match(p)]
        assert len(sections) <= 1 and len(rects) <= 1, op_name
        if len(sections) == 1 and len(rects) == 1:
            named[sections[0], rects[0]] += 1
        else:
            short.append((opcode, op_name))
    return named, short, compilers


@pytest.fixture(scope="module")
def programs():
    """role -> compiled text of the program the runtime ran: the dense and
    the gather / scatter step off the mesh, the sharded step on four
    virtual devices."""
    out = {}
    for mesh in (None, Mesh(np.array(jax.devices()[:4]), ("shard",))):
        m, rt, errors = deploy(mesh)
        try:
            send(rt, all_stages(range(16), T0))          # contiguous keys
            send(rt, all_stages([3, 40, 700, 1500], T0 + 10))
            assert not errors, errors[:1]
            for _role, fn, specs in compiled_steps(rt.query_runtimes["q"]):
                if specs is not None:
                    out[fn._siddhi_role] = fn.lower(*specs).compile() \
                        .as_text()
        finally:
            m.shutdown()
    return out


@pytest.mark.parametrize("role", [
    "pattern_dense", "pattern_step", "pattern_step_sharded"])
def test_every_op_of_the_program_stands_in_one_section_and_its_rectangle(
        role, programs):
    named, short, compilers = judged(programs[role], role)
    total = sum(named.values()) + len(short)
    print(f"{role}: {total} instructions of the program by (section, "
          f"rectangle): {dict(named)}; naming no section or rectangle: "
          f"{short}; the compiler's own, by opcode: {dict(compilers)}")
    assert total >= 30
    # one rectangle a program, and the sections the role must have
    assert len({rect for _, rect in named}) == 1, named
    want = {"state_load", "nfa_advance", "state_store",
            "emission_compaction", "emission_bands"}
    if role == "pattern_step_sharded":
        want |= {"event_load", "mesh_reduce"}
    assert want <= {s for s, _ in named}, named
    assert len(short) < 0.05 * total, short


def test_a_tiered_send_compiles_one_signature_a_rectangle_each_named():
    """A skewed send goes out as three `[Kb, E]` tiers through the SAME
    jitted step: three signatures of one role, each under its own `rect_*`
    — which is how a trace tells the three executions apart."""
    m, rt, errors = deploy()
    try:
        qr = rt.query_runtimes["q"]
        send(rt, rows(range(2048), 0, T0 - 10))           # bind every key
        fn = qr.planned.steps["T"]
        seen = []

        def recording(*args):
            seen.append(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), args))
            return fn(*args)
        recording._siddhi_role = fn._siddhi_role
        qr.planned = dataclasses.replace(qr.planned, steps={"T": recording})
        qr._replan = None
        # one key 500 times, thirty keys 8 times, 200 keys once; gappy,
        # so that no tier's slots are a contiguous run (the dense step's)
        keys = np.concatenate([np.full(500, 7), np.repeat(
            np.arange(100, 160, 2), 8), np.arange(1000, 1400, 2)])
        send(rt, rows(keys, 1 + np.arange(keys.size) % 4,
                      T0 + np.arange(keys.size) // 64))
        assert not errors, errors[:1]
        shapes = [args[5].shape for args in seen]
        assert len(set(shapes)) == len(shapes) == 3, shapes
        assert sorted(e for _, e in shapes) == [1, 8, 512], shapes
        for args in seen:
            text = fn.lower(*args).as_text(debug_info=True)
            assert set(re.findall(r"rect_\d+x\d+", text)) == \
                {"rect_%dx%d" % args[5].shape}
        assert fn._cache_size() >= 3
    finally:
        m.shutdown()

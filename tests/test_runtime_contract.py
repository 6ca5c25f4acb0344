"""A query runtime is one declared type (core/runtime.py `_QueryRuntimeBase`).

Two halves.  (1) Every kind of runtime the engine wires — plain, keyed
window, partitioned pattern, K = 1 pattern, bucket join, table join, a
merge group and its members, a named window and its consumer — carries
every field the base declares, its plan every field shared code reads off
any plan, and NOTHING is stuck on from outside after wiring and a send.
(2) No module of the library probes a declared field with `getattr` /
`hasattr` / `__dict__`: a rename must fail at the read, not come back as a
default.  The probes that stay are listed, with the reason, in `KEPT`.
"""
import ast
import os

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core import join as joinmod
from siddhi_tpu.core import pattern_planner, planner, runtime
from siddhi_tpu.core.window import WindowProcessor
from siddhi_tpu.optimizer.mqo import MergedGroupRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(ROOT, "siddhi_tpu")

# -- the declaration ----------------------------------------------------

# instance fields `_QueryRuntimeBase.__init__` declares (the inventory of
# CHANGES.md, PR 45), and the two facts of the class beside them
RUNTIME_FIELDS = {
    "planned", "app", "callbacks", "batch_callbacks", "next_wakeup",
    "_wake_replaces", "_timers_coalesce",
    "_qlock", "_query_ast", "async_emit", "pipeline_emit", "serve_emit",
    "serve_ring_capacity", "_fuse", "_fuse_requested", "_fuse_excluded",
    "_replan", "table_op", "rate_limiter", "_merged", "_merge_excluded",
    "_touch", "_touch_group", "slot_allocator", "_dirty", "_jk",
    "_nfa_facts",
    "_ingest_ns", "_e2e_owed", "_pending_emit", "_serve_ring",
    "_fused_ingests", "_fused_cache", "_out_row_nbytes",
    "_shard_router_memo", "_stateobs_tick", "_stateobs_probe",
    "_stateobs_probe_caps", "_stateobs_probe_off",
}
CLASS_FIELDS = {"_kind", "adopts_staged"}
# what each class adds in its own __init__ (and may write later)
OWN_FIELDS = {
    runtime.QueryRuntime: {"_state"},
    runtime.PatternQueryRuntime: {"state", "_block_cache"},
    runtime.JoinQueryRuntime: {"state", "_lane_k", "_probe_depth",
                               "_ring_last_ts", "_window_dropped"},
    runtime.NamedWindowRuntime: {
        "definition", "schema", "wproc", "needs_timer",
        "output_event_type", "subscribers", "stream_callbacks", "_step",
        "state"},
    MergedGroupRuntime: {
        "group", "stream_id", "members", "units", "_junction",
        "in_schema", "_slots", "_state", "raw_body", "_step"},
}
# fields shared code reads off ANY of the three plans
PLAN_FIELDS = {
    "name", "out_schema", "output_target", "output_event_type",
    "selector_exec", "needs_timer", "keyed_window", "key_capacity",
    "mesh", "keyed_mesh", "slot_allocator", "slot_allocator2",
    "join_key_allocator", "window_key_allocator", "pair_allocs",
    "compact_rows", "emit_explicit", "emits_uuid", "mixed_kinds",
}
# fields one kind of plan (or a part of it) declares, that used to be
# probed: no probe of them may come back either
OTHER_DECLARED = {
    "in_deps", "host_scheduled", "session_key_pos", "fastpath", "lane_k",
    "raw_step", "stage_body", "partition_key_fn", "shard_fused_steps",
    "is_table", "is_named_window", "is_aggregation",
}

_S = "define stream S (sym long, price float);\n"
_PATTERN = ("@info(name='q') from every e1=S[price > 1.0] -> "
            "e2=S[price > e1.price] select e1.sym as s, e2.price as p "
            "insert into O;")
APPS = {
    "plain": (runtime.QueryRuntime, _S +
              "@info(name='q') from S[price > 0.0] select sym, price "
              "insert into O;"),
    "keyed_window": (runtime.QueryRuntime, _S +
                     "partition with (sym of S) begin @info(name='q') from "
                     "S#window.length(4) select sym, sum(price) as t "
                     "insert into O; end;"),
    "pattern_partitioned": (runtime.PatternQueryRuntime, _S +
                            "partition with (sym of S) begin " + _PATTERN +
                            " end;"),
    "pattern_k1": (runtime.PatternQueryRuntime, _S + _PATTERN),
    "join_bucket": (runtime.JoinQueryRuntime, _S +
                    "define stream R (sym long, v float);\n"
                    "@info(name='q') from S#window.length(4) join "
                    "R#window.length(4) on S.sym == R.sym select S.sym as "
                    "s, R.v as v insert into O;"),
    "join_table": (runtime.JoinQueryRuntime, _S +
                   "@PrimaryKey('sym') define table T (sym long, v float);\n"
                   "@info(name='q') from S join T on S.sym == T.sym select "
                   "S.sym as s, T.v as v insert into O;"),
    "merged_member": (runtime.QueryRuntime, _S +
                      "@info(name='q') from S[price > 0.0] select sym, "
                      "price insert into O;\n@info(name='q2') from "
                      "S[price > 5.0] select sym insert into O2;"),
    "named_window_consumer": (runtime.QueryRuntime, _S +
                              "define window W (sym long, price float) "
                              "length(4);\nfrom S insert into W;\n"
                              "@info(name='q') from W select sym, price "
                              "insert into O;"),
}


def base_init_fields():
    """The `self.<x> = ...` targets of `_QueryRuntimeBase.__init__`, read
    off the source: the test's list is the code's."""
    with open(os.path.join(LIB, "core", "runtime.py")) as fh:
        tree = ast.parse(fh.read())
    cls = next(n for n in tree.body if isinstance(n, ast.ClassDef)
               and n.name == "_QueryRuntimeBase")
    init = next(n for n in cls.body if isinstance(n, ast.FunctionDef)
                and n.name == "__init__")
    out = set()
    for node in ast.walk(init):
        targets = node.targets if isinstance(node, ast.Assign) else \
            [node.target] if isinstance(node, ast.AnnAssign) else []
        for t in targets:
            if isinstance(t, ast.Attribute) and \
                    isinstance(t.value, ast.Name) and t.value.id == "self":
                out.add(t.attr)
    return out


def test_the_inventory_is_the_base_classes_init():
    assert base_init_fields() == RUNTIME_FIELDS
    for f in CLASS_FIELDS:
        assert f in vars(runtime._QueryRuntimeBase)


def test_the_three_query_runtimes_share_the_one_base():
    for cls in (runtime.QueryRuntime, runtime.PatternQueryRuntime,
                runtime.JoinQueryRuntime):
        assert cls.__bases__ == (runtime._QueryRuntimeBase,)
        # name, the wake, the TIMER batch and _emit exist once: inherited
        for once in ("name", "_apply_wake", "_timer_batch", "_emit",
                     "mesh", "keyed_mesh", "shard_router"):
            assert once not in vars(cls), (cls.__name__, once)
    assert runtime._QueryRuntimeBase.__bases__ == (object,)


def _declared(qr):
    assert isinstance(qr, runtime._QueryRuntimeBase)
    missing = RUNTIME_FIELDS - set(vars(qr))
    assert not missing, (type(qr).__name__, sorted(missing))
    stuck_on = set(vars(qr)) - RUNTIME_FIELDS - OWN_FIELDS[type(qr)]
    assert not stuck_on, (type(qr).__name__, sorted(stuck_on))
    assert qr._kind in ("plain", "pattern", "join", "merged", None)


@pytest.mark.parametrize("kind", sorted(APPS))
def test_a_wired_runtime_carries_the_declared_fields_and_no_other(kind):
    cls, app = APPS[kind]
    m = SiddhiManager()
    try:
        rt = m.create_siddhi_app_runtime("@app:playback\n" + app)
        errors, got = [], []
        rt.set_exception_listener(errors.append)
        rt.add_batch_callback("q", lambda _ts, b: got.append(b["n_valid"]))
        rt.start()
        qr = rt.query_runtimes["q"]
        assert type(qr) is cls
        _declared(qr)
        missing = PLAN_FIELDS - {f for f in dir(qr.planned)}
        assert not missing, (kind, sorted(missing))
        assert qr._kind == {runtime.QueryRuntime: "plain",
                            runtime.PatternQueryRuntime: "pattern",
                            runtime.JoinQueryRuntime: "join"}[cls]
        assert qr._query_ast is not None and qr.name == "q"
        # the case is the kind it says it is
        if kind == "keyed_window":
            assert qr.planned.keyed_window
        elif kind == "pattern_partitioned":
            assert qr.planned.partition_positions and \
                qr.slot_allocator is not None and qr._dirty is not None
        elif kind == "pattern_k1":
            assert not qr.planned.partition_positions and \
                qr.slot_allocator is None and qr._dirty is None
        elif kind == "join_bucket":
            assert qr.planned.fastpath == "bucket" and qr._jk is not None
        elif kind == "join_table":
            assert qr.planned.fastpath == "table" and qr._jk is None
        elif kind == "merged_member":
            (mg,) = rt.merged_groups.values()
            assert qr._merged is mg and qr._qlock is mg._qlock
            _declared(mg)
            assert mg.planned is None and mg.name == f"merged:{mg.group}"
        elif kind == "named_window_consumer":
            nw = rt.named_windows["W"]
            _declared(nw)
            assert nw.planned is None and nw.name == "W" and \
                qr in nw.subscribers
        # a send (both sides of a join) leaves nothing stuck on either
        for sid, j in rt.junctions.items():
            if j.queries:
                rt.get_input_handler(sid).send_columns(
                    [np.arange(1, 9, dtype=np.int64) % 3,
                     np.linspace(2, 9, 8, dtype=np.float32)],
                    timestamps=np.full(8, 1000, np.int64))
        rt.flush()
        assert not errors, errors[:1]
        _declared(qr)
        for mg in rt.merged_groups.values():
            _declared(mg)
        for nw in rt.named_windows.values():
            _declared(nw)
    finally:
        m.shutdown()


def test_every_plan_and_window_declares_what_shared_code_reads():
    for plan in (planner.PlannedQuery, pattern_planner.PlannedPatternQuery,
                 joinmod.PlannedJoinQuery):
        declared = set(plan.__dataclass_fields__)
        assert PLAN_FIELDS <= declared, (
            plan.__name__, sorted(PLAN_FIELDS - declared))
    assert WindowProcessor.host_scheduled is False
    assert WindowProcessor.session_key_pos is None


def test_one_adapter_binds_a_runtime_for_the_junction():
    sub = runtime._Subscription
    assert sub.__slots__ == ("_qr", "_lead", "locks")
    with open(os.path.join(LIB, "core", "runtime.py")) as fh:
        tree = ast.parse(fh.read())
    # no class is defined inside a function or a loop
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.For, ast.While)):
            for inner in ast.walk(node):
                assert not isinstance(inner, ast.ClassDef), inner.name
    # the wiring block exists once
    src = open(os.path.join(LIB, "core", "runtime.py")).read()
    assert src.count(".async_emit = ") == 2      # the default, the wiring
    assert src.count("self.query_runtimes[name] = runtime") == 1
    assert src.count("def _apply_wake") == 1
    assert src.count("ev.TIMER") - src.count("!= ev.TIMER") == 1


# -- no probe of a declared field ---------------------------------------

# (file under siddhi_tpu/, field) -> how many probes stay, and why
KEPT = {
    # `_fire` is handed ANY timer target — a trigger, a rate limiter, an
    # aggregation, the purger — and gives a lock to one that has none
    ("core/runtime.py", "_qlock"): 2,
    # `shard_count` takes an app runtime, a mesh or a plan
    ("sharding/router.py", "mesh"): 1,
    # a jax array's sharding's mesh
    ("serving/ring.py", "mesh"): 1,
    # window CLASSES looked up by name (static lint, no instance)
    ("core/plan_facts.py", "needs_timer"): 1,
    ("analysis/facts.py", "needs_timer"): 1,
    # `state` of a runtime / named window / aggregation store on the
    # scrape path, under "metrics must not throw"
    ("sharding/metrics.py", "state"): 2,
    ("observability/memory.py", "state"): 1,
}
DECLARED = RUNTIME_FIELDS | CLASS_FIELDS | PLAN_FIELDS | OTHER_DECLARED | \
    {"state"}
# common words that are ALSO fields of other things (an app runtime's
# `name`, an AST node's, a thread's): not judged by name alone
NOT_JUDGED = {"name", "app"}


def _probes(tree):
    """(field, line) of every `getattr(x, "f"[, d])`, `hasattr(x, "f")`,
    `x.__dict__.get / pop / setdefault("f")` and `x.__dict__["f"]`."""
    for node in ast.walk(tree):
        key = None
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id in ("getattr", "hasattr") \
                    and len(node.args) >= 2:
                key = node.args[1]
            elif isinstance(fn, ast.Attribute) and \
                    fn.attr in ("get", "pop", "setdefault") and \
                    isinstance(fn.value, ast.Attribute) and \
                    fn.value.attr == "__dict__" and node.args:
                key = node.args[0]
        elif isinstance(node, ast.Subscript) and \
                isinstance(node.value, ast.Attribute) and \
                node.value.attr == "__dict__":
            key = node.slice
        if isinstance(key, ast.Constant) and isinstance(key.value, str):
            yield key.value, node.lineno


def test_no_module_probes_a_declared_field():
    found = {}
    for dirpath, _dirs, files in os.walk(LIB):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            rel = os.path.relpath(path, LIB).replace(os.sep, "/")
            with open(path) as fh:
                tree = ast.parse(fh.read())
            for field, line in _probes(tree):
                if field in DECLARED and field not in NOT_JUDGED:
                    found.setdefault((rel, field), []).append(line)
    over = {k: v for k, v in found.items() if len(v) > KEPT.get(k, 0)}
    assert not over, (
        "a declared field of a query runtime or its plan is probed "
        "(read it as an attribute, or list the probe in KEPT with its "
        f"reason): {over}")
    stale = {k: n for k, n in KEPT.items() if len(found.get(k, ())) != n}
    assert not stale, f"KEPT lists probes that are gone: {stale}"

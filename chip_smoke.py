#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

Drives the normal path once — `SiddhiManager.create_siddhi_app_runtime` ->
`InputHandler.send_columns` -> native host staging -> jitted step on the
device -> emission header / ring fetch -> callback — at the size the repo
calls its target (BASELINE.md: the 4-state pattern over 1M partition keys),
then a few windows of every other shape `bench.py` drives, then the REST
service, then (when the host has four devices) the same flagship on a
4-way mesh.  Every phase is checked BY VALUE against a plain numpy
computation of the same semantics on the same seeded data; a wrong count is
a failure, not a warning.

One process, no children: a chip belongs to one process at a time.

    python chip_smoke.py                 # on the TPU; anything else exits 1
    python chip_smoke.py --rehearsal     # tiny size on whatever jax has
                                         # (the CPU here) — labelled as such

Without `--rehearsal` a non-TPU platform is an error and no result line is
printed.  The last line of stdout is one JSON object with exactly these
keys, the device as jax reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Everything else the run observed (peak bytes, compile seconds, failed
phases) is the `chip_smoke report:` line before it and `report.json`.

Exit code 0 only when every phase passed.  Seconds printed here are host
clocks around whole phases — smoke observations that say where a cold
start goes (compile vs run), not benchmark metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import re
import sys
import time
import traceback
import urllib.request

import numpy as np

# the size the repo calls its target (bench.py N_KEYS/BATCH/SLOTS and the
# `bench.py` default — not `small` — config sizes)
FULL = dict(
    n_keys=1 << 20, keys_per_send=1 << 17, slots=4,
    lb_batch=1 << 17, lb_sends=3,
    tw_batch=1 << 17, tw_syms=256, tw_sends=4,
    join_batch=1 << 13, join_sends=4,
    seq_batch=1 << 11, seq_sends=32, fuse_k=16,
)
# --rehearsal: same code path, sizes a CPU test run finishes in seconds
REHEARSAL = dict(
    n_keys=1 << 10, keys_per_send=1 << 7, slots=4,
    lb_batch=1 << 11, lb_sends=3,
    tw_batch=1 << 9, tw_syms=16, tw_sends=4,
    join_batch=1 << 8, join_sends=3,
    seq_batch=1 << 8, seq_sends=8, fuse_k=4,
)

# f32 sums accumulate in another order on the device than in the f64 numpy
# reference: n additions of values in [0, 1) bound the relative error by
# ~n * 2^-24 in the worst case; the window holds <= 2^18 rows
SUM_RTOL = 1e-3

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def say(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """One run: sizes, the seed, per-phase results and the jax compile
    meters the phases read."""

    def __init__(self, size: dict, seed: int, out_dir: str):
        self.size = size
        self.seed = seed
        self.out_dir = out_dir
        self.phases: dict = {}
        self.meters = {v: 0.0 for v in _COMPILE_EVENTS.values()}
        self.meters.update({v: 0 for v in _COUNT_EVENTS.values()})
        self.meters["programs"] = 0
        self.compile_durations: list = []
        self.hlo_files = 0
        self.f64_hits: list = []

    # jax.monitoring listeners (registered by main, removed in finally)
    def on_duration(self, event: str, secs: float, **_kw) -> None:
        key = _COMPILE_EVENTS.get(event)
        if key is None:
            return
        self.meters[key] += secs
        if key == "backend_compile_s":
            self.meters["programs"] += 1
            self.compile_durations.append(secs)

    def on_event(self, event: str, **_kw) -> None:
        key = _COUNT_EVENTS.get(event)
        if key is not None:
            self.meters[key] += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        """Run one phase: any exception fails it (and the run) but the
        later phases still execute, so one chip call reports them all."""
        from siddhi_tpu.observability.recompile import RECOMPILES

        def traces():
            return sum(o["count"] for o in RECOMPILES.snapshot().values())
        before = dict(self.meters)
        traces0 = traces()
        rec: dict = {"ok": False}
        self.phases[name] = rec
        say(f"[{name}] start")
        t0 = time.perf_counter()
        try:
            yield rec
            rec["ok"] = True
        except Exception as exc:  # noqa: BLE001 — reported, run continues
            rec["error"] = f"{type(exc).__name__}: {exc}"[:2000]
            say(f"[{name}] FAILED: {rec['error']}")
            traceback.print_exc(file=sys.stdout)
        rec["wall_s"] = round(time.perf_counter() - t0, 3)
        for k, v in self.meters.items():
            d = v - before[k]
            rec[k] = round(d, 3) if isinstance(d, float) else d
        rec["step_traces"] = traces() - traces0
        say(f"[{name}] {'ok' if rec['ok'] else 'FAILED'} "
            f"{json.dumps({k: v for k, v in rec.items() if k != 'error'})}")


# ---------------------------------------------------------------------------
# helpers shared by the phases
# ---------------------------------------------------------------------------

def _current_rows(b, names):
    """CURRENT rows of one batch-callback payload as {name: ndarray}."""
    sel = b["valid"] & (b["kind"] == 0)
    cols = b["cols"]
    return {n: np.asarray(cols[n])[sel] for n in names}


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def watch_errors(rt) -> list:
    """Collect what the runtime would otherwise only log.  A step the
    device refuses at run time is caught in the junction (on_error=LOG),
    logged, and its batch dropped; the async worker and the serving
    drainer do the same.  Without a listener a phase sees only the symptom
    ("0 matches"), never the cause."""
    errs: list = []
    rt.set_exception_listener(errs.append)
    return errs


def require_no_errors(errs: list, what: str) -> None:
    if errs:
        first = errs[0]
        raise AssertionError(
            f"{what}: the runtime caught {len(errs)} error(s) and dropped "
            f"the batch(es); first: {type(first).__name__}: {first}"
        ) from first


def dump_step_hlo(smoke: Smoke, rt, qname: str, tag: str) -> None:
    """Write the compiled HLO of every step of `qname` that has traced to
    <out>/hlo/ and record any f64 in it (the TPU has no f64; under
    jax_enable_x64 a bare jnp.zeros(n) or jnp.asarray(1.0) makes one)."""
    from siddhi_tpu.observability.recompile import RECOMPILES
    hlo_dir = os.path.join(smoke.out_dir, "hlo")
    os.makedirs(hlo_dir, exist_ok=True)
    for role, fn, specs in rt.compiled_steps(qname):
        if specs is None:
            continue            # this variant never ran in the phase
        with RECOMPILES.suppress():
            text = fn.lower(*specs).compile().as_text()
        safe = re.sub(r"[^A-Za-z0-9_.-]+", "_", f"{tag}.{qname}.{role}")
        with open(os.path.join(hlo_dir, safe + ".txt"), "w") as fh:
            fh.write(text)
        smoke.hlo_files += 1
        n64 = len(re.findall(r"\bf64\b", text))
        if n64:
            smoke.f64_hits.append({"where": safe, "count": n64})


def scan_ir_dump(ir_dir: str) -> dict:
    """Every module jax lowered this run was dumped to `ir_dir`
    (jax_dump_ir_to): count them and name the ones that carry f64."""
    n, bad = 0, []
    for fn in sorted(os.listdir(ir_dir)) if os.path.isdir(ir_dir) else ():
        n += 1
        with open(os.path.join(ir_dir, fn), errors="replace") as fh:
            if re.search(r"\bf64\b", fh.read()):
                bad.append(fn)
    return {"modules": n, "with_f64": bad}


# ---------------------------------------------------------------------------
# flagship: 4-state pattern over n_keys partition keys
# ---------------------------------------------------------------------------

def flagship_block(rng, lo: int, kb: int, stride: int = 1):
    """One send: each of kb keys from `lo` (every `stride`-th) gets its 4
    stages in arrival order, prices seeded so that each key completes
    exactly one match (p2 >= p1 and p4 >= p3) — the bench's
    one-match-per-key-per-sweep workload with payloads that differ per
    key."""
    r = rng.random((kb, 4), np.float32)
    price = np.stack([r[:, 0], r[:, 0] + r[:, 1],
                      r[:, 2], r[:, 2] + r[:, 3]], 1)
    keys = np.repeat(
        np.arange(lo, lo + kb * stride, stride, dtype=np.int64), 4)
    vol = np.tile(np.array([1, 2, 3, 4], np.int32), kb)
    return keys, np.ascontiguousarray(price.reshape(-1)), vol


def flagship_reference(keys, price, vol):
    """Plain per-key evaluation of
    every e1[v==1] -> e2[v==2, p>=e1.p] -> e3[v==3] -> e4[v==4, p>=e3.p]
    over one send (4 consecutive events per key, no partial match is
    alive at the start of a send)."""
    k = keys.reshape(-1, 4)
    p = price.reshape(-1, 4)
    v = vol.reshape(-1, 4)
    _require(bool((k == k[:, :1]).all()), "reference expects 4 rows/key")
    hit = ((v == np.array([1, 2, 3, 4])).all(1) &
           (p[:, 1] >= p[:, 0]) & (p[:, 3] >= p[:, 2]))
    return {"k": k[hit, 0], "p1": p[hit, 0], "p2": p[hit, 1],
            "p4": p[hit, 3]}


def run_flagship(smoke: Smoke, rec: dict, tag: str, serve: bool = False,
                 mesh=None):
    """Warm sweep, one checked sweep, then one checked GAPPY send.  At
    this size every block of a sweep is slot-contiguous and takes the
    dense-slice step (dense_step); the gappy send — every other key of
    the first two blocks — takes the other program real traffic runs, the
    gather/scatter step (step).  Returns the checked rows (sweep then
    gappy send, each sorted by key)."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.analysis.corpus import FLAGSHIP_QL_TEMPLATE
    sz = smoke.size
    n_keys, kb = sz["n_keys"], sz["keys_per_send"]
    blocks = n_keys // kb
    names = ("k", "p1", "p2", "p4")
    manager = SiddhiManager()
    try:
        rt = manager.create_siddhi_app_runtime(
            FLAGSHIP_QL_TEMPLATE.format(
                async_ann="", pipe_ann="@serve" if serve else "",
                n_keys=n_keys, slots=sz["slots"]), mesh=mesh)
        state = {"n_current": 0, "collect": False, "rows": []}

        def on_batch(_ts, b):
            state["n_current"] += b["n_current"]
            if state["collect"]:
                state["rows"].append(_current_rows(b, names))

        rt.add_batch_callback("flagship", on_batch)
        errs = watch_errors(rt)
        rt.start()
        h = rt.get_input_handler("TradeStream")
        rng = np.random.default_rng(smoke.seed)
        clock = 1000
        expected = []

        def send(lo: int, stride: int, check: bool):
            nonlocal clock
            keys, price, vol = flagship_block(rng, lo, kb, stride)
            if check:
                expected.append(flagship_reference(keys, price, vol))
            clock += 10
            ts = clock + np.tile(np.arange(4, dtype=np.int64), kb)
            h.send_columns([keys, price, vol], timestamps=ts)

        def sweep(check: bool):
            for blk in range(blocks):
                send(blk * kb, 1, check)
            rt.flush()
            require_no_errors(errs, f"{tag} sweep")

        t0 = time.perf_counter()
        sweep(check=False)
        rec["warm_sweep_s"] = round(time.perf_counter() - t0, 3)
        warm = state["n_current"]
        _require(warm == n_keys,
                 f"warm sweep: {warm} matches, expected {n_keys}")
        state["collect"] = True
        t0 = time.perf_counter()
        sweep(check=True)
        rec["checked_sweep_s"] = round(time.perf_counter() - t0, 3)
        rec["events_per_sweep"] = 4 * n_keys
        got_n = state["n_current"] - warm
        rec["matches"] = got_n
        _require(got_n == n_keys,
                 f"checked sweep: header n_current {got_n}, "
                 f"expected exactly {n_keys}")

        def checked_rows(what: str):
            """Delivered rows since the last call, sorted by key and
            compared by value with the per-key reference."""
            got = {n: np.concatenate([r[n] for r in state["rows"]])
                   for n in names}
            want = {n: np.concatenate([e[n] for e in expected])
                    for n in names}
            state["rows"].clear()
            expected.clear()
            order = np.argsort(got["k"], kind="stable")
            got = {n: a[order] for n, a in got.items()}
            for n in names:
                _require(got[n].shape == want[n].shape and
                         np.array_equal(got[n], want[n]),
                         f"{what}: payload column {n!r} differs from "
                         f"the per-key reference ({got[n].shape} vs "
                         f"{want[n].shape})")
            return got

        got = checked_rows("checked sweep")
        t0 = time.perf_counter()
        send(0, 2, check=True)
        rt.flush()
        require_no_errors(errs, f"{tag} gappy send")
        rec["gappy_send_s"] = round(time.perf_counter() - t0, 3)
        gappy_n = state["n_current"] - warm - n_keys
        rec["gappy_matches"] = gappy_n
        _require(gappy_n == kb,
                 f"gappy send: header n_current {gappy_n}, expected {kb}")
        gappy = checked_rows("gappy send")
        got = {n: np.concatenate([got[n], gappy[n]]) for n in names}
        rec["payload_rows_checked"] = int(len(got["k"]))

        # one more sweep with statistics at BASIC, which switches the
        # phase profiler on: where the host clocks say the wall goes on
        # this device (reported, not judged), and what the sweep costs
        # with the statistics on next to checked_sweep_s without
        rt.set_statistics_level("BASIC")
        state["collect"] = False
        t0 = time.perf_counter()
        sweep(check=False)
        rec["basic_stats_sweep_s"] = round(time.perf_counter() - t0, 3)
        node = rt.phase_report()["queries"].get("flagship", {})
        rec["phase_shares"] = {
            "accounted": node.get("accounted"),
            **{ph: v["share"] for ph, v in node.get("phases", {}).items()}}
        _require(state["n_current"] == warm + 2 * n_keys + kb,
                 "statistics sweep changed the match count")
        from siddhi_tpu.observability.memory import tree_nbytes
        qr = rt.query_runtimes["flagship"]
        rec["state_bytes"] = int(tree_nbytes(qr.state))
        if serve:
            rings = rt.serve_rings()
            _require("flagship" in rings, "@serve opened no ring")
            facts = rings["flagship"].facts()
            rec["ring"] = facts
            _require(facts["appends_total"] == 3 * blocks + 1,
                     f"ring saw {facts['appends_total']} appends")
            _require(facts["occupancy"] == 0,
                     "ring not drained to empty at flush()")
            _require(facts["placement_fallbacks"] == 0,
                     "a ring leaf fell back to the default device")
            stg = rt.serve_staging_facts()
            rec["staging"] = stg
            _require(stg["fallback_total"] == 0,
                     "accept-edge H2D staging fell back")
        if mesh is not None:
            n = mesh.devices.size
            from __graft_entry__ import _assert_state_distributed
            rec["sharded_state_leaves"] = _assert_state_distributed(
                qr.state, n, f"{tag} NFA state")
            if serve:
                rec["sharded_ring_leaves"] = _assert_state_distributed(
                    rt.serve_rings()["flagship"].state_leaves(), n,
                    f"{tag} ring")
        dump_step_hlo(smoke, rt, "flagship", tag)
        return got
    finally:
        manager.shutdown()


# ---------------------------------------------------------------------------
# the other bench shapes, a few windows each
# ---------------------------------------------------------------------------

def _drive_single(ql: str, qname: str, stream: str, sends, names, smoke,
                  tag):
    """Send (cols, ts) batches through one stream; returns the CURRENT
    rows of every delivery, concatenated in delivery order."""
    from siddhi_tpu import SiddhiManager
    manager = SiddhiManager()
    try:
        rt = manager.create_siddhi_app_runtime(ql)
        rows = []
        rt.add_batch_callback(
            qname, lambda _ts, b: rows.append(_current_rows(b, names)))
        errs = watch_errors(rt)
        rt.start()
        h = rt.get_input_handler(stream)
        for cols, ts in sends:
            h.send_columns([c.copy() for c in cols], timestamps=ts.copy())
        rt.flush()
        require_no_errors(errs, tag)
        dump_step_hlo(smoke, rt, qname, tag)
    finally:
        manager.shutdown()
    return {n: np.concatenate([r[n] for r in rows]) if rows
            else np.zeros(0) for n in names}


def phase_length_batch(smoke: Smoke, rec: dict) -> None:
    """lengthBatch(1000) + avg(price): each arriving event emits the
    running average of its 1000-event batch (reset at every flush)."""
    ql = """
    @app:playback
    define stream StockStream (symbol long, price float, volume int);
    @info(name='q') from StockStream#window.lengthBatch(1000)
    select avg(price) as ap insert into OutputStream;
    """
    B, n = smoke.size["lb_batch"], smoke.size["lb_sends"]
    rng = np.random.default_rng(smoke.seed + 1)
    sends = [([np.zeros(B, np.int64), rng.random(B, np.float32),
               np.ones(B, np.int32)], np.full(B, 1000 + i, np.int64))
             for i in range(n)]
    got = _drive_single(ql, "q", "StockStream", sends, ("ap",), smoke,
                        "length_batch")["ap"]
    price = np.concatenate([s[0][1] for s in sends]).astype(np.float64)
    full = len(price) // 1000
    ref = (np.cumsum(price[:full * 1000].reshape(full, 1000), 1) /
           np.arange(1, 1001)).reshape(-1)
    rec["rows_checked"] = int(len(ref))
    _require(got.shape == ref.shape,
             f"lengthBatch rows {got.shape} vs reference {ref.shape}")
    _require(np.allclose(got, ref, rtol=SUM_RTOL, atol=0.0),
             f"lengthBatch avg differs: max rel err "
             f"{np.max(np.abs(got - ref) / ref)}")


def phase_time_groupby(smoke: Smoke, rec: dict) -> None:
    """Sliding time(1 sec) group-by sum/count/avg + having.  Sends are
    600 ms apart, so at each send the batch two sends back has expired
    and the window holds two batches — sized to hold them
    (@capacity(window=2*B)): the default 2048-row slab would drop the
    oldest rows on overflow and never expire them out of the sums."""
    B, n_sym = smoke.size["tw_batch"], smoke.size["tw_syms"]
    n = smoke.size["tw_sends"]
    ql = f"""
    @app:playback
    define stream S (symbol long, price float, volume int);
    @capacity(window='{2 * B}')
    @info(name='q') from S#window.time(1 sec)
    select symbol, sum(price) as sp, count() as c, avg(volume) as av
    group by symbol having sp > 0.0
    insert into Out;
    """
    rng = np.random.default_rng(smoke.seed + 2)
    sends = [([rng.integers(0, n_sym, B).astype(np.int64),
               rng.random(B, np.float32),
               rng.integers(1, 5, B).astype(np.int32)],
              np.full(B, 1000 + 600 * i, np.int64)) for i in range(n)]
    got = _drive_single(ql, "q", "S", sends, ("symbol", "sp", "c", "av"),
                        smoke, "time_groupby")
    ref = {k: [] for k in ("symbol", "sp", "c", "av")}
    alive = []           # (ts, per-symbol price sum, count, volume sum)
    for (sym, price, vol), ts in sends:
        t = int(ts[0])
        alive = [a for a in alive if a[0] + 1000 > t]
        base = [sum((a[j] for a in alive), np.zeros(n_sym))
                for j in (1, 2, 3)]
        # running per-symbol totals in arrival order
        order = np.argsort(sym, kind="stable")
        s_sorted = sym[order]
        first = np.r_[True, s_sorted[1:] != s_sorted[:-1]]
        start = np.maximum.accumulate(
            np.where(first, np.arange(B), 0))

        def running(x):
            cs = np.cumsum(x[order].astype(np.float64))
            head = np.where(start > 0, cs[start - 1], 0.0)
            out = np.empty(B)
            out[order] = cs - head
            return out
        sp = base[0][sym] + running(price)
        c = base[1][sym] + running(np.ones(B))
        av = (base[2][sym] + running(vol)) / c
        keep = sp > 0.0
        ref["symbol"].append(sym[keep])
        ref["sp"].append(sp[keep])
        ref["c"].append(c[keep])
        ref["av"].append(av[keep])
        alive.append((t, np.bincount(sym, price.astype(np.float64),
                                     n_sym),
                      np.bincount(sym, minlength=n_sym).astype(np.float64),
                      np.bincount(sym, vol.astype(np.float64), n_sym)))
    ref = {k: np.concatenate(v) for k, v in ref.items()}
    rec["rows_checked"] = int(len(ref["symbol"]))
    _require(got["symbol"].shape == ref["symbol"].shape,
             f"time window rows {got['symbol'].shape} vs "
             f"{ref['symbol'].shape}")
    _require(np.array_equal(got["symbol"], ref["symbol"]),
             "time window symbol column differs")
    _require(np.array_equal(got["c"], ref["c"].astype(np.int64)),
             "time window count() differs (expiry or overflow)")
    for k in ("sp", "av"):
        _require(np.allclose(got[k], ref[k], rtol=SUM_RTOL, atol=0.0),
                 f"time window {k} differs: max rel err "
                 f"{np.max(np.abs(got[k] - ref[k]) / ref[k])}")


def phase_windowed_join(smoke: Smoke, rec: dict) -> None:
    """WINDOWED_JOIN_QL (bucketed equi-join fast path): every arriving
    row joins the OTHER side's length(128) window as it stood before the
    send."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.analysis.corpus import WINDOWED_JOIN_QL
    B, n, n_sym, W = smoke.size["join_batch"], smoke.size["join_sends"], \
        64, 128
    rng = np.random.default_rng(smoke.seed + 3)
    manager = SiddhiManager()
    got, ref = [], []
    try:
        rt = manager.create_siddhi_app_runtime(WINDOWED_JOIN_QL)
        qr = rt.query_runtimes["q"]
        rec["fastpath"] = qr.planned.fastpath
        _require(qr.planned.fastpath == "bucket",
                 f"join planned as {qr.planned.fastpath!r}, not the "
                 f"bucketed fast path")

        def on_batch(_ts, b):
            r = _current_rows(b, ("s", "p", "v"))
            got.append(np.stack([r["s"], r["p"], r["v"]], 1)
                       .astype(np.float64))
            _require(b["n_dropped"] == 0, "join emission cap dropped rows")

        rt.add_batch_callback("q", on_batch)
        errs = watch_errors(rt)
        rt.start()
        hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
        lwin = (np.zeros(0, np.int64), np.zeros(0, np.float32))
        rwin = (np.zeros(0, np.int64), np.zeros(0, np.int32))

        def pairs(arr_sym, win_sym):
            return np.nonzero(arr_sym[:, None] == win_sym[None, :])

        for i in range(n):
            ls = rng.integers(0, n_sym, B).astype(np.int64)
            lp = rng.random(B, np.float32)
            rs = rng.integers(0, n_sym, B).astype(np.int64)
            rq = rng.integers(1, 9, B).astype(np.int32)
            ts = np.full(B, 1000 + i, np.int64)
            hl.send_columns([ls.copy(), lp.copy()], timestamps=ts.copy())
            a, w = pairs(ls, rwin[0])
            ref.append(np.stack([ls[a], lp[a], rwin[1][w]], 1)
                       .astype(np.float64))
            lwin = (ls[-W:], lp[-W:])
            hr.send_columns([rs.copy(), rq.copy()], timestamps=ts.copy())
            a, w = pairs(rs, lwin[0])
            ref.append(np.stack([rs[a], lwin[1][w], rq[a]], 1)
                       .astype(np.float64))
            rwin = (rs[-W:], rq[-W:])
        rt.flush()
        require_no_errors(errs, "windowed_join")
        dump_step_hlo(smoke, rt, "q", "windowed_join")
    finally:
        manager.shutdown()
    got = np.concatenate(got)
    ref = np.concatenate(ref)
    rec["rows_checked"] = int(len(ref))
    _require(got.shape == ref.shape,
             f"join rows {got.shape} vs reference {ref.shape}")

    def canon(a):
        return a[np.lexsort(a.T[::-1])]
    rec["delivery_order_equal"] = bool(np.array_equal(got, ref))
    _require(np.array_equal(canon(got), canon(ref)),
             "join rows differ from the reference")


def phase_sequence(smoke: Smoke, rec: dict) -> None:
    """Single-key `e1, e2[price > e1.price] within 1 sec` (block-NFA),
    sequential and under @fuse(batches=K): volumes alternate 1,2 so the
    candidate pairs are (2i, 2i+1); seeded 2 s gaps make `within` bite."""
    from siddhi_tpu.analysis.corpus import SEQUENCE_QL
    B, n, k = smoke.size["seq_batch"], smoke.size["seq_sends"], \
        smoke.size["fuse_k"]
    rng = np.random.default_rng(smoke.seed + 4)
    sends, ref = [], []
    t0 = 1000
    for _ in range(n):
        price = rng.random(B, np.float32)
        vol = np.tile(np.array([1, 2], np.int32), B // 2)
        delta = np.where(rng.random(B) < 0.05, 2000,
                         rng.integers(0, 2, B)).astype(np.int64)
        ts = t0 + np.cumsum(delta)
        t0 = int(ts[-1])
        sends.append(([np.zeros(B, np.int64), price, vol], ts))
        p1, p2 = price[0::2], price[1::2]
        ok = (p2 > p1) & (ts[1::2] - ts[0::2] <= 1000)
        ref.append(np.stack([p1[ok], p2[ok]], 1))
    ref = np.concatenate(ref)
    rec["rows_checked"] = int(len(ref))
    for tag, ann in (("sequential", ""),
                     (f"fused_k{k}", f"@fuse(batches='{k}')")):
        got = _drive_single(SEQUENCE_QL.format(ann=ann), "q", "S", sends,
                            ("p1", "p2"), smoke, f"sequence_{tag}")
        got = np.stack([got["p1"], got["p2"]], 1)
        _require(got.shape == ref.shape and np.array_equal(got, ref),
                 f"sequence[{tag}] rows {got.shape} differ from the "
                 f"reference {ref.shape}")


# ---------------------------------------------------------------------------
# the server answers a few requests
# ---------------------------------------------------------------------------

REST_APP = """@app:name('ChipSmokeApp')
@app:statistics('BASIC')
define stream Trades (symbol string, price double, volume long);
define table BigTrades (symbol string, price double, volume long);
@info(name='big')
from Trades[volume >= 40] select symbol, price, volume
insert into BigTrades;
@info(name='vwap')
from Trades#window.lengthBatch(16)
select symbol, sum(price * volume) / sum(volume) as vwap
group by symbol insert into Vwap;
@info(name='spike')
from every e1=Trades[volume > 10] -> e2=Trades[price > e1.price]
select e1.symbol as symbol, e1.price as p1, e2.price as p2
insert into Spikes;
"""


def phase_rest(smoke: Smoke, rec: dict) -> None:
    from siddhi_tpu.service import SiddhiRestService
    svc = SiddhiRestService(port=0).start()
    try:
        base = f"http://127.0.0.1:{svc.port}"

        def call(path, data=None, method=None):
            req = urllib.request.Request(base + path, data=data,
                                         method=method)
            with urllib.request.urlopen(req, timeout=600) as r:
                return r.status, r.read().decode()

        code, _ = call("/siddhi-apps", REST_APP.encode(), "POST")
        _require(code == 201, f"deploy returned {code}")
        rt = svc.manager.runtimes["ChipSmokeApp"]
        errs = watch_errors(rt)
        rng = np.random.default_rng(smoke.seed + 5)
        events = [[f"S{int(s)}", round(float(p), 3), int(v)]
                  for s, p, v in zip(rng.integers(0, 4, 64),
                                     50 + 50 * rng.random(64),
                                     rng.integers(1, 80, 64))]
        code, body = call("/siddhi-apps/ChipSmokeApp/streams/Trades",
                          json.dumps({"events": events}).encode(), "POST")
        _require(code == 200 and json.loads(body)["accepted"] == 64, body)
        rt.flush()
        require_no_errors(errs, "rest_service ingest")
        code, body = call("/query", json.dumps({
            "app": "ChipSmokeApp",
            "query": "from BigTrades select symbol, price, volume"
        }).encode(), "POST")
        records = json.loads(body)["records"]
        want = [e for e in events if e[2] >= 40]
        rec["rows_read_back"] = len(records)
        _require(len(records) == len(want) and all(
            r[0] == w[0] and r[2] == w[2] and
            abs(r[1] - w[1]) <= 1e-4 * w[1]
            for r, w in zip(records, want)),
            f"rows read back differ: {records[:3]} vs {want[:3]}")
        code, text = call("/metrics")
        _require(code == 200 and 'siddhi_stream_events_total{'
                 'app="ChipSmokeApp",stream="Trades"} 64' in text,
                 "metrics scrape lacks the Trades stream counter")
        rec["metrics_bytes"] = len(text)
        code, body = call("/healthz")
        hz = json.loads(body)
        _require(code == 200 and hz["live"] is True and
                 hz["ready"] is True, f"healthz: {body[:400]}")
        unavailable = []
        for q in ("big", "vwap", "spike"):
            code, body = call(f"/siddhi-apps/ChipSmokeApp/explain/{q}")
            rep = json.loads(body)
            _require(code == 200 and rep["steps"], f"explain {q}: {code}")
            for role, c in rep["steps"].items():
                if not c.get("available"):
                    unavailable.append(
                        f"{q}/{role}: {c.get('reason', '?')[:300]}")
        rec["explain_steps_unavailable"] = unavailable
        _require(not unavailable,
                 f"explain steps unavailable: {unavailable}")
        code, body = call("/siddhi-apps/ChipSmokeApp/phases")
        ph = json.loads(body)
        _require(code == 200 and ph.get("queries"), "phases empty")
        rec["phases_accounted"] = {
            q: v.get("accounted") for q, v in ph["queries"].items()}
        require_no_errors(errs, "rest_service")
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny size on whatever platform jax has; the "
                         "output is labelled a rehearsal, not a chip run")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for every generated column")
    ap.add_argument("--out", default="chiprun_out/chip_smoke",
                    help="directory for the HLO/IR dumps and report.json")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    # importing the package sets jax_enable_x64 + the CPU-emitter flag;
    # the cache helper must run before the first compile
    from siddhi_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.rehearsal:
        print(f"chip_smoke: jax found no TPU (devices: {device}); this "
              f"script measures nothing on another platform — pass "
              f"--rehearsal for the tiny CPU walk-through",
              file=sys.stderr)
        return 1

    from siddhi_tpu import native
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    env = {
        **device,
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu, "numpy": np.__version__,
        "native_staging": native.LIB is not None,
        "compile_cache_dir": cache_dir,
        "compile_cache_placed_by_env":
            bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "rehearsal": args.rehearsal, "seed": args.seed,
    }
    say(f"chip_smoke environment: {json.dumps(env)}")
    if args.rehearsal:
        say("REHEARSAL: tiny sizes, not a chip run — nothing printed "
            "below is a device measurement")

    smoke = Smoke(REHEARSAL if args.rehearsal else FULL, args.seed,
                  os.path.abspath(args.out))
    os.makedirs(smoke.out_dir, exist_ok=True)
    ir_dir = os.path.join(smoke.out_dir, "ir")
    os.makedirs(ir_dir, exist_ok=True)
    for f in os.listdir(ir_dir):
        os.unlink(os.path.join(ir_dir, f))
    prev_dump = jax.config.read("jax_dump_ir_to")
    jax.config.update("jax_dump_ir_to", ir_dir)
    jax.monitoring.register_event_duration_secs_listener(smoke.on_duration)
    jax.monitoring.register_event_listener(smoke.on_event)
    try:
        with smoke.phase("environment") as rec:
            rec["native_staging"] = env["native_staging"]
            _require(native.LIB is not None,
                     "siddhi_tpu.native.LIB is None: staging.c did not "
                     "build, the host stage would run the slow numpy path")

        base_rows = served_rows = None
        with smoke.phase("flagship_blocking") as rec:
            base_rows = run_flagship(smoke, rec, "flagship_blocking")
        with smoke.phase("flagship_served") as rec:
            served_rows = run_flagship(smoke, rec, "flagship_served",
                                       serve=True)
            _require(base_rows is not None and all(
                np.array_equal(served_rows[n], base_rows[n])
                for n in base_rows),
                "@serve delivered different rows than the blocking run")
        with smoke.phase("length_batch_avg") as rec:
            phase_length_batch(smoke, rec)
        with smoke.phase("time_groupby_having") as rec:
            phase_time_groupby(smoke, rec)
        with smoke.phase("windowed_join") as rec:
            phase_windowed_join(smoke, rec)
        with smoke.phase("sequence_within") as rec:
            phase_sequence(smoke, rec)
        with smoke.phase("rest_service") as rec:
            phase_rest(smoke, rec)

        if len(devs) >= 4:
            from jax.sharding import Mesh
            mesh = Mesh(np.array(devs[:4]), ("shard",))
            for tag, serve in (("mesh4_blocking", False),
                               ("mesh4_served", True)):
                with smoke.phase(tag) as rec:
                    rows = run_flagship(smoke, rec, tag, serve=serve,
                                        mesh=mesh)
                    _require(base_rows is not None and all(
                        np.array_equal(rows[n], base_rows[n])
                        for n in base_rows),
                        f"{tag}: 4-way output differs from one device")
        else:
            say(f"[mesh4] skipped: {len(devs)} device(s), the 4-way mesh "
                f"part needs 4")

        with smoke.phase("no_f64") as rec:
            rec["hlo_files"] = smoke.hlo_files
            rec["ir"] = scan_ir_dump(ir_dir)
            rec["f64_in_compiled_steps"] = smoke.f64_hits
            _require(not smoke.f64_hits,
                     f"f64 in compiled steps: {smoke.f64_hits}")
            _require(not rec["ir"]["with_f64"],
                     f"f64 in lowered modules: {rec['ir']['with_f64']}")
    finally:
        jax.config.update("jax_dump_ir_to", prev_dump)
        jax.monitoring.unregister_event_duration_listener(smoke.on_duration)
        jax.monitoring.unregister_event_listener(smoke.on_event)

    peak = {}
    for d in devs:
        stats = d.memory_stats() or {}
        peak[str(d.id)] = stats.get("peak_bytes_in_use")
    durs = np.asarray(smoke.compile_durations, np.float64)
    compile_hist = {}
    for edge, lo, hi in (("<0.1s", 0, 0.1), ("0.1-1s", 0.1, 1),
                         ("1-5s", 1, 5), (">=5s", 5, np.inf)):
        inside = durs[(durs >= lo) & (durs < hi)]
        compile_hist[edge] = {"programs": int(inside.size),
                              "seconds": round(float(inside.sum()), 2)}
    ok = all(p["ok"] for p in smoke.phases.values())
    report = {
        "ok": ok, "device": device, "rehearsal": args.rehearsal,
        "native_staging": env["native_staging"],
        "mesh4": "ran" if len(devs) >= 4 else "skipped",
        "wall_s": round(time.perf_counter() - t_start, 1),
        "peak_bytes_in_use": peak,
        "compile": {**{k: (round(v, 2) if isinstance(v, float) else v)
                       for k, v in smoke.meters.items()},
                    "by_backend_compile_time": compile_hist,
                    "cache_dir": cache_dir},
        "failed": [n for n, p in smoke.phases.items() if not p["ok"]],
        "note": "seconds are smoke observations (host clocks around "
                "whole phases), not benchmark metrics",
    }
    with open(os.path.join(smoke.out_dir, "report.json"), "w") as fh:
        json.dump({**report, "environment": env, "phases": smoke.phases},
                  fh, indent=1, default=str)
        fh.write("\n")
    say(f"chip_smoke report: {json.dumps(report)}")
    # the verdict line: exactly "ok" and "device", nothing else
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark: events/sec on the 4-state pattern over a 1M-key partitioned
stream (BASELINE.json target metric), run on whatever jax.devices()[0] is.
The platform comes from the environment (JAX_PLATFORMS / XLA_FLAGS) and
nothing here changes it, shrinks the workload for it, or falls back from
it: every JSON line names the device it ran on, and a failed or
mismatching mode makes the run exit non-zero.  On the chip, run
`python chip_smoke.py` first.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}.

vs_baseline: the reference is a JVM library; no JVM exists in this image
(BASELINE.md), so the stand-in baseline is a measured pure-Python per-event
NFA interpreter that mimics the reference's execution model (one event at a
time through per-key pending-state lists, StreamPreStateProcessor-style).
Auxiliary numbers go to stderr.
"""
import json
import sys
import time

import numpy as np

N_KEYS = 1 << 20          # 1M partition keys
BATCH = 1 << 17           # 131072 keys per micro-batch (524288 events/send)
SLOTS = 4
SWEEPS = 4                # timed sweeps over all keys x 4 stages

# the serving shapes live in siddhi_tpu/analysis/corpus.py — ONE set of
# strings the benchmark drives and the plan-audit gate
# (`python -m siddhi_tpu.tools.audit`) fingerprints, so they cannot drift
from siddhi_tpu.analysis.corpus import (  # noqa: E402
    FLAGSHIP_QL_TEMPLATE as QL_TEMPLATE,
    MC_FLAGSHIP_QL,
    MC_JOIN_QL,
    SEQUENCE_QL,
    WINDOWED_JOIN_QL,
)


def _strict(rt, tag):
    """Make a failed batch fail the run.  A step the device refuses at run
    time is caught in the junction (on_error=LOG), logged, and its batch
    dropped — the async worker and the serving drainer do the same — so a
    driver that only reads a clock would print a FASTER events/sec with
    exit 0.  Registers the app's exception listener; call the returned
    check after every flush()."""
    errs = []
    rt.set_exception_listener(errs.append)

    def check():
        if errs:
            raise RuntimeError(
                f"{tag}: the runtime caught {len(errs)} error(s) and "
                f"dropped the batch(es); first: "
                f"{type(errs[0]).__name__}: {errs[0]}") from errs[0]
    return check


def _expect_rows(tag, got, want):
    """Delivered CURRENT rows against the closed form for the seeded data
    — a wrong answer is not a measurement."""
    if got != want:
        raise RuntimeError(f"{tag}: delivered {got} rows, expected {want}")


def run_tpu(async_ingest: bool = False, pipeline: bool = False,
            serve: bool = False):
    """One flagship measurement.  All four ingestion/emission modes are
    legitimate configurations (@async = the reference's Disruptor opt-in;
    @pipeline = one-deep deferred emission overlapping host staging with
    the device step on the producer thread; @serve = the device-resident
    serving loop, emissions ring on-device and the async drainer pays
    every fetch off the send path).  On a single-core driver host the
    sync path beats @async (the worker thread contends with the
    producer) while @pipeline/@serve should win wherever the emission
    fetch is slow next to a send (it never blocks one), so main()
    measures all and reports the best.  The device program is identical
    across them — the modes only change host threading/ordering — so
    the later runtimes' compiles hit the persistent cache.
    """
    from siddhi_tpu import SiddhiManager

    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(QL_TEMPLATE.format(
        async_ann="@async" if async_ingest else "",
        pipe_ann="@serve" if serve else
        ("@pipeline(depth='8')" if pipeline else ""),
        n_keys=N_KEYS, slots=SLOTS))
    matches = [0]
    # n_current is the device-computed count of valid CURRENT rows riding
    # the emission header (payload columns stay on device unless read)
    rt.add_batch_callback(
        "flagship",
        lambda ts, b: matches.__setitem__(0, matches[0] + b["n_current"]))
    mode = "served" if serve else ("async" if async_ingest else (
        "pipeline" if pipeline else "sync"))
    check = _strict(rt, f"flagship[{mode}]")
    rt.start()
    h = rt.get_input_handler("TradeStream")

    # one send carries all 4 stages per key, interleaved in arrival order
    # (the device scans E=4 events per key sequentially); 4*BATCH events/send
    blocks = N_KEYS // BATCH
    key_block = {b: np.repeat(
        np.arange(b * BATCH, (b + 1) * BATCH, dtype=np.int64), 4)
        for b in range(blocks)}
    vol4 = np.tile(np.array([1, 2, 3, 4], np.int32), BATCH)
    price4 = vol4.astype(np.float32)
    clock = [1000]

    def send(block):
        clock[0] += 10
        ts = clock[0] + np.tile(np.arange(4, dtype=np.int64), BATCH)
        h.send_columns([key_block[block], price4, vol4], timestamps=ts)

    # warmup / compile — a FULL sweep over the key space, not just block
    # 0: wherever the LAST block's key_lo + padded Kb exceeds
    # key_capacity it falls off the dense-slice fast path onto the
    # gather/scatter step — a DIFFERENT compiled program.  Warming only
    # block 0 left that compile mid-run, which was the entire 48-533x
    # p99/p50 tail of the reduced-scale CPU flagship suite (round 6: one
    # ~4.7 s XLA compile at sweep 0, block N-1).  At the default BATCH
    # (1<<17, an exact bucket) every block stays dense — chip_smoke.py
    # drives the gather/scatter program with a gappy send instead
    for b in range(blocks):
        send(b)
    rt.flush()
    check()
    warm_matches = matches[0]
    print(f"warmup done, matches={warm_matches}", file=sys.stderr)
    _expect_rows(f"flagship[{mode}] warm sweep", warm_matches, N_KEYS)
    lat = []
    total = 0
    t0 = time.perf_counter()
    for _ in range(SWEEPS):
        for block in range(blocks):
            tb = time.perf_counter()
            send(block)
            lat.append(time.perf_counter() - tb)
            total += 4 * BATCH
    rt.flush()            # all async deliveries done before the clock stops
    dt = time.perf_counter() - t0
    eps = total / dt
    stats = _lat_stats(lat)
    print(f"tpu[{mode}]: {total} events in {dt:.2f}s -> {eps:,.0f} ev/s; "
          f"matches={matches[0]}; batch p50={stats['p50_ms']}ms "
          f"p99={stats['p99_ms']}ms", file=sys.stderr)
    _report_tail(f"flagship[{mode}]", stats)
    manager.shutdown()
    check()
    # one match per key per sweep
    _expect_rows(f"flagship[{mode}]", matches[0] - warm_matches,
                 SWEEPS * blocks * BATCH)
    return eps, stats


def run_python_baseline(n_events=400_000):
    """Per-event interpreter in the reference's style: pending-state lists
    per key, one event at a time (no JVM in this image; see BASELINE.md)."""
    import collections

    pending = collections.defaultdict(list)
    seeds_on = True
    matches = 0
    nkeys = n_events // 16 or 1
    rng = np.random.default_rng(0)
    keys = rng.integers(0, nkeys, n_events).tolist()
    vols = rng.integers(1, 5, n_events).tolist()
    prices = rng.random(n_events).tolist()

    t0 = time.perf_counter()
    for key, vol, price in zip(keys, vols, prices):
        lst = pending[key]
        out = []
        for slot in lst:
            pos = slot[0]
            if pos == 1 and vol == 2 and price >= slot[1][1]:
                out.append((2, slot[1], (key, price)))
            elif pos == 2 and vol == 3:
                out.append((3, slot[1], slot[2], (key, price)))
            elif pos == 3 and vol == 4 and price >= slot[3][1]:
                matches += 1
            else:
                out.append(slot)
        if vol == 1:
            out.append((1, (key, price)))
        pending[key] = out
    dt = time.perf_counter() - t0
    eps = n_events / dt
    print(f"python per-event baseline: {eps:,.0f} ev/s "
          f"({matches} matches)", file=sys.stderr)
    return eps


# ---------------------------------------------------------------------------
# The other four BASELINE.json configs.  Each is a small self-contained
# harness (reference shape: modules/siddhi-samples/performance-samples,
# SimpleFilterSingleQueryPerformance.java:40-74).  They ride the flagship's
# JSON line under "configs"; a config that raises fails the whole run.
# ---------------------------------------------------------------------------

def _device():
    """The device every JSON line names, as jax reports it — a number
    without it cannot be told from a CPU-backend run."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _mesh_counts():
    """Shard counts the devices jax has allow (1/2/4 on a four-chip
    host, 1/2/4/8 on the 8-device virtual CPU mesh CPU_ENV sets up).
    The platform and device count come from the environment; a sharded
    mode on a single device would measure nothing."""
    import jax
    n = len(jax.devices())
    if n < 2:
        raise RuntimeError(
            f"sharded modes need >= 2 devices, jax has {n} "
            f"({_device()['platform']}); on the CPU run under "
            f"JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return tuple(c for c in (1, 2, 4, 8) if c <= n)


TAIL_RATIO_MAX = 10.0   # p99/p50 above this means an unwarmed compile,
                        # GC stall, or cap growth leaked into the timed run


def _lat_stats(lat_s):
    """{p50_ms, p99_ms, tail_ratio} from per-send wall times (seconds) —
    the BASELINE metric is 'events/sec ...; p99 match latency'."""
    arr = np.sort(np.asarray(lat_s, np.float64)) * 1000.0
    p50 = float(arr[len(arr) // 2])
    p99 = float(arr[min(len(arr) - 1, int(len(arr) * 0.99))])
    return {"p50_ms": round(p50, 2), "p99_ms": round(p99, 2),
            "tail_ratio": round(p99 / max(p50, 1e-9), 2)}


def _report_tail(tag, stats):
    """stderr note, not a gate: a p99/p50 above TAIL_RATIO_MAX usually
    means some one-time cost (an unwarmed XLA compile signature, adaptive
    cap growth) leaked into the timed window — pre-size/warm the bench
    instead of averaging it away.  Host-clock tails on a shared machine
    are too noisy to fail a run on; wrong answers and dropped batches do
    that (_strict, _expect_rows)."""
    r = stats["tail_ratio"]
    verdict = "within" if r <= TAIL_RATIO_MAX else "OVER"
    print(f"{tag}: p99/p50={r} ({verdict} the {TAIL_RATIO_MAX} bar)",
          file=sys.stderr)


def _drive(tag, ql, qname, stream, make_batch, n_batches, want, warmup=1):
    """Warm, then time n_batches sends through one stream.  `want()` is
    read after the last flush: the closed-form number of CURRENT rows the
    seeded data must have delivered over every send, warmup included —
    make_batch keeps the tally it needs.  Returns (events/sec, delivered
    rows, latency stats)."""
    from siddhi_tpu import SiddhiManager
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    count = [0]
    rt.add_batch_callback(
        qname, lambda ts, b: count.__setitem__(0, count[0] + b["n_current"]))
    check = _strict(rt, tag)
    rt.start()
    h = rt.get_input_handler(stream)
    for i in range(warmup):
        wcols, wkw = make_batch(i)
        h.send_columns(wcols, **wkw)
    rt.flush()
    check()
    total = 0
    lat = []
    t0 = time.perf_counter()
    for i in range(n_batches):
        cols, kw = make_batch(warmup + i)
        tb = time.perf_counter()
        h.send_columns(cols, **kw)
        lat.append(time.perf_counter() - tb)
        total += len(cols[0])
    rt.flush()
    dt = time.perf_counter() - t0
    manager.shutdown()
    check()
    _expect_rows(tag, count[0], want())
    return total / dt, count[0], _lat_stats(lat)


def config_length_batch(n_batches=16, B=1 << 17):
    """#1: lengthBatch(1000) + avg(price) (CPU reference sample exists)."""
    ql = """
    @app:playback
    define stream StockStream (symbol long, price float, volume int);
    @info(name='q') from StockStream#window.lengthBatch(1000)
    select avg(price) as ap insert into OutputStream;
    """
    rng = np.random.default_rng(1)
    sent = [0]

    def mk(i):
        sent[0] += B
        return ([np.zeros(B, np.int64),
                 rng.random(B, np.float32), np.ones(B, np.int32)],
                {"timestamps": np.full(B, 1000 + i, np.int64)})
    # every event of a COMPLETED 1000-event batch emits one running-avg row
    eps, _, lat = _drive("lengthBatch_avg", ql, "q", "StockStream", mk,
                         n_batches, want=lambda: sent[0] // 1000 * 1000)
    return eps, lat


def config_time_groupby_having(n_batches=16, B=1 << 17, n_sym=256):
    """#2: sliding time window group-by sum/count/avg + having — the
    shape chip_smoke.py checks by value: sends are 600 ms apart, so every
    send expires the batch two sends back and the 1 sec window holds two
    batches; the slab is sized to hold them.  (The default 2048-row slab
    drops the oldest rows on overflow WITHOUT expiring them out of the
    sums — a different query than the one named here.)"""
    ql = f"""
    @app:playback
    define stream S (symbol long, price float, volume int);
    @capacity(window='{2 * B}')
    @info(name='q') from S#window.time(1 sec)
    select symbol, sum(price) as sp, count() as c, avg(volume) as av
    group by symbol having sp > 0.0
    insert into Out;
    """
    rng = np.random.default_rng(2)
    sent = [0]

    def mk(i):
        sent[0] += B
        return ([rng.integers(0, n_sym, B).astype(np.int64),
                 1.0 - rng.random(B, np.float32),      # (0, 1]: sp > 0
                 np.ones(B, np.int32)],
                {"timestamps": np.full(B, 1000 + i * 600, np.int64)})
    # prices are strictly positive, so `having` passes every arrival
    # warmup=3: the third send is the first that expires rows — its
    # programs must not compile inside the timed window
    eps, _, lat = _drive("time_groupby_having", ql, "q", "S", mk,
                         n_batches, want=lambda: sent[0], warmup=3)
    return eps, lat


def config_windowed_join(n_batches=16, B=1 << 13, n_sym=64):
    """#3: two-stream window.length join on symbol (the audit-corpus
    shape — siddhi_tpu/analysis/corpus.py WINDOWED_JOIN_QL)."""
    ql = WINDOWED_JOIN_QL
    from siddhi_tpu import SiddhiManager
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(ql)
    count = [0]
    rt.add_batch_callback(
        "q", lambda ts, b: count.__setitem__(0, count[0] + b["n_current"]))
    check = _strict(rt, "windowed_join")
    rt.start()
    hl = rt.get_input_handler("L")
    hr = rt.get_input_handler("R")
    rng = np.random.default_rng(3)
    W = 128                     # WINDOWED_JOIN_QL: window.length(128)
    # closed form: every arriving row pairs with each same-symbol row of
    # the OTHER side's window as it stood before the send
    win = {"L": np.zeros(0, np.int64), "R": np.zeros(0, np.int64)}
    want = [0]

    def arrive(side, other, sym):
        want[0] += int(np.bincount(win[other], minlength=n_sym)[sym].sum())
        win[side] = sym[-W:]

    batches = []                # made (and tallied) outside the clock
    for i in range(n_batches + 1):
        ls = rng.integers(0, n_sym, B).astype(np.int64)
        lp = rng.random(B, np.float32)
        rs = rng.integers(0, n_sym, B).astype(np.int64)
        rq = rng.integers(1, 9, B).astype(np.int32)
        arrive("L", "R", ls)
        arrive("R", "L", rs)
        batches.append((ls, lp, rs, rq))

    def send(i):
        ls, lp, rs, rq = batches[i]
        ts = {"timestamps": np.full(B, 1000 + i, np.int64)}
        hl.send_columns([ls, lp], **ts)
        hr.send_columns([rs, rq], **ts)
    send(0)
    rt.flush()
    check()
    total = 0
    lat = []
    t0 = time.perf_counter()
    for i in range(n_batches):
        tb = time.perf_counter()
        send(1 + i)
        lat.append(time.perf_counter() - tb)
        total += 2 * B
    rt.flush()
    dt = time.perf_counter() - t0
    manager.shutdown()
    check()
    _expect_rows("windowed_join", count[0], want[0])
    return total / dt, _lat_stats(lat)


def config_sequence_within(n_batches=32, B=1 << 11):
    """#4: sequence e1=A, e2=B[price > e1.price] within 1 sec.  Non-
    partitioned: a single NFA consumes the stream sequentially, so the
    device scans E=batch events per step — the shape the reference's
    single-threaded loop also faces."""
    mk, want = _sequence_feed(B)
    eps, _, lat = _drive("sequence_within", SEQUENCE_QL.format(ann=""),
                         "q", "S", mk, n_batches, want)
    return eps, lat


def _sequence_feed(B):
    """(make_batch, want) for the sequence_within workload.  Volumes
    alternate 1,2, so the only candidate pairs are rows (2i, 2i+1) of one
    batch, 1 ms apart (`within` never bites): a pair matches iff its
    second price is the greater — `want()` is that count over every batch
    made so far."""
    rng = np.random.default_rng(4)
    pairs = [0]

    def mk(i):
        price = rng.random(B, np.float32)
        pairs[0] += int((price[1::2] > price[0::2]).sum())
        return ([np.zeros(B, np.int64), price,
                 np.tile(np.array([1, 2], np.int32), B // 2)],
                {"timestamps": 1000 + i * 50 +
                 np.arange(B, dtype=np.int64) % 50})
    return mk, lambda: pairs[0]


def flagship_small_batch(B, n_sends=64):
    """Low-latency mode: B events per send (B/4 keys x 4 stages) against a
    key space sized to the batch — the other end of the latency/throughput
    curve (BASELINE metric: 'events/sec ...; p99 match latency').  Sync
    ingest: each send runs staging + device step + emission inline, so the
    per-send time IS the end-to-end match latency."""
    from siddhi_tpu import SiddhiManager
    nk = max(B // 4, 64)
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(QL_TEMPLATE.format(
        async_ann="", pipe_ann="", n_keys=nk, slots=SLOTS))
    matches = [0]
    rt.add_batch_callback(
        "flagship",
        lambda ts, b: matches.__setitem__(0, matches[0] + b["n_current"]))
    check = _strict(rt, f"flagship_smallbatch[{B}]")
    rt.start()
    h = rt.get_input_handler("TradeStream")
    keys = np.repeat(np.arange(nk, dtype=np.int64), 4)
    vol4 = np.tile(np.array([1, 2, 3, 4], np.int32), nk)
    price4 = vol4.astype(np.float32)
    clock = [1000]

    def send():
        clock[0] += 10
        ts = clock[0] + np.tile(np.arange(4, dtype=np.int64), nk)
        h.send_columns([keys, price4, vol4], timestamps=ts)

    send()   # warmup / compile
    rt.flush()
    check()
    lat = []
    total = 0
    t0 = time.perf_counter()
    for _ in range(n_sends):
        tb = time.perf_counter()
        send()
        lat.append(time.perf_counter() - tb)
        total += 4 * nk
    rt.flush()
    dt = time.perf_counter() - t0
    manager.shutdown()
    check()
    # one match per key per send
    _expect_rows(f"flagship_smallbatch[{B}]", matches[0],
                 (1 + n_sends) * nk)
    return total / dt, _lat_stats(lat)


def _sequence_staged(B, k, interner):
    """K staged micro-batches of the sequence_within workload (one
    dispatch + one fetch per 2048 events: per-send fixed cost bound)."""
    from siddhi_tpu.core import event as ev
    rng = np.random.default_rng(4)
    items = []
    for i in range(k):
        ts = 1000 + i * 50 + np.arange(B, dtype=np.int64) % 50
        cols = [np.zeros(B, np.int64),
                rng.random(B).astype(np.float32),
                np.tile(np.array([1, 2], np.int32), B // 2)]
        valid = np.ones(B, np.bool_)
        kind = np.zeros(B, np.int32)
        items.append(("S", ev.StagedBatch(ts, kind, valid, cols, B),
                      1000 + i * 50))
    return items


def run_device_loop(k=16, B=1 << 11, iters=50):
    """--mode device_loop: DEVICE-SIDE events/sec of the compiled step.

    The fused step's inputs are staged to the device ONCE; the loop then
    re-dispatches the same [K, B] stack `iters` times with no emission
    fetch (no consumers) and no host staging, blocking only at the end —
    so the measured rate is the compiled query step's device throughput,
    independent of host packing, H2D and the emission fetch."""
    import jax

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core import fusion
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(
        SEQUENCE_QL.format(ann=f"@fuse(batches='{k}')"))
    rt.start()
    qr = rt.query_runtimes["q"]
    assert qr._fuse is not None, "sequence query must be fuse-eligible"
    items = _sequence_staged(B, k, manager.interner)
    fn, xs, const = fusion._prepare_pattern(qr, items)
    state = qr.state
    t0 = time.perf_counter()
    state, _ = fn(state, xs, const)
    jax.block_until_ready(state)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        state, _ = fn(state, xs, const)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    qr.state = state
    eps = iters * k * B / dt
    print(f"device_loop: {iters} fused dispatches x {k} batches x {B} "
          f"events in {dt:.3f}s (compile {compile_s:.1f}s)",
          file=sys.stderr)
    print(json.dumps({
        "metric": "device_loop_chip_events_per_sec",
        "value": round(eps),
        "unit": "events/sec",
        "k": k, "batch": B, "iters": iters,
        "dispatch_ms": round(dt / iters * 1000, 3),
        "device": _device(),
        "note": ("device-side throughput of the compiled sequence step: "
                 "device-resident [K,B] inputs, zero emission fetches, "
                 "no host staging"),
    }))
    manager.shutdown()
    return eps


def run_fuse_compare(k=8, B=1 << 11, n_batches=64):
    """--mode fuse_compare: end-to-end sequential vs @fuse(batches=K) on
    the sequence_within workload — the per-batch dispatch-overhead
    amortization measured through the full send path."""
    results = {}
    for tag, ann in (("sequential", ""),
                     (f"fused_k{k}", f"@fuse(batches='{k}')")):
        mk, want = _sequence_feed(B)
        eps, count, lat = _drive(f"fuse_compare[{tag}]",
                                 SEQUENCE_QL.format(ann=ann), "q", "S",
                                 mk, n_batches, want, warmup=max(2, k))
        results[tag] = {"value": round(eps), "unit": "events/sec",
                        "matches": count, **lat}
        print(f"fuse_compare[{tag}]: {eps:,.0f} ev/s "
              f"p50={lat['p50_ms']}ms p99={lat['p99_ms']}ms",
              file=sys.stderr)
    base = results["sequential"]["value"]
    fused = results[f"fused_k{k}"]["value"]
    print(json.dumps({
        "metric": "fuse_compare_sequence_events_per_sec",
        "k": k, "batch": B, "n_batches": n_batches,
        "speedup": round(fused / max(base, 1), 2),
        "configs": results,
        "device": _device(),
    }))
    return results


def run_serve_compare(k=8, B=1 << 11, n_batches=64, iters=20,
                      out_path=None):
    """--mode serve_compare: the device-resident serving loop A/B.

    The identical @fuse(batches=K) sequence workload end-to-end, twice:
    blocking (every fused drain pays the emission fetch on the send
    path) vs @serve (emissions append into the on-device ring; the
    async drainer pays the fetch off-path).  Match counts must agree —
    serving changes WHEN the fetch happens, never the outputs.  The
    device_loop chip ceiling for the same (K, B) closes the triangle:
    `served_over_device_loop` is the fraction of pure chip throughput
    the served send path sustains (the SERVE artifact's headline gap)."""
    results = {}
    for tag, ann in (("blocking", f"@fuse(batches='{k}')"),
                     ("served", f"@serve\n@fuse(batches='{k}')")):
        mk, want = _sequence_feed(B)
        eps, count, lat = _drive(f"serve_compare[{tag}]",
                                 SEQUENCE_QL.format(ann=ann), "q", "S",
                                 mk, n_batches, want, warmup=max(2, k))
        results[tag] = {"value": round(eps), "unit": "events/sec",
                        "matches": count, **lat}
        print(f"serve_compare[{tag}]: {eps:,.0f} ev/s "
              f"p50={lat['p50_ms']}ms p99={lat['p99_ms']}ms "
              f"matches={count}", file=sys.stderr)
    ceiling = run_device_loop(k=k, B=B, iters=iters)
    base = results["blocking"]["value"]
    served = results["served"]["value"]
    payload = {
        "metric": "serve_compare_sequence_events_per_sec",
        "k": k, "batch": B, "n_batches": n_batches,
        "speedup": round(served / max(base, 1), 2),
        "device_loop_events_per_sec": round(ceiling),
        "served_over_device_loop": round(served / max(ceiling, 1), 4),
        "configs": results,
        "shape": "analysis/corpus.py SEQUENCE_QL (+@serve)",
        "device": _device(),
    }
    print(json.dumps(payload))
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"wrote {out_path}", file=sys.stderr)
    return payload


def _phase_manager(sample_every):
    """SiddhiManager with the sampled deep-profiling mode armed
    (profile.sample.every=N fences every Nth dispatch to split
    dispatch_submit from device_compute — observability/phases.py)."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.utils.config import InMemoryConfigManager
    manager = SiddhiManager()
    manager.set_config_manager(InMemoryConfigManager(
        {"profile.sample.every": str(sample_every)}))
    return manager


def _phase_flagship(serve, n_keys, n_sends, sample_every):
    """Flagship pattern (blocking or @serve) with phase attribution on:
    returns (events/sec, the query's phase_report node).  Warmup phases
    are dropped (stats.reset after compile) so the table attributes the
    steady state only."""
    manager = _phase_manager(sample_every)
    rt = manager.create_siddhi_app_runtime(QL_TEMPLATE.format(
        async_ann="", pipe_ann="@serve" if serve else "",
        n_keys=n_keys, slots=SLOTS))
    rt.set_statistics_level("BASIC")
    matches = [0]
    rt.add_batch_callback(
        "flagship",
        lambda ts, b: matches.__setitem__(0, matches[0] + b["n_current"]))
    tag = f"phase_profile[flagship/{'served' if serve else 'blocking'}]"
    check = _strict(rt, tag)
    rt.start()
    h = rt.get_input_handler("TradeStream")
    keys = np.repeat(np.arange(n_keys, dtype=np.int64), 4)
    vol4 = np.tile(np.array([1, 2, 3, 4], np.int32), n_keys)
    price4 = vol4.astype(np.float32)
    clock = [1000]

    def send():
        clock[0] += 10
        ts = clock[0] + np.tile(np.arange(4, dtype=np.int64), n_keys)
        h.send_columns([keys, price4, vol4], timestamps=ts)

    send()
    rt.flush()
    rt.stats.reset()
    t0 = time.perf_counter()
    for _ in range(n_sends):
        send()
    rt.flush()
    dt = time.perf_counter() - t0
    rep = rt.phase_report()
    manager.shutdown()
    check()
    _expect_rows(tag, matches[0], (1 + n_sends) * n_keys)
    eps = n_sends * 4 * n_keys / dt
    return eps, rep["queries"].get("flagship", {})


def _phase_flagship_sharded(n, keys, B, sweeps, sample_every):
    """_mc_flagship with phase attribution on: same partitioned
    @fuse(batches=4) pattern on an n-way mesh, returning the fused
    group's / query's phase nodes alongside events/sec."""
    manager = _phase_manager(sample_every)
    rt = manager.create_siddhi_app_runtime(
        MC_FLAGSHIP_QL.format(keys=keys), mesh=_mc_mesh(n))
    rt.set_statistics_level("BASIC")
    matches = [0]
    rt.add_batch_callback(
        "flagship",
        lambda ts, b: matches.__setitem__(0, matches[0] + b["n_current"]))
    check = _strict(rt, f"phase_profile[sharded@{n}]")
    rt.start()
    h = rt.get_input_handler("TradeStream")
    key_col = np.arange(keys, dtype=np.int64)
    price = ((key_col % 7) + 1).astype(np.float32)
    clock = [1000]

    def cycle():
        for stage in (1, 2, 3, 4):
            vol = np.full(keys, stage, np.int32)
            pr = price + stage
            for lo in range(0, keys, B):
                clock[0] += 10
                h.send_columns(
                    [key_col[lo:lo + B].copy(), pr[lo:lo + B].copy(),
                     vol[lo:lo + B].copy()],
                    timestamps=np.full(min(B, keys - lo), clock[0],
                                       np.int64))
        rt.flush()

    cycle()
    rt.stats.reset()
    t0 = time.perf_counter()
    for _ in range(sweeps):
        cycle()
    dt = time.perf_counter() - t0
    rep = rt.phase_report()
    manager.shutdown()
    check()
    # every cycle walks each key through its 4 stages: one match per key
    _expect_rows(f"phase_profile[sharded@{n}]", matches[0],
                 (1 + sweeps) * keys)
    return sweeps * keys * 4 / dt, rep["queries"]


def run_phase_profile(quick=False, out_path=None, sample_every=16):
    """--mode phase_profile: where the wall time actually goes.

    Three tables from the always-on phase profiler + sampled deep mode
    (observability/phases.py), all host clocks:
      1. flagship blocking — every emission fetch on the send path,
      2. flagship @serve — device ring + async drain pays the fetch,
      3. sharded flagship at the shard counts the devices allow
         (1/2/4/8 on the virtual CPU mesh, 1/2/4 on a four-chip host).
    Each table is per-phase {seconds, count, share-of-e2e}; `accounted`
    is sum(phases)/e2e (the remainder is `other`).  The blocking-vs-
    @serve pair shows the d2h_drain share MOVING off the send path —
    the phase-level proof of the serving loop's design claim."""
    if quick:
        n_keys, n_sends = 256, 12
        sh_keys, sh_b, sweeps = 512, 256, 2
    else:
        n_keys, n_sends = 1 << 13, 32
        sh_keys, sh_b, sweeps = 1 << 13, 1 << 11, 3

    flagship = {}
    for tag, serve in (("blocking", False), ("served", True)):
        eps, node = _phase_flagship(serve, n_keys, n_sends, sample_every)
        flagship[tag] = {"events_per_sec": round(eps), **node}
        print(f"phase_profile[flagship/{tag}]: {eps:,.0f} ev/s "
              f"accounted={node.get('accounted')}", file=sys.stderr)

    # sections 1 and 2 need one device; only the sharded table needs more
    import jax
    shard_counts = _mesh_counts() if len(jax.devices()) >= 2 else ()
    if not shard_counts:
        print("phase_profile[sharded]: skipped, jax has one device",
              file=sys.stderr)
    sharded = {}
    for n in shard_counts:
        eps, queries = _phase_flagship_sharded(
            n, sh_keys, sh_b, sweeps, sample_every)
        sharded[str(n)] = {"events_per_sec": round(eps),
                           "queries": queries}
        acc = {q: v.get("accounted") for q, v in queries.items()}
        print(f"phase_profile[sharded@{n}]: {eps:,.0f} ev/s "
              f"accounted={acc}", file=sys.stderr)

    payload = {
        "mode": "phase_profile",
        "device": _device(),
        "shard_counts": list(shard_counts),
        "sample_every": sample_every,
        "quick": quick,
        "phases": "stage_host h2d dispatch_submit device_compute "
                  "ring_wait d2h_drain demux sink".split(),
        "flagship": flagship,
        "sharded_flagship": sharded,
        "note": (
            "per-(query, phase) wall seconds from host clocks only "
            "(observability/phases.py); device_compute comes from the "
            "sampled deep mode fencing every Nth dispatch, so its "
            "count < dispatch count by design.  share = phase/e2e; "
            "`accounted` = sum(phases)/e2e, remainder `other` "
            "(scheduler/queue wait).  blocking vs served shows "
            "d2h_drain leaving the send path for the drainer thread."),
    }
    print(json.dumps({k: v for k, v in payload.items() if k != "note"}))
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
            fh.write("\n")
        print(f"wrote {out_path}", file=sys.stderr)
    return payload


def _state_trace(dist, n_batches, B, n_keys, seed=16):
    """[n_batches, B] int64 key trace: 'zipf' (s=1.2, clipped to the
    key space) or 'uniform'."""
    rng = np.random.default_rng(seed)
    if dist == "zipf":
        keys = np.minimum(rng.zipf(1.2, (n_batches, B)) - 1, n_keys - 1)
    else:
        keys = rng.integers(0, n_keys, (n_batches, B))
    return keys.astype(np.int64)


def _exact_hot_share(keys, fraction=0.01):
    """Ground truth for the observatory's estimate: exact share of
    traffic landing in the hottest ceil(distinct * fraction) keys."""
    _, counts = np.unique(keys, return_counts=True)
    top = max(1, int(np.ceil(len(counts) * fraction)))
    counts.sort()
    return float(counts[-top:].sum() / counts.sum())


def run_state_profile(quick=False, out_path=None):
    """--mode state_profile: what the state observatory measures on the
    flagship under skewed vs flat key traffic (STATE artifact).

    Two arms of the partitioned flagship NFA, identical except for the
    key trace: Zipf(1.2) vs uniform over the same key space.  Each arm
    reports the observatory's per-structure occupancy/high-water and
    its estimated hot-set concentration (share of traffic in the top
    1% of keys, from the count-min + space-saving sketches) against
    the EXACT concentration computed from the generated trace — the
    sketch error is part of the artifact.  The Zipf arm's hot-set
    share is the measured motivation for ROADMAP item 4's tiered key
    state; the high-water table is the sizing-hints ledger a restart
    would adopt."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.utils.config import InMemoryConfigManager
    if quick:
        n_keys, B, n_batches = 256, 256, 8
    else:
        n_keys, B, n_batches = 1 << 12, 1 << 11, 32

    arms = {}
    for dist in ("zipf", "uniform"):
        manager = SiddhiManager()
        manager.set_config_manager(InMemoryConfigManager(
            {"state.obs.sample.every": "4"}))
        rt = manager.create_siddhi_app_runtime(QL_TEMPLATE.format(
            async_ann="", pipe_ann="", n_keys=n_keys, slots=SLOTS))
        rt.set_statistics_level("BASIC")
        matches = [0]
        rt.add_batch_callback(
            "flagship", lambda ts, b: matches.__setitem__(
                0, matches[0] + b["n_current"]))
        check = _strict(rt, f"state_profile[{dist}]")
        rt.start()
        h = rt.get_input_handler("TradeStream")
        keys = _state_trace(dist, n_batches, B, n_keys)
        clock = 1000
        t0 = time.perf_counter()
        for i in range(n_batches):
            kb = keys[i]
            # volumes cycle 1..4 so NFA chains progress and complete
            vol = np.full(B, (i % 4) + 1, np.int32)
            price = ((kb % 7) + (i % 4) + 1).astype(np.float32)
            clock += 10
            h.send_columns([kb.copy(), price, vol],
                           timestamps=np.full(B, clock, np.int64))
        rt.flush()
        dt = time.perf_counter() - t0
        check()
        rep = rt.state_report()
        node = rep["structures"].get("flagship", {})
        hot = rep["hotness"].get("flagship", {})
        exact = _exact_hot_share(keys)
        arms[dist] = {
            "events_per_sec": round(n_batches * B / dt),
            "matches": matches[0],
            "distinct_keys_sent": int(len(np.unique(keys))),
            "hot_share_top1pct_exact": round(exact, 4),
            "hot_share_top1pct_estimated": hot.get("hot_share_1pct"),
            "hotness": hot,
            "structures": node,
            "sizing_hints": rep["sizing_hints"].get("flagship", {}),
        }
        print(f"state_profile[{dist}]: {arms[dist]['events_per_sec']:,}"
              f" ev/s, hot-1% exact={exact:.3f} "
              f"est={hot.get('hot_share_1pct')}", file=sys.stderr)
        manager.shutdown()

    # the artifact's claim: the observatory separates skewed from flat
    z = arms["zipf"]["hot_share_top1pct_estimated"] or 0.0
    u = arms["uniform"]["hot_share_top1pct_estimated"] or 1.0
    assert z > 2 * u, f"hot-set estimate failed to separate " \
        f"zipf ({z}) from uniform ({u})"

    payload = {
        "mode": "state_profile",
        "device": _device(),
        "quick": quick,
        "n_keys": n_keys, "batch": B, "n_batches": n_batches,
        "arms": arms,
        "note": (
            "flagship partitioned NFA driven by Zipf(1.2) vs uniform "
            "key traces over the same key space; hot_share_top1pct_* "
            "is the share of keyed traffic in the hottest 1% of "
            "distinct keys — 'exact' from the generated trace, "
            "'estimated' from the observatory's count-min + space-"
            "saving sketches fed by staging's per-batch key sets "
            "(observability/stateobs.py, zero device fetches).  "
            "structures/sizing_hints are the per-structure occupancy "
            "and high-water a snapshot carries across restarts."),
    }
    print(json.dumps({k: v for k, v in payload.items() if k != "note"}))
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
            fh.write("\n")
        print(f"wrote {out_path}", file=sys.stderr)
    return payload


def run_join_compare(B=1 << 10, n_batches=8, out_path=None):
    """--mode join_compare: the windowed_join corpus shape with the
    equi-join fast path ON vs OFF (full [R,C] grid), plus the
    cost_analysis bytes-accessed delta for the same two plans."""
    from siddhi_tpu.core import join as joinmod

    results = {}
    costs = {}
    for tag, fast in (("fastpath", True), ("grid", False)):
        joinmod.FASTPATH_ENABLED = fast
        try:
            eps, lat = config_windowed_join(n_batches=n_batches, B=B)
            results[tag] = {"value": round(eps), "unit": "events/sec",
                            **lat}
            costs[tag] = _join_cost_fingerprint()
        finally:
            joinmod.FASTPATH_ENABLED = True
        print(f"join_compare[{tag}]: {eps:,.0f} ev/s "
              f"p50={lat['p50_ms']}ms p99={lat['p99_ms']}ms "
              f"bytes/dispatch={costs[tag]['bytes_accessed']:,}",
              file=sys.stderr)
    base = results["grid"]["value"]
    fastv = results["fastpath"]["value"]
    payload = {
        "metric": "join_compare_windowed_join_events_per_sec",
        "batch": B, "n_batches": n_batches,
        "speedup": round(fastv / max(base, 1), 2),
        "bytes_accessed_delta": round(
            1.0 - costs["fastpath"]["bytes_accessed"] /
            max(costs["grid"]["bytes_accessed"], 1), 4),
        "configs": results,
        "cost_analysis": costs,
        "shape": "analysis/corpus.py WINDOWED_JOIN_QL",
        "device": _device(),
    }
    print(json.dumps(payload))
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"wrote {out_path}", file=sys.stderr)
    return payload


def _mqo_ql(n_queries):
    """The mqo_compare app: N co-resident queries on ONE stream — half
    plain filters (each its own threshold), half window aggregations
    sharing the identical pre-filter + window.length(128) + group-by
    (the surveillance/fraud/IoT tenant shape ROADMAP item 3 names).
    The multi-query optimizer merges all of them into one dispatch; the
    aggregation half additionally shares ONE window buffer."""
    aggs = ["sum(v) as a", "max(v) as a", "min(v) as a", "avg(v) as a",
            "count() as a"]
    lines = ["define stream S (key long, v double, c int);"]
    for i in range(n_queries):
        if i % 2 == 0:
            t = 1.0 + (i % 7)
            lines.append(
                f"@info(name='q{i}') from S[v > {t} and c != {i % 5}] "
                f"select key, v insert into F{i};")
        else:
            lines.append(
                f"@info(name='q{i}') from S[v > 0.0]"
                f"#window.length(128) select key, {aggs[i % 5]} "
                f"group by key insert into W{i};")
    return "\n".join(lines)


def run_mqo_compare(n_queries=50, B=1 << 11, n_batches=24,
                    out_path=None, check_bars=True):
    """--mode mqo_compare: the ROADMAP item-3 A-B artifact — a
    {n_queries}-query single-stream app served with the multi-query
    optimizer ON (merged dispatch, default) vs OFF
    (optimizer.merge.enabled=false), byte-identical per-query outputs
    asserted on a seeded prefix, then throughput + dispatch counts
    measured with a counting batch callback on EVERY query (each
    emission is consumed, as a dashboard tenant would)."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.utils.config import InMemoryConfigManager

    ql = _mqo_ql(n_queries)
    qnames = [f"q{i}" for i in range(n_queries)]
    rng = np.random.default_rng(11)
    sends = []
    for i in range(n_batches + 4):
        sends.append((
            [rng.integers(0, 64, B).astype(np.int64),
             rng.random(B).astype(np.float64) * 10.0,
             rng.integers(0, 8, B).astype(np.int32)],
            1000 + i * 50 + np.arange(B, dtype=np.int64) % 50))

    # -- parity: byte-identical per-query outputs on a seeded prefix ----
    def capture(merge, k=6):
        manager = SiddhiManager()
        if not merge:
            manager.set_config_manager(InMemoryConfigManager(
                {"optimizer.merge.enabled": "false"}))
        rt = manager.create_siddhi_app_runtime(ql)
        outs = {q: [] for q in qnames}
        check = _strict(rt, f"mqo_compare parity[merge={merge}]")
        for q in qnames:
            rt.add_callback(q, lambda ts, cur, exp, _q=q: outs[_q].append(
                ([e.data for e in (cur or [])],
                 [e.data for e in (exp or [])])))
        rt.start()
        h = rt.get_input_handler("S")
        for cols, ts in sends[:k]:
            h.send_columns([c.copy() for c in cols],
                           timestamps=ts.copy())
        rt.flush()
        check()
        groups = sorted(getattr(rt, "merged_groups", {}))
        manager.shutdown()
        return outs, groups

    merged_outs, groups = capture(True)
    unmerged_outs, _ = capture(False)
    identical = merged_outs == unmerged_outs
    print(f"mqo_compare parity: byte-identical={identical} over "
          f"{sum(len(v) for v in merged_outs.values())} emissions / "
          f"{n_queries} queries (groups={groups})", file=sys.stderr)
    assert identical, "merged vs unmerged per-query outputs diverged"

    # -- throughput + dispatch count A/B --------------------------------
    results = {}
    for tag, merge in (("merged", True), ("unmerged", False)):
        manager = SiddhiManager()
        if not merge:
            manager.set_config_manager(InMemoryConfigManager(
                {"optimizer.merge.enabled": "false"}))
        rt = manager.create_siddhi_app_runtime(ql)
        check = _strict(rt, f"mqo_compare[{tag}]")
        counts = {q: 0 for q in qnames}
        for q in qnames:
            rt.add_batch_callback(q, lambda ts, b, _q=q: counts.__setitem__(
                _q, counts[_q] + b["n_valid"]))
        # count ACTUAL jitted-step invocations in both modes by wrapping
        # the compiled entry points (in-process bench, zero steady cost)
        disp = [0]

        def _wrap(fn):
            def counted(*a, **kw):
                disp[0] += 1
                return fn(*a, **kw)
            return counted
        if merge:
            for mg in rt.merged_groups.values():
                mg._step = _wrap(mg._step)
        else:
            for q in qnames:
                qr = rt.query_runtimes[q]
                qr.planned.step = _wrap(qr.planned.step)
        rt.start()
        h = rt.get_input_handler("S")
        for cols, ts in sends[:4]:          # warmup / compile
            h.send_columns([c.copy() for c in cols],
                           timestamps=ts.copy())
        rt.flush()
        check()
        warm_counts = dict(counts)
        warm_disp = disp[0]
        lat = []
        t0 = time.perf_counter()
        for cols, ts in sends[4:4 + n_batches]:
            tb = time.perf_counter()
            h.send_columns([c.copy() for c in cols],
                           timestamps=ts.copy())
            lat.append(time.perf_counter() - tb)
        rt.flush()
        dt = time.perf_counter() - t0
        check()
        events = n_batches * B
        dispatches = disp[0] - warm_disp
        rows = sum(counts[q] - warm_counts[q] for q in qnames)
        eps = events / dt
        stats = _lat_stats(lat)
        results[tag] = {
            "value": round(eps), "unit": "events/sec",
            "aggregate_query_events_per_sec": round(eps * n_queries),
            "dispatches": int(dispatches),
            "rows_delivered": int(rows),
            "state_bytes": sum(
                n for comps in rt.state_memory().values()
                for n in comps.values()),
            **stats,
        }
        print(f"mqo_compare[{tag}]: {eps:,.0f} ev/s x {n_queries} "
              f"queries, {dispatches} dispatches, "
              f"p50={stats['p50_ms']}ms p99={stats['p99_ms']}ms",
              file=sys.stderr)
        manager.shutdown()
    base = results["unmerged"]["value"]
    fast = results["merged"]["value"]
    disp_ratio = results["merged"]["dispatches"] / \
        max(1, results["unmerged"]["dispatches"])
    payload = {
        "metric": "mqo_compare_events_per_sec",
        "queries": n_queries, "batch": B, "n_batches": n_batches,
        "speedup": round(fast / max(base, 1), 2),
        "dispatch_ratio": round(disp_ratio, 4),
        "outputs_byte_identical": identical,
        "merge_groups": groups,
        "state_bytes_saved": results["unmerged"]["state_bytes"] -
        results["merged"]["state_bytes"],
        "configs": results,
        "shape": "bench._mqo_ql (half filters, half shared-window "
                 "aggregations on one stream)",
        "bars": {"dispatch_ratio<=0.25": disp_ratio <= 0.25,
                 "aggregate_speedup>=4x": fast / max(base, 1) >= 4.0},
        "device": _device(),
    }
    print(json.dumps(payload))
    ok = payload["bars"]["dispatch_ratio<=0.25"] and \
        payload["bars"]["aggregate_speedup>=4x"]
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"wrote {out_path}", file=sys.stderr)
    if check_bars and not ok:
        print(f"MQO BARS MISSED: {payload['bars']}", file=sys.stderr)
        sys.exit(1)
    return payload


def _join_cost_fingerprint():
    """Hot-path flops/bytes of the CURRENT windowed_join plan (both side
    steps summed) via the audit extractor — traffic-free, synthesized
    signatures."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.analysis.audit import query_fingerprint
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(WINDOWED_JOIN_QL)
    rt.start()
    try:
        fp = query_fingerprint(rt, "q")
        tot = fp.get("totals", {})
        return {"flops": int(tot.get("flops", 0)),
                "bytes_accessed": int(tot.get("bytes_accessed", 0)),
                "fastpath": fp.get("equi_fastpath", {})}
    finally:
        manager.shutdown()


def _enable_compile_cache():
    """Persistent XLA compile cache, placed from outside: the directory is
    JAX_COMPILATION_CACHE_DIR when set (nothing is set in code), else
    <checkout>/.jax_cache — siddhi_tpu/utils/compile_cache.py."""
    from siddhi_tpu.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)


def main():
    _enable_compile_cache()
    print(f"device: {_device()}", file=sys.stderr)
    baseline = run_python_baseline()
    # any mode or config that raises (a wrong match count included) ends
    # the run with a traceback and a non-zero exit: partial numbers next
    # to a hidden failure are worse than none
    results = {}
    for mode_name, kw in (("sync", {}), ("pipeline", {"pipeline": True}),
                          ("async", {"async_ingest": True}),
                          ("served", {"serve": True})):
        results[mode_name] = run_tpu(**kw)
    mode = max(results, key=lambda m: results[m][0])
    eps, lat = results[mode]
    configs = {}
    for m, (v, l) in results.items():
        configs[f"flagship_{m}"] = {"value": round(v),
                                    "unit": "events/sec", **l}
    for key, cfg_fn in (
            ("lengthBatch_avg", config_length_batch),
            ("time_groupby_having", config_time_groupby_having),
            ("windowed_join", config_windowed_join),
            ("sequence_within", config_sequence_within),
            ("flagship_smallbatch_1k",
             lambda: flagship_small_batch(1 << 10)),
            ("flagship_smallbatch_8k",
             lambda: flagship_small_batch(1 << 13))):
        t0 = time.perf_counter()
        v, lat_c = cfg_fn()
        configs[key] = {"value": round(v), "unit": "events/sec", **lat_c}
        print(f"config {key}: {v:,.0f} ev/s p50={lat_c['p50_ms']}ms "
              f"p99={lat_c['p99_ms']}ms "
              f"({time.perf_counter()-t0:.1f}s)", file=sys.stderr)

    def _git_hash():
        import os
        import subprocess
        try:
            return subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=10).stdout.strip()
        except Exception:  # noqa: BLE001
            return "unknown"
    print(json.dumps({
        "metric": "pattern_4state_1Mkeys_events_per_sec",
        "value": round(eps),
        "unit": "events/sec",
        "vs_baseline": round(eps / baseline, 2),
        "device": _device(),
        "ingest_mode": mode,
        "p50_ms": lat["p50_ms"],
        "p99_ms": lat["p99_ms"],
        "git": _git_hash(),
        "configs": configs,
        "baseline_note": (
            "vs_baseline compares against a measured CPython per-event NFA "
            "interpreter (no JVM exists in this image). A JVM runs that "
            "interpreter-shaped loop roughly 10-50x faster than CPython, "
            "so vs_baseline/10..50 estimates the multiple over real "
            "single-JVM Siddhi; treat vs_baseline near 10 as parity."),
    }))


def run_cost_analysis(B=1 << 12, n_keys=1 << 12):
    """--mode cost_analysis: the PERF.md round-7 table — EXPLAIN's XLA
    cost/memory analysis of the flagship and sequence_within steps at the
    signatures real traffic traces (observability/explain.py).  Device
    numbers, not wall clock: flops, bytes accessed, and peak memory per
    dispatch, so perf PRs can argue arithmetic intensity instead of only
    end-to-end seconds."""
    from siddhi_tpu import SiddhiManager
    rng = np.random.default_rng(0)
    workloads = []
    ql_flag = QL_TEMPLATE.format(async_ann="", pipe_ann="",
                                 n_keys=n_keys, slots=SLOTS)
    nk = B // 4

    def send_flagship(h, s):
        h.send_columns(
            [np.repeat(np.arange(nk, dtype=np.int64), 4),
             rng.random(B).astype(np.float32),
             np.tile(np.array([1, 2, 3, 4], np.int32), nk)],
            timestamps=1000 + s * 100 + np.arange(B, dtype=np.int64) % 50)
    workloads.append(("flagship", ql_flag, "TradeStream", "flagship",
                      send_flagship))
    ql_seq = SEQUENCE_QL.format(ann="")

    def send_seq(h, s):
        h.send_columns(
            [np.zeros(B, np.int64), rng.random(B).astype(np.float32),
             np.tile(np.array([1, 2], np.int32), B // 2)],
            timestamps=1000 + s * 50 + np.arange(B, dtype=np.int64) % 50)
    workloads.append(("sequence_within", ql_seq, "S", "q", send_seq))
    out = {}
    for label, ql, sid, qname, send in workloads:
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(ql)
        check = _strict(rt, f"cost_analysis[{label}]")
        rt.start()
        h = rt.get_input_handler(sid)
        for s in range(2):          # warm: trace the steady-state step
            send(h, s)
        rt.flush()
        check()
        rep = rt.explain(qname)
        steps = {}
        for role, c in rep["steps"].items():
            if not c.get("available"):
                if c.get("signature"):
                    # a step that RAN but whose analysis the backend
                    # refused is a failure to report, not a row to skip
                    raise RuntimeError(
                        f"cost_analysis[{label}/{role}]: "
                        f"{c.get('reason')}")
                continue
            memb = c.get("memory", {})
            steps[role] = {
                "flops": c.get("flops"),
                "bytes_accessed": c.get("bytes_accessed"),
                "peak_bytes": memb.get("peak_bytes"),
                "temp_bytes": memb.get("temp_bytes"),
                "flops_per_byte": round(
                    c["flops"] / c["bytes_accessed"], 4)
                if c.get("bytes_accessed") else None,
            }
            print(f"{label}/{role}: flops={c.get('flops'):,.0f} "
                  f"bytes={c.get('bytes_accessed'):,.0f} "
                  f"peak={memb.get('peak_bytes', 0):,}", file=sys.stderr)
        out[label] = {"B": B, "steps": steps,
                      "state_bytes": rep["state"]["component_bytes"]}
        m.shutdown()
    print(json.dumps({"mode": "cost_analysis", "device": _device(),
                      **out}))


def _mc_mesh(n):
    import jax
    from jax.sharding import Mesh
    if n <= 1:
        return None
    return Mesh(np.array(jax.devices()[:n]), ("shard",))


def _mc_collect(rt, qname):
    rows = []
    rt.add_callback(qname, lambda ts, i, o: rows.extend(
        tuple(e.data) for e in (i or []) + (o or [])))
    return rows


def _mc_flagship(n, keys, B, sweeps):
    """Partitioned 4-state pattern (the flagship serving shape) on an
    n-way mesh: keys round-robin onto shards behind the unchanged
    InputHandler path, @fuse(batches=4) amortizing dispatch per shard."""
    from siddhi_tpu import SiddhiManager
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(
        MC_FLAGSHIP_QL.format(keys=keys), mesh=_mc_mesh(n))
    rows = _mc_collect(rt, "flagship")
    check = _strict(rt, f"multichip flagship@{n}")
    rt.start()
    h = rt.get_input_handler("TradeStream")
    key_col = np.arange(keys, dtype=np.int64)
    price = ((key_col % 7) + 1).astype(np.float32)
    clock = [1000]

    def cycle():
        for stage in (1, 2, 3, 4):
            vol = np.full(keys, stage, np.int32)
            pr = price + stage
            for lo in range(0, keys, B):
                clock[0] += 10
                h.send_columns(
                    [key_col[lo:lo + B].copy(), pr[lo:lo + B].copy(),
                     vol[lo:lo + B].copy()],
                    timestamps=np.full(min(B, keys - lo), clock[0],
                                       np.int64))
        rt.flush()

    cycle()                       # warm: trace/compile every shard step
    t0 = time.perf_counter()
    for _ in range(sweeps):
        cycle()
    dt = time.perf_counter() - t0
    if n >= 2:
        from __graft_entry__ import _assert_state_distributed
        _assert_state_distributed(
            rt.query_runtimes["flagship"].state, n, f"flagship@{n}")
    manager.shutdown()
    check()
    _expect_rows(f"multichip flagship@{n}", len(rows), (1 + sweeps) * keys)
    return sweeps * keys * 4 / dt, sorted(rows)


def _mc_windowed_join(n, B, n_batches):
    """Windowed equi-join: window buffers shard via
    GSPMD row placement; the [R,C] compare gathers over the mesh."""
    from siddhi_tpu import SiddhiManager
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(MC_JOIN_QL, mesh=_mc_mesh(n))
    rows = _mc_collect(rt, "wjoin")
    check = _strict(rt, f"multichip windowed_join@{n}")
    rt.start()
    hl = rt.get_input_handler("JL")
    hr = rt.get_input_handler("JR")
    sym = (np.arange(B, dtype=np.int64) % 32)

    def send(i):
        ts = np.full(B, 1000 + i * 10, np.int64)
        hl.send_columns([sym.copy(),
                         (sym % 5 + i).astype(np.float32)],
                        timestamps=ts)
        hr.send_columns([sym.copy(), (sym % 3 + i).astype(np.int32)],
                        timestamps=ts + 1)

    send(0)
    rt.flush()
    t0 = time.perf_counter()
    for i in range(1, n_batches + 1):
        send(i)
    rt.flush()
    dt = time.perf_counter() - t0
    manager.shutdown()
    check()
    return n_batches * 2 * B / dt, sorted(rows)


def _mc_block_nfa(n, B, n_batches):
    """Single-key block-NFA sequence served through
    a MESHED runtime: the block path is mesh-invariant by design (one
    key cannot shard), so the check here is that the sharded serving
    runtime runs it byte-identically — scaling is expected flat."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.pattern_block import block_eligible
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(
        SEQUENCE_QL.format(ann=""), mesh=_mc_mesh(n))
    assert block_eligible(rt.query_runtimes["q"].planned.spec), \
        "sequence shape must take the block-NFA path"
    rows = _mc_collect(rt, "q")
    check = _strict(rt, f"multichip block_nfa@{n}")
    rt.start()
    h = rt.get_input_handler("S")
    price = ((np.arange(B) * 2654435761 % 97) / 97.0).astype(np.float32)
    vol = np.tile(np.array([1, 2], np.int32), B // 2)

    def send(i):
        h.send_columns(
            [np.zeros(B, np.int64), price.copy(), vol.copy()],
            timestamps=1000 + i * 50 + np.arange(B, dtype=np.int64) % 50)

    send(0)
    rt.flush()
    t0 = time.perf_counter()
    for i in range(1, n_batches + 1):
        send(i)
    rt.flush()
    dt = time.perf_counter() - t0
    manager.shutdown()
    check()
    # pairs (2i, 2i+1) of each batch match iff the second price is greater
    _expect_rows(f"multichip block_nfa@{n}", len(rows),
                 (1 + n_batches) * int((price[1::2] > price[0::2]).sum()))
    return n_batches * B / dt, sorted(rows)


def run_multichip(quick: bool = False, out_path=None):
    """--mode multichip: scaling efficiency of the sharded serving
    runtime vs 1 device, at every shard count the devices jax has allow
    (the 8-device virtual CPU mesh under CPU_ENV, 1/2/4 on a four-chip
    host — the platform comes from the environment).  Every shape
    serves through the normal InputHandler path; outputs are asserted
    byte-identical across mesh sizes before any number is reported."""
    import jax
    shard_counts = _mesh_counts()

    if quick:
        shapes = {
            "flagship": lambda n: _mc_flagship(n, keys=512, B=256,
                                               sweeps=2),
            "windowed_join": lambda n: _mc_windowed_join(n, B=128,
                                                         n_batches=4),
            "block_nfa_sequence": lambda n: _mc_block_nfa(n, B=512,
                                                          n_batches=4),
        }
    else:
        shapes = {
            "flagship": lambda n: _mc_flagship(n, keys=1 << 13, B=1 << 11,
                                               sweeps=3),
            "windowed_join": lambda n: _mc_windowed_join(n, B=256,
                                                         n_batches=8),
            "block_nfa_sequence": lambda n: _mc_block_nfa(n, B=1 << 11,
                                                          n_batches=16),
        }
    out = {}
    for name, fn in shapes.items():
        series = {}
        base_eps = None
        base_rows = None
        for n in shard_counts:
            eps, rows = fn(n)
            if n == 1:
                base_eps, base_rows = eps, rows
            parity = rows == base_rows
            assert parity, (
                f"{name}@{n}: sharded output diverged from unsharded "
                f"({len(rows)} vs {len(base_rows)} rows)")
            series[str(n)] = {
                "events_per_sec": round(eps),
                "speedup_vs_1": round(eps / base_eps, 3),
                "efficiency": round(eps / base_eps / n, 3),
                "output_rows": len(rows),
                "parity_vs_unsharded": parity,
            }
            print(f"multichip {name}@{n}: {eps:,.0f} ev/s "
                  f"(x{eps / base_eps:.2f}, eff "
                  f"{eps / base_eps / n:.2f}, {len(rows)} rows, "
                  f"parity ok)", file=sys.stderr)
        out[name] = series
    payload = {
        "mode": "multichip",
        "device": _device(),
        "devices": [str(d) for d in jax.devices()[:shard_counts[-1]]],
        "quick": quick,
        "shard_counts": list(shard_counts),
        "shapes": out,
        "note": (
            "on a virtual CPU mesh (one physical host) efficiency "
            "measures sharded-serving OVERHEAD, not speedup — real "
            "scaling needs N physical chips (see `device`); parity "
            "asserts the sharded runtime emits byte-identical output at "
            "every mesh size. block_nfa_sequence is single-key and "
            "mesh-invariant by design (included to prove the serving "
            "path)."),
    }
    line = json.dumps(payload)
    print(line)
    if out_path:
        with open(out_path, "w") as fh:
            fh.write(json.dumps(payload, indent=2) + "\n")
    return payload


SOAK_QL = """
@app:name('{name}')
@app:statistics('BASIC')

@async(buffer.size='128', workers='1')
define stream In (k long, v float, s int);

@sink(type='chaos', id='{sink_id}', on.error='retry',
      retry.initial.ms='2', retry.max.ms='25', retry.jitter='0',
      breaker.failures='100000'{chaos_opts})
define stream Out (k long, v float);

@info(name='hot') from In[v > 2.95] select k, v insert into Out;

@info(name='agg') from In#window.lengthBatch(512)
select s, avg(v) as av, count() as c group by s insert into Agg;
"""


def _soak_app(manager, i: int, chaos: bool):
    """One tenant: @async ingest, a filter query feeding a chaos sink
    (retry policy, optional mid-run outage), and a grouped lengthBatch
    aggregation consumed by a counting batch callback."""
    name = f"soak{i}"
    # deterministic mid-run transport outage: publish attempts 40-60 fail
    # (1-based, counted across retries), the retry policy must redeliver
    # with zero loss; the window is attempt-indexed so it lands mid-run
    # at any --seconds
    chaos_opts = ", fail.publishes='40-60'" if chaos else ""
    rt = manager.create_siddhi_app_runtime(SOAK_QL.format(
        name=name, sink_id=name, chaos_opts=chaos_opts))
    agg_rows = [0]
    rt.add_batch_callback(
        "agg", lambda ts, b: agg_rows.__setitem__(
            0, agg_rows[0] + b["n_current"]))
    rt.start()
    return name, rt, agg_rows


def run_soak(seconds: int = 60, apps: int = 2, chaos: bool = False,
             out_path=None, interval_s: float = 1.0,
             p99_ms: float = 500.0, B: int = 1 << 10):
    """--mode soak: M co-resident tenant apps under sustained @async
    ingest for `seconds` wall seconds while the in-process time-series
    sampler ticks every `interval_s` and the SLO engine judges each tick
    (observability/timeseries.py, observability/slo.py).  With --chaos,
    utils/chaos.py kills each tenant's sink transport mid-run (publish
    attempts 40-60 fail) and the retry policy must redeliver with zero
    loss.  With --out, writes the long-run artifact: per-second series,
    per-tenant accounting, p99 trajectories, and a machine-checked SLO
    verdict.  Exit contract: rc 0 only when the
    final verdict is `ok` AND zero events were silently dropped."""
    import threading as _threading

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.observability.slo import SLORule, default_rules
    from siddhi_tpu.utils.chaos import ChaosSink
    manager = SiddhiManager()
    tenants = {}
    for i in range(apps):
        name, rt, agg_rows = _soak_app(manager, i, chaos)
        tenants[name] = {"rt": rt, "agg_rows": agg_rows, "sent": 0}

    rng = np.random.default_rng(7)
    # fixed full-bucket columns: constant shapes keep the steady state
    # recompile-free (adopted zero-copy by send_columns — never mutated)
    kcol = np.arange(B, dtype=np.int64)
    vcol = (rng.random(B) * 3.0).astype(np.float32)
    scol = (np.arange(B) % 8).astype(np.int32)
    sel = int((vcol > 2.95).sum())       # sink rows per send, exact

    # warm EVERY app's query signatures before the SLO clock starts: the
    # one-time XLA compiles are a deploy cost, not a soak violation
    for t in tenants.values():
        h = t["rt"].get_input_handler("In")
        for _ in range(2):
            h.send_columns([kcol, vcol, scol])
        t["rt"].flush()
        t["sent"] += 2 * B

    rules = default_rules() + [
        SLORule("max-p99", "max_p99", float(p99_ms), for_ticks=3)]
    sampler = manager.start_sampler(interval_s=interval_s, rules=rules)

    stop = _threading.Event()

    def produce(t):
        h = t["rt"].get_input_handler("In")
        while not stop.is_set():
            h.send_columns([kcol, vcol, scol])
            t["sent"] += B

    threads = [_threading.Thread(target=produce, args=(t,), daemon=True,
                                 name=f"soak-load-{name}")
               for name, t in tenants.items()]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        time.sleep(0.1)
    stop.set()
    for th in threads:
        th.join(timeout=10.0)
    for t in tenants.values():
        t["rt"].flush()
    elapsed = time.perf_counter() - t0
    sampler.tick()                      # final post-flush evaluation
    manager.stop_sampler()

    total_sent = sum(t["sent"] for t in tenants.values())
    app_reports = {}
    verdicts = []
    all_zero_drops = True
    for name, t in tenants.items():
        rt = t["rt"]
        ts = rt.timeseries()
        acct = ts.get("tenant", {})
        slo = ts.get("slo", {})
        verdicts.append(slo.get("verdict", "unknown"))
        snap = rt.stats.exposition_snapshot()
        counters = snap.get("counters", {})
        drops = sum(v for k, v in counters.items()
                    if k.endswith(".dropped"))
        sink_drops = sum(
            int(getattr(conn, "dropped_total", 0))
            for sk in rt.sinks for conn in getattr(sk, "connections", ()))
        hot_rows = counters.get("hot.emitted_rows", 0)
        delivered = len(ChaosSink.instances[name].delivered)
        expected_hot = (t["sent"] // B) * sel
        # "silent" drop = an accepted event that vanished without a
        # counter: emission drops and sink drops must be zero AND every
        # row the hot query emitted must have reached the (chaos) sink
        zero = drops == 0 and sink_drops == 0 and \
            delivered == hot_rows == expected_hot
        all_zero_drops = all_zero_drops and zero
        app_reports[name] = {
            "sent_events": t["sent"],
            "tenant": acct,
            "slo": slo,
            "series": ts.get("series", {}),
            "p99_trajectory_us": {
                k[len("query."):-len(".p99_us")]: v
                for k, v in ts.get("series", {}).items()
                if k.startswith("query.") and k.endswith(".p99_us")},
            "sink_delivered": delivered,
            "hot_rows_emitted": hot_rows,
            "hot_rows_expected": expected_hot,
            "agg_rows_delivered": t["agg_rows"][0],
            "sink_retries": acct.get("sink_retries", 0),
            "dropped": drops + sink_drops,
            "zero_silent_drops": zero,
        }
        print(f"soak[{name}]: sent={t['sent']} "
              f"hot={hot_rows}/{expected_hot} delivered={delivered} "
              f"agg_rows={t['agg_rows'][0]} "
              f"retries={acct.get('sink_retries', 0)} "
              f"verdict={slo.get('verdict')} zero_drops={zero}",
              file=sys.stderr)
    order = {"firing": 2, "pending": 1, "ok": 0}
    verdict = max(verdicts, key=lambda v: order.get(v, 3))
    payload = {
        "mode": "soak",
        "seconds": seconds, "elapsed_s": round(elapsed, 2),
        "apps": apps, "chaos": chaos,
        "interval_s": interval_s, "batch": B,
        "p99_rule_ms": p99_ms,
        "device": _device(),
        "total_events": total_sent,
        "events_per_sec": round(total_sent / elapsed),
        "sampler_ticks": sampler.ticks,
        "verdict": verdict,
        "zero_silent_drops": all_zero_drops,
        "tenants": app_reports,
        "note": ("sustained multi-tenant soak through the normal "
                 "@async InputHandler path; series are ring-buffer "
                 "samples from the in-process sampler (host counters "
                 "only, no device fetches); with chaos on, each "
                 "tenant's sink transport dies for publish attempts "
                 "40-60 and on.error='retry' must redeliver with zero "
                 "loss"),
    }
    manager.shutdown()
    line = dict(payload)
    line.pop("tenants")               # the one-line summary stays short
    print(json.dumps(line))
    if out_path:
        with open(out_path, "w") as fh:
            fh.write(json.dumps(payload, indent=2) + "\n")
        print(f"soak artifact written to {out_path}", file=sys.stderr)
    if verdict != "ok" or not all_zero_drops:
        print(f"SOAK FAILED: verdict={verdict} "
              f"zero_silent_drops={all_zero_drops}", file=sys.stderr)
        sys.exit(1)
    return payload


NOISY_QL = """
@app:name('noisy')
@app:statistics('BASIC')
@app:admission(overload='shed', max.events.per.sec='{rate}',
               burst='{burst}', max.recompiles.per.min='5',
               compile.penalty.ms='200')

@async(buffer.size='64', workers='1', queue.policy='shed')
define stream In (k long, v float, s int);

@info(name='hot') from In[v > 2.95] select k, v insert into Out;
"""

STORM_QL = """
@app:name('{name}')
@app:statistics('BASIC')
@app:admission(max.recompiles.per.min='2', compile.penalty.ms='60000',
               compile.penalty.max.ms='600000')
define stream S (k long, v float);
@info(name='sq') from S#window.length(32)
select k, avg(v) as av group by k insert into Out;
"""

OVER_CEILING_QL = """
@app:name('hog')
define stream S (sym string, price double, v long);
@info(name='hog') from S#window.length(50000000)
select sym, avg(price) as ap insert into Out;
"""


def _victim_p99_us(rt) -> float:
    q = rt.statistics().get("queries", {}).get("hot", {})
    return float(q.get("p99_us", 0.0))


def run_soak_noisy(seconds: int = 30, out_path=None,
                   interval_s: float = 1.0, B: int = 1 << 10):
    """--mode soak --noisy-tenant: the noisy-neighbor isolation proof
    (ISSUE 8 acceptance).  Phase 1 runs ONE victim tenant solo and
    records its step p99 baseline.  Phase 2 co-runs the victim with a
    deliberately abusive tenant that (a) over-offers into a shed-policy
    rate limit, (b) recompile-storms by hot deploy/undeploy churn, and
    (c) attempts an over-ceiling deploy — while the admission layer
    sheds, penalizes, and denies.  With --out, writes the artifact.

    Exit contract (rc 1 on violation):
      - victim co-run step p99 within 25% of its solo baseline
      - zero SILENT drops anywhere: the victim's sink ledger balances
        and the noisy tenant's offered == accepted + shed EXACTLY
      - the over-ceiling deploy was denied BEFORE any compile
      - the compile gate actually penalized the storming tenant"""
    import threading as _threading

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.admission import COMPILE_GATE, denied_deploys
    from siddhi_tpu.exceptions import AdmissionDeniedError
    from siddhi_tpu.observability.recompile import RECOMPILES
    from siddhi_tpu.utils.chaos import ChaosSink
    from siddhi_tpu.utils.config import InMemoryConfigManager

    rng = np.random.default_rng(7)
    kcol = np.arange(B, dtype=np.int64)
    vcol = (rng.random(B) * 3.0).astype(np.float32)
    scol = (np.arange(B) % 8).astype(np.int32)
    sel = int((vcol > 2.95).sum())

    def _warm(t):
        h = t["rt"].get_input_handler("In")
        for _ in range(2):
            h.send_columns([kcol, vcol, scol])
        t["rt"].flush()
        t["sent"] += 2 * B

    def _produce_loop(t, stop, pace_s=None):
        """Open-loop producer: with `pace_s` the offer rate is FIXED
        (one batch per period, deadline-scheduled), not closed-loop —
        a latency comparison across phases is only meaningful when the
        offered load is identical in both, and a spin-loop producer on
        a small host measures GIL starvation, not admission isolation."""
        h = t["rt"].get_input_handler("In")
        next_t = time.perf_counter()
        while not stop.is_set():
            h.send_columns([kcol, vcol, scol])
            t["sent"] += B
            if pace_s:
                next_t += pace_s
                lag = next_t - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                else:           # fell behind: reschedule, don't burst
                    next_t = time.perf_counter()

    def _produce_for(t, secs, pace_s=None):
        stop = _threading.Event()
        th = _threading.Thread(target=_produce_loop,
                               args=(t, stop, pace_s), daemon=True)
        th.start()
        time.sleep(secs)
        stop.set()
        th.join(timeout=10.0)
        t["rt"].flush()

    def _calibrate_pace(t, n=8):
        """Victim batch period for BOTH phases: ~4x the uncontended
        batch cost (≈25% duty solo), clamped to [40ms, 500ms]."""
        h = t["rt"].get_input_handler("In")
        t0 = time.perf_counter()
        for _ in range(n):
            h.send_columns([kcol, vcol, scol])
        t["rt"].flush()
        t["sent"] += n * B
        per = (time.perf_counter() - t0) / n
        return min(0.5, max(0.04, 4.0 * per))

    # ---- phase 1: victim solo baseline --------------------------------------
    # settle window first (the one-time compiles + allocator warmup are
    # a deploy cost, not a noisy-neighbor signal), then RESET the
    # histograms and measure the steady window — the co-run phase uses
    # the same settle/reset/measure shape, so the p99s compare like
    # for like
    # the settle window also absorbs the storm's within-budget compiles
    # (max.recompiles.per.min='2' grants it two free ones; the third is
    # parked at the gate for its 60s penalty quantum — decided, not
    # discovered, so it cannot land inside the measure window)
    settle_s = max(6, seconds // 3)
    measure_s = max(6, seconds // 2)
    # p99 of a single measurement window is the handful of slowest
    # batches — on a shared host, that's dominated by scheduler jitter
    # spikes, not steady-state behavior.  Each phase therefore measures
    # THREE consecutive sub-windows and compares MEDIAN p99s; the
    # victim's row/delivery ledger accumulates across the resets so the
    # zero-silent-drop reconciliation still covers every sub-window.
    def _measured_p99(t, rt, pace):
        vals, hot_rows, drops = [], 0, 0
        for _ in range(3):
            rt.stats.reset()
            _produce_for(t, measure_s / 3.0, pace)
            vals.append(_victim_p99_us(rt))
            ctr = rt.stats.exposition_snapshot().get("counters", {})
            hot_rows += ctr.get("hot.emitted_rows", 0)
            drops += sum(v for k, v in ctr.items()
                         if k.endswith(".dropped"))
        return sorted(vals)[1], vals, hot_rows, drops

    m1 = SiddhiManager()
    name, rt, agg_rows = _soak_app(m1, 0, chaos=False)
    victim = {"rt": rt, "sent": 0}
    _warm(victim)
    pace_s = _calibrate_pace(victim)
    # the solo baseline must face the SAME serving infrastructure as
    # the co-run phase (sampler ticking included) — the phases differ
    # only by the noisy tenant's presence
    m1.start_sampler(interval_s=interval_s)
    _produce_for(victim, settle_s, pace_s)
    solo_p99_us, solo_p99s, _, _ = _measured_p99(victim, rt, pace_s)
    m1.stop_sampler()
    m1.shutdown()
    print(f"noisy-soak baseline: victim solo p99 {solo_p99_us:.0f}us "
          f"(median of {['%.0f' % v for v in solo_p99s]}) at "
          f"{1.0 / pace_s:.1f} batch/s open-loop over {measure_s}s "
          "steady", file=sys.stderr)

    # ---- phase 2: victim + noisy tenant -------------------------------------
    m2 = SiddhiManager()
    m2.set_config_manager(InMemoryConfigManager(system_configs={
        # a generous box ceiling the 'hog' deploy must overshoot
        "admission.global.max.state.bytes": str(1 << 30),
    }))
    vname, vrt, vagg = _soak_app(m2, 0, chaos=False)
    victim2 = {"rt": vrt, "sent": 0}
    _warm(victim2)

    # the over-offering tenant: a paced transport offering ~250x the
    # admitted quota (1 batch/s admitted, ~250 batch/s offered) — the
    # admission bucket sheds the difference at the edge, so the noisy
    # engine only ever dispatches its small admitted slice.  The quota
    # is sized for the box: one victim batch-time per second of noisy
    # dispatch is what a single shared core can absorb without the
    # victim's tail seeing it — exactly the sizing decision the quota
    # knob exists for
    noisy_rt = m2.create_siddhi_app_runtime(NOISY_QL.format(
        rate=B, burst=B))
    noisy_rt.start()
    noisy = {"rt": noisy_rt, "sent": 0}
    _warm(noisy)

    # the over-ceiling deploy: denied BEFORE any compile (provable via
    # the recompile registry: the hog's owner label never appears)
    denied_before = denied_deploys()
    hog_denied = False
    try:
        m2.create_siddhi_app_runtime(OVER_CEILING_QL)
    except AdmissionDeniedError as exc:
        hog_denied = True
        print(f"noisy-soak: hog deploy denied: {str(exc)[:100]}",
              file=sys.stderr)
    hog_never_compiled = RECOMPILES.count("hog") == 0

    penalized_before = COMPILE_GATE.penalized_total
    storm_deploys = [0]
    stop2 = _threading.Event()

    def storm_loop():
        """Hot deploy/undeploy churn: every cycle plans fresh jitted
        steps whose first batch traces+compiles — a sustained compile
        storm attributed to (and penalized, escalatingly, on) the
        storming tenant's owner labels at the shared gate."""
        i = 0
        h_cols = [np.arange(64, dtype=np.int64),
                  np.ones(64, dtype=np.float32)]
        while not stop2.is_set():
            app_name = f"storm{i % 4}"
            i += 1
            try:
                srt = m2.create_siddhi_app_runtime(
                    STORM_QL.format(name=app_name))
                srt.start()
                srt.get_input_handler("S").send_columns(h_cols)
                srt.flush()
                storm_deploys[0] += 1
            except Exception as exc:  # noqa: BLE001 — storm must storm
                print(f"storm cycle error: {exc!r}", file=sys.stderr)
            finally:
                srt2 = m2.runtimes.pop(app_name, None)
                if srt2 is not None:
                    srt2.shutdown()

    noise_threads = [
        _threading.Thread(target=_produce_loop,
                          args=(noisy, stop2, 0.004),
                          daemon=True, name="noisy-offer-load"),
        _threading.Thread(target=storm_loop, daemon=True,
                          name="noisy-storm"),
    ]
    sampler = m2.start_sampler(interval_s=interval_s)
    t0 = time.perf_counter()
    for th in noise_threads:
        th.start()
    # settle with the noise already running, then measure the victim's
    # steady sub-windows UNDER noise — the same open-loop pace and
    # settle/measure shape as the solo baseline, so the median p99s
    # compare like for like
    _produce_for(victim2, settle_s, pace_s)
    delivered0 = len(ChaosSink.instances[vname].delivered)
    victim2["sent"] = 0
    co_p99_us, co_p99s, v_hot, v_drops = _measured_p99(
        victim2, vrt, pace_s)
    stop2.set()
    for th in noise_threads:
        # the storm thread may be parked mid-penalty at the compile
        # gate (that IS the mechanism under test) — it is a daemon;
        # don't wait out its sentence
        th.join(timeout=3.0)
    vrt.flush()
    noisy_rt.flush()
    elapsed = time.perf_counter() - t0
    sampler.tick()
    m2.stop_sampler()

    # LogHistogram p99 interpolates inside octave buckets; allow a
    # small absolute epsilon below which ratio noise is quantization
    eps_us = 200.0
    ratio = co_p99_us / solo_p99_us if solo_p99_us > 0 else float("inf")
    p99_ok = co_p99_us <= solo_p99_us * 1.25 + eps_us

    # victim silent-drop ledger over the measured sub-windows (rows and
    # drop counters accumulated across the resets by _measured_p99; the
    # sink delivery list is cumulative, so compare its delta)
    v_sink_drops = sum(
        int(getattr(conn, "dropped_total", 0))
        for sk in vrt.sinks for conn in getattr(sk, "connections", ()))
    v_delivered = len(ChaosSink.instances[vname].delivered) - delivered0
    v_expected = (victim2["sent"] // B) * sel
    victim_zero = v_drops == 0 and v_sink_drops == 0 and \
        v_delivered == v_hot == v_expected

    # noisy shed ledger: offered == dispatched + admission-shed +
    # async-shed EXACTLY — every dropped event was a counted DECISION
    # at one of the two shedding edges, nothing silent
    nadm = noisy_rt.admission
    nsnap = noisy_rt.stats.exposition_snapshot()
    n_accept = nsnap["stream_in"].get("In", 0)
    n_async_shed = nsnap["counters"].get("async.In.shed", 0)
    ledger_exact = noisy["sent"] == \
        n_accept + nadm.shed_total + n_async_shed
    penalties = COMPILE_GATE.penalized_total - penalized_before

    ok = (p99_ok and victim_zero and ledger_exact and hog_denied
          and hog_never_compiled and penalties > 0)
    payload = {
        "mode": "soak",
        "noisy_tenant": True,
        "seconds": seconds, "elapsed_s": round(elapsed, 2),
        "interval_s": interval_s, "batch": B,
        "device": _device(),
        "verdict": "ok" if ok else "violated",
        "victim": {
            "solo_p99_us": round(solo_p99_us, 1),
            "solo_p99_us_windows": [round(v, 1) for v in solo_p99s],
            "corun_p99_us": round(co_p99_us, 1),
            "corun_p99_us_windows": [round(v, 1) for v in co_p99s],
            "p99_ratio": round(ratio, 3),
            "p99_within_25pct": p99_ok,
            "sent_events": victim2["sent"],
            "sink_delivered": v_delivered,
            "hot_rows_emitted": v_hot,
            "hot_rows_expected": v_expected,
            "zero_silent_drops": victim_zero,
            "slo": vrt.timeseries().get("slo", {}),
        },
        "noisy": {
            "offered_events": noisy["sent"],
            "accepted_events": n_accept,
            "admission_shed": nadm.shed_total,
            "async_shed": n_async_shed,
            "ledger_exact": ledger_exact,
            "admission": nadm.report(),
        },
        "storm": {
            "deploy_cycles": storm_deploys[0],
            "compile_penalties": penalties,
            "denied_deploys": denied_deploys() - denied_before,
            "hog_denied_before_compile": hog_denied and
            hog_never_compiled,
        },
        "note": ("noisy-neighbor isolation artifact (ISSUE 8): one "
                 "victim tenant serves steady load while a noisy "
                 "tenant over-offers into a shed-policy rate limit, "
                 "recompile-storms via hot deploy/undeploy churn "
                 "(penalized at the shared compile-admission gate), "
                 "and attempts an over-ceiling deploy (denied by the "
                 "static-estimate memory gate before any compile).  "
                 "Every dropped event is a COUNTED admission decision: "
                 "offered == accepted + shed exactly; the victim's "
                 "sink ledger balances to the row."),
    }
    m2.shutdown()
    line = {k: v for k, v in payload.items() if k != "note"}
    print(json.dumps(line))
    if out_path:
        with open(out_path, "w") as fh:
            fh.write(json.dumps(payload, indent=2) + "\n")
        print(f"noisy-soak artifact written to {out_path}",
              file=sys.stderr)
    if not ok:
        print(f"NOISY SOAK FAILED: p99_ok={p99_ok} "
              f"victim_zero={victim_zero} ledger={ledger_exact} "
              f"hog_denied={hog_denied} penalties={penalties}",
              file=sys.stderr)
        sys.exit(1)
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="full",
                    choices=["full", "device_loop", "fuse_compare",
                             "cost_analysis", "multichip", "soak",
                             "join_compare", "mqo_compare",
                             "serve_compare", "phase_profile",
                             "state_profile"],
                    help="full: the flagship suite (default); "
                         "device_loop: device-side events/sec of the "
                         "compiled step via fused dispatch re-execution; "
                         "fuse_compare: end-to-end @fuse vs sequential; "
                         "cost_analysis: EXPLAIN flops/bytes/peak-memory "
                         "of the flagship + sequence_within steps; "
                         "multichip: sharded-serving scaling efficiency "
                         "at the shard counts the devices allow, with "
                         "parity asserts; "
                         "soak: sustained multi-tenant load with the "
                         "time-series sampler + SLO verdicts "
                         "(SOAK artifact); "
                         "join_compare: windowed_join equi-join fast "
                         "path ON vs OFF + bytes-accessed delta "
                         "(JOIN artifact); "
                         "mqo_compare: 50-query single-stream app with "
                         "the multi-query optimizer ON vs OFF — "
                         "byte-identical outputs asserted, dispatch "
                         "count + aggregate ev/s A/B (MQO artifact); "
                         "serve_compare: blocking emission fetch vs "
                         "@serve device ring + async drain, plus the "
                         "device_loop ceiling gap (SERVE artifact); "
                         "phase_profile: per-phase wall-time tables "
                         "for flagship blocking vs @serve and sharded "
                         "flagship from the always-on phase profiler "
                         "(PHASES artifact); "
                         "state_profile: flagship under Zipf vs "
                         "uniform key traces — observatory occupancy/"
                         "high-water tables and hot-set concentration "
                         "estimate vs exact (STATE artifact)")
    ap.add_argument("--k", type=int, default=16,
                    help="fused stack depth (device_loop/fuse_compare)")
    ap.add_argument("--batch", type=int, default=1 << 11,
                    help="events per micro-batch (device_loop/fuse_compare)")
    ap.add_argument("--iters", type=int, default=50,
                    help="fused dispatches to time (device_loop)")
    ap.add_argument("--quick", action="store_true",
                    help="reduced scale (CI smoke; multichip)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="also write the result JSON to PATH (default: "
                         "no file — a run never rewrites a committed "
                         "record)")
    ap.add_argument("--seconds", type=int, default=60,
                    help="soak: sustained-load duration")
    ap.add_argument("--apps", type=int, default=2,
                    help="soak: co-resident tenant apps")
    ap.add_argument("--chaos", action="store_true",
                    help="soak: kill each tenant's sink transport "
                         "mid-run (retry must redeliver, zero loss)")
    ap.add_argument("--noisy-tenant", action="store_true",
                    help="soak: noisy-neighbor isolation mode — one "
                         "tenant over-offers + recompile-storms while "
                         "admission sheds/penalizes/denies; asserts "
                         "the victim's step p99 stays within 25% of "
                         "its solo baseline")
    ap.add_argument("--interval", type=float, default=1.0,
                    help="soak: sampler tick period (seconds)")
    ap.add_argument("--p99-ms", type=float, default=500.0,
                    help="soak: max-p99 SLO rule threshold (ms)")
    args = ap.parse_args()
    if args.mode == "device_loop":
        _enable_compile_cache()
        run_device_loop(args.k, args.batch, args.iters)
    elif args.mode == "fuse_compare":
        _enable_compile_cache()
        run_fuse_compare(args.k, args.batch)
    elif args.mode == "cost_analysis":
        run_cost_analysis(B=args.batch)
    elif args.mode == "join_compare":
        _enable_compile_cache()
        run_join_compare(B=1 << 8 if args.quick else 1 << 10,
                         n_batches=2 if args.quick else 8,
                         out_path=args.out)
    elif args.mode == "mqo_compare":
        _enable_compile_cache()
        # quick mode shrinks the app below the 50-query artifact shape,
        # so the 4x/quarter-dispatch bars apply only to the full run
        run_mqo_compare(n_queries=12 if args.quick else 50,
                        B=1 << 9 if args.quick else 1 << 10,
                        n_batches=8 if args.quick else 24,
                        out_path=args.out, check_bars=not args.quick)
    elif args.mode == "serve_compare":
        _enable_compile_cache()
        run_serve_compare(k=4 if args.quick else 8,
                          B=1 << 9 if args.quick else args.batch,
                          n_batches=8 if args.quick else 64,
                          iters=5 if args.quick else 20,
                          out_path=args.out)
    elif args.mode == "phase_profile":
        _enable_compile_cache()
        run_phase_profile(quick=args.quick, out_path=args.out)
    elif args.mode == "state_profile":
        _enable_compile_cache()
        run_state_profile(quick=args.quick, out_path=args.out)
    elif args.mode == "multichip":
        _enable_compile_cache()
        run_multichip(quick=args.quick, out_path=args.out)
    elif args.mode == "soak" and args.noisy_tenant:
        # NO persistent compile cache here: the storm must genuinely
        # compile each deploy cycle, as a hot-churning tenant would
        run_soak_noisy(seconds=args.seconds, out_path=args.out,
                       interval_s=args.interval, B=args.batch)
    elif args.mode == "soak":
        _enable_compile_cache()
        run_soak(seconds=args.seconds, apps=args.apps, chaos=args.chaos,
                 out_path=args.out, interval_s=args.interval,
                 p99_ms=args.p99_ms)
    else:
        main()

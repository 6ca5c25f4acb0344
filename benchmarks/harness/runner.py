"""One run of one cell: deploy, warm, measure, drain, check.

The system under test is reached through its public surface only
(`SiddhiManager.create_siddhi_app_runtime`, `get_input_handler().send_columns`,
`add_batch_callback`, `set_exception_listener`, `state_memory`, `flush`,
`shutdown`) plus the `RECOMPILES` registry; statistics stay OFF, as
deployed.  Everything that decides a number — the clock, the stamps, the
window, the comparison — is in this directory.

What a deployment brings (`configs/<name>/`), written down once
------------------------------------------------------------------
`config.json`: `query` (the subscribed query), `columns` (the result columns
the subscriber reads), `sizes` (formatted into `app.siddhi`), `stream` (where
a send that names no stream goes) and optionally `streams` — every input
stream a send may name (default `[stream]`).  Every listed stream's handler
is taken at deploy, never inside a send.

`model.py` is plain numpy, imports nothing of the program, and gives:

- `plan(seed, traffic, sizes) -> dict`: what the generator keeps between sends;
- `make_send(rng, i, traffic, plan, clock_ms) -> send`: the i-th send of
  `traffic`, a dict with `cols` (the columns `send_columns` takes), `ts` (the
  timestamps), `events` (how many events it carries) and optionally `stream`
  (which input stream it goes to), plus whatever the model's own reference
  wants to find again;
- `expected_rows(send) -> int`, `events_per_send(traffic) -> int`,
  `clock_step_ms(traffic) -> int`;
- `Attribution(plan)` with `on_issue(sid, send)` and `attribute(rows) ->
  sids`: which send each delivered row completes;
- `reference(sends, plan) -> [rows]` (every send since the app started, in
  order), `canonical(rows)`, `compare(got, want) -> {number: value}` held to
  `LIMITS`, `control_rows(want)` (the reference at the nearest lower
  precision) and `least_bytes(traffic, sizes, config)`.  `LIMITS` names the
  numbers compared — `rows_missing`, `rows_unexpected`, `rows_differing` at
  the least, and whatever else the model counts — each with its limit; the
  result line's `compared` holds exactly these and the harness's own three
  (`stray_rows`, `listener_errors`, `sends_undelivered`).  Every number is a
  COUNT held to 0: a tolerance the configuration states is applied inside
  `compare`, which counts the rows over it (`lengthbatch_1000`'s `AP_RTOL`),
  never as a limit above 0.

Two rules hold for every model:

1. ONE CALL A SEND, TO THE STREAM THE SEND NAMES.  A model that feeds several
   streams interleaves them by the send's index; `siddhi:send`, `trace_sends`,
   every `*_per_send` reader and the latency keep their meaning.  A send that
   names a stream `config.json` does not list ends the run with an error:
   there is no stream it falls back to.
2. A SEND THAT OWES NO ROWS IS DONE WHEN ITS CALL RETURNS (`expected_rows` 0:
   the first send of an inner join, a batch no filter passes).  Its latency
   is `returned - due`.  Nothing is loosened by it: the check still compares
   its rows with the reference's (none), so a row delivered for it is
   `rows_unexpected` and the send fails.
"""
from __future__ import annotations

import contextlib
import os
import shutil
import threading
import time

import numpy as np

from .loader import ROOT, Cell

now = time.perf_counter

OUT_DIR = os.path.join(ROOT, ".bench_out")

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
}
_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


class CompileMeters:
    """jax.monitoring listeners: backend-compile seconds, programs, cache
    hits and misses since the process started."""

    def __init__(self):
        self.v = {"backend_compile_s": 0.0, "cache_read_s": 0.0,
                  "programs": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(self, event: str, secs: float, **_kw) -> None:
        key = _COMPILE_EVENTS.get(event)
        if key is not None:
            self.v[key] += secs
            if key == "backend_compile_s":
                self.v["programs"] += 1

    def on_event(self, event: str, **_kw) -> None:
        key = _COUNT_EVENTS.get(event)
        if key is not None:
            self.v[key] += 1

    def register(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)

    def snapshot(self) -> dict:
        return dict(self.v)


class Tracker:
    """The subscriber's side: every delivered batch is read in full and
    stamped, its rows mapped to the sends they complete (the configuration's
    `Attribution`), and a send is done when its last expected row is in —
    one that expects none, when its call returns."""

    def __init__(self, attribution, columns):
        self.attr = attribution
        self.columns = tuple(columns)
        self.cv = threading.Condition()
        self.expected = {}
        self.got = {}
        self.done_t = {}
        self.batches = []          # (sids, rows) in delivery order
        self.stray_rows = 0        # rows no issued send accounts for

    def issued(self, sid: int, send: dict, n_expected: int) -> None:
        with self.cv:
            self.expected[sid] = n_expected
            self.got[sid] = 0
            self.attr.on_issue(sid, send)

    def returned(self, sid: int, t: float) -> None:
        """The send's call returned at `t`: a send that owes no rows is done
        then (a row that still comes for it is the check's to find)."""
        if self.expected[sid] != 0:      # written by this thread, in `issued`
            return
        with self.cv:
            if sid not in self.done_t:
                self.done_t[sid] = t
                self.cv.notify_all()

    def on_batch(self, _ts, b) -> None:
        sel = b["valid"] & (b["kind"] == 0)
        cols = b["cols"]
        rows = {n: np.asarray(cols[n])[sel] for n in self.columns}
        t = now()
        n = rows[self.columns[0]].shape[0]
        if n == 0:
            return
        with self.cv:
            sids = self.attr.attribute(rows)
            self.batches.append((sids, rows))
            lo, hi = int(sids.min()), int(sids.max())
            if lo == hi:
                counts = {lo: n}
            else:
                u, c = np.unique(sids, return_counts=True)
                counts = dict(zip(u.tolist(), c.tolist()))
            for sid, c in counts.items():
                if sid not in self.expected:
                    self.stray_rows += c
                    continue
                self.got[sid] += c
                if self.got[sid] >= self.expected[sid] and \
                        sid not in self.done_t:
                    self.done_t[sid] = t
            self.cv.notify_all()

    def wait(self, sid: int, timeout: float):
        """Delivery time of the send's last row, or None after `timeout`."""
        with self.cv:
            self.cv.wait_for(lambda: sid in self.done_t, timeout)
            return self.done_t.get(sid)

    def rows_by_send(self, sids_wanted) -> dict:
        """{sid: rows} for the wanted sends, concatenated in delivery
        order."""
        wanted = set(sids_wanted)
        parts = {sid: [] for sid in wanted}
        for sids, rows in self.batches:
            lo, hi = int(sids.min()), int(sids.max())
            if lo == hi:
                if lo in wanted:
                    parts[lo].append(rows)
                continue
            for sid in np.unique(sids).tolist():
                if sid in wanted:
                    m = sids == sid
                    parts[sid].append({n: a[m] for n, a in rows.items()})
        out = {}
        for sid, ps in parts.items():
            if ps:
                out[sid] = {n: np.concatenate([p[n] for p in ps])
                            for n in self.columns}
            else:
                out[sid] = None
        return out


class Deployment:
    """The app deployed and subscribed, with the generator's bookkeeping:
    every send since the app started (the reference needs them in order),
    its stamps, and the model's per-run plan."""

    def __init__(self, cell: Cell, seed: int, annotate: bool):
        from siddhi_tpu import SiddhiManager
        self.cell = cell
        self.seed = seed & (2 ** 64 - 1)
        self.model = cell.model
        self.plan = self.model.plan(self.seed, cell.traffic, cell.sizes)
        self.tracker = Tracker(self.model.Attribution(self.plan),
                               cell.config["columns"])
        self.errors = []
        self.sends = []            # every send, by sid
        self.stamps = {}           # sid -> {"due", "issued"}
        self.clock_ms = 1000
        self.position = {}         # id(traffic) -> next index in its sequence
        self.annotate = annotate
        self._in_call = None       # (sender thread, sid) inside send_columns
        self.manager = SiddhiManager()
        self.rt = self.manager.create_siddhi_app_runtime(cell.app_text)
        self.rt.add_batch_callback(cell.config["query"], self._subscriber)
        # a step the device refuses at run time is caught in the junction,
        # logged, and its batch DROPPED while the send returns normally:
        # without a listener a dropped batch reads as a faster run
        self.rt.set_exception_listener(self.errors.append)
        self.rt.start()
        self.default_stream = cell.config.get("stream")
        self.handlers = {
            name: self.rt.get_input_handler(name) for name in
            cell.config.get("streams") or [cell.config["stream"]]}

    def span(self, name: str, **kw):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench:" + name, **kw)

    def _subscriber(self, ts, b) -> None:
        t_in = now()
        with self.span("subscriber"):
            self.tracker.on_batch(ts, b)
        t_out = now()
        call = self._in_call
        if call is not None and call[0] == threading.get_ident():
            st = self.stamps[call[1]]
            st["subscriber_s"] += t_out - t_in
            st["subscriber_end"] = t_out

    def make(self, traffic: dict) -> int:
        """Generate the next send of `traffic` from the seed; returns its
        sid.  Nothing is sent yet."""
        i = self.position.get(id(traffic), 0)
        self.position[id(traffic)] = i + 1
        sid = len(self.sends)
        self.clock_ms += self.model.clock_step_ms(traffic)
        rng = np.random.default_rng([self.seed, sid])
        self.sends.append(self.model.make_send(
            rng, i, traffic, self.plan, self.clock_ms))
        return sid

    def issue(self, sid: int, due: float) -> None:
        send = self.sends[sid]
        stream = send.get("stream", self.default_stream)
        handler = self.handlers.get(stream)
        if handler is None:
            raise RuntimeError(
                f"send {sid} names stream {stream!r}; config.json lists "
                f"{sorted(self.handlers)} and a send goes nowhere else")
        owed = self.model.expected_rows(send)
        self.tracker.issued(sid, send, owed)
        st = self.stamps[sid] = {
            "due": due, "owed": owed, "issued": now(),
            "subscriber_s": 0.0, "subscriber_end": None}
        self._in_call = (threading.get_ident(), sid)
        try:
            with self.span("send_columns", sid=sid, stream=stream):
                handler.send_columns(send["cols"], timestamps=send["ts"])
        finally:
            self._in_call = None
        st["returned"] = now()
        self.tracker.returned(sid, st["returned"])

    def flush(self) -> None:
        with self.span("flush"):
            self.rt.flush()

    def run_untimed(self, traffic: dict, n: int, what: str) -> None:
        """Prefill or warm-up: n sends through the window's own call and
        subscriber, each waited for; all must deliver and none may err."""
        limit = float(self.cell.traffic["drain_limit_s"])
        for _ in range(n):
            sid = self.make(traffic)
            self.issue(sid, now())
            if self.tracker.wait(sid, 0.0) is None:
                self.flush()
            if self.tracker.wait(sid, limit) is None:
                raise RuntimeError(
                    f"{what}: send {sid} delivered "
                    f"{self.tracker.got[sid]} of "
                    f"{self.tracker.expected[sid]} rows within {limit} s"
                    + self._first_error())
        if self.errors:
            raise RuntimeError(f"{what}: the runtime reported "
                               f"{len(self.errors)} error(s)"
                               + self._first_error())

    def _first_error(self) -> str:
        if not self.errors:
            return ""
        e = self.errors[0]
        return f"; first error: {type(e).__name__}: {e}"

    def close(self) -> None:
        self.manager.shutdown()


class TracedRun:
    """What `--trace 1` adds to a window: its first `trace_sends` sends run
    inside a jax.profiler trace, from which the device's busy and idle time
    are read.  The slice ends at its last send's DELIVERY: where the call
    returns before its results (`@serve`), the profiler stays on until the
    tracker has them.  Then it stops and the window goes on as an untraced
    run's does, with the harness's spans still on (they cost nothing
    without a profiler)."""

    def __init__(self, dep: "Deployment", n_sends: int):
        self.dir = os.path.join(OUT_DIR, "trace", dep.cell.name)
        self.n_sends = n_sends
        self.tracker = dep.tracker
        self.limit = float(dep.cell.traffic["drain_limit_s"])
        self.tracing = False

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the harness's spans, not every
        opts.host_tracer_level = 2       # Python call
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.tracing = True

    def send_issued(self, j: int, sid: int) -> None:
        """After the j-th send of the window, `sid`, returned.  In an open
        loop the wait for the slice's last delivery falls in the schedule's
        planned hole, so it holds no timed send."""
        if j + 1 >= self.n_sends and self.tracing:
            self.tracker.wait(sid, self.limit)
            self.stop()

    def stop(self) -> None:
        if self.tracing:
            import jax
            jax.profiler.stop_trace()
            self.tracing = False


def closed_loop(dep: Deployment, sids: list, seconds: float,
                tracer: TracedRun | None) -> dict:
    """One send outstanding.  A send is due the moment the previous send's
    last result was delivered; the window runs from the first issue to the
    delivery of the last result of the first send that completes after
    `seconds` — whole sends only."""
    traffic = dep.cell.traffic
    limit = float(traffic["drain_limit_s"])
    if tracer is not None:
        tracer.start()
    t0 = due = now()
    issued = []
    j = 0
    while True:
        if j == len(sids):
            sids.append(dep.make(traffic))      # past what was prepared:
        sid = sids[j]                           # made now, and it shows as
        j += 1                                  # generator lateness
        dep.issue(sid, due)
        issued.append(sid)
        done = dep.tracker.wait(sid, limit)
        if tracer is not None:
            tracer.send_issued(j - 1, sid)
        if done is None or done - t0 >= seconds:
            break
        due = done
    return {"t0": t0, "issued": issued}


def wait_until(t: float) -> None:
    """Sleep to within half a millisecond of `t`, then spin."""
    while True:
        left = t - now()
        if left <= 0:
            return
        if left > 0.0006:
            time.sleep(left - 0.0005)


def open_loop(dep: Deployment, sids: list, seconds: float,
              tracer: TracedRun | None) -> dict:
    """Sends on a schedule fixed before the window and never stretched: a
    send that overruns makes the next one late, and the late one's latency
    still counts from its due time.  In a traced run the schedule has one
    planned hole of `trace_gap_s`, after its first `trace_sends` sends, in
    which the profiler stops and writes its trace."""
    traffic = dep.cell.traffic
    interval = dep.model.events_per_send(traffic) / \
        float(traffic["rate_events_per_s"])
    n = len(sids)
    offsets = np.arange(n) * interval
    if tracer is not None:
        offsets[tracer.n_sends:] += float(traffic["trace_gap_s"])
        tracer.start()
    t0 = now()
    for j, sid in enumerate(sids):
        with dep.span("wait_due"):
            wait_until(t0 + offsets[j])
        dep.issue(sid, t0 + offsets[j])
        if tracer is not None:
            tracer.send_issued(j, sid)
    return {"t0": t0, "issued": list(sids)}


def planned_sends(cell: Cell, seconds: float) -> int:
    """How many sends are generated before the window: the whole schedule
    of an open loop; for a closed loop what `prepare_sends_per_s` allows."""
    t = cell.traffic
    if t["loop"] == "open":
        return max(1, int(float(t["rate_events_per_s"]) * seconds /
                          cell.model.events_per_send(t)))
    return max(1, int(np.ceil(float(t["prepare_sends_per_s"]) * seconds)))


def check(dep: Deployment, timed: list, control: bool, say) -> dict:
    """By-value comparison of every timed send's delivered rows with the
    plain reference, after the window.  Returns {"failed_sends", "numbers":
    {name: (value, limit)}, "control": {...}}."""
    model = dep.model
    refs = model.reference(dep.sends, dep.plan)
    got = dep.tracker.rows_by_send(timed)
    limits = model.LIMITS
    worst = {n: 0 for n in limits}
    worst_ctl = {n: 0 for n in limits}
    failed = []
    for sid in timed:
        want = model.canonical(refs[sid])
        rows = got[sid]
        if rows is None:
            rows = {n: a[:0] for n, a in want.items()}
        nums = model.compare(model.canonical(rows), want)
        if sid not in dep.tracker.done_t or \
                any(nums[n] > limits[n] for n in limits):
            failed.append(sid)
        for n in limits:
            worst[n] = max(worst[n], nums[n])
        if control:
            ctl = model.compare(
                model.canonical(model.control_rows(want)), want)
            for n in limits:
                worst_ctl[n] = max(worst_ctl[n], ctl[n])
    say("check: each number compared (worst over the timed sends) beside "
        "its limit")
    for n in limits:
        verdict = "ok" if worst[n] <= limits[n] else "OVER"
        say(f"  {n} = {worst[n]!r}  limit {limits[n]!r}  {verdict}")
    stray = dep.tracker.stray_rows
    say(f"  stray_rows = {stray}  limit 0  {'ok' if stray == 0 else 'OVER'}"
        f"  (rows no issued send accounts for)")
    say(f"  listener_errors = {len(dep.errors)}  limit 0  "
        f"{'ok' if not dep.errors else 'OVER'}")
    if control:
        say("control (the reference's rows at the nearest lower precision, "
            "in the program's place): it has to fail one number")
        for n in limits:
            verdict = "passes" if worst_ctl[n] <= limits[n] else "FAILS"
            say(f"  control {n} = {worst_ctl[n]!r}  limit {limits[n]!r}  "
                f"{verdict}")
    return {"failed_sends": failed, "numbers": worst, "control": worst_ctl}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             control: bool, t_start: float, meters: CompileMeters,
             devices: list, say=print) -> dict:
    """Deploy, warm, measure, drain, check.  Returns everything the result
    line and the per-layer readers need."""
    from siddhi_tpu.observability.recompile import RECOMPILES
    traffic = cell.traffic

    def traces() -> int:
        return sum(o["count"] for o in RECOMPILES.snapshot().values())

    marks = [("imports", now())]
    dep = Deployment(cell, seed, annotate=trace)
    marks.append(("deploy", now()))
    try:
        pre = traffic.get("prefill")
        if pre:
            dep.run_untimed(pre, int(pre["sends"]), "prefill")
            marks.append(("prefill", now()))
        dep.run_untimed(traffic, int(traffic["warmup_sends"]), "warm-up")
        dep.flush()
        marks.append(("warm-up", now()))
        sids = [dep.make(traffic)
                for _ in range(planned_sends(cell, seconds))]
        marks.append(("prepare", now()))
        compile_setup = meters.snapshot()
        traces0 = traces()
        tracer = TracedRun(dep, int(traffic["trace_sends"])) \
            if trace else None
        setup_s = now() - t_start
        say("set-up by part (s): " + ", ".join(
            f"{name} {t - prev:.2f}" for (name, t), prev in
            zip(marks, [t_start] + [t for _, t in marks])))
        say(f"set-up {setup_s:.3f} s: {len(dep.sends) - len(sids)} untimed "
            f"sends, {len(sids)} sends prepared; compile "
            f"{compile_setup['backend_compile_s']:.2f} s in "
            f"{compile_setup['programs']} programs, cache hits "
            f"{compile_setup['cache_hits']} misses "
            f"{compile_setup['cache_misses']}")
        loop = closed_loop if traffic["loop"] == "closed" else open_loop
        win = loop(dep, sids, seconds, tracer)
        dep.flush()
        limit = float(traffic["drain_limit_s"])
        t_limit = now() + limit
        for sid in win["issued"]:
            dep.tracker.wait(sid, max(0.0, t_limit - now()))
        if tracer is not None:
            tracer.stop()
        compile_end = meters.snapshot()
        traces1 = traces()
        state_memory = dep.rt.state_memory()
        peak = 0
        for d in devices[:cell.chips]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    finally:
        dep.close()

    timed = win["issued"]
    t0 = win["t0"]
    done = [dep.tracker.done_t[s] for s in timed if s in dep.tracker.done_t]
    checked = check(dep, timed, control, say)
    n_errors = len(dep.errors)
    for e in dep.errors[:3]:
        say(f"runtime error heard by the listener: {type(e).__name__}: {e}")
    failed = len(checked["failed_sends"]) + n_errors + \
        (1 if dep.tracker.stray_rows else 0)
    lat_ms = [(dep.tracker.done_t[s] - dep.stamps[s]["due"]) * 1e3
              for s in timed if s in dep.tracker.done_t]
    late_ms = [(dep.stamps[s]["issued"] - dep.stamps[s]["due"]) * 1e3
               for s in timed]
    window_s = (max(done) - t0) if done else 0.0
    events = sum(dep.sends[s]["events"] for s in timed
                 if s in dep.tracker.done_t)
    return {
        "cell": cell, "setup_s": setup_s, "window_s": window_s, "events": events,
        "attempted": len(timed), "failed": failed,
        "completed": len(done), "n_errors": n_errors,
        "stray_rows": dep.tracker.stray_rows,
        "latency_ms": lat_ms, "gen_late_ms": late_ms,
        # a compile is one step trace AND one backend compile (or cache
        # read): the larger count, not their sum
        "compiles_in_window": max(
            traces1 - traces0,
            compile_end["programs"] - compile_setup["programs"]),
        "compile_setup": compile_setup,
        "stamps": [dep.stamps[s] for s in timed],
        "state_memory": state_memory, "peak_hbm_bytes": peak,
        "trace_dir": tracer.dir if tracer is not None else None,
        "least_bytes": cell.model.least_bytes(traffic, cell.sizes,
                                              cell.config),
        "check": checked,
    }

"""REST deployment service.

Reference (what): modules/siddhi-service —
SiddhiApiServiceImpl.java:42 (POST deploy :51, GET undeploy :100) plus an
on-demand query endpoint; an MSF4J microservice wrapping SiddhiManager.
TPU design (how): a stdlib ThreadingHTTPServer wrapping one SiddhiManager —
no framework dependency (nothing outside the baked-in stack).

Endpoints (JSON in/out):
  GET    /siddhi-apps                       -> {"apps": [names]}
  POST   /siddhi-apps        body=SiddhiQL  -> deploy + start
  DELETE /siddhi-apps/<name>                -> undeploy (shutdown)
  POST   /siddhi-apps/<name>/streams/<sid>  body={"events":[[...],...],
                                                  "timestamp": opt}
  POST   /query              body={"app": name, "query": on-demand QL}
  GET    /siddhi-apps/<name>/statistics     -> metrics report
  GET    /metrics                           -> Prometheus text exposition
                                               (all apps; latency histogram
                                               buckets, throughput counters,
                                               recompile counts)
  GET    /trace/<query>                     -> recent DETAIL-level pipeline
                                               traces touching <query>
                                               (searched across apps)
  GET    /siddhi-apps/<name>/trace/<query>  -> same, one app
  GET    /siddhi-apps/<name>/explain/<query> -> EXPLAIN: operator tree +
                                               per-step XLA cost analysis,
                                               state bytes, fusion
                                               eligibility (?deep=0 skips
                                               the compile for memory
                                               analysis)
  GET    /siddhi-apps/<name>/lint           -> static analyzer findings
                                               for the deployed app, from
                                               its actual compiled plans
                                               (siddhi_tpu/analysis; never
                                               traces or fetches)
  GET    /healthz                           -> liveness+readiness verdicts
                                               (200 live / 503 not); also
                                               /healthz/live, /healthz/ready;
                                               per-app `slo` section when the
                                               time-series sampler runs (a
                                               FIRING rule flips `degraded`)
  GET    /siddhi-apps/<name>/phases         -> phase-level latency report:
                                               per-query wall seconds for
                                               stage_host/h2d/dispatch_
                                               submit/device_compute/ring_
                                               wait/d2h_drain/demux/sink,
                                               share of e2e accounted, and
                                               sampled-dispatch counts
                                               (observability/phases.py;
                                               host clocks only — never
                                               fetches or blocks)
  GET    /siddhi-apps/<name>/state          -> state observatory report:
                                               per-structure occupancy /
                                               capacity / high-water, key
                                               hotness (top-K + hot-set
                                               share), near-capacity
                                               verdicts, and the sizing-
                                               hints ledger persisted in
                                               snapshots (observability/
                                               stateobs.py; host counters
                                               only — never fetches)
  GET    /siddhi-apps/<name>/timeseries     -> windowed ring-buffer series
                                               (events/s, drops, p99
                                               trajectories, queue depths),
                                               per-tenant accounting, and
                                               SLO rule states from the
                                               in-process sampler
                                               (observability/timeseries.py;
                                               auto-started with the service
                                               unless config property
                                               metrics.sampler.enabled=false)
  POST   /profiler/start  body={"log_dir"?} -> start a guarded jax.profiler
                                               session (409 if running): the
                                               capture holds the runtime's
                                               `siddhi:*` spans and the
                                               device's ops on one clock
  POST   /profiler/stop                     -> stop it (409 if not running)
  GET    /siddhi-apps/<name>/admission      -> admission-control report:
                                               overload policy, quota
                                               state, effective rate,
                                               shed/blocked/denied
                                               counters (core/admission)
  PUT    /siddhi-apps/<name>/admission body={"overload"?, "max.events.
                                               per.sec"?, "max.state.
                                               bytes"?, ...} -> update
                                               the app's quotas live;
                                               returns the new report
  GET    /siddhi-apps/<name>/error-store    -> error-store stats + captured
                                               entries (?stream=S filters;
                                               ?limit=N caps entries)
  POST   /siddhi-apps/<name>/error-store/replay
                       body={"ids"?, "stream"?} -> re-inject captured
                                               events through the normal
                                               InputHandler path
  GET    /health                            -> {"status": "ok"}
"""
from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

from .core.runtime import SiddhiManager
from .exceptions import SiddhiError


# ---------------------------------------------------------------------------
# jax.profiler guard: explicit start/stop, one session at a time.  A capture
# holds the runtime's own `siddhi:*` spans (observability/phases.py) beside
# the device's XLA ops, on one clock.
# ---------------------------------------------------------------------------

_prof_lock = threading.Lock()
_prof_dir: Optional[str] = None


def start_profiler(log_dir: str = "/tmp/siddhi_tpu_profile") -> dict:
    """Start a jax.profiler trace session.  Returns {started, log_dir} or
    raises RuntimeError when a session is already active (the profiler is
    process-global — two sessions would corrupt each other's capture)."""
    global _prof_dir
    with _prof_lock:
        if _prof_dir is not None:
            raise RuntimeError(
                f"profiler already running (log_dir={_prof_dir!r}); "
                f"POST /profiler/stop first")
        import jax
        jax.profiler.start_trace(log_dir)
        _prof_dir = log_dir
    return {"started": True, "log_dir": log_dir}


def stop_profiler() -> dict:
    """Stop the active jax.profiler session; raises RuntimeError when
    none is running."""
    global _prof_dir
    with _prof_lock:
        if _prof_dir is None:
            raise RuntimeError("no profiler session running")
        import jax
        d, _prof_dir = _prof_dir, None
        jax.profiler.stop_trace()
    return {"stopped": True, "log_dir": d}


def _qparam(query_str: str, name: str) -> Optional[str]:
    """First value of a URL query parameter, or None."""
    from urllib.parse import parse_qs
    vals = parse_qs(query_str).get(name)
    return vals[0] if vals else None


class SiddhiRestService:
    """Deploy/undeploy/ingest/query over HTTP (reference:
    SiddhiApiServiceImpl.java:42)."""

    def __init__(self, manager: Optional[SiddhiManager] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.manager = manager or SiddhiManager()
        svc = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):   # quiet
                pass

            def _json(self, code: int, payload) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _body(self) -> bytes:
                n = int(self.headers.get("Content-Length", 0))
                return self.rfile.read(n)

            def _text(self, code: int, body: str, ctype: str) -> None:
                raw = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(raw)))
                self.end_headers()
                self.wfile.write(raw)

            def do_GET(self):
                try:
                    path, _, query_str = self.path.partition("?")
                    parts = [p for p in path.split("/") if p]
                    if parts == ["health"]:
                        self._json(200, {"status": "ok"})
                    elif parts and parts[0] == "healthz":
                        # readiness vs. liveness are distinct verdicts:
                        # /healthz/live restarts pods, /healthz/ready
                        # gates traffic (observability/health.py)
                        from .observability import health as _health
                        if parts == ["healthz", "live"]:
                            code, payload = _health.liveness(svc.manager)
                        elif parts == ["healthz", "ready"]:
                            code, payload = _health.readiness(svc.manager)
                        else:
                            code, payload = _health.healthz(svc.manager)
                        self._json(code, payload)
                    elif len(parts) == 4 and parts[0] == "siddhi-apps" \
                            and parts[2] == "explain":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        elif parts[3] not in rt.query_runtimes:
                            self._json(404, {"error": "no such query"})
                        else:
                            deep = _qparam(query_str, "deep") != "0"
                            self._json(200, rt.explain(parts[3],
                                                       deep=deep))
                    elif len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "lint":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            self._json(200, rt.analyze())
                    elif len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "admission":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            self._json(200, {
                                "app": parts[1],
                                **rt.admission.report()})
                    elif len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "phases":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            # host-clock phase attribution only — this
                            # endpoint never fetches or blocks on the
                            # device (observability/phases.py)
                            self._json(200, rt.phase_report())
                    elif len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "state":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            # occupancy/high-water/hotness from host
                            # counters only (observability/stateobs.py)
                            self._json(200, rt.state_report())
                    elif len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "timeseries":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            self._json(200, rt.timeseries())
                    elif len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "error-store":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            stream = _qparam(query_str, "stream")
                            limit = _qparam(query_str, "limit")
                            entries = rt.error_store.entries(stream)
                            if limit is not None:
                                entries = entries[-int(limit):]
                            self._json(200, {
                                "app": parts[1],
                                "stats": rt.error_store.stats(),
                                "entries": [e.to_dict()
                                            for e in entries]})
                    elif parts == ["metrics"]:
                        # Prometheus scrape endpoint (text format 0.0.4);
                        # never touches the device — see observability/
                        # exposition.py
                        from .observability import render_prometheus
                        self._text(
                            200, render_prometheus(svc.manager.runtimes),
                            "text/plain; version=0.0.4; charset=utf-8")
                    elif len(parts) == 2 and parts[0] == "trace":
                        traces = []
                        for rt in svc.manager.runtimes.values():
                            traces.extend(rt.trace_dump(parts[1]))
                        self._json(200, {"query": parts[1],
                                         "traces": traces})
                    elif parts == ["siddhi-apps"]:
                        self._json(200, {
                            "apps": sorted(svc.manager.runtimes)})
                    elif len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "statistics":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            self._json(200, rt.statistics())
                    elif len(parts) == 4 and parts[0] == "siddhi-apps" \
                            and parts[2] == "trace":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                        else:
                            self._json(200, {
                                "query": parts[3],
                                "traces": rt.trace_dump(parts[3])})
                    else:
                        self._json(404, {"error": "unknown path"})
                except Exception as exc:  # noqa: BLE001 — HTTP boundary
                    self._json(500, {"error": repr(exc)})

            def do_POST(self):
                try:
                    parts = [p for p in self.path.split("/") if p]
                    if len(parts) == 2 and parts[0] == "profiler":
                        # guarded jax.profiler session; one at a time,
                        # never implicit
                        try:
                            if parts[1] == "start":
                                req = json.loads(self._body() or b"{}")
                                self._json(200, start_profiler(
                                    req.get("log_dir",
                                            "/tmp/siddhi_tpu_profile")))
                            elif parts[1] == "stop":
                                self._json(200, stop_profiler())
                            else:
                                self._json(404, {"error": "unknown path"})
                        except RuntimeError as exc:
                            self._json(409, {"error": str(exc)})
                        return
                    if len(parts) == 4 and parts[0] == "siddhi-apps" \
                            and parts[2] == "error-store" \
                            and parts[3] == "replay":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                            return
                        req = json.loads(self._body() or b"{}")
                        result = rt.replay_errors(
                            ids=req.get("ids"),
                            stream_id=req.get("stream"))
                        self._json(200, result)
                        return
                    if parts == ["siddhi-apps"]:
                        ql = self._body().decode()
                        from .compiler import SiddhiCompiler
                        app = SiddhiCompiler.parse(ql)
                        name = app.name or "SiddhiApp"
                        if name in svc.manager.runtimes:
                            # reference: duplicate deployment is rejected,
                            # never silently replaced (the old runtime's
                            # threads would leak unreachable)
                            self._json(409, {
                                "error": f"app {name!r} already deployed"})
                            return
                        rt = svc.manager.create_siddhi_app_runtime(app)
                        rt.start()
                        self._json(201, {"app": rt.name})
                    elif len(parts) == 4 and parts[0] == "siddhi-apps" \
                            and parts[2] == "streams":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                            return
                        req = json.loads(self._body() or b"{}")
                        h = rt.get_input_handler(parts[3])
                        ts = req.get("timestamp")
                        for e in req.get("events", []):
                            h.send(list(e), timestamp=ts)
                        self._json(200, {"accepted":
                                         len(req.get("events", []))})
                    elif parts == ["query"]:
                        req = json.loads(self._body() or b"{}")
                        rt = svc.manager.runtimes.get(req.get("app", ""))
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                            return
                        rows = rt.query(req["query"])
                        self._json(200, {
                            "records": [list(e.data) for e in rows]})
                    else:
                        self._json(404, {"error": "unknown path"})
                except SiddhiError as exc:
                    self._json(400, {"error": str(exc)})
                except Exception as exc:  # noqa: BLE001 — HTTP boundary
                    self._json(500, {"error": repr(exc)})

            def do_PUT(self):
                try:
                    parts = [p for p in self.path.split("/") if p]
                    if len(parts) == 3 and parts[0] == "siddhi-apps" \
                            and parts[2] == "admission":
                        rt = svc.manager.runtimes.get(parts[1])
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                            return
                        req = json.loads(self._body() or b"{}")
                        self._json(200, {
                            "app": parts[1],
                            **rt.admission.configure(req)})
                    else:
                        self._json(404, {"error": "unknown path"})
                except SiddhiError as exc:
                    self._json(400, {"error": str(exc)})
                except Exception as exc:  # noqa: BLE001 — HTTP boundary
                    self._json(500, {"error": repr(exc)})

            def do_DELETE(self):
                try:
                    parts = [p for p in self.path.split("/") if p]
                    if len(parts) == 2 and parts[0] == "siddhi-apps":
                        rt = svc.manager.runtimes.pop(parts[1], None)
                        if rt is None:
                            self._json(404, {"error": "no such app"})
                            return
                        rt.shutdown()
                        self._json(200, {"undeployed": parts[1]})
                    else:
                        self._json(404, {"error": "unknown path"})
                except Exception as exc:  # noqa: BLE001 — HTTP boundary
                    self._json(500, {"error": repr(exc)})

        self._server = ThreadingHTTPServer((host, port), _Handler)
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None
        # a served manager gets the time-series sampler by default: the
        # /timeseries, /healthz slo, and siddhi_slo_state surfaces are
        # empty without its tick (opt out: metrics.sampler.enabled=false)
        try:
            enabled = str(self.manager.config_manager.extract_property(
                "metrics.sampler.enabled") or "true").lower() != "false"
        except Exception:  # noqa: BLE001 — config must not break boot
            enabled = True
        if enabled:
            self.manager.start_sampler()

    def start(self) -> "SiddhiRestService":
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True,
            name="siddhi-rest")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread:
            self._thread.join(timeout=2.0)
        self.manager.shutdown()

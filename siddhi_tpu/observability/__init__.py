"""Observability layer: latency histograms, pipeline tracing, JIT/recompile
accounting, and Prometheus text exposition.

Reference (what): the reference engine ships a Dropwizard-metrics statistics
subsystem (throughput/latency/memory/buffered-event gauges, runtime-
switchable OFF/BASIC/DETAIL — SiddhiAppRuntimeImpl.setStatisticsLevel
:859-895) plus log4j TRACE-level event tracing.

TPU design (how): a JAX/XLA deployment has two failure modes the reference
never had — *tail latency* dominated by device dispatch + blocking fetches,
and *silent XLA recompilation* (a re-trace stalls a query for seconds, tens
of seconds for a large step on the TPU).  This package therefore records

- fixed-bucket log2 latency **histograms** (p50/p95/p99/max) instead of
  avg/max scalars (`histogram.py`),
- per-batch **pipeline traces** with per-stage spans in a ring buffer
  (`tracing.py`),
- per-query **recompile counters** with the triggering abstract shapes,
  hooked into `steputil.jit_step` (`recompile.py`),
- **Prometheus text exposition** of all of the above (`exposition.py`),

and the v2 introspection layer (where the time and memory actually go):

- query **EXPLAIN**: planned operator tree annotated with XLA
  `cost_analysis()` per jitted step — flops, bytes accessed, estimated
  peak memory — plus state shapes, emission caps, and fusion
  eligibility (`explain.py`),
- **state-memory accounting**: nbytes per device-state component from
  shape/dtype metadata only, exported as `siddhi_state_bytes`
  (`memory.py`),
- **one span primitive** at every hot-path boundary (`phases.phase`):
  a `jax.profiler.TraceAnnotation` on the profiler's clock — a capture
  (`POST /profiler/start|stop`) holds the runtime's `siddhi:*` spans
  beside the device's ops — that also feeds the phase profiler and the
  DETAIL trace ring when statistics are on,
- **health probes**: readiness vs. liveness, per-stream last-event age
  and backlog, sliding-window drop/recompile rates (`health.py`),

and the soak-telemetry layer (metrics over TIME, not just at scrape):

- **time-series sampler**: a daemon tick snapshots every host-side
  counter/gauge/histogram-quantile into per-app ring-buffer series with
  derived windowed rates, plus per-tenant accounting (events in/out,
  emitted bytes, dispatch wall-time, recompile blame, state bytes) —
  `timeseries.py`,
- **SLO engine**: declarative rules (zero-drop, max-p99, breaker,
  shard-imbalance, recompile-rate) evaluated over those series each
  tick with ok/pending/firing hysteresis, surfaced as
  `siddhi_slo_state` in `/metrics` and an `slo` section in `/healthz`
  (`slo.py`),
- **state observatory**: always-on per-(app, query, structure)
  occupancy/capacity/high-water tracking for every sized device
  structure (keyed slabs, group slots, join lanes, window fill,
  emission caps, serve rings) plus key hotness from a host-side
  count-min sketch + space-saving top-K; high-water marks persist
  across restarts as a sizing-hints ledger carried in snapshots
  (`stateobs.py`; surfaced as `siddhi_state_occupancy` /
  `siddhi_state_high_water` / `siddhi_key_hotset_share`,
  `GET /siddhi-apps/<app>/state`, EXPLAIN `utilization`, and a
  `state` section in `/healthz`),
- **phase profiler**: always-on per-(app, query, phase) wall-time
  counters over the canonical hot-path taxonomy (stage_host, h2d,
  dispatch_submit, device_compute, ring_wait, d2h_drain, demux, sink)
  from host clocks only, a sampled deep mode
  (`profile.sample.every=N`) that fences every Nth dispatch to split
  submit from device compute, and cross-thread trace handoff/adoption
  so one pipeline trace spans ingest -> dispatch -> drain -> sink
  (`phases.py`; surfaced as `siddhi_phase_seconds_total`,
  `GET /siddhi-apps/<app>/phases` and EXPLAIN).

Everything is allocation-free on the hot path when statistics are OFF: each
hook sits behind a single `enabled`/`active()` check, and every scrape/
probe path (`/metrics`, `/healthz`) reads host-side metadata only — no
`device_get`, ever.
"""
from .histogram import LogHistogram                       # noqa: F401
from .recompile import RECOMPILES, RecompileRegistry      # noqa: F401
from .tracing import (PipelineTracer, active, adopt,      # noqa: F401
                      handoff, span)
from .phases import PHASES, PhaseProfiler, phase_report   # noqa: F401
from .stateobs import (STRUCTURES, KeyHotness,            # noqa: F401
                       StateObservatory, state_report)
from .exposition import render_prometheus                 # noqa: F401
from .explain import explain_app, explain_query           # noqa: F401
from .memory import component_bytes, total_bytes          # noqa: F401
from .health import app_health, healthz, liveness, readiness  # noqa: F401
from .timeseries import (Series, SeriesStore,                 # noqa: F401
                         TimeSeriesSampler, tenant_account)
from .slo import SLOEngine, SLORule, default_rules            # noqa: F401

__all__ = [
    "LogHistogram", "PipelineTracer", "RECOMPILES", "RecompileRegistry",
    "active", "adopt", "handoff", "span", "render_prometheus",
    "PHASES", "PhaseProfiler", "phase_report",
    "STRUCTURES", "KeyHotness", "StateObservatory", "state_report",
    "explain_app", "explain_query", "component_bytes", "total_bytes",
    "app_health", "healthz", "liveness", "readiness",
    "Series", "SeriesStore", "TimeSeriesSampler", "tenant_account",
    "SLOEngine", "SLORule", "default_rules",
]

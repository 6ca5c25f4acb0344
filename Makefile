# Dev entry points (reference role: the Maven build's verify/test lifecycle).
PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: test lint lint-apps lint-smoke dryrun bench metrics-smoke \
	fuse-smoke explain-smoke chaos-smoke multichip-smoke soak-smoke \
	admission-smoke audit audit-update audit-smoke docgen-check \
	join-smoke mqo-smoke serve-smoke phase-smoke state-smoke all

all: lint lint-apps docgen-check audit test dryrun metrics-smoke \
	fuse-smoke explain-smoke lint-smoke chaos-smoke multichip-smoke \
	soak-smoke admission-smoke audit-smoke join-smoke mqo-smoke \
	serve-smoke phase-smoke state-smoke

# static gate on our own code: ruff (rule set in pyproject.toml) when
# available, with compileall kept as the syntax floor for samples and
# for environments without ruff
lint:
	$(PY) -m compileall -q siddhi_tpu tests samples
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check siddhi_tpu tests samples bench.py; \
	else \
		echo "ruff not installed; syntax gate only (pip install ruff)"; \
	fi

# static plan analysis of the sample apps: any ERROR finding fails the
# build (siddhi_tpu/analysis; rule catalog via tools/docgen.py)
lint-apps:
	$(CPU_ENV) $(PY) -m siddhi_tpu.tools.lint samples/apps/*.siddhi

# corpus-clean + CLI exit-code contract + REST /lint + explain/healthz
# agreement (static-analysis layer, README "Static analysis")
lint-smoke:
	$(CPU_ENV) $(PY) samples/lint_smoke.py

# plan-audit gate: fingerprint the corpus (samples + bench shapes) and
# diff against the committed PLAN_BASELINE.json — exit 1 on any
# flops/bytes/memory/collectives regression (README "Plan audit")
audit:
	$(CPU_ENV) $(PY) -m siddhi_tpu.tools.audit check

# refresh the baseline after an INTENTIONAL plan change (commit the
# rewritten PLAN_BASELINE.json and say why in the PR)
audit-update:
	$(CPU_ENV) $(PY) -m siddhi_tpu.tools.audit update

# exit-code contract end-to-end through the real CLI: HEAD clean,
# injected flops/bytes/collectives regression -> 1, missing baseline
# -> 2, diff informational -> 0
audit-smoke:
	$(CPU_ENV) $(PY) samples/audit_smoke.py

# regenerate the committed docgen pages (lint rule catalog + audit
# metric/tolerance table) and fail on drift from the registries
docgen-check:
	$(CPU_ENV) $(PY) -m siddhi_tpu.tools.docgen /tmp/siddhi_docs_check
	diff -u docs/extensions/lint-rules.md \
		/tmp/siddhi_docs_check/lint-rules.md
	diff -u docs/extensions/audit-metrics.md \
		/tmp/siddhi_docs_check/audit-metrics.md

test:
	$(CPU_ENV) $(PY) -m pytest tests/ -q

dryrun:
	$(CPU_ENV) $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

bench:
	$(PY) bench.py

# sharded serving layer: the sharded/router suites (parity shapes,
# mesh-resize restore, @fuse-over-mesh, shard metrics) + a quick
# multichip scaling run asserting byte-identical output at 1/2/4/8
# shards (README "Sharded serving")
multichip-smoke:
	$(CPU_ENV) $(PY) -m pytest tests/test_sharded.py \
		tests/test_shard_router.py -q
	$(CPU_ENV) $(PY) bench.py --mode multichip --quick

# boots a sample app behind the REST service, scrapes GET /metrics, and
# asserts the required metric families are present (observability layer)
metrics-smoke:
	$(CPU_ENV) $(PY) samples/metrics_smoke.py

# fused-vs-sequential parity + throughput check on CPU (<60 s): identical
# workloads run with and without @fuse(batches=K); fails on any emission
# mismatch (scan-fusion layer, README "Fused stepping")
fuse-smoke:
	$(CPU_ENV) $(PY) samples/fuse_smoke.py

# boots a sample app, then asserts the whole introspection surface:
# GET /explain carries XLA cost analysis, /healthz reports live+ready,
# /trace/<query> serves DETAIL traces in the runtime's span names, and
# the siddhi_state_bytes family scrapes (observability v2 layer)
explain-smoke:
	$(CPU_ENV) $(PY) samples/explain_smoke.py

# deterministic fault injection end-to-end: retry zero-loss, error
# store + REST replay exactly-once, breaker -> degraded /healthz, and
# torn-snapshot restore fallback (resilience layer, README "Fault
# tolerance")
chaos-smoke:
	$(CPU_ENV) $(PY) samples/chaos_smoke.py

# sustained-load telemetry loop in <=30 s: 2 co-resident tenants under
# @async ingest with chaos ON (sink transport dies mid-run), the
# in-process sampler ticking, and the SLO verdict required to come back
# `ok` with zero silent drops (soak-telemetry layer, README "Soak & SLOs")
soak-smoke:
	$(CPU_ENV) $(PY) samples/soak_smoke.py

# equi-join fast path (ROADMAP item 2) in <60 s: windowed_join plans
# with bucketing ACTIVE (JOIN002 INFO), grid-vs-bucketed outputs
# byte-identical across inner/outer/residual/group-by/@fuse + the
# stream-table index probe, and the audit bytes-accessed fingerprint
# collapsed vs the grid plan (README "Equi-join fast path")
join-smoke:
	$(CPU_ENV) $(PY) samples/join_smoke.py

# multi-query optimizer (ROADMAP item 3) in <60 s: a 7-query app merges
# into one dispatch group with byte-identical per-query outputs vs the
# unmerged plan, the shared window buffer counted ONCE under the group,
# EXPLAIN/MQO001/static lint agreeing on the grouping, snapshots
# round-tripping merged<->unmerged, and per-query accounting + an
# admission quota surviving the merge (README "Multi-query
# optimization"); plus the quick dispatch/throughput A-B
mqo-smoke:
	$(CPU_ENV) $(PY) samples/mqo_smoke.py
	$(CPU_ENV) $(PY) bench.py --mode mqo_compare --quick

# device-resident serving (ROADMAP item 2) in <30 s: @serve parity with
# the blocking fetch (zero send-path device_get, asserted), ring
# overflow growth with zero loss, quiesce draining rings to empty,
# EXPLAIN/metrics/healthz serving surfaces, SERVE001 lint (README
# "Device-resident serving"); plus the quick blocking-vs-served A-B
serve-smoke:
	$(CPU_ENV) $(PY) samples/serve_smoke.py
	$(CPU_ENV) $(PY) bench.py --mode serve_compare --quick

# phase-level hot-path profiler in <60 s: all 8 taxonomy phases recorded
# for a @serve query, cross-thread trace handoff (drain spans share the
# dispatch trace id on the drain track), sampled
# deep-mode overhead < 5%, and every surface (/metrics families,
# phase_report, EXPLAIN phases) touching zero device state (README
# "Phase profiling"); plus the quick per-phase budget A-B
phase-smoke:
	$(CPU_ENV) $(PY) samples/phase_smoke.py
	$(CPU_ENV) $(PY) bench.py --mode phase_profile --quick --out /tmp/phases_quick.json

# the state observatory in <30 s: occupancy arithmetic against known
# traffic, the sizing-hints ledger surviving snapshot->restore, the
# near-capacity healthz verdict with its config-key cite, and all
# surfaces (3 /metrics families, EXPLAIN utilization, state_report)
# touching zero device state (README "State observatory"); plus the
# quick Zipf-vs-uniform hot-set A-B
state-smoke:
	$(CPU_ENV) $(PY) samples/state_smoke.py
	$(CPU_ENV) $(PY) bench.py --mode state_profile --quick --out /tmp/state_quick.json

# overload is decided, not discovered, in <30 s: an over-ceiling deploy
# denied BEFORE any compile, exact shed accounting (offered == accepted
# + shed), recompile-storm penalties at the shared compile gate with a
# lossless victim, and the REST/healthz admission surfaces agreeing
# (admission layer, README "Admission control & overload")
admission-smoke:
	$(CPU_ENV) $(PY) samples/admission_smoke.py

# Developer entry points (reference Makefile). Python-only build; no wheels
# of native code — the TPU compute path is JAX/XLA compiled at runtime.
PY ?= python

.PHONY: help test test-fast test-policy lint lint-invariants lint-changed fmt smoke chip-smoke bench-smoke chaos-smoke fleet-smoke fleet-trace-smoke kv-economy-smoke econ-smoke dashboards-validate helm-lint airgap clean

help:
	@grep -E '^[a-z-]+:' Makefile | sed 's/:.*//' | sort | uniq

test:
	# >=2 workers REQUIRED, not an optimization: a single process running
	# the whole suite segfaults around test ~335 (XLA:CPU state
	# accumulation; see docs/TROUBLESHOOTING.md). xdist keeps each worker
	# under the threshold; without it (minimal containers), two sequential
	# half-suite PROCESSES hold the same bound — slower, same signal.
	@if $(PY) -c "import xdist" 2>/dev/null; then \
	  $(PY) -m pytest tests/ -q -n 2; \
	else \
	  echo "NOTE: pytest-xdist not installed — running the suite as two sequential half-processes (single-process full suite segfaults ~test 335, docs/TROUBLESHOOTING.md)"; \
	  r=0; $(PY) -m pytest tests/test_[a-l]*.py -q || r=1; \
	  $(PY) -m pytest tests/test_[m-z]*.py -q || r=1; exit $$r; \
	fi

test-fast: lint-invariants  ## harness-only tests (skip JAX model/runtime suites)
	# -n 4: the harness lane is embarrassingly parallel; measured 11 min
	# -> <3 min on this box (the single-process segfault threshold only
	# bites the FULL suite, and xdist workers stay far under it)
	# (without xdist the fast tier runs single-process: it stays far
	# under the segfault threshold, so only wall time is lost)
	@if $(PY) -c "import xdist" 2>/dev/null; then XDIST="-n 4"; \
	else XDIST=""; echo "NOTE: pytest-xdist not installed — fast tier running single-process"; fi; \
	$(PY) -m pytest tests/ -q -m "not slow" $$XDIST --ignore=tests/test_model.py \
	  --ignore=tests/test_parallel.py --ignore=tests/test_flash_attention.py \
	  --ignore=tests/test_runtime.py --ignore=tests/test_loader.py \
	  --ignore=tests/test_quant.py

lint:
	# the ruff gate runs wherever ruff exists; a minimal container gets a
	# LOUD skip line, never a silent pass (tier-1 signal stays honest)
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
	  $(PY) -m ruff check kserve_vllm_mini_tpu tests; \
	else \
	  echo "SKIPPED: ruff not installed — the ruff gate DID NOT RUN in this container"; \
	fi
	$(PY) -c "import yaml,glob;[list(yaml.safe_load_all(open(f))) for f in glob.glob('profiles/**/*.yaml',recursive=True)+glob.glob('policies/**/*.yaml',recursive=True)]"
	$(PY) -c "import json,glob;[json.load(open(f)) for f in glob.glob('dashboards/*.json')]"

lint-invariants:  ## kvmini-lint: jit purity, lockstep, metrics drift, thread safety, dtype flow, buffer lifecycle, mesh/sharding, resource safety, protocol/contract, async discipline, config surface
	# gates on lint-baseline.json: new findings fail, fixed-but-still-
	# listed entries fail too (ratchet toward an empty baseline).
	# Rule table: docs/LINTING.md. JAX-free; runs in ~9s (families run
	# in a thread pool sized to the CPU count; --jobs 1 forces the
	# byte-identical serial path). --timing prints
	# per-checker wall time so a budget regression names its checker;
	# --timing-out writes the same report as the lint-timing.json
	# artifact CI uploads; --sarif writes the code-scanning doc CI
	# uploads as PR annotations — one run gates AND reports.
	$(PY) -m kserve_vllm_mini_tpu.lint kserve_vllm_mini_tpu/ --timing \
	  --timing-out lint-timing.json --sarif lint-results.sarif

# the fast pre-commit loop: lint only files changed vs REF (default HEAD)
# plus their cross-file importers. Directory-scan-only surfaces (KVM032
# docs drift, KVM131-133 config-surface joins) stay full-scan — run
# `make lint-invariants` before merging. FAMILY narrows to a comma list
# of rule families (e.g. `make lint-changed FAMILY=KVM05,KVM12`).
REF ?= HEAD
FAMILY ?=
lint-changed:  ## kvmini-lint over `git diff --name-only $(REF)` + importers; FAMILY=KVM05,KVM12 narrows
	$(PY) -m kserve_vllm_mini_tpu.lint --changed $(REF) $(if $(FAMILY),--family $(FAMILY))

fmt:
	$(PY) -m ruff format kserve_vllm_mini_tpu tests 2>/dev/null || true

smoke:  ## full pipeline on the CPU-faked mesh, no hardware
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) -m kserve_vllm_mini_tpu bench --self-serve --model llama-tiny \
	  --requests 20 --concurrency 4 --max-tokens 8

chip-smoke:  ## the serving path on the attached TPU (fails without one)
	$(PY) chip_smoke.py

# asserts the decode-pipeline counters (docs/DECODE_PIPELINE.md) land in
# results.json via the real stage chain — the same tier-1 gate CI runs.
# Also validates the exported traces.json against core/schema.py's
# TRACES_JSON_SCHEMA (docs/TRACING.md), and the live monitor's
# timeline.jsonl + results `monitor` block against TIMELINE_SAMPLE_SCHEMA /
# MONITOR_JSON_SCHEMA incl. the scripted-stall event (docs/MONITORING.md).
bench-smoke:  ## bench pipeline vs the mock server, tiny budget, no TPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_bench_smoke.py tests/test_monitor.py -q

# the resilience acceptance gate (docs/RESILIENCE.md): the local chaos
# scenario matrix end-to-end against the mock server — one fault per
# class through POST /faults, MTTR measured from fault-clear to first
# healthy completion, and a resilience_table.json that validates against
# core/schema.py RESILIENCE_JSON_SCHEMA — plus the loadgen retry/shed
# accounting and monitor event rules they feed.
chaos-smoke:  ## local-mode chaos matrix vs the mock server, no TPU, no cluster
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos_local.py tests/test_resilience.py -q -m "not slow"

# the fleet acceptance gate (docs/FLEET.md): supervisor + cache-aware
# router + local actuator + replica chaos against JAX-free mock replica
# processes — placement scoring, 429 re-placement, per-replica metric
# aggregation, replica-kill with zero hung requests, and the
# resilience-table replica rows, all with no engine and no cluster.
fleet-smoke:  ## fleet router/supervisor/actuator vs mock replicas, no TPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet.py -q -m "not slow"

# the fleet tracing acceptance gate (docs/TRACING.md "Fleet tracing"):
# the router's fleet.route/fleet.proxy span rail, honest shed /
# replica_lost terminal status, the bounded decision audit ring behind
# GET /fleet/decisions, per-replica clock-offset stitching of client +
# router + replica lanes into one schema-valid traces.json (one replica
# clock-skewed, one forced re-placement), and the report's fleet lane —
# all against JAX-free in-process mock replicas.
fleet-trace-smoke:  ## router spans + decision audit + 3-lane stitch, no TPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fleet_tracing.py -q -m "not slow"

# the KV-block economy acceptance gate (docs/DISAGGREGATION.md v2,
# docs/FLEET.md warm-from-sibling, docs/TROUBLESHOOTING.md host tier):
# a mock-server fleet respawn warms the new replica from its
# deepest-owning sibling (/kv/export -> /kv/import) and the hit-depth
# gauge recovers in the first scrape window, with schema-valid Results
# blocks for the handoff/tier counters — no engine, no TPU.
kv-economy-smoke:  ## zero-copy handoff + prefix migration + host tier, no TPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_kv_economy.py -q -m "not slow"

# the live cost & energy rail acceptance gate (docs/ECONOMICS.md): the
# rolling-window $/1K-tok agrees with the post-hoc estimator within 10%
# on a steady run, scripted mock /metrics drive both economics events
# through the real scrape->sample->detector path, the scraped
# Results.economics block validates, and the cost-aware policy sheds the
# unprofitable marginal replica 2->1 while queue pressure and an SLO
# breach veto the shed — no engine, no TPU.
econ-smoke:  ## live $/1K-tok + Wh/1K-tok rail, events, cost-aware policy
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_economics.py -q -m "not slow"

dashboards-validate:  ## dashboard JSON structure + panel/query checks
	$(PY) -m pytest tests/test_assets.py -q -k "dashboard"

test-policy:  ## policies vs a LIVE Gatekeeper (needs kubectl+cluster; skips without)
	bash tests/policy_admission_test.sh

helm-lint:
	@command -v helm >/dev/null && helm lint charts/kvmini-tpu || \
	  echo "helm not installed; skipping"

airgap:  ## wheel + charts + profiles tarball for disconnected installs
	$(PY) -m pip wheel . -w dist/ --no-deps
	tar czf dist/kvmini-tpu-airgap.tar.gz dist/*.whl charts profiles policies dashboards slo.json tpu-cost.yaml tpu-matrix.yaml

clean:
	rm -rf dist build *.egg-info runs artifacts

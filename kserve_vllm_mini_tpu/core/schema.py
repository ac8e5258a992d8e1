"""The canonical results.json schema — the framework's real API.

Gate, canary, planner, report, and sweeps all key off this flat dict
(reference SURVEY.md §5.5; /root/reference/analyze.py:573-595,
cost_estimator.py:465-482). We keep the reference's key names where the
semantics are hardware-agnostic (p50_ms, ttft_p95_ms, cost_per_1k_tokens, ...)
and replace the GPU-specific keys with TPU-native ones:

- gpu_util_avg        -> tpu_duty_cycle_avg   (duty cycle %, libtpu-style)
- gpu_mem_used_avg    -> tpu_hbm_used_avg_gib
- gpu_power_watts_avg -> tpu_power_watts_avg  (+ power_provenance)

Only knowingly-populated keys are written; merges are last-writer-wins at key
granularity, matching the reference's read-modify-write of results.json.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional


@dataclass
class Results:
    """Typed view of results.json. All fields optional; ``to_dict`` drops Nones."""

    # identity / provenance
    run_id: Optional[str] = None
    model: Optional[str] = None
    runtime: Optional[str] = None           # "jax-native" | "jetstream" | "vllm-tpu" | ...
    accelerator: Optional[str] = None       # e.g. "tpu-v5e-8"
    pattern: Optional[str] = None
    requests: Optional[int] = None
    concurrency: Optional[int] = None
    streaming: Optional[bool] = None

    # latency (ms)
    p50_ms: Optional[float] = None
    p95_ms: Optional[float] = None
    p99_ms: Optional[float] = None
    mean_ms: Optional[float] = None
    ttft_p50_ms: Optional[float] = None
    ttft_p95_ms: Optional[float] = None
    ttft_avg_ms: Optional[float] = None
    tpot_p50_ms: Optional[float] = None     # time-per-output-token
    tpot_p95_ms: Optional[float] = None

    # throughput
    throughput_rps: Optional[float] = None
    tokens_per_sec: Optional[float] = None
    tokens_per_sec_per_chip: Optional[float] = None
    error_rate: Optional[float] = None
    # overload shedding (docs/RESILIENCE.md): requests 429-shed past the
    # loadgen's retry budget, counted SEPARATELY from errors (error_rate
    # excludes them — an overload run shedding by design is not broken),
    # and the total 429 resends absorbed into surviving records
    shed_requests: Optional[int] = None
    shed_rate: Optional[float] = None
    retries_total: Optional[int] = None
    truncated_requests: Optional[int] = None  # prompts cut to the prefill
                                              # budget (workload changed)
    truncated_prompt_tokens: Optional[int] = None  # total tokens dropped

    # cold/warm split (reference analyze.py:422-460)
    cold_requests: Optional[int] = None
    warm_requests: Optional[int] = None
    cold_p50_ms: Optional[float] = None
    cold_p95_ms: Optional[float] = None
    warm_p50_ms: Optional[float] = None
    warm_p95_ms: Optional[float] = None
    cold_multiplier: Optional[float] = None
    cold_start_seconds: Optional[float] = None

    # utilization / telemetry (TPU-native). `*_avg` keys are only written
    # when a real window backs them (a Prometheus range or the monitor's
    # timeline — docs/MONITORING.md); a single runtime /metrics snapshot
    # lands in the instant keys with tpu_metrics_source saying so.
    tpu_duty_cycle_avg: Optional[float] = None
    tpu_duty_cycle: Optional[float] = None  # instantaneous, one scrape
    tpu_hbm_used_avg_gib: Optional[float] = None
    tpu_power_watts_avg: Optional[float] = None
    power_provenance: Optional[str] = None  # "measured" | "modeled"
    cpu_util_avg: Optional[float] = None
    host_mem_used_avg_gib: Optional[float] = None
    # queue-depth distribution over the run, from the monitor timeline
    queue_depth_p50: Optional[float] = None
    queue_depth_p95: Optional[float] = None
    queue_depth_max: Optional[float] = None

    # cache
    cache_hit_ratio: Optional[float] = None
    cache_hit_source: Optional[str] = None  # "metrics" | "logs" | "ttft-inference"

    # energy
    energy_wh: Optional[float] = None
    energy_wh_per_request: Optional[float] = None
    energy_wh_per_1k_tokens: Optional[float] = None

    # cost
    cost_total: Optional[float] = None
    cost_per_request: Optional[float] = None
    cost_per_1k_tokens: Optional[float] = None
    cost_breakdown: Optional[dict[str, float]] = None
    cold_cost_total: Optional[float] = None
    warm_cost_total: Optional[float] = None

    # io probe
    network_rtt_p50_ms: Optional[float] = None
    network_rtt_p95_ms: Optional[float] = None
    storage_fetch_mbps: Optional[float] = None

    # quality
    quality_score: Optional[float] = None
    quality_tasks: Optional[dict[str, float]] = None

    # window + distributions
    window: Optional[dict[str, float]] = None        # {"start": t0, "end": t1, "duration_s": d}
    latency_histogram: Optional[dict[str, Any]] = None
    ttft_histogram: Optional[dict[str, Any]] = None
    token_timing: Optional[dict[str, Any]] = None

    # decode-pipeline telemetry (docs/DECODE_PIPELINE.md): the runtime's
    # double-buffering counters, scraped from /metrics (analysis/
    # telemetry.py PIPELINE_METRIC_KEYS) or snapshotted directly in
    # self-serve runs (bench_pipeline). Declared so gates/reports see
    # typed fields instead of untyped extras.
    pipeline_dispatch_depth: Optional[float] = None
    pipeline_pipelined_sweeps: Optional[float] = None
    pipeline_host_overlap_s: Optional[float] = None
    pipeline_bubble_s: Optional[float] = None

    # chunked-prefill telemetry (docs/TROUBLESHOOTING.md "Long prompts
    # stall streaming"): compiled prefill piece dispatches and the prefill
    # wall that ran while decode work was live, scraped from /metrics
    # (analysis/telemetry.py PREFILL_METRIC_KEYS); absent for external
    # engines
    prefill_chunks: Optional[float] = None
    prefill_chunk_stall_s: Optional[float] = None

    # server-side phase attribution (docs/TRACING.md): per-phase duration
    # stats from the runtime's /traces spans merged by the analyzer —
    # {"queue"|"prefill"|"decode": {count, mean_ms, p50_ms, p95_ms,
    # max_ms}, "clock_offset_ms_est": ..., "source": "server:/traces"}.
    # Runs through a fleet router also carry the router-lane phases
    # "route" (placement+proxy window) and "proxy" (per-attempt upstream
    # call), with source "fleet:/traces".
    phase_breakdown: Optional[dict[str, Any]] = None
    # p99-outlier routing attribution (docs/TRACING.md "Fleet tracing"):
    # the slowest request's trace_id joined to its placement decision(s)
    # from the router's audit ring — {trace_id, latency_ms, placements,
    # decisions: [...]}; absent for single-server runs and when the ring
    # already evicted the run's entries.
    routing_outlier: Optional[dict[str, Any]] = None

    # live-monitor summary (docs/MONITORING.md): rolling SLO burn-rates,
    # detected events, sampler accounting and abort info — the shape
    # validate_monitor checks, backed by runs/<id>/timeline.jsonl
    monitor: Optional[dict[str, Any]] = None
    # reason string when the run was early-terminated by the monitor's
    # abort hook (sweeps record it per cell; absent for completed runs)
    aborted_early: Optional[str] = None

    # compile-stats block (docs/PROFILING.md): the runtime's accumulated
    # lower().compile() capture — {compiles, compile_wall_s, flops,
    # bytes_accessed, peak_bytes} — snapshotted directly in self-serve
    # runs or scraped from /metrics (analysis/telemetry.py
    # COMPILE_METRIC_KEYS); absent for external engines
    compile_stats: Optional[dict[str, Any]] = None

    # KV-cache & HBM observability block (docs/TROUBLESHOOTING.md "HBM
    # pressure & KV thrash"): prefix-cache attribution (hit-depth
    # percentiles, bytes reused), paged-block lifecycle counters
    # (allocations, retained-LRU evictions, share reclaims), pool
    # occupancy/fragmentation gauges and HBM watermarks — snapshotted
    # directly in self-serve runs or scraped from /metrics (analysis/
    # telemetry.py KV_METRIC_KEYS); shape gated by validate_kv_cache.
    # Absent for external engines.
    kv_cache: Optional[dict[str, Any]] = None
    # resilience block (docs/RESILIENCE.md): the runtime's shed /
    # watchdog / degrade counters — {requests_shed, watchdog_trips,
    # engine_faults, degrade_level, faults_armed, source} — snapshotted
    # directly in self-serve runs or scraped from /metrics (analysis/
    # telemetry.py RESILIENCE_METRIC_KEYS); absent for external engines
    # and for runs with zero resilience activity.
    resilience: Optional[dict[str, Any]] = None
    # disaggregated-serving block (docs/DISAGGREGATION.md): the prefill-
    # lane handoff rail — {handoffs, handoff_blocks, handoff_wait_s,
    # handoff_drops, handoff_bytes_copied, lane_busy_s,
    # colocated_fallbacks, queue_depth,
    # degraded, source} — snapshotted directly in self-serve runs or
    # scraped from /metrics (analysis/telemetry.py DISAGG_METRIC_KEYS);
    # absent for colocated engines, external engines, and runs with zero
    # handoff activity.
    disagg: Optional[dict[str, Any]] = None
    # fleet block (docs/FLEET.md): the multi-replica router's rail —
    # {replicas_desired, replicas_live, placements, reroutes, sheds,
    # stream_errors, replica_restarts, scale_ups, scale_downs,
    # last_cold_start_s, source} — scraped from the router's aggregated
    # /metrics (analysis/telemetry.py FLEET_METRIC_KEYS); absent for
    # single-server runs and external engines.
    fleet: Optional[dict[str, Any]] = None
    # live-economics block (docs/ECONOMICS.md): the rolling-window cost/
    # energy rail — {usd_per_1k_tokens, wh_per_1k_tokens, usd_per_hour,
    # tokens_per_sec, marginal_replica_usd_per_1k_tokens, source} —
    # snapshotted directly in self-serve runs (engine.economics_snapshot)
    # or scraped from /metrics (analysis/telemetry.py ECON_METRIC_KEYS);
    # shape gated by validate_economics. Absent for CPU backends without
    # an econ_accelerator and for external engines — never a $0 block.
    economics: Optional[dict[str, Any]] = None
    # headroom-model validation (profiling/headroom.py): signed % error
    # of the analytic admission estimate vs the observed HBM peak —
    # negative = the model UNDERESTIMATES (the OOM direction). Present
    # only when the run observed a real (or mocked) HBM watermark.
    headroom_error_pct: Optional[float] = None

    extras: dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for f in dataclasses.fields(self):
            if f.name == "extras":
                continue
            v = getattr(self, f.name)
            if v is not None:
                out[f.name] = v
        out.update(self.extras)
        return out

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "Results":
        known = {f.name for f in dataclasses.fields(cls)} - {"extras"}
        kwargs = {k: v for k, v in d.items() if k in known}
        extras = {k: v for k, v in d.items() if k not in known}
        return cls(**kwargs, extras=extras)


def merge_results(base: dict[str, Any], update: dict[str, Any]) -> dict[str, Any]:
    """Key-granular merge; nested dicts (cost_breakdown, window, ...) are
    replaced wholesale like the reference does."""
    out = dict(base)
    out.update(update)
    return out


# -- traces.json schema -------------------------------------------------------
#
# The OTLP/JSON subset both trace writers (loadgen/tracing.py, runtime/
# tracing.py) emit and the analyzer's merge preserves. Expressed as a
# JSON-Schema document for tooling, enforced by validate_traces (hand-
# rolled — the validation must not grow a jsonschema dependency for the
# harness layers). `make bench-smoke` gates on it.

TRACES_JSON_SCHEMA: dict[str, Any] = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "kvmini-tpu traces.json (OTLP/JSON subset)",
    "type": "object",
    "required": ["resourceSpans"],
    "properties": {
        "resourceSpans": {
            "type": "array",
            "items": {
                "type": "object",
                "required": ["scopeSpans"],
                "properties": {
                    "scopeSpans": {
                        "type": "array",
                        "items": {
                            "type": "object",
                            "required": ["spans"],
                            "properties": {
                                "spans": {
                                    "type": "array",
                                    "items": {
                                        "type": "object",
                                        "required": [
                                            "traceId", "spanId", "name",
                                            "startTimeUnixNano",
                                            "endTimeUnixNano",
                                        ],
                                        "properties": {
                                            "traceId": {
                                                "type": "string",
                                                "pattern": "^[0-9a-f]{32}$",
                                            },
                                            "spanId": {
                                                "type": "string",
                                                "pattern": "^[0-9a-f]{16}$",
                                            },
                                            "parentSpanId": {
                                                "type": "string",
                                                "pattern": "^[0-9a-f]{16}$",
                                            },
                                            "name": {"type": "string"},
                                            "startTimeUnixNano": {"type": "string"},
                                            "endTimeUnixNano": {"type": "string"},
                                        },
                                    },
                                }
                            },
                        },
                    }
                },
            },
        },
        "clockOffsetNanosEstimate": {"type": "integer"},
        # fleet stitches (analysis/traces.merge_fleet_traces): one offset
        # PER replica keyed by rid — two replicas' clocks can disagree,
        # so a single estimate cannot shift both lanes correctly — plus
        # the router's own offset against the client clock
        "clockOffsetsNanosByReplica": {
            "type": "object",
            "additionalProperties": {"type": "integer"},
        },
        "clockOffsetNanosRouter": {"type": "integer"},
        "droppedSpans": {"type": "integer"},
    },
}


_HEX_CHARS = frozenset("0123456789abcdef")


def _hex_id(v: Any, width: int) -> bool:
    # the SAME strictness as the schema's ^[0-9a-f]{N}$ patterns
    # (lowercase-only; int(v, 16) would accept uppercase/'0x'/underscores
    # and make this gate disagree with the published JSON Schema)
    return (
        isinstance(v, str) and len(v) == width and _HEX_CHARS.issuperset(v)
    )


def validate_traces(doc: Any) -> list[str]:
    """Validate a traces.json document against TRACES_JSON_SCHEMA's
    contract. Returns a list of violation strings — empty means valid.
    Checks the invariants downstream consumers rely on: id shapes, the
    nano-timestamp strings, and end >= start (negative durations were the
    exact bug the export clamp fixed)."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["document is not an object"]
    rss = doc.get("resourceSpans")
    if not isinstance(rss, list):
        return ["resourceSpans missing or not an array"]
    for ri, rs in enumerate(rss):
        if not isinstance(rs, dict):
            errs.append(f"resourceSpans[{ri}] is not an object")
            continue
        sss = rs.get("scopeSpans")
        if not isinstance(sss, list):
            errs.append(f"resourceSpans[{ri}].scopeSpans missing")
            continue
        for si, ss in enumerate(sss):
            spans = ss.get("spans") if isinstance(ss, dict) else None
            if not isinstance(spans, list):
                errs.append(
                    f"resourceSpans[{ri}].scopeSpans[{si}].spans missing"
                )
                continue
            for pi, s in enumerate(spans):
                where = f"resourceSpans[{ri}].scopeSpans[{si}].spans[{pi}]"
                if not isinstance(s, dict):
                    errs.append(f"{where} is not an object")
                    continue
                if not _hex_id(s.get("traceId"), 32):
                    errs.append(f"{where}: bad traceId {s.get('traceId')!r}")
                if not _hex_id(s.get("spanId"), 16):
                    errs.append(f"{where}: bad spanId {s.get('spanId')!r}")
                if "parentSpanId" in s and not _hex_id(s["parentSpanId"], 16):
                    errs.append(
                        f"{where}: bad parentSpanId {s['parentSpanId']!r}"
                    )
                if not isinstance(s.get("name"), str) or not s.get("name"):
                    errs.append(f"{where}: missing name")
                try:
                    start = int(s.get("startTimeUnixNano", ""))
                    end = int(s.get("endTimeUnixNano", ""))
                except (TypeError, ValueError):
                    errs.append(f"{where}: non-integer time stamps")
                    continue
                if end < start:
                    errs.append(
                        f"{where}: negative duration "
                        f"({s.get('name')}: {end} < {start})"
                    )
    return errs


# -- monitor block + timeline.jsonl schemas -----------------------------------
#
# The live-monitor surfaces (docs/MONITORING.md): the `monitor` block the
# sampler merges into results.json and the per-line sample shape of
# runs/<id>/timeline.jsonl. Hand-rolled validators for the same reason as
# validate_traces — no jsonschema dependency in the harness layers.
# `make bench-smoke` gates on both.

MONITOR_JSON_SCHEMA: dict[str, Any] = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "kvmini-tpu results.json `monitor` block",
    "type": "object",
    "required": ["interval_s", "samples", "skipped_samples", "events",
                 "burn_rates", "burn_rates_peak"],
    "properties": {
        "interval_s": {"type": "number", "exclusiveMinimum": 0},
        "window_s": {"type": "number"},
        "samples": {"type": "integer", "minimum": 0},
        "skipped_samples": {"type": "integer", "minimum": 0},
        "events": {
            "type": "array",
            "items": {
                "type": "object",
                "required": ["t", "type", "detail"],
                "properties": {
                    "t": {"type": "number"},
                    "type": {"type": "string"},
                    "detail": {"type": "string"},
                    "data": {"type": "object"},
                },
            },
        },
        "burn_rates": {
            "type": "object", "additionalProperties": {"type": "number"}
        },
        "burn_rates_peak": {
            "type": "object", "additionalProperties": {"type": "number"}
        },
        "aborted": {"type": "string"},
    },
}

TIMELINE_SAMPLE_SCHEMA: dict[str, Any] = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "kvmini-tpu timeline.jsonl sample (one JSON object per line)",
    "type": "object",
    "required": ["t"],
    "properties": {
        "t": {"type": "number"},
        "scrape_ms": {"type": "number", "minimum": 0},
        "runtime": {
            "type": "object", "additionalProperties": {"type": "number"}
        },
        "loadgen": {
            "type": "object", "additionalProperties": {"type": "number"}
        },
        "burn_rates": {
            "type": "object", "additionalProperties": {"type": "number"}
        },
        "events": {"type": "array"},
        # trace ids in flight at sample time (docs/MONITORING.md): rides
        # TOP-level, not inside `loadgen` — that block's contract is a
        # flat name->number map and must stay numeric
        "inflight_trace_ids": {
            "type": "array", "items": {"type": "string"}
        },
    },
}


def _num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


# -- kv_cache block schema ----------------------------------------------------
#
# The KV-cache & HBM observability block (docs/TROUBLESHOOTING.md): what
# the engine's kv_cache_snapshot and the analyzer's KV_METRIC_KEYS scrape
# both produce under the `kv_cache` results key. Hand-rolled validator
# like the others — no jsonschema dependency in the harness layers.
# `make bench-smoke` gates on it.

KV_CACHE_JSON_SCHEMA: dict[str, Any] = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "kvmini-tpu results.json `kv_cache` block",
    "type": "object",
    "required": ["hit_depth_p50", "hit_depth_p95", "reused_bytes",
                 "blocks_allocated", "retained_evictions"],
    "properties": {
        "source": {"type": "string"},
        "hit_depth_p50": {"type": "number", "minimum": 0},
        "hit_depth_p95": {"type": "number", "minimum": 0},
        "bytes_per_token": {"type": "number", "minimum": 0},
        "reused_bytes": {"type": "number", "minimum": 0},
        "blocks_allocated": {"type": "number", "minimum": 0},
        "retained_evictions": {"type": "number", "minimum": 0},
        "share_reclaims": {"type": "number", "minimum": 0},
        "prefix_hits": {"type": "number", "minimum": 0},
        "prefix_lookups": {"type": "number", "minimum": 0},
        "pool_blocks": {"type": "number", "minimum": 0},
        "free_blocks": {"type": "number", "minimum": 0},
        "retained_blocks": {"type": "number", "minimum": 0},
        "used_blocks": {"type": "number", "minimum": 0},
        "block_size": {"type": "number", "minimum": 1},
        "occupancy": {"type": "number", "minimum": 0, "maximum": 1},
        "retained_fraction": {"type": "number", "minimum": 0, "maximum": 1},
        "fragmentation": {"type": "number", "minimum": 0, "maximum": 1},
        "logical_bytes": {"type": "number", "minimum": 0},
        "physical_bytes": {"type": "number", "minimum": 0},
        "hbm_bytes_in_use": {"type": "number", "minimum": 0},
        "hbm_peak_bytes": {"type": "number", "minimum": 0},
        "hbm_bytes_limit": {"type": "number", "minimum": 0},
        "headroom_estimate_bytes": {"type": "number", "minimum": 0},
        "tier_demotions": {"type": "number", "minimum": 0},
        "tier_promotions": {"type": "number", "minimum": 0},
        "tier_hits": {"type": "number", "minimum": 0},
        "tier_blocks": {"type": "number", "minimum": 0},
        "tier_bytes": {"type": "number", "minimum": 0},
        "tier_capacity_bytes": {"type": "number", "minimum": 0},
        "tier_disabled": {"type": "number", "minimum": 0, "maximum": 1},
        "migrated_blocks": {"type": "number", "minimum": 0},
        "migrated_bytes": {"type": "number", "minimum": 0},
        "export_blocks": {"type": "number", "minimum": 0},
    },
}

_KV_FRACTIONS = ("occupancy", "retained_fraction", "fragmentation",
                 "tier_disabled")


def validate_kv_cache(doc: Any) -> list[str]:
    """Validate a results.json ``kv_cache`` block against
    KV_CACHE_JSON_SCHEMA's contract. Returns violations; empty = valid.
    The invariants downstream consumers rely on: the required
    hit-depth/reuse/churn keys present and numeric, every present
    numeric non-negative, ratios inside [0, 1], p95 >= p50, and the
    paged pool arithmetic (free + retained + used == pool) when the
    pool gauges are present."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["kv_cache block is not an object"]
    for key in KV_CACHE_JSON_SCHEMA["required"]:
        if not _num(doc.get(key)) or doc[key] < 0:
            errs.append(f"{key} missing or not a non-negative number")
    for key, spec in KV_CACHE_JSON_SCHEMA["properties"].items():
        if key not in doc or spec.get("type") != "number":
            continue
        v = doc[key]
        if not _num(v):
            errs.append(f"{key} is not a number")
            continue
        if v < spec.get("minimum", 0):
            errs.append(f"{key} below {spec.get('minimum', 0)} ({v})")
        if key in _KV_FRACTIONS and v > 1:
            errs.append(f"{key} above 1 ({v})")
    if (
        _num(doc.get("hit_depth_p50")) and _num(doc.get("hit_depth_p95"))
        and doc["hit_depth_p95"] < doc["hit_depth_p50"]
    ):
        errs.append(
            f"hit_depth_p95 < hit_depth_p50 "
            f"({doc['hit_depth_p95']} < {doc['hit_depth_p50']})"
        )
    pool_keys = ("pool_blocks", "free_blocks", "retained_blocks",
                 "used_blocks")
    if all(_num(doc.get(k)) for k in pool_keys):
        total = (doc["free_blocks"] + doc["retained_blocks"]
                 + doc["used_blocks"])
        if total != doc["pool_blocks"]:
            errs.append(
                f"pool arithmetic broken: free+retained+used={total} "
                f"!= pool_blocks={doc['pool_blocks']}"
            )
    if "source" in doc and not isinstance(doc["source"], str):
        errs.append("source is not a string")
    return errs


# -- economics block schema ---------------------------------------------------
#
# The live cost/energy rail (docs/ECONOMICS.md): what the engine's
# economics_snapshot and the analyzer's ECON_METRIC_KEYS scrape both
# produce under the `economics` results key. Hand-rolled validator like
# the others — no jsonschema dependency in the harness layers. `make
# econ-smoke` gates on it.

ECONOMICS_JSON_SCHEMA: dict[str, Any] = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "kvmini-tpu results.json `economics` block",
    "type": "object",
    "required": ["usd_per_hour"],
    "properties": {
        "source": {"type": "string"},
        "usd_per_hour": {"type": "number", "exclusiveMinimum": 0},
        "usd_per_1k_tokens": {"type": "number", "minimum": 0},
        "wh_per_1k_tokens": {"type": "number", "minimum": 0},
        "tokens_per_sec": {"type": "number", "minimum": 0},
        "marginal_replica_usd_per_1k_tokens": {
            "type": "number", "minimum": 0
        },
    },
}


def validate_economics(doc: Any) -> list[str]:
    """Validate a results.json ``economics`` block against
    ECONOMICS_JSON_SCHEMA's contract. Returns violations; empty = valid.
    The invariants downstream consumers rely on: the $/hr accrual
    present and strictly positive (a block that exists but prices the
    deployment at $0/hr is a pricing-sheet failure, not a cheap fleet),
    every present rate numeric and non-negative, and — for SINGLE-engine
    blocks, where all three gauges come from one snapshot window — the
    derivation closed: the reported $/1K-tok must equal usd_per_hour /
    (3.6 x tokens_per_sec) to float tolerance. Fleet-scraped blocks are
    exempt (flagged by the marginal-replica key): there usd_per_hour and
    tokens_per_sec are label-SUMMED fleet totals while usd_per_1k_tokens
    is the healthy-replica MEAN of ratios, which legitimately differs
    from the ratio of sums on a skewed fleet."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["economics block is not an object"]
    v = doc.get("usd_per_hour")
    if not _num(v) or v <= 0:
        errs.append("usd_per_hour missing or not a positive number")
    for key in ("usd_per_1k_tokens", "wh_per_1k_tokens", "tokens_per_sec",
                "marginal_replica_usd_per_1k_tokens"):
        if key in doc and (not _num(doc[key]) or doc[key] < 0):
            errs.append(f"{key} not a non-negative number ({doc[key]!r})")
    if (
        "marginal_replica_usd_per_1k_tokens" not in doc
        and _num(doc.get("usd_per_hour"))
        and _num(doc.get("tokens_per_sec")) and doc["tokens_per_sec"] > 0
        and _num(doc.get("usd_per_1k_tokens"))
    ):
        implied = doc["usd_per_hour"] / (3.6 * doc["tokens_per_sec"])
        if abs(doc["usd_per_1k_tokens"] - implied) > max(
            1e-6, 0.01 * implied
        ):
            errs.append(
                f"usd_per_1k_tokens={doc['usd_per_1k_tokens']} does not "
                f"match usd_per_hour/(3.6*tokens_per_sec)={implied:.9f}"
            )
    if "source" in doc and not isinstance(doc["source"], str):
        errs.append("source is not a string")
    return errs


def _rate_map_errs(v: Any, where: str) -> list[str]:
    if not isinstance(v, dict):
        return [f"{where} is not an object"]
    return [
        f"{where}[{k!r}] is not a number"
        for k, val in v.items() if not _num(val)
    ]


def validate_monitor(doc: Any) -> list[str]:
    """Validate a results.json ``monitor`` block against
    MONITOR_JSON_SCHEMA's contract. Returns violations; empty = valid."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["monitor block is not an object"]
    for key in ("interval_s",):
        if not _num(doc.get(key)) or doc.get(key) <= 0:
            errs.append(f"{key} missing or not a positive number")
    for key in ("samples", "skipped_samples"):
        v = doc.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errs.append(f"{key} missing or not a non-negative integer")
    events = doc.get("events")
    if not isinstance(events, list):
        errs.append("events missing or not an array")
    else:
        for i, e in enumerate(events):
            if not isinstance(e, dict):
                errs.append(f"events[{i}] is not an object")
                continue
            if not _num(e.get("t")):
                errs.append(f"events[{i}].t missing or not a number")
            for key in ("type", "detail"):
                if not isinstance(e.get(key), str) or not e.get(key):
                    errs.append(f"events[{i}].{key} missing or empty")
    for key in ("burn_rates", "burn_rates_peak"):
        errs += _rate_map_errs(doc.get(key), key)
    if "aborted" in doc and not isinstance(doc["aborted"], str):
        errs.append("aborted is not a string")
    return errs


# -- resilience_table.json schema ---------------------------------------------
#
# The chaos harness's per-fault table (chaos/harness.py + chaos/local.py,
# docs/RESILIENCE.md): one row per fault scenario with MTTR (time to first
# healthy completion after the fault cleared), p95-under-fault, and shed/
# error rates. Hand-rolled validator like the others — no jsonschema
# dependency in the harness layers. `make chaos-smoke` gates on it.

RESILIENCE_JSON_SCHEMA: dict[str, Any] = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "title": "kvmini-tpu resilience_table.json (chaos harness output)",
    "type": "object",
    "required": ["faults", "all_recovered"],
    "properties": {
        "service": {"type": "string"},
        "namespace": {"type": "string"},
        "target": {"enum": ["kserve", "local"]},
        "all_recovered": {"type": "boolean"},
        "worst_mttr_s": {"type": ["number", "null"], "minimum": 0},
        "faults": {
            "type": "array",
            "items": {
                "type": "object",
                "required": ["fault", "injected", "recovered"],
                "properties": {
                    "fault": {"type": "string"},
                    "injected": {"type": "boolean"},
                    "recovered": {"type": "boolean"},
                    "mttr_s": {"type": ["number", "null"], "minimum": 0},
                    "p95_ms": {"type": ["number", "null"], "minimum": 0},
                    "error_rate": {"type": ["number", "null"],
                                   "minimum": 0, "maximum": 1},
                    "shed_rate": {"type": ["number", "null"],
                                  "minimum": 0, "maximum": 1},
                    # None when injection failed or no gate was configured:
                    # a broken injector must NEVER read as a green gate
                    "gate_ok": {"type": ["boolean", "null"]},
                    "detail": {"type": "string"},
                },
            },
        },
    },
}


def validate_resilience(doc: Any) -> list[str]:
    """Validate a resilience_table.json document against
    RESILIENCE_JSON_SCHEMA's contract. Returns violations; empty = valid.
    The invariants downstream consumers rely on: per-fault rows typed,
    rates inside [0, 1], MTTR non-negative, a recovered row carrying a
    numeric MTTR, and gate_ok left null (never false-green) on rows whose
    injection failed."""
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["resilience table is not an object"]
    faults = doc.get("faults")
    if not isinstance(faults, list):
        return ["faults missing or not an array"]
    if not isinstance(doc.get("all_recovered"), bool):
        errs.append("all_recovered missing or not a boolean")
    worst = doc.get("worst_mttr_s")
    if worst is not None and (not _num(worst) or worst < 0):
        errs.append(f"worst_mttr_s not a non-negative number ({worst!r})")
    if "target" in doc and doc["target"] not in ("kserve", "local"):
        errs.append(f"target must be 'kserve'|'local' (got {doc['target']!r})")
    for i, row in enumerate(faults):
        where = f"faults[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where} is not an object")
            continue
        if not isinstance(row.get("fault"), str) or not row.get("fault"):
            errs.append(f"{where}.fault missing or empty")
        for key in ("injected", "recovered"):
            if not isinstance(row.get(key), bool):
                errs.append(f"{where}.{key} missing or not a boolean")
        for key in ("mttr_s", "p95_ms"):
            v = row.get(key)
            if v is not None and (not _num(v) or v < 0):
                errs.append(f"{where}.{key} not a non-negative number ({v!r})")
        for key in ("error_rate", "shed_rate"):
            v = row.get(key)
            if v is not None and (not _num(v) or not 0 <= v <= 1):
                errs.append(f"{where}.{key} outside [0, 1] ({v!r})")
        if row.get("recovered") is True and not _num(row.get("mttr_s")):
            errs.append(f"{where}: recovered row must carry a numeric mttr_s")
        if row.get("injected") is False and row.get("gate_ok") is not None:
            errs.append(
                f"{where}: gate_ok must be null when injection failed "
                "(a broken injector must not produce a gate verdict)"
            )
        g = row.get("gate_ok")
        if g is not None and not isinstance(g, bool):
            errs.append(f"{where}.gate_ok not a boolean/null ({g!r})")
    return errs


def validate_timeline(samples: list[Any]) -> list[str]:
    """Validate parsed timeline.jsonl samples (RunDir.read_timeline)
    against TIMELINE_SAMPLE_SCHEMA's contract: every line an object with
    a numeric monotone-friendly ``t``, and the runtime/loadgen/burn_rates
    blocks flat name->number maps."""
    errs: list[str] = []
    prev_t: Optional[float] = None
    for i, s in enumerate(samples):
        where = f"sample[{i}]"
        if not isinstance(s, dict):
            errs.append(f"{where} is not an object")
            continue
        t = s.get("t")
        if not _num(t):
            errs.append(f"{where}.t missing or not a number")
        else:
            if prev_t is not None and t < prev_t:
                errs.append(f"{where}.t went backwards ({t} < {prev_t})")
            prev_t = float(t)
        if "scrape_ms" in s and not _num(s["scrape_ms"]):
            errs.append(f"{where}.scrape_ms is not a number")
        for block in ("runtime", "loadgen", "burn_rates"):
            if block in s:
                errs += _rate_map_errs(s[block], f"{where}.{block}")
        if "events" in s and not isinstance(s["events"], list):
            errs.append(f"{where}.events is not an array")
    return errs

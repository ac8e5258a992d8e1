"""In-process mock OpenAI-compatible server for load-generator tests.

Plays the role of the reference CI's stubbed cluster (SURVEY.md §4.3): a real
HTTP socket + SSE stream, no model behind it. Supports configurable per-token
delay so TTFT/TPOT assertions have something to measure.

Request tracing (docs/TRACING.md): the mock ECHOES the received W3C
``traceparent`` — it records server.queue/prefill/decode spans parented
under the client's http.request span id into the same ring-buffer
recorder the real runtime uses (runtime/tracing.py) and serves them at
``GET /traces``, so the loadgen->analyzer join path is exercised
end-to-end without booting the JAX engine.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass

from aiohttp import web

from kserve_vllm_mini_tpu.runtime.tracing import (
    PHASES,
    SCHED_PHASES,
    PhaseHistogram,
    SpanRecorder,
    parse_traceparent,
    render_phase_histograms,
)


@dataclass
class MockStats:
    requests: int = 0
    streamed: int = 0


def scripted_metrics(
    rates: dict[str, float],
    base: dict[str, float] | None = None,
    stall: tuple[float, float] | None = None,
    stall_values: dict[str, float] | None = None,
):
    """Build a ``metrics_script`` callable for time-varying ``/metrics``:
    each counter in ``rates`` ramps linearly (units/second) from app
    start, FREEZING inside the ``stall`` window (start_s, end_s) — the
    scripted mid-run stall the monitor's decode-stall detector must catch
    (docs/MONITORING.md). ``base`` gauges are served as-is outside the
    stall; ``stall_values`` overrides them inside it (e.g. a collapsed
    duty cycle)."""

    def active_seconds(elapsed: float) -> float:
        if stall is None:
            return elapsed
        s0, s1 = stall
        return elapsed - max(0.0, min(elapsed, s1) - s0)

    def at(elapsed: float) -> dict[str, float]:
        out = dict(base or {})
        for name, rate in rates.items():
            out[name] = rate * active_seconds(elapsed)
        if stall is not None and stall[0] <= elapsed < stall[1]:
            out.update(stall_values or {})
        return out

    return at


def make_app(
    token_delay_s: float = 0.002,
    n_tokens: int = 8,
    fail_every: int = 0,
    capabilities: set[str] | None = None,
    pipeline_metrics: dict[str, float] | None = None,
    metrics_script=None,
    server_id: str | None = None,
    clock_skew_ns: int = 0,
) -> web.Application:
    """``capabilities`` toggles OpenAI-dialect extras for parity-probe tests:
    any subset of {"tools", "parallel_tools", "json_mode", "logprobs",
    "sampling_penalties", "n_choices"}. None means all supported.

    ``pipeline_metrics`` overrides the decode-pipeline gauges the /metrics
    endpoint reports (kvmini_tpu_* names, docs/DECODE_PIPELINE.md); the
    defaults mimic a runtime whose double-buffered steady state engaged.

    ``metrics_script``: elapsed-seconds -> {metric: value} overrides
    merged over the static values per scrape (see scripted_metrics), so
    monitor event detection is testable without a device.

    ``server_id`` names this instance (multi-instance fleets,
    docs/FLEET.md): responses carry it in ``system_fingerprint`` and an
    ``x-kvmini-mock-replica`` header so router-placement tests can see
    WHICH replica served without parsing logs; per-instance
    ``pipeline_metrics``/``metrics_script`` give each port its own
    scripted /metrics.

    ``clock_skew_ns`` shifts every recorded span timestamp by a fixed
    offset — a replica whose wall clock disagrees with the client's, so
    the analyzer's PER-replica clock-offset estimation
    (docs/TRACING.md "Fleet tracing") is testable with two mock replicas
    at different skews and no real clock drift."""
    stats = MockStats()
    caps = capabilities if capabilities is not None else {
        "tools", "parallel_tools", "json_mode", "logprobs",
        "sampling_penalties", "n_choices",
    }
    tracer = SpanRecorder(capacity=1024)
    phase_hist = {p: PhaseHistogram() for p in PHASES}

    # In-process fault injection, same wire shape as the real runtime's
    # POST /faults (docs/RESILIENCE.md) so the local chaos harness and
    # the loadgen's retry/timeout paths are testable with no JAX engine.
    # Armed points: sweep_stall (responses HOLD until cleared),
    # device_error (500), kv_alloc_fail (503), shed (429 + Retry-After),
    # sse_disconnect (stream transport drops after after_tokens chunks),
    # sse_stall (stream stops producing chunks without closing — the
    # read-timeout satellite's prey).
    faults: dict[str, dict] = {}

    def _fault(name: str) -> dict | None:
        spec = faults.get(name)
        if spec is None:
            return None
        times = int(spec.get("times", 0) or 0)
        if times > 0 and spec.get("_fired", 0) >= times:
            return None
        spec["_fired"] = spec.get("_fired", 0) + 1
        return spec

    def _record_trace(trace_ctx, header, t_arrive_ns, t_first_ns, t_done_ns):
        """Echo the received traceparent as server phase spans: queue /
        prefill / decode parented under the client's http.request span —
        the same span model the real engine stamps."""
        if trace_ctx is None:
            return
        tid, parent = trace_ctx
        skew = int(clock_skew_ns)
        t_arrive_ns += skew
        t_first_ns += skew
        t_done_ns += skew
        q_end = t_arrive_ns + max((t_first_ns - t_arrive_ns) // 4, 1)
        tracer.record("server.queue", tid, t_arrive_ns, q_end,
                      parent_span_id=parent,
                      attrs={"traceparent": header})
        # prefill_chunks rides the span like the real engine's
        # _activate_slot stamp (docs/TROUBLESHOOTING.md "Long prompts
        # stall streaming") so bench-smoke can pin the attribute contract
        tracer.record("server.prefill", tid, q_end, t_first_ns,
                      parent_span_id=parent,
                      attrs={"prefill_chunks": 1})
        tracer.record("server.decode", tid, t_first_ns, t_done_ns,
                      parent_span_id=parent)
        phase_hist["queue"].observe((q_end - t_arrive_ns) / 1e9)
        phase_hist["prefill"].observe((t_first_ns - q_end) / 1e9)
        phase_hist["decode"].observe((t_done_ns - t_first_ns) / 1e9)

    async def chat(request: web.Request) -> web.StreamResponse:
        stats.requests += 1
        if "sweep_stall" in faults:
            # wedged backend: hold every response until the fault clears
            # (the local chaos harness measures MTTR from the clear to
            # the first completion that escapes this loop); a client
            # that gave up releases its handler immediately
            t_hold = time.time()
            while "sweep_stall" in faults and time.time() - t_hold < 60.0:
                if request.transport is None or request.transport.is_closing():
                    raise ConnectionResetError("client gone during wedge")
                await asyncio.sleep(0.05)
        if _fault("device_error") is not None:
            return web.json_response(
                {"error": {"message": "injected device error"}}, status=500
            )
        if _fault("kv_alloc_fail") is not None:
            return web.json_response(
                {"error": {"message": "kv pool exhausted (injected)"}},
                status=503,
            )
        shed_spec = _fault("shed")
        if shed_spec is not None:
            return web.json_response(
                {"error": {"message": "shed (injected)",
                           "code": "request_shed"}},
                status=429,
                headers={"Retry-After":
                         str(shed_spec.get("retry_after", 1))},
            )
        if fail_every and stats.requests % fail_every == 0:
            return web.json_response({"error": "injected"}, status=500)
        tp_header = request.headers.get("traceparent", "")
        trace_ctx = parse_traceparent(tp_header)
        t_arrive_ns = time.time_ns()
        body = await request.json()
        stream = body.get("stream", False)

        if body.get("tools") and "tools" in caps:
            tools = body["tools"]
            calls = [
                {
                    "id": f"call_{i}",
                    "type": "function",
                    "function": {
                        "name": t["function"]["name"],
                        "arguments": json.dumps({"city": "Paris"}),
                    },
                }
                for i, t in enumerate(tools)
            ]
            if len(tools) > 1 and "parallel_tools" not in caps:
                calls = calls[:1]
            return web.json_response(
                {
                    "id": "mock",
                    "choices": [
                        {
                            "index": 0,
                            "message": {
                                "role": "assistant",
                                "content": None,
                                "tool_calls": calls,
                            },
                            "finish_reason": "tool_calls",
                        }
                    ],
                    "usage": {"prompt_tokens": 5, "completion_tokens": 8},
                }
            )

        if body.get("response_format", {}).get("type") == "json_object":
            if "json_mode" not in caps:
                return web.json_response({"error": "response_format unsupported"}, status=400)
            return web.json_response(
                {
                    "id": "mock",
                    "choices": [
                        {
                            "index": 0,
                            "message": {
                                "role": "assistant",
                                "content": json.dumps({"city": "Paris", "country": "France"}),
                            },
                        }
                    ],
                    "usage": {"prompt_tokens": 5, "completion_tokens": 8},
                }
            )

        if body.get("logprobs") and "logprobs" in caps and not stream:
            return web.json_response(
                {
                    "id": "mock",
                    "choices": [
                        {
                            "index": 0,
                            "message": {"role": "assistant", "content": "hello"},
                            "logprobs": {
                                "content": [
                                    {
                                        "token": "hello",
                                        "logprob": -0.01,
                                        "top_logprobs": [
                                            {"token": "hello", "logprob": -0.01},
                                            {"token": "hi", "logprob": -4.2},
                                        ],
                                    }
                                ]
                            },
                        }
                    ],
                    "usage": {"prompt_tokens": 3, "completion_tokens": 1},
                }
            )
        max_toks = min(int(body.get("max_tokens", 16)), n_tokens)
        words = [f"tok{i} " for i in range(max_toks)]
        # sampling_penalties capability: a penalized request produces
        # DIFFERENT output than the unpenalized baseline (what the probe
        # checks); without the capability the knobs are silently ignored
        penalized = (
            float(body.get("frequency_penalty", 0) or 0) != 0
            or float(body.get("presence_penalty", 0) or 0) != 0
        )
        if penalized and "sampling_penalties" in caps:
            words = [f"uniq{i} " for i in range(max_toks)]
        n = int(body.get("n", 1) or 1)
        n = n if ("n_choices" in caps and not stream) else 1
        if not stream:
            await asyncio.sleep(token_delay_s * max_toks)
            t_done = time.time_ns()
            _record_trace(trace_ctx, tp_header, t_arrive_ns,
                          t_arrive_ns + max((t_done - t_arrive_ns) // 2, 1),
                          t_done)
            return web.json_response(
                {
                    "id": "mock",
                    "system_fingerprint": server_id or "mock",
                    "choices": [
                        {"index": i,
                         "message": {"role": "assistant", "content": "".join(words)}}
                        for i in range(n)
                    ],
                    "usage": {
                        "prompt_tokens": 5,
                        "completion_tokens": max_toks,
                        "total_tokens": 5 + max_toks,
                    },
                    "metrics": {"server_ttft_ms": token_delay_s * 1000.0},
                },
                headers={"x-kvmini-mock-replica": server_id or "mock"},
            )
        stats.streamed += 1
        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "x-kvmini-mock-replica": server_id or "mock"},
        )
        await resp.prepare(request)
        cut_spec = _fault("sse_disconnect")
        cut_after = int(cut_spec.get("after_tokens", 1)) if cut_spec else None
        stall_spec = _fault("sse_stall")
        stall_after = (
            int(stall_spec.get("after_tokens", 1)) if stall_spec else None
        )
        t_first_ns = 0
        for i, w in enumerate(words):
            await asyncio.sleep(token_delay_s)
            evt = {
                "id": "mock",
                "choices": [{"index": 0, "delta": {"content": w}}],
                **({"metrics": {"server_ttft_ms": token_delay_s * 1000.0}} if i == 0 else {}),
            }
            if i == 0:
                t_first_ns = time.time_ns()
            await resp.write(f"data: {json.dumps(evt)}\n\n".encode())
            if cut_after is not None and i + 1 >= cut_after:
                # injected mid-stream disconnect: drop the transport the
                # way a network fault would (no [DONE], no clean close)
                if request.transport is not None:
                    request.transport.close()
                return resp
            if stall_after is not None and i + 1 >= stall_after:
                # injected stream STALL: the connection stays open but no
                # further chunk ever arrives — only the client's read
                # timeout can end this (loadgen split-timeout satellite).
                # A client that gave up releases the handler so server
                # cleanup never waits out the stall.
                t_end = time.time() + float(stall_spec.get("duration", 30.0))
                while time.time() < t_end:
                    if (request.transport is None
                            or request.transport.is_closing()):
                        break
                    await asyncio.sleep(0.05)
                break
        usage_evt = {
            "id": "mock",
            "choices": [],
            "usage": {"prompt_tokens": 5, "completion_tokens": max_toks},
        }
        await resp.write(f"data: {json.dumps(usage_evt)}\n\n".encode())
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        _record_trace(trace_ctx, tp_header, t_arrive_ns,
                      t_first_ns or time.time_ns(), time.time_ns())
        return resp

    pipe = {
        "kvmini_tpu_dispatch_depth": 2.0,
        "kvmini_tpu_pipelined_sweeps_total": 40.0,
        "kvmini_tpu_host_overlap_seconds_total": 0.25,
        "kvmini_tpu_bubble_seconds_total": 0.01,
        # the scheduler ledger's decode gaps (docs/TRACING.md): the bubble
        # above is gap_seconds summed over the causes, an admission gap's
        # phases add up to its cause's seconds
        'kvmini_tpu_sched_gaps_total{cause="admission"}': 2.0,
        'kvmini_tpu_sched_gaps_total{cause="prefill_piece"}': 0.0,
        'kvmini_tpu_sched_gaps_total{cause="sweep"}': 1.0,
        'kvmini_tpu_sched_gap_seconds_total{cause="admission"}': 0.008,
        'kvmini_tpu_sched_gap_seconds_total{cause="prefill_piece"}': 0.0,
        'kvmini_tpu_sched_gap_seconds_total{cause="sweep"}': 0.002,
        "kvmini_tpu_sched_gap_slot_seconds_total": 0.012,
        **{f'kvmini_tpu_sched_admit_gap_seconds_total{{phase="{p}"}}': v
           for p, v in {**dict.fromkeys(SCHED_PHASES, 0.0), "prefill": 0.004,
                        "first_token": 0.002, "decode_prepare": 0.0008,
                        "emit": 0.0005, "admit": 0.0004, "loop": 0.0003}.items()},
        "kvmini_tpu_sched_admit_gap_admissions_total": 2.0,
        # admissions behind the sweep in flight, and those that drained it
        # (docs/DECODE_PIPELINE.md)
        "kvmini_tpu_admissions_inflight_total": 3.0,
        'kvmini_tpu_pipeline_fallback_total{reason="active_set"}': 1.0,
        # chunked-prefill rail (docs/TROUBLESHOOTING.md)
        "kvmini_tpu_prefills_total": 4.0,
        "kvmini_tpu_prefill_chunks_total": 6.0,
        "kvmini_tpu_prefill_chunk_stall_seconds_total": 0.125,
        # what the pieces were padded to, and those of them whose program
        # dispatched to the routed experts grouped by expert (docs/TRACING.md)
        "kvmini_tpu_prefill_bucket_tokens_total": 2048.0,
        "kvmini_tpu_prefill_grouped_bucket_tokens_total": 1536.0,
        # monitor-facing gauges/counters (docs/MONITORING.md) so the 1 Hz
        # sampler's timeline has runtime series without a JAX engine
        "kvmini_tpu_duty_cycle": 0.8,
        "kvmini_tpu_queue_depth": 0.0,
        "kvmini_tpu_active_slots": 2.0,
        # KV-cache & HBM rail (docs/TROUBLESHOOTING.md "HBM pressure &
        # KV thrash"): the gauges the sampler polls into timeline.jsonl
        # and the analyzer scrapes into the kv_cache block — a mocked-HBM
        # watermark + estimate pair so headroom_error_pct closes without
        # a device (estimate 12 GB vs peak 10 GB -> +20%)
        "kvmini_tpu_kv_prefix_hit_depth_p50": 8.0,
        "kvmini_tpu_kv_prefix_hit_depth_p95": 16.0,
        "kvmini_tpu_kv_bytes_per_token": 128.0,
        "kvmini_tpu_kv_reused_bytes_total": 2048.0,
        "kvmini_tpu_kv_blocks_allocated_total": 6.0,
        "kvmini_tpu_kv_retained_evictions_total": 2.0,
        "kvmini_tpu_kv_share_reclaims_total": 2.0,
        "kvmini_tpu_prefix_hits_total": 1.0,
        "kvmini_tpu_cache_lookups_total": 2.0,
        "kvmini_tpu_kv_pool_blocks": 8.0,
        "kvmini_tpu_kv_free_blocks": 4.0,
        "kvmini_tpu_kv_retained_blocks": 0.0,
        "kvmini_tpu_kv_used_blocks": 4.0,
        "kvmini_tpu_kv_block_size": 4.0,
        "kvmini_tpu_kv_occupancy": 0.5,
        "kvmini_tpu_hbm_bytes_in_use": 9.5e9,
        "kvmini_tpu_hbm_peak_bytes": 10e9,
        "kvmini_tpu_hbm_bytes_limit": 16e9,
        "kvmini_tpu_hbm_headroom_estimate_bytes": 12e9,
        # KV-block economy rail (docs/FLEET.md cross-replica migration +
        # host-RAM tier): the counters the kv-economy smoke and the
        # telemetry scrape read without a JAX engine
        "kvmini_tpu_kv_handoff_bytes_copied_total": 0.0,
        "kvmini_tpu_kv_tier_demotions_total": 0.0,
        "kvmini_tpu_kv_tier_promotions_total": 0.0,
        "kvmini_tpu_kv_tier_hits_total": 0.0,
        "kvmini_tpu_kv_tier_blocks": 0.0,
        "kvmini_tpu_kv_tier_bytes": 0.0,
        "kvmini_tpu_kv_tier_capacity_bytes": 0.0,
        "kvmini_tpu_kv_tier_disabled": 0.0,
        "kvmini_tpu_kv_migrated_blocks_total": 0.0,
        "kvmini_tpu_kv_migrated_bytes_total": 0.0,
        "kvmini_tpu_kv_export_blocks_total": 0.0,
        # fleet-router placement input (docs/FLEET.md): per-instance
        # overrides let multi-instance tests give each replica a
        # distinct load picture
        "kvmini_tpu_estimated_wait_seconds": 0.0,
        **(pipeline_metrics or {}),
    }
    t_app_start = time.time()

    async def metrics(_request: web.Request) -> web.Response:
        # the same Prometheus exposition shape runtime/server.py serves, so
        # the analyzer's pipeline-counter scrape is exercised end-to-end
        # without booting the JAX engine
        vals = dict(pipe)
        if metrics_script is not None:
            vals.update(metrics_script(time.time() - t_app_start))
        lines, typed = [], set()
        for name, value in vals.items():
            family = name.split("{", 1)[0]      # one TYPE line a labelled family
            if family not in typed:
                typed.add(family)
                kind = "counter" if family.endswith("_total") else "gauge"
                lines.append(f"# TYPE {family} {kind}")
            lines.append(f"{name} {value}")
        # phase-latency histograms, same renderer as runtime/server.py
        lines += render_phase_histograms(phase_hist)
        return web.Response(text="\n".join(lines) + "\n",
                            content_type="text/plain")

    async def traces(_request: web.Request) -> web.Response:
        # per-replica service identity: the fleet stitcher joins each
        # replica's /traces doc to its rid, so every instance must say
        # who it is (single-instance mocks keep the runtime's name)
        svc = (f"kvmini-tpu-runtime/{server_id}" if server_id
               else "kvmini-tpu-runtime")
        return web.json_response(tracer.to_otlp(service_name=svc))

    async def faults_get(_request: web.Request) -> web.Response:
        return web.json_response({
            "enabled": True,
            "active": {
                n: {k: v for k, v in s.items() if not k.startswith("_")}
                for n, s in faults.items()
            },
        })

    async def faults_post(request: web.Request) -> web.Response:
        # same wire shape as runtime/server.py POST /faults
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        action = body.get("action", "arm")
        name = body.get("name")
        if action == "clear":
            if name is None:
                faults.clear()
            else:
                faults.pop(name, None)
            return web.json_response({"status": "ok",
                                      "cleared": name or "all"})
        if action != "arm" or not name:
            return web.json_response(
                {"error": {"message": "need action 'arm'|'clear' and, for "
                           "arm, a fault 'name'"}}, status=400,
            )
        faults[name] = {k: v for k, v in body.items()
                        if k not in ("action", "name")}
        return web.json_response({"status": "ok",
                                  "armed": {"name": name, **faults[name]}})

    async def healthz(_request: web.Request) -> web.Response:
        return web.json_response({"status": "ok",
                                  "server_id": server_id or "mock"})

    async def kv_export(request: web.Request) -> web.Response:
        """Mock donor side of cross-replica KV migration: synthesize one
        wire block per owned prefix block, derived from this instance's
        live ``kv_prefix_hit_depth_p50`` gauge — warm replicas ship
        depth, cold ones ship nothing, no JAX anywhere. Armable fault
        ``kv_export_fail`` -> 503 (the donor-death-mid-export path)."""
        if "kv_export_fail" in faults:
            return web.json_response(
                {"error": {"message": "injected kv_export_fail"}},
                status=503,
            )
        try:
            body = await request.json()
        except Exception:
            body = {}
        budget = int((body or {}).get("budget_bytes", 1 << 24))
        blk = int(pipe["kvmini_tpu_kv_block_size"]) or 1
        depth = int(pipe["kvmini_tpu_kv_prefix_hit_depth_p50"])
        n = max(depth // blk, 0)
        # ~per-block wire cost so budget truncation is exercisable
        per_block = 1024
        n = min(n, max(budget // per_block, 0))
        blocks = [
            {"key": f"{server_id or 'mock'}-{i:08x}", "depth": i + 1,
             "kv": {}}
            for i in range(n)
        ]
        pipe["kvmini_tpu_kv_export_blocks_total"] += n
        return web.json_response({
            "block_size": blk,
            "blocks": blocks,
            "bytes": n * per_block,
            "truncated": n * per_block + per_block > budget,
        })

    async def kv_import(request: web.Request) -> web.Response:
        """Mock target side: installing N blocks of depth D raises this
        instance's hit-depth gauge to D*block_size — the observable
        'warm' signal the fleet respawn smoke asserts on."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        blocks = (body or {}).get("blocks") or []
        blk = int((body or {}).get(
            "block_size", pipe["kvmini_tpu_kv_block_size"])) or 1
        if blocks:
            depth = max(int(b.get("depth", 0)) for b in blocks)
            pipe["kvmini_tpu_kv_prefix_hit_depth_p50"] = max(
                pipe["kvmini_tpu_kv_prefix_hit_depth_p50"],
                float(depth * blk),
            )
        per_block = 1024
        pipe["kvmini_tpu_kv_migrated_blocks_total"] += len(blocks)
        pipe["kvmini_tpu_kv_migrated_bytes_total"] += len(blocks) * per_block
        return web.json_response({
            "imported": len(blocks), "skipped": 0,
            "bytes": len(blocks) * per_block, "exhausted": False,
        })

    async def models(_request: web.Request) -> web.Response:
        # same OpenAI list shape runtime/server.py serves — the fleet
        # router proxies the first healthy replica's answer verbatim, so
        # the mock fleet has to serve the endpoint too (KVM113)
        return web.json_response({
            "object": "list",
            "data": [{"id": server_id or "mock-model", "object": "model",
                      "created": int(t_app_start),
                      "owned_by": "kvmini-tpu-mock"}],
        })

    app = web.Application()
    app.router.add_post("/v1/chat/completions", chat)
    app.router.add_get("/v1/models", models)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/traces", traces)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/faults", faults_get)
    app.router.add_post("/faults", faults_post)
    app.router.add_post("/kv/export", kv_export)
    app.router.add_post("/kv/import", kv_import)
    return app


class MockServer:
    """async context manager yielding the base URL of a live mock endpoint."""

    def __init__(self, **kwargs):
        self.app = make_app(**kwargs)
        self.runner: web.AppRunner | None = None
        self.url = ""

    async def __aenter__(self) -> "MockServer":
        self.runner = web.AppRunner(self.app)
        await self.runner.setup()
        site = web.TCPSite(self.runner, "127.0.0.1", 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        self.url = f"http://127.0.0.1:{port}"
        return self

    async def __aexit__(self, *exc) -> None:
        if self.runner:
            await self.runner.cleanup()


class MockFleet:
    """N in-process mock endpoints with DISTINCT scripted metrics per
    port (docs/FLEET.md) — router placement and failover are testable
    with no JAX engine. ``specs`` is one make_app kwargs dict per
    replica; each gets ``server_id`` "r<i>" unless the spec names one.

    async with MockFleet([{"pipeline_metrics": {...}}, {...}]) as fleet:
        fleet.urls        # ["http://127.0.0.1:p0", ...]
        fleet.replicas()  # [("r0", url0), ...] — FleetRouter's shape
    """

    def __init__(self, specs: list[dict]):
        self.servers = [
            MockServer(**{"server_id": f"r{i}", **spec})
            for i, spec in enumerate(specs)
        ]
        self.ids = [
            spec.get("server_id", f"r{i}") for i, spec in enumerate(specs)
        ]
        self.urls: list[str] = []

    async def __aenter__(self) -> "MockFleet":
        for s in self.servers:
            await s.__aenter__()
        self.urls = [s.url for s in self.servers]
        return self

    async def __aexit__(self, *exc) -> None:
        for s in self.servers:
            await s.__aexit__(*exc)

    def replicas(self) -> list[tuple[str, str]]:
        return list(zip(self.ids, self.urls))


def main(argv: list[str] | None = None) -> int:
    """``python -m tests.mock_server``: one mock endpoint as a real OS
    process — what the fleet supervisor spawns for JAX-free fleet tests
    (kill-able, wedge-able via POST /faults, per-instance metrics via
    --metrics-json)."""
    import argparse

    parser = argparse.ArgumentParser(prog="tests.mock_server")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--server-id", default=None)
    parser.add_argument("--token-delay", type=float, default=0.002)
    parser.add_argument("--n-tokens", type=int, default=8)
    parser.add_argument("--metrics-json", default=None,
                        help="JSON dict merged over the default /metrics "
                             "gauges (distinct per instance)")
    parser.add_argument("--clock-skew-ns", type=int, default=0,
                        help="shift every recorded span timestamp by this "
                             "many ns (per-replica offset-estimation tests)")
    args = parser.parse_args(argv)
    overrides = json.loads(args.metrics_json) if args.metrics_json else None
    app = make_app(
        token_delay_s=args.token_delay,
        n_tokens=args.n_tokens,
        pipeline_metrics=overrides,
        server_id=args.server_id,
        clock_skew_ns=args.clock_skew_ns,
    )
    web.run_app(app, host=args.host, port=args.port, print=None)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

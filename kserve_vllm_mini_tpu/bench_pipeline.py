"""``kvmini-tpu bench`` — the one-command pipeline (reference bench.sh).

Stages (reference bench.sh:201-289): validate -> [serve in-process] ->
load-test (+ concurrent power sampling) -> io probe -> analyze -> energy
integrate -> cost -> [gate] — all in-process against a run directory, no
bash heredocs. With ``--self-serve`` the in-repo runtime is started for the
duration, so the full pipeline runs with no cluster at all (SURVEY.md §7.1b).
"""

from __future__ import annotations

import argparse
import sys
import threading
from typing import Any, Optional

import yaml

from kserve_vllm_mini_tpu.core.rundir import RunDir
from kserve_vllm_mini_tpu.core.validate import validate_profile


def _monitor_budgets(monitor_slo: Any) -> dict[str, float]:
    """Budgets for live burn-rates: a dict is taken as-is, a string is a
    budgets JSON path (the same file format gates/slo.py loads)."""
    if isinstance(monitor_slo, dict):
        return {k: float(v) for k, v in monitor_slo.items()}
    if isinstance(monitor_slo, str):
        from kserve_vllm_mini_tpu.gates.slo import load_slo

        return load_slo(monitor_slo)
    return {}


def run_bench(
    url: Optional[str],
    profile: dict[str, Any],
    run_dir: Optional[RunDir] = None,
    self_serve: bool = False,
    prom_url: Optional[str] = None,
    namespace: Optional[str] = None,
    service: Optional[str] = None,
    cost_file: Optional[str] = None,
    chips: Optional[float] = None,
    slo_file: Optional[str] = None,
    idle_tax: str = "none",
    monitor: bool = True,
    monitor_slo: Any = None,
    monitor_abort: bool = False,
    cost_budget_usd_per_1k_tok: Optional[float] = None,
) -> tuple[dict[str, Any], int]:
    """Returns (results, exit_code).

    ``monitor`` runs the 1 Hz live sampler (docs/MONITORING.md) for the
    duration of the load stage: timeline.jsonl, rolling burn-rates
    against ``monitor_slo`` budgets (path or dict; also profile keys
    ``monitor_slo`` / ``monitor_abort`` / ``monitor_interval_s``), and —
    with ``monitor_abort`` — early termination of hopeless runs."""
    from kserve_vllm_mini_tpu.energy.collector import collect_power

    if not url and not self_serve:
        print("bench: either --url or --self-serve is required", file=sys.stderr)
        return {}, 2

    # Stage 0: validate — against the limits the run will actually use (the
    # self-serve engine defaults max_model_len to 1024, not the validator's
    # external-backend default)
    if self_serve:
        profile.setdefault("max_model_len", 1024)
    rep = validate_profile(profile)
    for w in rep.warnings:
        print(f"WARNING: {w}")
    if not rep.ok:
        for e in rep.errors:
            print(f"ERROR: {e}")
        return {}, 1

    run_dir = run_dir or RunDir.create()
    run_dir.path.mkdir(parents=True, exist_ok=True)
    print(f"bench: run dir {run_dir.path}")

    server = None
    cold_start_instants: list[float] = []
    cold_window_s = 30.0
    if self_serve:
        # start the in-repo runtime on a free port; its startup IS a cold
        # start — the cold-start instant is when boot BEGAN (pod-startedAt
        # analog), not when readiness was observed
        from kserve_vllm_mini_tpu.runtime.local import start_local_server

        server = start_local_server(profile)
        url = server.url
        cold_start_instants = [server.boot_began]
        # requests can only begin after readiness, so the cold window must
        # cover boot (weights + XLA compile) plus the usual 30 s of load
        cold_window_s += server.boot_seconds
        print(f"bench: self-serve runtime up in {server.boot_seconds:.1f}s at {url}")

    # Live monitor (docs/MONITORING.md): profile keys override the
    # arguments so sweeps can vary monitoring per cell
    monitor_on = bool(profile.get("monitor", monitor))
    run_monitor = None
    live = None
    abort = None
    if monitor_on:
        from kserve_vllm_mini_tpu.loadgen.runner import LiveStats
        from kserve_vllm_mini_tpu.monitor import (
            AbortSignal,
            MonitorConfig,
            RunMonitor,
        )

        budgets = _monitor_budgets(profile.get("monitor_slo", monitor_slo))
        live = LiveStats()
        abort = AbortSignal()
        run_monitor = RunMonitor(
            run_dir.timeline_jsonl,
            endpoint=url,
            live=live,
            cfg=MonitorConfig(
                interval_s=float(profile.get("monitor_interval_s", 1.0)),
                budgets=budgets,
                abort_enabled=bool(profile.get("monitor_abort", monitor_abort)),
                cost_budget_usd_per_1k_tok=(
                    float(profile["cost_budget_usd_per_1k_tok"])
                    if profile.get("cost_budget_usd_per_1k_tok") is not None
                    else cost_budget_usd_per_1k_tok
                ),
            ),
            abort=abort,
        )
        run_monitor.start()

    # Stage 1: load test with concurrent power sampling. Everything from here
    # to the SLO gate runs under try/finally: a failing stage must still stop
    # the sampler and the self-serve engine (its decode-loop thread and KV
    # cache would otherwise outlive the run — sweeps record-and-continue on
    # failure, so a leak here skews every subsequent config).
    #
    # With the monitor on and no Prometheus, the dedicated power-sampler
    # thread is NOT started: the monitor's timeline already carries
    # duty/busy from the same endpoint at the same 1 Hz, and power.json is
    # derived from it after the load stage (energy/collector.py
    # power_from_timeline) — one scrape loop, not two, against the
    # endpoint being measured. A Prometheus URL still gets the sampling
    # loop (measured node power beats modeled duty x TDP).
    stop_sampling = threading.Event()
    sampler: Optional[threading.Thread] = None
    if run_monitor is None or prom_url:
        sampler = threading.Thread(
            target=collect_power,
            args=(run_dir, prom_url, url),
            kwargs={
                "interval_s": 1.0,
                "accelerator": profile.get("accelerator"),
                "stop_check": stop_sampling.is_set,
            },
            daemon=True,
            name="power-sampler",
        )
        sampler.start()

    try:
        return _run_stages(
            profile,
            url,
            run_dir,
            server,
            cold_start_instants,
            cold_window_s,
            sampler,
            stop_sampling,
            prom_url=prom_url,
            namespace=namespace,
            service=service,
            cost_file=cost_file,
            chips=chips,
            slo_file=slo_file,
            idle_tax=idle_tax,
            run_monitor=run_monitor,
            live=live,
            abort=abort,
        )
    finally:
        stop_sampling.set()
        if run_monitor is not None:
            run_monitor.stop()
        if server is not None:
            server.stop()


def _run_stages(
    profile: dict[str, Any],
    url: str,
    run_dir: RunDir,
    server,
    cold_start_instants: list[float],
    cold_window_s: float,
    sampler: Optional[threading.Thread],
    stop_sampling: threading.Event,
    *,
    prom_url: Optional[str],
    namespace: Optional[str],
    service: Optional[str],
    cost_file: Optional[str],
    chips: Optional[float],
    slo_file: Optional[str],
    idle_tax: str,
    run_monitor=None,
    live=None,
    abort=None,
) -> tuple[dict[str, Any], int]:
    from kserve_vllm_mini_tpu.analysis.analyzer import analyze_run
    from kserve_vllm_mini_tpu.costs.estimator import estimate_cost
    from kserve_vllm_mini_tpu.costs.pricing import load_pricing
    from kserve_vllm_mini_tpu.energy.collector import integrate_energy
    from kserve_vllm_mini_tpu.loadgen.runner import LoadConfig, run_load

    cfg = LoadConfig(
        url=url,
        model=profile.get("model", "default"),
        models=profile.get("models"),
        backend=profile.get("backend", "openai"),
        num_requests=int(profile.get("requests", 100)),
        concurrency=int(profile.get("concurrency", 10)),
        pattern=profile.get("pattern", "steady"),
        target_rps=profile.get("target_rps"),
        duration_s=profile.get("duration_s"),
        streaming=bool(profile.get("streaming", True)),
        max_tokens=int(profile.get("max_tokens", 64)),
        temperature=float(profile.get("temperature", 0.0)),
        n=int(profile.get("n", 1)),
        presence_penalty=float(profile.get("presence_penalty", 0.0)),
        frequency_penalty=float(profile.get("frequency_penalty", 0.0)),
        stop=profile.get("stop"),
        prompt_set=profile.get("prompt_set", "default"),
        input_tokens=int(profile.get("input_tokens", 0)),
        seed=int(profile.get("seed", 42)),
        connect_timeout_s=float(profile.get("connect_timeout_s", 10.0)),
        read_timeout_s=float(profile.get("read_timeout_s", 30.0)),
        max_retries=int(profile.get("max_retries", 3)),
        deadline_ms=(
            float(profile["deadline_ms"])
            if profile.get("deadline_ms") is not None else None
        ),
        extra_body=profile.get("extra_body", {}) or {},
    )
    records = run_load(cfg, run_dir, live=live, abort=abort)
    stop_sampling.set()
    monitor_summary: Optional[dict[str, Any]] = None
    if run_monitor is not None:
        # stop BEFORE analyze: the analyzer reads timeline.jsonl and the
        # last line must be flushed
        monitor_summary = run_monitor.stop()
        if sampler is None:
            # the monitor replaced the power-sampling loop — derive
            # power.json from its timeline (one scrape loop, not two)
            from kserve_vllm_mini_tpu.energy.collector import collect_power

            collect_power(
                run_dir, None, None,
                accelerator=profile.get("accelerator"),
                # snapshot, not the live list: stop()'s join is bounded, so
                # a wedged scrape can leave the sampler thread appending
                # while the energy integration iterates (KVM055 bug class)
                timeline=run_monitor.timeline(),
            )
    if sampler is not None:
        # worst-case iteration = power-query timeouts (~8 s with 2 s
        # timeouts); power.json must exist before Stage 4 integrates it
        sampler.join(timeout=30.0)
    ok = sum(1 for r in records if r.ok)
    print(f"bench: load complete {ok}/{len(records)} ok")

    # annotate meta for downstream stages
    meta = run_dir.read_meta()
    meta.update(
        {
            "accelerator": profile.get("accelerator"),
            "chips": chips or profile.get("chips", 1),
            "runtime": "jax-native" if server is not None else profile.get("backend", "openai"),
        }
    )
    run_dir.write_meta(meta)

    # Stage 2: io probe (best-effort RTT against the endpoint)
    try:
        from kserve_vllm_mini_tpu.probes.net_storage import measure_http_rtt

        run_dir.write_io_probe(measure_http_rtt(url))
    except Exception:  # kvmini: workload-ok — optional probe; absence shows
        pass           # up as missing network_rtt_* fields, not silence

    # Stage 3: analyze
    results = analyze_run(
        run_dir,
        prom_url=prom_url,
        endpoint=url,
        namespace=namespace,
        service=service,
        cold_start_times=cold_start_instants or None,
        cold_window_s=cold_window_s,
    )

    # self-serve boot time is the run's measured cold start; persist it so
    # downstream consumers (autoscale sweep deploy_time_s) can read it
    if server is not None:
        run_dir.merge_into_results(
            {"cold_start_seconds": round(server.boot_seconds, 2)}
        )

    # live-monitor summary (docs/MONITORING.md): burn rates, events,
    # sampler accounting, and — when the abort hook fired — the reason,
    # which sweeps surface per cell as aborted_early
    if monitor_summary is not None:
        run_dir.merge_into_results({"monitor": monitor_summary})
        if abort is not None and abort.is_set():
            run_dir.merge_into_results({"aborted_early": abort.reason})

    # Stage 4: energy
    integrate_energy(run_dir, idle_tax=idle_tax)

    # Stage 5: cost
    estimate_cost(
        run_dir,
        load_pricing(cost_file),
        namespace=namespace,
        service=service,
        chips=chips or profile.get("chips"),
        accelerator=profile.get("accelerator"),
    )

    # self-serve: the engine is in-process, so record its decode-pipeline
    # counters (docs/DECODE_PIPELINE.md) authoritatively — the analyzer's
    # /metrics scrape covers external endpoints, but a direct snapshot
    # can't race the server teardown
    if server is not None:
        es = server.engine.snapshot_stats()
        run_dir.merge_into_results({
            "pipeline_dispatch_depth": es["dispatch_depth"],
            "pipeline_pipelined_sweeps": es["pipelined_sweeps"],
            "pipeline_host_overlap_s": round(es["host_overlap_s"], 6),
            "pipeline_bubble_s": round(es["bubble_s"], 6),
            # chunked-prefill rail (docs/TROUBLESHOOTING.md "Long prompts
            # stall streaming"): same authoritative-direct-snapshot rule
            "prefill_chunks": es["prefill_chunks"],
            "prefill_chunk_stall_s": round(es["prefill_chunk_stall_s"], 6),
        })
        # compile-stats block (docs/PROFILING.md): the direct snapshot is
        # authoritative (per-executable entries included) and replaces
        # whatever the /metrics scrape merged above
        cs = server.engine.compile_stats_snapshot()
        if cs.get("compiles"):
            run_dir.merge_into_results({"compile_stats": cs})
        # KV-cache & HBM block (docs/TROUBLESHOOTING.md): same
        # authoritative-direct-snapshot rule, and the headroom-model
        # validation closes here when the device reported a peak
        kv = server.engine.kv_cache_snapshot()
        run_dir.merge_into_results({"kv_cache": kv})
        # resilience block (docs/RESILIENCE.md): authoritative direct
        # snapshot, present only when the run saw resilience activity
        # (same zero-activity absence rule as the /metrics scrape)
        res = {
            key: es[key]
            for key in ("requests_shed", "watchdog_trips", "engine_faults",
                        "degrade_level", "faults_armed")
        }
        if any(res.values()):
            res["source"] = "engine:snapshot"
            run_dir.merge_into_results({"resilience": res})
        # live-economics block (docs/ECONOMICS.md): same authoritative-
        # direct-snapshot rule; engines without the rail (CPU backends
        # with no econ_accelerator) get no block — absent, never $0
        econ = server.engine.economics_snapshot()
        if econ:
            run_dir.merge_into_results({"economics": econ})
        # disaggregated-serving block (docs/DISAGGREGATION.md): same
        # authoritative-direct-snapshot rule; colocated engines (and
        # disagg runs with zero handoff activity) get no block
        dg = server.engine.disagg_snapshot()
        if dg and any(
            dg[k] for k in ("handoffs", "handoff_drops",
                            "colocated_fallbacks")
        ):
            run_dir.merge_into_results({"disagg": dg})
        from kserve_vllm_mini_tpu.profiling.headroom import headroom_error_pct

        err = headroom_error_pct(
            kv.get("headroom_estimate_bytes"), kv.get("hbm_peak_bytes")
        )
        if err is not None:
            run_dir.merge_into_results({"headroom_error_pct": err})
    results = run_dir.read_results()

    code = 0
    if slo_file:
        from kserve_vllm_mini_tpu.gates.slo import gate_results, load_slo, print_table

        verdicts = gate_results(results, load_slo(slo_file))
        print_table(verdicts)
        code = 0 if all(v.ok for v in verdicts) else 3

    p95 = results.get("p95_ms")
    print(
        f"bench: done p95={p95:.1f}ms " if p95 is not None else "bench: done ",
        end="",
    )
    print(
        f"rps={results.get('throughput_rps', 0):.2f} "
        f"cost/1Ktok=${results.get('cost_per_1k_tokens', 0):.6f} "
        f"-> {run_dir.results_json}"
    )
    return results, code


# -- CLI ---------------------------------------------------------------------

def register(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--url", default=None, help="Existing endpoint base URL")
    parser.add_argument("--self-serve", action="store_true",
                        help="Start the in-repo runtime for the bench")
    parser.add_argument("--profile", default=None, help="Profile YAML")
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--concurrency", type=int, default=None)
    parser.add_argument("--pattern", default=None)
    parser.add_argument("--max-tokens", type=int, default=None)
    parser.add_argument("--model", default=None)
    parser.add_argument("--run-dir", default=None)
    parser.add_argument("--prom-url", default=None)
    parser.add_argument("--namespace", default=None)
    parser.add_argument("--service", default=None)
    parser.add_argument("--cost-file", default=None)
    parser.add_argument("--chips", type=float, default=None)
    parser.add_argument("--slo", default=None, help="SLO budgets JSON; exit 3 on violation")
    parser.add_argument("--idle-tax", choices=["none", "series", "baseline"], default="none")
    parser.add_argument("--no-monitor", action="store_true",
                        help="Disable the 1 Hz live run monitor "
                             "(timeline.jsonl, burn rates, events — "
                             "docs/MONITORING.md)")
    parser.add_argument("--monitor-slo", default=None,
                        help="Budgets JSON for LIVE rolling burn-rates "
                             "(default: none; --slo still gates post-hoc)")
    parser.add_argument("--monitor-abort", action="store_true",
                        help="Let the monitor abort the run on sustained "
                             "budget burn or a decode stall (records "
                             "aborted_early in results.json)")
    parser.add_argument("--cost-budget-usd-per-1k-tok", type=float,
                        default=None,
                        help="Live $/1K-token budget for the "
                             "cost_burn_exceeded / replica_unprofitable "
                             "monitor events (docs/ECONOMICS.md; also "
                             "the profile key "
                             "cost_budget_usd_per_1k_tok)")


def run(args: argparse.Namespace) -> int:
    profile: dict[str, Any] = {}
    if args.profile:
        with open(args.profile) as f:
            profile = yaml.safe_load(f) or {}
    for key in ("requests", "concurrency", "pattern", "max_tokens", "model"):
        v = getattr(args, key)
        if v is not None:
            profile[key] = v
    _, code = run_bench(
        url=args.url,
        profile=profile,
        run_dir=RunDir(args.run_dir) if args.run_dir else None,
        self_serve=args.self_serve,
        prom_url=args.prom_url,
        namespace=args.namespace,
        service=args.service,
        cost_file=args.cost_file,
        chips=args.chips,
        slo_file=args.slo,
        idle_tax=args.idle_tax,
        monitor=not args.no_monitor,
        monitor_slo=args.monitor_slo,
        monitor_abort=args.monitor_abort,
        cost_budget_usd_per_1k_tok=args.cost_budget_usd_per_1k_tok,
    )
    return code

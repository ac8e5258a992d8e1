"""Executive HTML reports: single run, grid sweep, topology matrix.

Reference surface (/root/reference/report_generator.py:398-827): metric
cards, embedded charts, cold/warm section, prewarm break-even, bottleneck
classification, recommendations, a zero-dependency trace viewer deep-linked
at the p95 request, sweep heatmaps, and the topology (née MIG) matrix.
Everything inlines into one .html file.
"""

from __future__ import annotations

import argparse
import csv
import html as html_mod
import json
from pathlib import Path
from typing import Any, Optional

from kserve_vllm_mini_tpu.report import charts
from kserve_vllm_mini_tpu.report.recommendations import (
    classify_bottleneck,
    generate_recommendations,
    prewarm_breakeven,
)

_CSS = """
body{font-family:system-ui,-apple-system,sans-serif;margin:2em auto;max-width:1100px;
     color:#111827;padding:0 1em}
h1{border-bottom:3px solid #2563eb;padding-bottom:.3em}
.cards{display:flex;flex-wrap:wrap;gap:12px;margin:1em 0}
.card{border:1px solid #e5e7eb;border-radius:10px;padding:14px 18px;min-width:150px;
      box-shadow:0 1px 3px rgba(0,0,0,.06)}
.card .v{font-size:1.6em;font-weight:700;color:#2563eb}
.card .l{font-size:.8em;color:#6b7280;text-transform:uppercase;letter-spacing:.05em}
.warn{color:#b45309}.bad{color:#dc2626}.ok{color:#16a34a}
section{margin:2em 0}
ul.recs li{margin:.5em 0}
pre.trace{background:#0b1020;color:#c9d4ff;padding:1em;border-radius:8px;
          overflow-x:auto;font-size:.85em}
table{border-collapse:collapse}td,th{border:1px solid #e5e7eb;padding:6px 10px}
"""


def _card(label: str, value: Any, unit: str = "") -> str:
    if value is None:
        return ""
    if isinstance(value, float):
        value = f"{value:,.2f}" if value >= 10 else f"{value:.4g}"
    return (
        f'<div class="card"><div class="v">{value}{unit}</div>'
        f'<div class="l">{html_mod.escape(label)}</div></div>'
    )


def _trace_viewer(run_dir: Optional[Path], results: dict[str, Any]) -> str:
    """Find the request closest to p95 and render its span tree
    (reference report_generator.py:423-491)."""
    if run_dir is None:
        return ""
    traces_path = run_dir / "traces" / "traces.json"
    requests_path = run_dir / "requests.csv"
    if not traces_path.exists() or not requests_path.exists():
        return ""
    p95 = results.get("p95_ms")
    if p95 is None:
        return ""
    best: Optional[dict] = None
    with requests_path.open(newline="") as f:
        for row in csv.DictReader(f):
            try:
                lat = float(row.get("latency_ms") or 0)
            except ValueError:
                continue
            if row.get("ok") != "1" or lat <= 0:
                continue
            if best is None or abs(lat - p95) < abs(float(best["latency_ms"]) - p95):
                best = row
    if not best:
        return ""
    trace_id = best.get("trace_id", "")
    doc = json.loads(traces_path.read_text())
    from kserve_vllm_mini_tpu.runtime.tracing import spans_from_otlp

    # up to three lanes: the loadgen's client spans; the router's
    # fleet.route/fleet.proxy spans when the run went through the fleet
    # router; and the server's phase spans, clock-corrected onto the
    # client timeline — per replica when the fleet merge estimated one
    # offset per lane (docs/TRACING.md "Fleet tracing"), by the single
    # merge estimate otherwise
    offset_ns = int(doc.get("clockOffsetNanosEstimate", 0) or 0)
    router_offset_ns = int(doc.get("clockOffsetNanosRouter", 0) or 0)
    replica_offsets = {
        str(k): int(v)
        for k, v in (doc.get("clockOffsetsNanosByReplica") or {}).items()
    }

    def _replica(s: dict) -> str:
        for a in s.get("attributes") or []:
            if a.get("key") == "replica":
                return str((a.get("value") or {}).get("stringValue", ""))
        return ""

    def _srv_shift(s: dict) -> int:
        return replica_offsets.get(_replica(s), offset_ns)

    client_spans, fleet_spans, server_spans = [], [], []
    for svc, s in spans_from_otlp(doc):
        if s.get("traceId") != trace_id:
            continue
        if str(s.get("name", "")).startswith("fleet."):
            fleet_spans.append(s)
        elif s.get("kind") == 2:
            server_spans.append(s)
        else:
            client_spans.append(s)
    if not client_spans and not fleet_spans and not server_spans:
        return ""

    def _ns(s: dict, key: str, shift: int = 0) -> int:
        return int(s.get(key, 0)) - shift

    all_starts = (
        [_ns(s, "startTimeUnixNano") for s in client_spans]
        + [_ns(s, "startTimeUnixNano", router_offset_ns) for s in fleet_spans]
        + [_ns(s, "startTimeUnixNano", _srv_shift(s)) for s in server_spans]
    )
    t0 = min(all_starts)
    lines = [f"trace {trace_id}  (request {best['request_id']}, "
             f"{float(best['latency_ms']):.1f} ms ~ p95)"]

    def _render(pairs: list[tuple[dict, int]], lane: str) -> None:
        for s, shift in sorted(pairs, key=lambda p: int(p[0]["startTimeUnixNano"])):
            start_ms = (_ns(s, "startTimeUnixNano", shift) - t0) / 1e6
            dur_ms = (int(s["endTimeUnixNano"]) - int(s["startTimeUnixNano"])) / 1e6
            indent = "  " if s.get("parentSpanId") else ""
            rid = _replica(s)
            name = s["name"] + (f" @{rid}" if rid else "")
            bar = "#" * max(
                int(dur_ms / max(float(best["latency_ms"]), 1e-9) * 40), 1
            )
            lines.append(f"{lane}{indent}{name:<24} +{start_ms:8.1f}ms "
                         f"{dur_ms:8.1f}ms  {bar}")

    _render([(s, 0) for s in client_spans], "")
    if fleet_spans:
        lines.append("")
        lines.append(
            f"fleet lane (router clock offset est {router_offset_ns / 1e6:+.2f} ms)"
        )
        _render([(s, router_offset_ns) for s in fleet_spans], "  ")
    if server_spans:
        lines.append("")
        if replica_offsets:
            offs = ", ".join(
                f"{rid} {off / 1e6:+.2f} ms"
                for rid, off in sorted(replica_offsets.items())
            )
            lines.append(f"server lane (per-replica clock offsets: {offs})")
        else:
            lines.append(
                f"server lane (clock offset est {offset_ns / 1e6:+.2f} ms)"
            )
        _render([(s, _srv_shift(s)) for s in server_spans], "  ")
    return (
        "<section><h2>p95 request trace</h2>"
        f"<pre class='trace'>{html_mod.escape(chr(10).join(lines))}</pre></section>"
    )


def _duty_pct(results: dict[str, Any]) -> Optional[float]:
    """Windowed average when a real window backed it; the instantaneous
    snapshot otherwise (tpu_metrics_source says which — see
    docs/MONITORING.md on the *_avg honesty rule)."""
    duty = results.get("tpu_duty_cycle_avg")
    if duty is None:
        duty = results.get("tpu_duty_cycle")
    return duty * 100 if duty is not None else None


def _timeline_section(
    run_dir: Optional[Path], results: dict[str, Any],
    samples: Optional[list[dict[str, Any]]] = None,
) -> str:
    """Monitor timeline lane (docs/MONITORING.md): throughput / duty /
    queue over the run with event markers, plus the burn-rate and abort
    summary from the results `monitor` block. Renders beside the trace
    viewer — the trace explains one request, this explains the run."""
    if run_dir is None:
        return ""
    if samples is None:
        from kserve_vllm_mini_tpu.core.rundir import RunDir

        samples = RunDir(run_dir).read_timeline()
    mon = results.get("monitor") or {}
    events = mon.get("events") or []
    chart = charts.run_timeline_chart(samples, events)
    if not chart and not mon:
        return ""
    parts = ["<section><h2>Run timeline</h2>"]
    facts = []
    if mon:
        facts.append(f"{mon.get('samples', 0)} samples "
                     f"@ {mon.get('interval_s', '?')}s")
        if mon.get("skipped_samples"):
            facts.append(f"{mon['skipped_samples']} skipped")
        for key, label in (("burn_rates", "burn"),
                           ("burn_rates_peak", "peak burn")):
            rates = mon.get(key) or {}
            if rates:
                facts.append(label + " " + ", ".join(
                    f"{k}={v:.2f}" for k, v in sorted(rates.items())
                ))
    if facts:
        parts.append(f"<p>{html_mod.escape(' · '.join(facts))}</p>")
    aborted = results.get("aborted_early") or mon.get("aborted")
    if aborted:
        parts.append(
            f"<p class='bad'>aborted early: {html_mod.escape(str(aborted))}</p>"
        )
    for e in events:
        parts.append(
            f"<p class='warn'>event @ {e.get('t', 0):.0f}: "
            f"{html_mod.escape(str(e.get('detail', e.get('type', '?'))))}</p>"
        )
    if chart:
        parts.append(chart)
    parts.append("</section>")
    return "".join(parts)


def _compile_stats_section(results: dict[str, Any]) -> str:
    """Compile-stats block (docs/PROFILING.md) when the run carried one:
    totals plus the per-executable table from the self-serve snapshot."""
    cs = results.get("compile_stats")
    if not isinstance(cs, dict) or not cs.get("compiles"):
        return ""
    parts = ["<section><h2>Compile stats</h2>"]
    facts = [f"{cs['compiles']} executables"]
    if cs.get("compile_wall_s") is not None:
        facts.append(f"{cs['compile_wall_s']:.2f}s compile wall")
    if cs.get("flops"):
        facts.append(f"{cs['flops']:.3g} cost-model FLOPs/step total")
    if cs.get("peak_bytes"):
        facts.append(f"{cs['peak_bytes'] / 1e9:.2f} GB peak buffer estimate")
    parts.append(f"<p>{html_mod.escape(' · '.join(facts))}</p>")
    exes = cs.get("executables") or []
    if exes:
        rows = "".join(
            "<tr>"
            f"<td>{html_mod.escape(str(e.get('label', '?')))}</td>"
            f"<td>{e.get('compile_wall_s', '—')}</td>"
            f"<td>{e.get('flops', '—')}</td>"
            f"<td>{e.get('bytes_accessed', '—')}</td>"
            f"<td>{e.get('peak_bytes', '—')}</td></tr>"
            for e in exes
        )
        parts.append(
            "<table><tr><th>executable</th><th>compile s</th><th>FLOPs</th>"
            "<th>bytes accessed</th><th>peak bytes</th></tr>"
            + rows + "</table>"
        )
    parts.append("</section>")
    return "".join(parts)


def _kv_cache_section(
    results: dict[str, Any], run_dir: Optional[Path] = None,
    samples: Optional[list[dict[str, Any]]] = None,
) -> str:
    """The "KV cache & memory" section (docs/TROUBLESHOOTING.md "HBM
    pressure & KV thrash"): prefix-cache attribution facts, paged-pool
    occupancy, HBM watermarks, the headroom-model verdict, and the
    occupancy/watermark/churn timeline lanes with kv_thrash /
    hbm_watermark_high markers. Rendered only when the run carried the
    observability rail (kv_cache block or KV timeline series) — an
    external engine's report simply has no section."""
    kv = results.get("kv_cache")
    kv = kv if isinstance(kv, dict) else {}
    chart = ""
    if run_dir is not None:
        if samples is None:
            from kserve_vllm_mini_tpu.core.rundir import RunDir

            samples = RunDir(run_dir).read_timeline()
        events = (results.get("monitor") or {}).get("events") or []
        chart = charts.kv_timeline_chart(samples, events)
    if not kv and not chart:
        return ""
    parts = ["<section><h2>KV cache & memory</h2>"]
    facts = []
    if kv.get("prefix_lookups"):
        hits = kv.get("prefix_hits", 0)
        facts.append(
            f"prefix hits {hits:.0f}/{kv['prefix_lookups']:.0f} lookups"
        )
    if kv.get("hit_depth_p95"):
        facts.append(
            f"hit depth p50/p95 {kv.get('hit_depth_p50', 0):.0f}/"
            f"{kv['hit_depth_p95']:.0f} tok"
        )
    if kv.get("reused_bytes"):
        facts.append(f"{kv['reused_bytes'] / 1e6:.1f} MB KV reused")
    if kv.get("blocks_allocated") is not None:
        facts.append(
            f"{kv['blocks_allocated']:.0f} blocks allocated · "
            f"{kv.get('retained_evictions', 0):.0f} retained evictions · "
            f"{kv.get('share_reclaims', 0):.0f} share reclaims"
        )
    if kv.get("occupancy") is not None:
        facts.append(
            f"pool occupancy {kv['occupancy']:.0%}"
            + (f" · fragmentation {kv['fragmentation']:.0%}"
               if kv.get("fragmentation") is not None else "")
            + (f" · retained {kv['retained_fraction']:.0%}"
               if kv.get("retained_fraction") is not None else "")
        )
    if kv.get("tier_demotions") or kv.get("tier_promotions"):
        tier = (
            f"host tier {kv.get('tier_demotions', 0):.0f} demotions · "
            f"{kv.get('tier_promotions', 0):.0f} promotions · "
            f"{kv.get('tier_hits', 0):.0f} hits"
        )
        if kv.get("tier_bytes"):
            tier += f" · {kv['tier_bytes'] / 1e6:.1f} MB resident"
        if kv.get("tier_disabled"):
            tier += " · DISABLED (thrash guard)"
        facts.append(tier)
    if kv.get("migrated_blocks"):
        facts.append(
            f"{kv['migrated_blocks']:.0f} blocks migrated in from "
            f"siblings ({kv.get('migrated_bytes', 0) / 1e6:.1f} MB)"
        )
    if kv.get("hbm_peak_bytes"):
        hbm = f"HBM peak {kv['hbm_peak_bytes'] / 1e9:.2f} GB"
        if kv.get("hbm_bytes_limit"):
            hbm += (f" of {kv['hbm_bytes_limit'] / 1e9:.2f} GB "
                    f"({kv['hbm_peak_bytes'] / kv['hbm_bytes_limit']:.0%})")
        facts.append(hbm)
    if facts:
        parts.append(f"<p>{html_mod.escape(' · '.join(facts))}</p>")
    err = results.get("headroom_error_pct")
    if err is not None:
        # negative = the analytic model UNDERESTIMATED the observed peak —
        # the direction that RESOURCE_EXHAUSTs a run the guard admitted
        cls = "bad" if err < 0 else ("warn" if err > 50 else "ok")
        verdict = ("UNDERESTIMATES the observed peak (OOM risk)" if err < 0
                   else "overestimates the observed peak")
        parts.append(
            f"<p class='{cls}'>headroom model {verdict}: "
            f"{err:+.1f}% vs observed HBM peak</p>"
        )
    if chart:
        parts.append(chart)
    parts.append("</section>")
    return "".join(parts)


def _resilience_section(results: dict[str, Any]) -> str:
    """The "Resilience" section (docs/RESILIENCE.md): shed/retry
    accounting from the per-request CSV, the runtime's watchdog/degrade
    rail, and the overload_shedding / engine_fault monitor events.
    Rendered only when the run saw resilience activity — a clean run's
    report simply has no section."""
    res = results.get("resilience")
    res = res if isinstance(res, dict) else {}
    shed = results.get("shed_requests") or 0
    retries = results.get("retries_total") or 0
    events = [
        e for e in ((results.get("monitor") or {}).get("events") or [])
        if isinstance(e, dict)
        and e.get("type") in ("overload_shedding", "engine_fault")
    ]
    if not res and not shed and not retries and not events:
        return ""
    parts = ["<section><h2>Resilience</h2>"]
    facts = []
    if shed:
        rate = results.get("shed_rate")
        facts.append(
            f"{shed} request(s) shed"
            + (f" ({rate:.1%} of the run)" if rate is not None else "")
            + " — counted separately from errors"
        )
    if retries:
        facts.append(f"{retries} 429 resend(s) absorbed by client backoff")
    if res.get("requests_shed"):
        facts.append(f"server shed {res['requests_shed']:.0f} at admission")
    if res.get("watchdog_trips"):
        facts.append(f"{res['watchdog_trips']:.0f} watchdog trip(s)")
    if res.get("engine_faults"):
        facts.append(f"{res['engine_faults']:.0f} engine fault(s) recovered")
    if res.get("faults_armed"):
        facts.append(
            f"{res['faults_armed']:.0f} injection point(s) armed (chaos run)"
        )
    if facts:
        parts.append(f"<p>{html_mod.escape(' · '.join(facts))}</p>")
    level = res.get("degrade_level")
    if level:
        ladder = {1: "sync pipeline", 2: "decode chunk 1", 3: "spec off",
                  4: "gave up"}
        parts.append(
            f"<p class='warn'>engine finished DEGRADED at level "
            f"{level:.0f} ({ladder.get(int(level), '?')}) — each watchdog "
            "trip/device fault gives up one optimization</p>"
        )
    for e in events:
        parts.append(
            f"<p>event @{e.get('t', 0):.0f}: "
            f"<b>{html_mod.escape(str(e.get('type')))}</b> — "
            f"{html_mod.escape(str(e.get('detail', '')))}</p>"
        )
    parts.append("</section>")
    return "".join(parts)


def _disagg_section(results: dict[str, Any]) -> str:
    """The "Disaggregated serving" section (docs/DISAGGREGATION.md):
    prefill-lane handoff volume, wait/busy accounting, drops and the
    degrade ladder, plus the handoff_stall monitor event. Rendered only
    for runs that actually handed off — a colocated run's report simply
    has no section."""
    dg = results.get("disagg")
    if not isinstance(dg, dict):
        return ""
    parts = ["<section><h2>Disaggregated serving</h2>"]
    facts = []
    handoffs = dg.get("handoffs") or 0
    if handoffs:
        facts.append(
            f"{handoffs:.0f} prefill(s) handed off "
            f"({dg.get('handoff_blocks', 0):.0f} KV blocks)"
        )
        wait = dg.get("handoff_wait_s")
        if wait is not None and handoffs:
            facts.append(
                f"mean handoff wait {wait / handoffs * 1000.0:.1f} ms"
            )
        copied = dg.get("handoff_bytes_copied")
        if copied:
            facts.append(f"{copied / 1e6:.1f} MB KV copied (dense v1 stripe)")
        elif copied == 0:
            facts.append("0 B KV copied (paged zero-copy handoff)")
    busy = dg.get("lane_busy_s")
    if busy:
        facts.append(f"prefill lane busy {busy:.2f} s")
    if dg.get("handoff_drops"):
        facts.append(f"{dg['handoff_drops']:.0f} handoff(s) dropped")
    if dg.get("colocated_fallbacks"):
        facts.append(
            f"{dg['colocated_fallbacks']:.0f} prefill(s) degraded to "
            "colocated"
        )
    if facts:
        parts.append(f"<p>{html_mod.escape(' · '.join(facts))}</p>")
    if dg.get("degraded"):
        parts.append(
            "<p class='warn'>engine finished with the prefill lane "
            "DEGRADED to colocated routing — repeated handoff drops or a "
            "dead lane (docs/DISAGGREGATION.md degrade ladder)</p>"
        )
    for e in ((results.get("monitor") or {}).get("events") or []):
        if isinstance(e, dict) and e.get("type") == "handoff_stall":
            parts.append(
                f"<p>event @{e.get('t', 0):.0f}: <b>handoff_stall</b> — "
                f"{html_mod.escape(str(e.get('detail', '')))}</p>"
            )
    parts.append("</section>")
    return "".join(parts)


def _economics_section(
    results: dict[str, Any], run_dir: Optional[Path] = None,
    samples: Optional[list[dict[str, Any]]] = None,
) -> str:
    """The "Economics" section (docs/ECONOMICS.md): the live rail's
    rolling $/1K-tok, Wh/1K-tok and hourly burn from the results
    ``economics`` block, the cost/energy timeline lanes, and the
    cost_burn_exceeded / replica_unprofitable monitor events. Rendered
    only when the run priced itself — an unpriced engine's report simply
    has no section; the post-hoc cost estimate keeps its own card."""
    econ = results.get("economics")
    econ = econ if isinstance(econ, dict) else {}
    chart = ""
    if samples is not None:
        events = (results.get("monitor") or {}).get("events") or []
        chart = charts.econ_timeline_chart(samples, events)
    if not econ and not chart:
        return ""
    parts = ["<section><h2>Economics</h2>"]
    facts = []
    if econ.get("usd_per_1k_tokens") is not None:
        facts.append(f"live ${econ['usd_per_1k_tokens']:.4f}/1K tok")
    if econ.get("wh_per_1k_tokens") is not None:
        facts.append(f"{econ['wh_per_1k_tokens']:.3f} Wh/1K tok")
    if econ.get("usd_per_hour") is not None:
        facts.append(f"${econ['usd_per_hour']:.2f}/h burn")
    if econ.get("tokens_per_sec") is not None:
        facts.append(f"{econ['tokens_per_sec']:.1f} tok/s priced")
    if econ.get("marginal_replica_usd_per_1k_tokens") is not None:
        facts.append(
            "marginal replica "
            f"${econ['marginal_replica_usd_per_1k_tokens']:.4f}/1K tok"
        )
    posthoc = results.get("cost_per_1k_tokens")
    if posthoc is not None and econ.get("usd_per_1k_tokens"):
        facts.append(f"post-hoc estimate ${posthoc:.4f}/1K tok")
    if facts:
        parts.append(f"<p>{html_mod.escape(' · '.join(facts))}</p>")
    if econ.get("source"):
        parts.append(
            f"<p class='l'>source: {html_mod.escape(str(econ['source']))}</p>"
        )
    for e in ((results.get("monitor") or {}).get("events") or []):
        if isinstance(e, dict) and e.get("type") in (
            "cost_burn_exceeded", "replica_unprofitable"
        ):
            parts.append(
                f"<p class='warn'>event @{e.get('t', 0):.0f}: "
                f"<b>{html_mod.escape(str(e.get('type')))}</b> — "
                f"{html_mod.escape(str(e.get('detail', '')))}</p>"
            )
    if chart:
        parts.append(chart)
    parts.append("</section>")
    return "".join(parts)


def _fleet_section(results: dict[str, Any]) -> str:
    """The "Serving fleet" section (docs/FLEET.md): replica counts,
    placement mix, re-placements the clients never saw, fleet-level
    sheds, self-healing restarts and scale-step cold starts. Rendered
    only for runs that went through the fleet router — a single-server
    run's report simply has no section."""
    fl = results.get("fleet")
    if not isinstance(fl, dict):
        return ""
    parts = ["<section><h2>Serving fleet</h2>"]
    facts = [
        f"{fl.get('replicas_live', 0):.0f}/{fl.get('replicas_desired', 0):.0f}"
        " replicas live"
    ]
    if fl.get("placements"):
        facts.append(f"{fl['placements']:.0f} placement(s)")
    if fl.get("reroutes"):
        facts.append(
            f"{fl['reroutes']:.0f} re-placement(s) absorbed before any "
            "client saw them"
        )
    if fl.get("sheds"):
        facts.append(f"{fl['sheds']:.0f} fleet-level shed(s)")
    if fl.get("stream_errors"):
        facts.append(
            f"{fl['stream_errors']:.0f} mid-stream replica loss(es) "
            "surfaced as honest terminal events"
        )
    if fl.get("replica_restarts"):
        facts.append(
            f"{fl['replica_restarts']:.0f} replica(s) self-healed"
        )
    scale_steps = (fl.get("scale_ups") or 0) + (fl.get("scale_downs") or 0)
    if scale_steps:
        facts.append(
            f"{fl.get('scale_ups', 0):.0f} scale-up(s) / "
            f"{fl.get('scale_downs', 0):.0f} scale-down(s)"
        )
    if fl.get("last_cold_start_s"):
        facts.append(
            f"last scale-up cold start {fl['last_cold_start_s']:.2f} s"
        )
    parts.append(f"<p>{html_mod.escape(' · '.join(facts))}</p>")
    outlier = results.get("routing_outlier")
    if isinstance(outlier, dict) and outlier.get("decisions"):
        # the analyzer joined the p99-latency request back to its router
        # decision(s) (docs/TRACING.md "Fleet tracing"): where it landed
        # and why — two placement rows mean the request was re-placed
        where = "; ".join(
            f"{d.get('chosen', '?')} ({d.get('reason', '?')}, "
            f"{len(d.get('candidates') or [])} candidate(s))"
            for d in outlier["decisions"]
        )
        parts.append(
            f"<p class='warn'>p99 outlier trace "
            f"{html_mod.escape(str(outlier.get('trace_id', '?')))} "
            f"({outlier.get('latency_ms', 0):.1f} ms) placed on: "
            f"{html_mod.escape(where)}</p>"
        )
    for e in ((results.get("monitor") or {}).get("events") or []):
        if isinstance(e, dict) and e.get("type") == "replica_down":
            parts.append(
                f"<p>event @{e.get('t', 0):.0f}: <b>replica_down</b> — "
                f"{html_mod.escape(str(e.get('detail', '')))}</p>"
            )
    parts.append("</section>")
    return "".join(parts)


def generate_single_run_html(
    results: dict[str, Any], run_dir: Optional[Path] = None
) -> str:
    label, why = classify_bottleneck(results)
    recs = generate_recommendations(results)
    breakeven = prewarm_breakeven(results)

    cards = "".join(
        [
            _card("p95 latency", results.get("p95_ms"), " ms"),
            _card("TTFT p50", results.get("ttft_p50_ms"), " ms"),
            _card("throughput", results.get("throughput_rps"), " rps"),
            _card("tokens/sec", results.get("tokens_per_sec")),
            _card("error rate", (results.get("error_rate") or 0) * 100, "%"),
            _card("$/1K tokens", results.get("cost_per_1k_tokens")),
            _card("Wh/1K tokens", results.get("energy_wh_per_1k_tokens")),
            _card("TPU duty", _duty_pct(results), "%"),
            _card("cold multiplier", results.get("cold_multiplier"), "x"),
            _card("quality", results.get("quality_score")),
        ]
    )

    sections = [
        f"<h1>Benchmark report — {html_mod.escape(str(results.get('model', 'run')))}</h1>",
        f"<p>{html_mod.escape(str(results.get('runtime', '')))} · "
        f"{html_mod.escape(str(results.get('accelerator', '') or ''))} · "
        f"pattern {html_mod.escape(str(results.get('pattern', '?')))} · "
        f"{results.get('requests', '?')} requests</p>",
        f'<div class="cards">{cards}</div>',
        f"<section><h2>Bottleneck: {label}</h2><p>{html_mod.escape(why)}</p></section>",
        "<section><h2>Latency</h2>",
        charts.latency_histogram_chart(results),
        charts.ttft_vs_latency_chart(results),
        "</section>",
    ]
    pm = results.get("per_model")
    if pm:
        # multi-LoRA runs: one row per adapter/model so a slow fine-tune
        # can't hide behind a fast base in the aggregates
        def _cell(m: dict, key: str) -> str:
            # an all-error adapter has NO latency keys (metrics.py omits
            # them on purpose) — absence must render as "—", never 0.0 ms,
            # or the broken adapter looks like the fastest row
            return f"{m[key]:.1f}" if key in m else "—"

        rows = "".join(
            f"<tr><td>{html_mod.escape(name)}</td>"
            f"<td>{m.get('requests', 0)}</td>"
            f"<td>{_cell(m, 'p50_ms')}</td>"
            f"<td>{_cell(m, 'p95_ms')}</td>"
            f"<td>{_cell(m, 'ttft_p95_ms')}</td>"
            f"<td>{_cell(m, 'tokens_per_sec')}</td>"
            f"<td>{100 * m.get('error_rate', 0):.1f}%</td></tr>"
            for name, m in pm.items()
        )
        sections.append(
            "<section><h2>Per model / adapter</h2><table>"
            "<tr><th>model</th><th>requests</th><th>p50 ms</th>"
            "<th>p95 ms</th><th>TTFT p95 ms</th><th>tok/s</th>"
            "<th>errors</th></tr>" + rows + "</table></section>"
        )

    if run_dir is not None:
        # convention: the autoscale controller's --decision-log written
        # into the run dir as autoscale_decisions.jsonl
        dec_path = run_dir / "autoscale_decisions.jsonl"
        if dec_path.exists():
            decisions = []
            for line in dec_path.read_text().splitlines():
                try:
                    decisions.append(json.loads(line))
                except ValueError:
                    continue  # a kill mid-append truncates the last line —
                              # degrade, don't abort the whole report
            chart = charts.autoscale_timeline_chart(decisions)
            if chart:
                sections.append(
                    f"<section><h2>Autoscale decisions</h2>{chart}</section>"
                )
        # the policy simulator's replay (kvmini-tpu autoscale-sim
        # --run-dir ...) writes the same decision shape plus a summary —
        # render it beside the live timeline so recorded traffic and its
        # simulated what-if share one report
        sim_path = run_dir / "autoscale_sim.json"
        if sim_path.exists():
            try:
                sim = json.loads(sim_path.read_text())
            except ValueError:
                sim = None
            if isinstance(sim, dict) and sim.get("decisions"):
                chart = charts.autoscale_timeline_chart(sim["decisions"])
                summ = sim.get("summary", {})
                facts = " · ".join(
                    f"{k.replace('_', ' ')}: {v}"
                    for k, v in summ.items()
                    if k in ("peak_replicas", "replica_seconds",
                             "wait_p95_s", "peak_queue", "unserved_at_end")
                )
                if chart:
                    sections.append(
                        "<section><h2>Autoscale policy simulation</h2>"
                        f"<p>{html_mod.escape(facts)}</p>{chart}</section>"
                    )

    cw = charts.cold_warm_chart(results)
    if cw:
        sections.append(f"<section><h2>Cold vs warm</h2>{cw}")
        if breakeven:
            sections.append(f"<p>{html_mod.escape(breakeven['explanation'])}</p>")
        sections.append("</section>")
    cb = charts.cost_breakdown_chart(results)
    if cb:
        sections.append(f"<section><h2>Cost</h2>{cb}</section>")
    sections.append(
        "<section><h2>Recommendations</h2><ul class='recs'>"
        + "".join(f"<li>{html_mod.escape(r)}</li>" for r in recs)
        + "</ul></section>"
    )
    sections.append(_compile_stats_section(results))
    # one timeline.jsonl parse shared by the KV/memory and run-timeline
    # sections (a long run's 1 Hz timeline is multi-MB)
    timeline_samples: Optional[list[dict[str, Any]]] = None
    if run_dir is not None:
        from kserve_vllm_mini_tpu.core.rundir import RunDir

        timeline_samples = RunDir(run_dir).read_timeline()
    sections.append(_kv_cache_section(results, run_dir, timeline_samples))
    sections.append(_economics_section(results, run_dir, timeline_samples))
    sections.append(_disagg_section(results))
    sections.append(_fleet_section(results))
    sections.append(_resilience_section(results))
    sections.append(_timeline_section(run_dir, results, timeline_samples))
    sections.append(_trace_viewer(run_dir, results))
    sections.append(
        "<section><h2>Raw results</h2><details><summary>results.json</summary>"
        f"<pre>{html_mod.escape(json.dumps(results, indent=2, sort_keys=True))}</pre>"
        "</details></section>"
    )
    return (
        f"<html><head><meta charset='utf-8'><title>kvmini-tpu report</title>"
        f"<style>{_CSS}</style></head><body>{''.join(sections)}</body></html>"
    )


def _read_sweep_csv(path: Path) -> list[dict[str, str]]:
    with path.open(newline="") as f:
        return list(csv.DictReader(f))


def generate_grid_sweep_html(csv_path: Path, metric: str = "p95_ms") -> str:
    """Heatmaps over concurrency x max_tokens per pattern
    (reference report_generator.py:597-771)."""
    rows = _read_sweep_csv(csv_path)
    patterns = sorted({r.get("pattern", "?") for r in rows})
    sections = [f"<h1>Grid sweep — {html_mod.escape(metric)}</h1>"]
    for pat in patterns:
        sub = [r for r in rows if r.get("pattern") == pat]
        concs = sorted({int(r["concurrency"]) for r in sub if r.get("concurrency")})
        toks = sorted({int(r["max_tokens"]) for r in sub if r.get("max_tokens")})
        grid: list[list[Optional[float]]] = []
        for c in concs:
            row_vals: list[Optional[float]] = []
            for t in toks:
                match = [
                    r for r in sub
                    if int(r.get("concurrency", -1)) == c and int(r.get("max_tokens", -1)) == t
                ]
                try:
                    row_vals.append(float(match[0][metric]) if match else None)
                except (KeyError, ValueError):
                    row_vals.append(None)
            grid.append(row_vals)
        sections.append(f"<section><h2>pattern: {html_mod.escape(pat)}</h2>")
        sections.append(
            charts.heatmap_chart(
                [f"conc {c}" for c in concs],
                [f"{t} tok" for t in toks],
                grid,
                f"{metric} ({pat})",
                fmt="{:.0f}",
            )
        )
        sections.append("</section>")
    # cost-vs-latency Pareto over the whole sweep (docs/ECONOMICS.md):
    # cells that carried neither a live nor a post-hoc price drop out of
    # the scatter; with fewer than two priced cells there is no frontier
    pareto = charts.cost_pareto_chart(rows)
    if pareto:
        sections.append(
            "<section><h2>Cost vs TTFT p95 (Pareto)</h2>"
            "<p>Cells northeast of the frontier pay for latency they "
            f"aren't getting.</p>{pareto}</section>"
        )
    return (
        f"<html><head><meta charset='utf-8'><style>{_CSS}</style></head>"
        f"<body>{''.join(sections)}</body></html>"
    )


def generate_topology_matrix_html(csv_path: Path) -> str:
    """Topology-slice matrix (v5e-1/-4/-8 ...), the MIG-matrix analog
    (reference report_generator.py:774-827)."""
    rows = _read_sweep_csv(csv_path)
    header = (
        "<tr><th>topology</th><th>chips</th><th>p95 ms</th><th>TTFT p50 ms</th>"
        "<th>tokens/s</th><th>tokens/s/chip</th><th>$/1K tok</th><th>verdict</th></tr>"
    )
    body = []
    best_eff: Optional[float] = None
    for r in rows:
        try:
            eff = float(r.get("tokens_per_sec_per_chip") or 0)
        except ValueError:
            eff = 0.0
        best_eff = max(best_eff or 0.0, eff)
    for r in rows:
        try:
            eff = float(r.get("tokens_per_sec_per_chip") or 0)
        except ValueError:
            eff = 0.0
        verdict = "most efficient" if best_eff and eff == best_eff else ""
        body.append(
            "<tr>"
            f"<td>{html_mod.escape(r.get('topology', '?'))}</td>"
            f"<td>{html_mod.escape(r.get('chips', '?'))}</td>"
            f"<td>{html_mod.escape(r.get('p95_ms', ''))}</td>"
            f"<td>{html_mod.escape(r.get('ttft_p50_ms', ''))}</td>"
            f"<td>{html_mod.escape(r.get('tokens_per_sec', ''))}</td>"
            f"<td>{eff:.1f}</td>"
            f"<td>{html_mod.escape(r.get('cost_per_1k_tokens', ''))}</td>"
            f"<td class='ok'>{verdict}</td></tr>"
        )
    return (
        f"<html><head><meta charset='utf-8'><style>{_CSS}</style></head><body>"
        "<h1>Topology matrix</h1><table>"
        + header + "".join(body) + "</table></body></html>"
    )


# -- CLI ---------------------------------------------------------------------

def register(parser: argparse.ArgumentParser) -> None:
    src = parser.add_mutually_exclusive_group(required=True)
    src.add_argument("--input", help="results.json or run dir")
    src.add_argument("--grid-sweep", help="Grid sweep CSV")
    src.add_argument("--topology-matrix", help="Topology matrix CSV")
    parser.add_argument("--metric", default="p95_ms", help="Sweep heatmap metric")
    parser.add_argument("--output", required=True, help="Output .html path")


def run(args: argparse.Namespace) -> int:
    if args.input:
        p = Path(args.input)
        run_dir = p if p.is_dir() else p.parent
        results_path = p / "results.json" if p.is_dir() else p
        with results_path.open() as f:
            results = json.load(f)
        html = generate_single_run_html(results, run_dir=run_dir)
    elif args.grid_sweep:
        html = generate_grid_sweep_html(Path(args.grid_sweep), metric=args.metric)
    else:
        html = generate_topology_matrix_html(Path(args.topology_matrix))
    Path(args.output).write_text(html)
    print(f"report: wrote {args.output} ({len(html)} bytes)")
    return 0

"""matplotlib chart helpers -> base64 <img> tags (self-contained HTML).

Reference pattern (/root/reference/report_generator.py:66-312): every chart
renders to a base64 PNG embedded inline so reports are single-file
artifacts. Degrades to a styled placeholder when matplotlib is absent.
"""

from __future__ import annotations

import base64
import io
from typing import Any, Optional

try:
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    HAVE_MPL = True
except ImportError:  # pragma: no cover
    HAVE_MPL = False

_PALETTE = {"primary": "#2563eb", "warm": "#f59e0b", "cold": "#60a5fa",
            "ok": "#16a34a", "bad": "#dc2626", "grid": "#e5e7eb"}


def _to_img(fig) -> str:
    buf = io.BytesIO()
    fig.savefig(buf, format="png", dpi=110, bbox_inches="tight")
    plt.close(fig)
    b64 = base64.b64encode(buf.getvalue()).decode()
    return f'<img src="data:image/png;base64,{b64}" style="max-width:100%"/>'


def _placeholder(title: str) -> str:
    return (
        f'<div style="border:1px dashed #aaa;padding:2em;text-align:center;'
        f'color:#888">chart unavailable (matplotlib not installed): {title}</div>'
    )


def latency_histogram_chart(results: dict[str, Any]) -> str:
    hist = results.get("latency_histogram") or {}
    if not HAVE_MPL or not hist.get("buckets"):
        return _placeholder("latency distribution")
    fig, ax = plt.subplots(figsize=(7, 3))
    buckets, counts = hist["buckets"], hist["counts"]
    width = (buckets[1] - buckets[0]) if len(buckets) > 1 else 1.0
    ax.bar(buckets, counts, width=width * 0.9, color=_PALETTE["primary"], alpha=0.85)
    for pct, key in ((50, "p50_ms"), (95, "p95_ms"), (99, "p99_ms")):
        v = results.get(key)
        if v is not None:
            ax.axvline(v, color=_PALETTE["bad"] if pct >= 95 else _PALETTE["ok"],
                       linestyle="--", linewidth=1)
            ax.text(v, max(counts) * 0.92, f"p{pct}", fontsize=8, rotation=90)
    ax.set_xlabel("latency (ms)")
    ax.set_ylabel("requests")
    ax.set_title("Latency distribution")
    ax.grid(color=_PALETTE["grid"], axis="y")
    return _to_img(fig)


def ttft_vs_latency_chart(results: dict[str, Any]) -> str:
    if not HAVE_MPL:
        return _placeholder("ttft vs latency")
    pairs = [
        ("TTFT p50", results.get("ttft_p50_ms")),
        ("TTFT p95", results.get("ttft_p95_ms")),
        ("latency p50", results.get("p50_ms")),
        ("latency p95", results.get("p95_ms")),
        ("latency p99", results.get("p99_ms")),
    ]
    pairs = [(k, v) for k, v in pairs if v is not None]
    if not pairs:
        return _placeholder("ttft vs latency")
    fig, ax = plt.subplots(figsize=(7, 3))
    names = [k for k, _ in pairs]
    vals = [v for _, v in pairs]
    colors = [_PALETTE["cold"] if "TTFT" in n else _PALETTE["primary"] for n in names]
    ax.barh(names, vals, color=colors)
    for i, v in enumerate(vals):
        ax.text(v, i, f" {v:.0f} ms", va="center", fontsize=9)
    ax.set_title("Latency percentiles")
    ax.grid(color=_PALETTE["grid"], axis="x")
    return _to_img(fig)


def autoscale_timeline_chart(decisions: list[dict[str, Any]]) -> str:
    """Replica count + duty/queue signals over the controller's decision
    log (autoscale/controller.py JSONL rows)."""
    rows = [d for d in decisions if "applied" in d and "ts" in d]
    if len(rows) < 2:
        return ""  # not enough decisions to plot — caller skips the section
    if not HAVE_MPL:
        return _placeholder("autoscale timeline")
    t0 = rows[0]["ts"]
    ts = [d["ts"] - t0 for d in rows]
    fig, ax = plt.subplots(figsize=(7, 3))
    ax.step(ts, [d["applied"] for d in rows], where="post",
            color=_PALETTE["primary"], linewidth=2, label="replicas")
    ax.set_xlabel("time (s)")
    ax.set_ylabel("replicas")
    ax.grid(color=_PALETTE["grid"], axis="y")
    ax2 = ax.twinx()
    duty_rows = [(t, d["duty"]) for t, d in zip(ts, rows) if "duty" in d]
    if duty_rows:
        ax2.plot([t for t, _ in duty_rows], [v for _, v in duty_rows],
                 color=_PALETTE["warm"], linewidth=1, label="duty")
    queue_rows = [(t, d["queue"]) for t, d in zip(ts, rows) if "queue" in d]
    if queue_rows:
        qmax = max((v for _, v in queue_rows), default=0) or 1
        ax2.plot([t for t, _ in queue_rows],
                 [v / qmax for _, v in queue_rows],
                 color=_PALETTE["cold"], linewidth=1,
                 label=f"queue (/{qmax:.0f})")
    ax2.set_ylabel("duty / queue (normalized)")
    ax2.set_ylim(0, 1.1)
    breaches = [t for t, d in zip(ts, rows) if d.get("slo_breached")]
    for b in breaches:
        ax.axvline(b, color=_PALETTE["bad"], linestyle=":", linewidth=1)
    lines1, labels1 = ax.get_legend_handles_labels()
    lines2, labels2 = ax2.get_legend_handles_labels()
    ax.legend(lines1 + lines2, labels1 + labels2, fontsize=8, loc="upper left")
    ax.set_title("Autoscale decisions")
    return _to_img(fig)


def run_timeline_chart(
    samples: list[dict[str, Any]], events: list[dict[str, Any]] | None = None
) -> str:
    """The monitor's 1 Hz timeline (docs/MONITORING.md) as three stacked
    lanes — completion throughput, windowed duty cycle, queue depth —
    with detected events as vertical markers. Mirrors the trace viewer's
    role: the trace explains ONE request, the timeline explains the RUN."""
    rows = [
        s for s in samples
        if isinstance(s.get("t"), (int, float))
    ]
    if len(rows) < 2:
        return ""  # a sub-2-sample run has no timeline to draw — skip
    if not HAVE_MPL:
        return _placeholder("run timeline")
    t0 = rows[0]["t"]
    ts = [s["t"] - t0 for s in rows]

    def series(block: str, key: str) -> list[tuple[float, float]]:
        return [
            (t, s[block][key])
            for t, s in zip(ts, rows)
            if isinstance(s.get(block), dict) and key in s[block]
        ]

    fig, axes = plt.subplots(3, 1, figsize=(7, 5), sharex=True)
    ax_thr, ax_duty, ax_q = axes

    thr = series("loadgen", "window_throughput_rps")
    if thr:
        ax_thr.plot([t for t, _ in thr], [v for _, v in thr],
                    color=_PALETTE["primary"], linewidth=1.5)
    ax_thr.set_ylabel("rps")
    ax_thr.set_title("Run timeline")

    # windowed duty from the busy-seconds counter, cumulative gauge as
    # fallback — the same derivation energy integration uses
    from kserve_vllm_mini_tpu.analysis.telemetry import windowed_duty_series

    duty_pts = [
        (t - t0, d)
        for t, d in windowed_duty_series([
            (s["t"], s["runtime"]) for s in rows
            if isinstance(s.get("runtime"), dict)
        ])
    ]
    if duty_pts:
        ax_duty.plot([t for t, _ in duty_pts], [v for _, v in duty_pts],
                     color=_PALETTE["warm"], linewidth=1.5)
    ax_duty.set_ylabel("duty")
    ax_duty.set_ylim(0, 1.05)

    q = series("runtime", "queue_depth")
    infl = series("loadgen", "inflight")
    if q:
        ax_q.plot([t for t, _ in q], [v for _, v in q],
                  color=_PALETTE["cold"], linewidth=1.5, label="queue depth")
    if infl:
        ax_q.plot([t for t, _ in infl], [v for _, v in infl],
                  color=_PALETTE["primary"], linewidth=1, linestyle="--",
                  label="in flight")
    if q or infl:
        ax_q.legend(fontsize=8, loc="upper left")
    ax_q.set_ylabel("requests")
    ax_q.set_xlabel("time (s)")

    for ax in axes:
        ax.grid(color=_PALETTE["grid"], axis="y")
        for e in events or []:
            et = e.get("t")
            if isinstance(et, (int, float)) and et >= t0:
                ax.axvline(et - t0, color=_PALETTE["bad"], linestyle=":",
                           linewidth=1)
    for e in events or []:
        et = e.get("t")
        if isinstance(et, (int, float)) and et >= t0:
            ax_thr.text(et - t0, ax_thr.get_ylim()[1] * 0.9,
                        str(e.get("type", "event")), fontsize=7, rotation=90,
                        color=_PALETTE["bad"], va="top")
    return _to_img(fig)


def kv_timeline_chart(
    samples: list[dict[str, Any]], events: list[dict[str, Any]] | None = None
) -> str:
    """KV-cache & memory over the run (docs/TROUBLESHOOTING.md "HBM
    pressure & KV thrash") as three stacked lanes: paged-pool occupancy,
    HBM watermark vs the device limit, and retained-eviction churn rate —
    with the kv_thrash / hbm_watermark_high markers where they fired.
    Lanes with no data stay empty rather than suppressing the chart, so
    a dense-layout run still gets its HBM lane."""
    rows = [
        s for s in samples
        if isinstance(s.get("t"), (int, float))
        and isinstance(s.get("runtime"), dict)
    ]
    kv_keys = ("kv_occupancy", "kv_free_blocks", "hbm_bytes_in_use",
               "kv_retained_evictions_total")
    rows = [s for s in rows if any(k in s["runtime"] for k in kv_keys)]
    if len(rows) < 2:
        return ""  # no KV/HBM series sampled — nothing to draw
    if not HAVE_MPL:
        return _placeholder("KV cache & memory timeline")
    t0 = rows[0]["t"]

    def series(key: str) -> list[tuple[float, float]]:
        return [
            (s["t"] - t0, s["runtime"][key])
            for s in rows if key in s["runtime"]
        ]

    fig, axes = plt.subplots(3, 1, figsize=(7, 5), sharex=True)
    ax_occ, ax_hbm, ax_churn = axes

    occ = series("kv_occupancy")
    if occ:
        ax_occ.plot([t for t, _ in occ], [v for _, v in occ],
                    color=_PALETTE["primary"], linewidth=1.5,
                    label="occupancy")
        ax_occ.set_ylim(0, 1.05)
    free = series("kv_free_blocks")
    if free:
        ax_free = ax_occ.twinx()
        ax_free.plot([t for t, _ in free], [v for _, v in free],
                     color=_PALETTE["cold"], linewidth=1, linestyle="--",
                     label="free blocks")
        ax_free.set_ylabel("free blocks", fontsize=8)
    ax_occ.set_ylabel("pool occupancy")
    ax_occ.set_title("KV cache & memory")

    in_use = series("hbm_bytes_in_use")
    limit = series("hbm_bytes_limit")
    if in_use:
        ax_hbm.plot([t for t, _ in in_use],
                    [v / 1e9 for _, v in in_use],
                    color=_PALETTE["warm"], linewidth=1.5, label="in use")
    if limit:
        ax_hbm.plot([t for t, _ in limit],
                    [v / 1e9 for _, v in limit],
                    color=_PALETTE["bad"], linewidth=1, linestyle=":",
                    label="limit")
    if in_use or limit:
        ax_hbm.legend(fontsize=8, loc="upper left")
    ax_hbm.set_ylabel("HBM (GB)")

    def rate(key: str) -> list[tuple[float, float]]:
        pts = series(key)
        return [
            (tb, max(vb - va, 0.0) / (tb - ta))
            for (ta, va), (tb, vb) in zip(pts, pts[1:]) if tb > ta
        ]

    # Eviction churn split: an eviction that lands in the host-RAM tier
    # (kv_tier_demotions_total) is recoverable; the remainder is a true
    # discard. Both derive from counters the timeline already samples.
    churn = rate("kv_retained_evictions_total")
    demo = dict(rate("kv_tier_demotions_total"))
    if churn:
        if demo:
            discard = [(t, max(v - demo.get(t, 0.0), 0.0)) for t, v in churn]
            ax_churn.plot([t for t, _ in discard], [v for _, v in discard],
                          color=_PALETTE["bad"], linewidth=1.5,
                          label="true discards")
            dpts = sorted(demo.items())
            ax_churn.plot([t for t, _ in dpts], [v for _, v in dpts],
                          color=_PALETTE["cold"], linewidth=1.5,
                          linestyle="--", label="demoted to tier")
            ax_churn.legend(fontsize=8, loc="upper left")
        else:
            ax_churn.plot([t for t, _ in churn], [v for _, v in churn],
                          color=_PALETTE["bad"], linewidth=1.5)
    ax_churn.set_ylabel("evictions/s")
    ax_churn.set_xlabel("time (s)")

    kv_events = [
        e for e in events or []
        if e.get("type") in ("kv_thrash", "hbm_watermark_high")
    ]
    for ax in axes:
        ax.grid(color=_PALETTE["grid"], axis="y")
        for e in kv_events:
            et = e.get("t")
            if isinstance(et, (int, float)) and et >= t0:
                ax.axvline(et - t0, color=_PALETTE["bad"], linestyle=":",
                           linewidth=1)
    for e in kv_events:
        et = e.get("t")
        if isinstance(et, (int, float)) and et >= t0:
            ax_occ.text(et - t0, ax_occ.get_ylim()[1] * 0.9,
                        str(e.get("type", "event")), fontsize=7, rotation=90,
                        color=_PALETTE["bad"], va="top")
    return _to_img(fig)


def econ_timeline_chart(
    samples: list[dict[str, Any]], events: list[dict[str, Any]] | None = None
) -> str:
    """The live economics rail (docs/ECONOMICS.md) over the run as two
    stacked lanes — $/1K-tok (fleet marginal-replica gauge dashed beside
    it when the router exported one) and Wh/1K-tok — with the
    cost_burn_exceeded / replica_unprofitable markers where they fired.
    Runs whose timeline carried no econ gauges (unpriced engine, CPU
    backend) draw nothing: absent, never a fabricated $0 lane."""
    rows = [
        s for s in samples
        if isinstance(s.get("t"), (int, float))
        and isinstance(s.get("runtime"), dict)
        and "econ_usd_per_1k_tokens" in s["runtime"]
    ]
    if len(rows) < 2:
        return ""  # rail never warmed up (or never existed) — skip
    if not HAVE_MPL:
        return _placeholder("cost & energy timeline")
    t0 = rows[0]["t"]

    def series(key: str) -> list[tuple[float, float]]:
        return [
            (s["t"] - t0, s["runtime"][key])
            for s in rows if key in s["runtime"]
        ]

    fig, (ax_usd, ax_wh) = plt.subplots(2, 1, figsize=(7, 4), sharex=True)

    usd = series("econ_usd_per_1k_tokens")
    ax_usd.plot([t for t, _ in usd], [v for _, v in usd],
                color=_PALETTE["primary"], linewidth=1.5, label="$/1K-tok")
    marginal = series("econ_marginal_replica_usd_per_1k_tokens")
    if marginal:
        ax_usd.plot([t for t, _ in marginal], [v for _, v in marginal],
                    color=_PALETTE["bad"], linewidth=1.2, linestyle="--",
                    label="marginal replica $/1K-tok")
    ax_usd.legend(fontsize=8, loc="upper right")
    ax_usd.set_ylabel("$ / 1K tok")
    ax_usd.set_title("Cost & energy")

    wh = series("econ_wh_per_1k_tokens")
    if wh:
        ax_wh.plot([t for t, _ in wh], [v for _, v in wh],
                   color=_PALETTE["warm"], linewidth=1.5)
    ax_wh.set_ylabel("Wh / 1K tok")
    ax_wh.set_xlabel("time (s)")

    econ_events = [
        e for e in events or []
        if e.get("type") in ("cost_burn_exceeded", "replica_unprofitable")
    ]
    for ax in (ax_usd, ax_wh):
        ax.grid(color=_PALETTE["grid"], axis="y")
        for e in econ_events:
            et = e.get("t")
            if isinstance(et, (int, float)) and et >= t0:
                ax.axvline(et - t0, color=_PALETTE["bad"], linestyle=":",
                           linewidth=1)
    for e in econ_events:
        et = e.get("t")
        if isinstance(et, (int, float)) and et >= t0:
            ax_usd.text(et - t0, ax_usd.get_ylim()[1] * 0.9,
                        str(e.get("type", "event")), fontsize=7, rotation=90,
                        color=_PALETTE["bad"], va="top")
    return _to_img(fig)


def cost_pareto_chart(rows: list[dict[str, Any]]) -> str:
    """Cost vs latency Pareto scatter over sweep cells: $/1K-tok (live
    economics when the cell carried the rail, post-hoc cost otherwise)
    against TTFT p95. The Pareto-efficient cells — no other cell both
    cheaper AND faster — are highlighted and connected; everything
    northeast of the frontier is paying for latency it isn't getting."""
    pts = []
    for r in rows:
        econ = r.get("economics") if isinstance(r.get("economics"), dict) else {}
        cost = econ.get("usd_per_1k_tokens", r.get("cost_per_1k_tokens"))
        ttft = r.get("ttft_p95_ms")
        # sweep CSV rows carry strings ("" for a cell that never priced)
        try:
            pts.append((
                float(ttft), float(cost),
                str(r.get("run_id") or r.get("concurrency") or "?"),
            ))
        except (TypeError, ValueError):
            continue
    if len(pts) < 2:
        return ""  # a frontier needs at least two priced cells
    if not HAVE_MPL:
        return _placeholder("cost vs TTFT Pareto")
    frontier = sorted(
        p for p in pts
        if not any(
            q[0] <= p[0] and q[1] <= p[1] and q != p for q in pts
        )
    )
    fig, ax = plt.subplots(figsize=(7, 3.6))
    dominated = [p for p in pts if p not in frontier]
    if dominated:
        ax.scatter([p[0] for p in dominated], [p[1] for p in dominated],
                   color=_PALETTE["cold"], s=36, label="dominated")
    ax.scatter([p[0] for p in frontier], [p[1] for p in frontier],
               color=_PALETTE["ok"], s=48, zorder=3, label="Pareto frontier")
    ax.plot([p[0] for p in frontier], [p[1] for p in frontier],
            color=_PALETTE["ok"], linewidth=1, linestyle="--", zorder=2)
    for t, c, name in pts:
        ax.annotate(name, (t, c), fontsize=7,
                    xytext=(4, 4), textcoords="offset points")
    ax.set_xlabel("TTFT p95 (ms)")
    ax.set_ylabel("$ / 1K tok")
    ax.set_title("Cost vs TTFT p95")
    ax.legend(fontsize=8, loc="upper left")
    ax.grid(color=_PALETTE["grid"])
    return _to_img(fig)


def cold_warm_chart(results: dict[str, Any]) -> str:
    cold, warm = results.get("cold_p95_ms"), results.get("warm_p95_ms")
    if not HAVE_MPL or cold is None or warm is None:
        return ""
    fig, ax = plt.subplots(figsize=(5, 3))
    ax.bar(["warm p50", "warm p95", "cold p50", "cold p95"],
           [results.get("warm_p50_ms", 0), warm, results.get("cold_p50_ms", 0), cold],
           color=[_PALETTE["warm"], _PALETTE["warm"], _PALETTE["cold"], _PALETTE["cold"]])
    mult = results.get("cold_multiplier")
    ax.set_title(
        f"Cold vs warm latency (cold multiplier {mult:.2f}x)" if mult else
        "Cold vs warm latency"
    )
    ax.set_ylabel("ms")
    ax.grid(color=_PALETTE["grid"], axis="y")
    return _to_img(fig)


def cost_breakdown_chart(results: dict[str, Any]) -> str:
    bd = results.get("cost_breakdown") or {}
    bd = {k: v for k, v in bd.items() if v and v > 0}
    if not HAVE_MPL or not bd:
        return ""
    fig, ax = plt.subplots(figsize=(4.5, 3))
    ax.pie(list(bd.values()), labels=list(bd.keys()), autopct="%1.0f%%",
           colors=[_PALETTE["primary"], _PALETTE["warm"], _PALETTE["cold"], "#a78bfa"])
    ax.set_title(f"Cost breakdown (total ${results.get('cost_total', 0):.4f})")
    return _to_img(fig)


def heatmap_chart(
    rows: list[str], cols: list[str], values: list[list[Optional[float]]],
    title: str, fmt: str = "{:.0f}",
) -> str:
    if not HAVE_MPL:
        return _placeholder(title)
    import numpy as np

    arr = np.array([[v if v is not None else np.nan for v in row] for row in values],
                   dtype=float)
    fig, ax = plt.subplots(figsize=(1.2 + 0.9 * len(cols), 1.0 + 0.6 * len(rows)))
    im = ax.imshow(arr, cmap="viridis", aspect="auto")
    ax.set_xticks(range(len(cols)), cols, rotation=30, ha="right", fontsize=8)
    ax.set_yticks(range(len(rows)), rows, fontsize=8)
    for i in range(len(rows)):
        for j in range(len(cols)):
            if not np.isnan(arr[i, j]):
                ax.text(j, i, fmt.format(arr[i, j]), ha="center", va="center",
                        fontsize=8, color="white")
    ax.set_title(title)
    fig.colorbar(im, shrink=0.8)
    return _to_img(fig)

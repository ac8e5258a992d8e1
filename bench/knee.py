#!/usr/bin/env python3
"""The knee sweep's table: ``python3 bench/knee.py <cell> <seed> [<seed> ...]``.

Reads the records of finished runs (``chiprun_out/bench/<cell>/<seed>/``, made
with ``bench/run.py --rate-rps``) and prints, for each, the offered rate, the
tails, the share of requests due in the window that met both limits of the
traffic file (``limits.ttft_ms``; ``limits.itl_mean_ms`` on a request's mean
gap between tokens), and whether the backlog grew: the mean wait for the first
token in the last third of the window against the first third. The knee is the
highest rate at which the backlog does not grow and at least 90% meet both.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import metrics  # noqa: E402


def row(cell: str, seed: str) -> dict:
    d = ROOT / "chiprun_out" / "bench" / cell / seed
    plan = json.loads((d / "plan.json").read_text())
    run = json.loads((d / "records.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / f"{plan['traffic']}.json").read_text())
    limits = mix["limits"]
    w, end = plan["window_s"], run["ended_s"]
    due = metrics.due_in_window(run["records"], w)
    met = 0
    for r in due:
        ts = r["token_s"]
        if metrics.failed(r) or not ts:
            continue
        gap = (ts[-1] - ts[0]) / (len(ts) - 1) * 1000.0 if len(ts) > 1 else 0.0
        met += metrics.ttft_ms(r, end) <= limits["ttft_ms"] and gap <= limits["itl_mean_ms"]
    tt = metrics.ttfts_ms(run["records"], w, end)
    gaps = metrics.token_gaps_ms(run["records"], w)
    third = [[metrics.ttft_ms(r, end) for r in due if lo <= r["due_s"] < hi]
             for lo, hi in ((0, w / 3), (2 * w / 3, w))]
    return {"seed": seed, "rate_rps": plan["rate_rps"], "due": len(due),
            "ttft_p50": metrics.percentile(tt, 50), "ttft_p95": metrics.percentile(tt, 95),
            "itl_p50": metrics.percentile(gaps, 50), "itl_p95": metrics.percentile(gaps, 95),
            "met_both": met / len(due),
            "ttft_first_third": sum(third[0]) / max(len(third[0]), 1),
            "ttft_last_third": sum(third[1]) / max(len(third[1]), 1),
            "tokens_per_s": metrics.tokens_in_window(run["records"], w) / w}


if __name__ == "__main__":
    rows = sorted((row(sys.argv[1], s) for s in sys.argv[2:]), key=lambda r: r["rate_rps"])
    for r in rows:
        print(json.dumps({k: (round(v, 3) if isinstance(v, float) else v) for k, v in r.items()}))

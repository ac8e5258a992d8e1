"""From the client's records to numbers: the arithmetic every metric shares.

Times in a record are seconds from the instant the window opened. A metric
is taken over all the work of the window: every request due in it, every
token that arrived in it. A request that failed, was shed or has no first
token by the end of the run counts as the worst.
"""

from __future__ import annotations

import math
from bisect import bisect_right
from typing import Optional


def percentile(values: list[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def due_in_window(records: list[dict], window_s: float) -> list[dict]:
    return [r for r in records if r["due_s"] is not None and 0.0 <= r["due_s"] < window_s]


def failed(r: dict) -> bool:
    """Refused, errored before its first token, or answered with another
    status than 200. A stream cut at the end of the run is not a failure."""
    return r["status"] not in (None, 200) or (r["error"] is not None and not r["token_s"]
                                              and r["sent_s"] is not None
                                              and "cancelled" not in r["error"])


def ttft_ms(r: dict, run_end_s: float, from_send: bool = False) -> float:
    """Wait for the first token from the instant the request was due. With
    no first token, the whole time it waited until the run ended: the worst."""
    start = r["sent_s"] if from_send else r["due_s"]
    end = r["token_s"][0] if r["token_s"] else run_end_s
    return (end - start) * 1000.0


def ttfts_ms(records: list[dict], window_s: float, run_end_s: float) -> list[float]:
    """One per request due in the window; a failed, refused or unanswered one
    waited from when it was due until the run ended."""
    return [(run_end_s - r["due_s"]) * 1000.0 if failed(r) or r["sent_s"] is None
            else ttft_ms(r, run_end_s) for r in due_in_window(records, window_s)]


def token_gaps_ms(records: list[dict], window_s: float) -> list[float]:
    """Every gap between two consecutive tokens of one request whose later
    token arrived inside the window, pooled over all requests."""
    out = []
    for r in records:
        ts = r["token_s"]
        out.extend((b - a) * 1000.0 for a, b in zip(ts, ts[1:]) if 0.0 <= b < window_s)
    return out


def tokens_in_window(records: list[dict], window_s: float) -> int:
    return sum(1 for r in records for t in r["token_s"] if 0.0 <= t < window_s)


def token_silences(records: list[dict], window_s: float, least_s: float = 0.1) -> list[float]:
    """The stretches of the window, of ``least_s`` or longer, in which no token
    reached any client: a prefill of a long prompt, or a host that stood still."""
    ts = sorted(t for r in records for t in r["token_s"] if 0.0 <= t < window_s)
    return [b - a for a, b in zip(ts, ts[1:]) if b - a >= least_s]


def completed(r: dict) -> bool:
    return r["done_s"] is not None and r["finish_reason"] is not None


def lateness_ms(records: list[dict], window_s: float) -> list[float]:
    return [(r["sent_s"] - r["due_s"]) * 1000.0
            for r in due_in_window(records, window_s) if r["sent_s"] is not None]


def resident_tokens(records: list[dict], t: float) -> int:
    """Tokens whose keys and values a slot holds at time ``t``: prompt plus
    tokens received so far, for every request between its first token and its end."""
    total = 0
    for r in records:
        ts = r["token_s"]
        if not ts or ts[0] > t:
            continue
        end = r["done_s"] if r["done_s"] is not None else ts[-1]
        if t <= end:
            total += r["prompt_tokens"] + bisect_right(ts, t)
    return total


def mean_resident_tokens(records: list[dict], window_s: float) -> Optional[float]:
    """``resident_tokens`` sampled at 10 Hz over the window, averaged."""
    n = int(window_s * 10)
    if n <= 0:
        return None
    return sum(resident_tokens(records, (i + 0.5) / 10.0) for i in range(n)) / n


def ttft_p95_ms(records: list[dict], window_s: float, run_end_s: float) -> Optional[float]:
    v = ttfts_ms(records, window_s, run_end_s)
    return percentile(v, 95.0) if v else None


def step_ms(trace: Optional[dict], program: str) -> Optional[float]:
    """Median device duration of one program's executions in the reduced trace."""
    durs = (trace or {}).get("programs", {}).get(program)
    return 1000.0 * durs[len(durs) // 2] if durs else None


def span_sums(trace: Optional[dict], name: str) -> dict:
    """What the program's ``name`` spans counted in the traced slice: the sum of
    each numeric argument over the spans that began in the seconds the device
    times come from (``trace_reduce.span_counts``). Empty without a trace,
    without such spans or where they carry no arguments (an older program)."""
    return ((trace or {}).get("spans") or {}).get(name, {}).get("sums", {})


def idle_share(trace: Optional[dict]) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device."""
    t = trace or {}
    if not t.get("window_s") or not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def median(values: list[float]) -> Optional[float]:
    return percentile(values, 50.0) if values else None

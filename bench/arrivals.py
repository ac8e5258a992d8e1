"""Arrival schedules: one fixed, stratified schedule for a cell; the seed draws where it opens.

After the program's ``loadgen/arrivals.py`` (steady, poisson, bursty), with one
change the benchmark needs: the schedule of a cell is one fixed period. Its
gaps are the quantiles of the pattern's distribution (``exponential``: those
of a Poisson process's gaps), scaled to fill the window exactly, in an order
fixed by the traffic file; the run's seed only draws where in the period the
window opens (``rotated``). This is NOT a seeded Poisson queue: the number of
arrivals in a window never varies, and the judged tail is that of the one
order the traffic file's ``pairing_seed`` fixes (PERF.md gives the tail
under three other orders). Every seed then
offers the same requests with the same neighbours, so two seeds differ as two
runs of one seed do, and not in the work they bring. (With the order drawn
from the seed, the 95th percentile of the wait for the first token spread by
23% over six seeds while two runs of one seed agreed within 4%: my chip
runs, PR 25.)
"""

from __future__ import annotations

import math
import random

PATTERNS = ("steady", "exponential", "bursty")


def gaps(pattern: str, n: int, duration_s: float, burst_factor: float = 5.0) -> list[float]:
    """The ``n`` inter-arrival gaps of one duration, summing to it: the
    quantiles of the pattern's distribution at ``(i + 0.5) / n``. ``bursty``
    draws them ``burst_factor`` times shorter; ``arrival_times`` then adds the
    idle time that restores the mean after every tenth of the requests."""
    if pattern not in PATTERNS:
        raise ValueError(f"unknown arrival pattern {pattern!r}; known: {PATTERNS}")
    if n <= 0:
        return []
    if pattern == "steady":
        raw = [1.0] * n
    else:
        raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = duration_s / sum(raw) / (burst_factor if pattern == "bursty" else 1.0)
    return [g * scale for g in raw]


def cycle(pattern: str, n: int, duration_s: float, rng: random.Random,
          burst_factor: float = 5.0) -> list[float]:
    """The ``n`` gaps in the order ``rng`` draws (``gap[i]`` is the time before
    arrival ``i``), summing to ``duration_s``: one period of the schedule."""
    g = gaps(pattern, n, duration_s, burst_factor)
    rng.shuffle(g)
    if pattern == "bursty" and g:
        burst = max(n // 10, 1)
        ends = range(burst - 1, n, burst)
        idle = (duration_s - sum(g)) / len(ends)
        for i in ends:
            g[i] += idle
    return g


def rotated(gap: list[float], start: int, phase: float, before: int) -> list[tuple[int, float]]:
    """``(index, time)`` of one period of arrivals beginning with arrival
    ``start`` at ``phase`` of its gap after time 0, preceded by the ``before``
    arrivals that lead up to it (negative times), indices modulo the period."""
    n = len(gap)
    out, t = [], gap[start % n] * phase
    for i in range(n):
        if i:
            t += gap[(start + i) % n]
        out.append(((start + i) % n, t))
    lead, t = [], gap[start % n] * phase
    for i in range(1, before + 1):
        t -= gap[(start - i + 1) % n]
        lead.append(((start - i) % n, t))
    return lead[::-1] + out

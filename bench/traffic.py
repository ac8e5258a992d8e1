"""The one general traffic generator: a traffic file's parameters -> a plan.

A traffic mix is a data file (``bench/traffic/<mix>.json``): loop kind,
arrival pattern, length distributions, limits. The cell's own file
(``bench/workloads/<cell>.json``) adds the offered rate or the client count.
The plan lists every request with the instant it is due, its prompt and its
exact output length. Lengths, gaps and their order are fixed by the files (one
period of the schedule); the seed draws where in the period the window opens,
and the filler text. A mix that needs other code
(sessions, shared prefixes) names a ``kind``, and ``plan`` hands over to
``bench/traffic_kinds/<kind>.py``.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist

from bench import arrivals, config

_WORDS = ("alpha bravo charlie delta echo foxtrot golf hotel india juliet kilo lima "
          "mike november oscar papa quebec romeo sierra tango uniform victor whiskey "
          "xray yankee zulu").split()


def quantiles(dist: dict, n: int) -> list[int]:
    """``n`` whole-number lengths: the distribution's quantiles at
    ``(i + 0.5) / n``, clipped to ``[min, max]``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            x = math.exp(math.log(dist["median"]) + dist["sigma"] * NormalDist().inv_cdf(u))
        elif dist["dist"] == "uniform":
            x = lo + u * (hi - lo)
        elif dist["dist"] == "fixed":
            x = dist["value"]
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        out.append(min(max(int(round(x)), lo), hi))
    return out


def length_pairs(traffic: dict, n: int, rng: random.Random) -> list[tuple[int, int]]:
    """``n`` (prompt, output) lengths. Which prompt goes with which output is
    fixed by the traffic file's ``pairing_seed``; ``rng`` draws the order."""
    prompts = quantiles(traffic["prompt_tokens"], n)
    outputs = quantiles(traffic["output_tokens"], n)
    random.Random(traffic["pairing_seed"]).shuffle(outputs)
    pairs = list(zip(prompts, outputs))
    rng.shuffle(pairs)
    return pairs


def filler(n_bytes: int, rng: random.Random) -> str:
    """Seeded filler text of exactly ``n_bytes`` ASCII bytes."""
    parts, size = [], 0
    while size < n_bytes:
        w = rng.choice(_WORDS) + str(rng.randrange(10)) + " "
        parts.append(w)
        size += len(w)
    return "".join(parts)[:n_bytes]


def _request(rid: str, phase: str, due_s, pair, overhead: int, rng, client=None) -> dict:
    prompt_tokens, out_tokens = pair
    return {"id": rid, "phase": phase, "due_s": due_s, "client": client,
            "prompt_tokens": prompt_tokens, "max_tokens": out_tokens,
            "content": filler(prompt_tokens - overhead, rng)}


def plan(traffic: dict, params: dict, seed: int, window_s: float, overhead: int) -> dict:
    """The requests of one run. ``overhead``: the tokens the program's chat
    template adds to a message, so that ``prompt_tokens`` is the served count.
    Times are seconds from the instant the window opens; the lead-in (set-up,
    not measured) has negative ones."""
    kind = traffic.get("kind", "independent")
    if kind != "independent":
        return config.load_module("traffic_kinds", kind).plan(
            traffic, params, seed, window_s, overhead)
    rng = random.Random(seed)
    lead_s = float(traffic["lead_in_s"])
    if traffic["prompt_tokens"]["min"] <= overhead:
        raise ValueError("the shortest prompt must be longer than the chat template")
    base = {"loop": traffic["loop"], "window_s": window_s, "lead_in_s": lead_s,
            "drain_s": float(traffic["drain_s"])}
    if traffic["loop"] == "open":
        # one fixed period of the schedule (gaps and lengths in the order the
        # traffic file's own seed draws); the run's seed draws where it opens
        rate = float(params["rate_rps"])
        n = int(round(rate * window_s))
        fixed = random.Random(traffic["pairing_seed"])
        gap = arrivals.cycle(traffic["arrivals"], n, window_s, fixed)
        pairs = length_pairs(traffic, n, fixed)
        n_lead = min(int(round(rate * lead_s)), n)
        reqs = []
        for i, t in arrivals.rotated(gap, rng.randrange(n), rng.random(), n_lead):
            phase = "window" if t >= 0.0 else "lead"
            reqs.append(_request(f"{phase[0]}{i}", phase, t, pairs[i], overhead, rng))
        return {**base, "rate_rps": rate, "requests": reqs}
    if traffic["loop"] == "closed":
        clients, per = int(params["clients"]), int(traffic["requests_per_client"])
        pairs = length_pairs(traffic, clients * per, rng)
        phases = [(c + 0.5) / clients for c in range(clients)]
        rng.shuffle(phases)
        reqs = []
        for c in range(clients):
            for k in range(per):
                p, o = pairs[c * per + k]
                if k == 0:
                    # each client's first answer is cut to a drawn share, so the
                    # slots are out of phase when the window opens
                    o = max(int(o * phases[c]), int(traffic["output_tokens"].get("first_min", 8)))
                reqs.append(_request(f"c{c}.{k}", "closed", None, (p, o), overhead, rng, client=c))
        return {**base, "clients": clients, "requests": reqs}
    raise ValueError(f"unknown loop {traffic['loop']!r}; known: open, closed")

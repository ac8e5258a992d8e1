"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample of the requests it finished (the longest
among them, the rest drawn from the seed) is run through
the configuration's plain reference (``bench/references/``) teacher-forced: the prompt with the tokens that were
served. The traffic is greedy, so every served token should be the
reference's best, or lie below it by no more than rounding in the stated
precision moves a logit. Compared are, over all sampled positions, the mean
and the widest gap by which the served token's reference logit lies below the
reference's best; each has a limit of its own in
``bench/limits/<config>.json``, with the readings it was set from. The share of
positions where the two differ is printed beside them, without a limit. The
control is the program's own lower precision (``--engine quant_mode=w8a8``,
int8 activations where the configuration states bfloat16): it must read over
a limit, on the chip and at test size (``tests/yardstick``).
"""

from __future__ import annotations

import json
import random
from pathlib import Path

import numpy as np

from bench import config, metrics

LIMITS = Path(__file__).resolve().parent / "limits"


def sample(records: list[dict], seed: int, spec: dict) -> list[dict]:
    """Finished requests to check: the longest first, then drawn from the
    seed, up to ``spec['requests']`` and ``spec['max_tokens_checked']`` served
    tokens (at least one request)."""
    done = [r for r in records if metrics.completed(r) and r["token_s"]
            and r["phase"] != "lead"]
    if not done:
        return []
    done.sort(key=lambda r: (r["prompt_tokens"] + r["max_tokens"], r["id"]), reverse=True)
    rest = done[1:]
    random.Random(seed).shuffle(rest)
    picked, budget = [], spec["max_tokens_checked"]
    for r in [done[0]] + rest:
        if len(picked) >= spec["requests"]:
            break
        if picked and r["max_tokens"] > budget:
            continue
        picked.append(r)
        budget -= r["max_tokens"]
    return picked


def gaps(logits: np.ndarray, tokens: list[int]) -> np.ndarray:
    """By how much each token's logit lies below the best of its row."""
    rows = np.arange(len(tokens))
    return logits.max(axis=-1) - logits[rows, np.asarray(tokens)]


def numbers(all_gaps: np.ndarray) -> dict[str, float]:
    return {"gap_mean": float(all_gaps.mean()), "gap_max": float(all_gaps.max()),
            "differ_share": float((all_gaps > 0).mean())}


def served_numbers(seed: int, model: dict, sequences: list[list[int]],
                   first: list[int], spec: dict) -> tuple[dict, int]:
    """The served tokens' gaps under the reference: (numbers, positions)."""
    reference = config.load_module("references", model["reference"])
    logits = reference.logits_after(seed, model, sequences, first,
                                    spec["pad_sequence_to"], spec["pad_rows_to"])
    g = np.concatenate([gaps(lg, seq[f:]) for lg, seq, f in zip(logits, sequences, first)])
    return numbers(g), int(g.size)


def limits_for(config_name: str) -> dict:
    return json.loads((LIMITS / f"{config_name}.json").read_text())["limits"]


def beside(nums: dict, limits: dict) -> dict:
    """Each limited number beside its limit: ``{name: {"value", "limit"}}``."""
    return {name: {"value": nums[name], "limit": lim} for name, lim in limits.items()}


def holds(c: dict) -> bool:
    """One compared number: at most its ``limit``, or at least its ``limit_at_least``."""
    return c["value"] >= c["limit_at_least"] if "limit_at_least" in c else c["value"] <= c["limit"]


def lines_of(compared: dict) -> list[str]:
    """The one rendering of compared numbers: ``check <name>=<value> limit=<limit> ok|OVER``."""
    return [f"check {name}={c['value']} "
            + " ".join(f"{k}={v}" for k, v in c.items() if k != "value")
            + (" ok" if holds(c) else " OVER") for name, c in compared.items()]


def within(nums: dict, limits: dict) -> tuple[bool, list[str]]:
    """Every number beside its limit, and whether all hold."""
    compared = beside(nums, limits)
    return all(map(holds, compared.values())), lines_of(compared)

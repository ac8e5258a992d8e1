"""Token sampling under jit with per-slot parameters.

The decode step samples for all engine slots in one fused call: temperature,
top-k, and top-p are [B] vectors so heterogeneous requests batch together
(continuous batching must not re-trace when a new request's temperature
differs). Greedy is temperature == 0 via jnp.where, not Python branching —
everything stays traceable.

The sampler does the sorting its batch's parameters ask for
(``filter_logits``): no full-vocabulary sort while no sampled row sets
``top_k`` or ``top_p``, one sort where a row does, and a row that sets
neither reads the same either way. One executable: the choice is a
``lax.cond`` on the device over the [B] parameter vectors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def apply_penalties(
    logits: jnp.ndarray,      # [B, V] float32
    counts: jnp.ndarray,      # [B, V] int32: per-slot GENERATED-token counts
    presence: jnp.ndarray,    # [B] float32; 0 => disabled
    frequency: jnp.ndarray,   # [B] float32; 0 => disabled
) -> jnp.ndarray:
    """OpenAI presence/frequency penalties over the generated-token counts.

    vLLM semantics (the reference's flagship backend, which its load
    generator exercises with these knobs — reference scripts/loadtest.py:
    260-342): penalties consider OUTPUT tokens only, not the prompt.
    ``counts`` is device-resident engine state updated inside the decode
    scan, so fused multi-step chunks see each step's emission immediately.

    Zero penalties are bit-exact identity (``x - 0.0 == x`` for every
    float including ±inf), so unpenalized requests keep oracle equality.
    """
    cf = counts.astype(logits.dtype)
    pen = frequency[:, None] * cf + jnp.where(
        counts > 0, presence[:, None], jnp.zeros_like(presence)[:, None]
    )
    return logits - pen


def count_tokens(counts: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """Record one sampled token per slot in the counts table: [B, V] += 1
    at (row, tokens[row])."""
    B = counts.shape[0]
    return counts.at[jnp.arange(B), tokens].add(1)


def sample_tokens(
    logits: jnp.ndarray,        # [B, V] float32
    rng: jax.Array,
    temperature: jnp.ndarray,   # [B] float32; 0 => greedy
    top_k: jnp.ndarray,         # [B] int32; 0 => disabled
    top_p: jnp.ndarray,         # [B] float32; >= 1.0 => disabled
) -> jnp.ndarray:
    """Returns [B] int32 sampled token ids.

    Expressed over ``filter_logits`` so the sampler and the speculative
    rejection test share ONE masking pipeline: spec decode's distribution-
    exactness depends on p/q being exactly this sampler's distribution,
    and a masking fix applied to only one copy would silently break it.
    Greedy rows still take the explicit argmax (bit-stable, and rows whose
    filtered logits are one-hot sample that token with probability 1
    anyway)."""
    greedy = jnp.argmax(logits, axis=-1)
    filtered = filter_logits(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(rng, filtered, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)


def filter_logits(
    logits: jnp.ndarray,        # [B, V] float32
    temperature: jnp.ndarray,   # [B] float32; 0 => greedy (one-hot dist)
    top_k: jnp.ndarray,         # [B] int32; 0 => disabled
    top_p: jnp.ndarray,         # [B] float32; >= 1.0 => disabled
) -> jnp.ndarray:
    """The filtered/scaled logits whose softmax IS each row's sampling
    distribution — the single masking pipeline ``sample_tokens`` samples
    from and the speculative rejection test computes p/q with (one
    implementation, so they can never drift apart).

    The full-vocabulary sort runs only when the batch asks for it: a
    ``lax.cond`` on ``any(temperature > 0 and (top_k > 0 or top_p < 1))``,
    computed on the device from the [B] parameters, takes the
    sort-and-mask branch; a batch of greedy rows, plain-temperature rows
    and free slots passes its scaled logits through untouched. The
    predicate reads nothing but the call's own inputs, so the branch
    cannot be seen in any row's distribution: inside the sort branch a row
    with ``top_k == 0`` keeps every entry and a row with ``top_p >= 1.0``
    gets no cut at all (not even of the ~1e-6 tail a float32 cumulative
    sum would round away), so a row that asks for no filter reads
    ``logits / temperature`` whether or not a neighbour filters.

    Temperature-0 rows become a one-hot at the argmax, which makes
    rejection-sampling verification DEGENERATE to the exact greedy accept
    rule: accept prob p(x)/q(x) is 1 on an argmax match and 0 otherwise,
    and the residual distribution is a one-hot at the target's argmax — so
    greedy requests under the sampled spec path emit bit-identical tokens
    to plain greedy decode.
    """
    V = logits.shape[-1]
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_t[:, None]
    use_k, use_p = top_k > 0, top_p < 1.0

    def sort_and_mask(scaled):
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        k_idx = jnp.clip(top_k - 1, 0, V - 1)
        kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
        below_k = use_k[:, None] & (scaled < kth)
        scaled = jnp.where(below_k, -jnp.inf, scaled)
        # the top-k mask applied to the sorted copy IS the sort of the
        # masked row: what falls below kth is the sorted copy's tail
        sorted_desc = jnp.where(
            use_k[:, None] & (sorted_desc < kth), -jnp.inf, sorted_desc
        )
        probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs_sorted, axis=-1)
        inside = cum - probs_sorted < top_p[:, None]
        cut = jnp.min(jnp.where(inside, sorted_desc, jnp.inf), axis=-1)
        below_p = use_p[:, None] & (scaled < cut[:, None])
        return jnp.where(below_p, -jnp.inf, scaled)

    asks = (temperature > 0) & (use_k | use_p)
    scaled = jax.lax.cond(jnp.any(asks), sort_and_mask, lambda x: x, scaled)

    greedy_lg = jnp.where(
        jax.nn.one_hot(jnp.argmax(logits, axis=-1), V, dtype=bool),
        0.0, -jnp.inf,
    )
    return jnp.where(temperature[:, None] > 0, scaled, greedy_lg)


# OpenAI caps top_logprobs at 5; one static K keeps a single decode
# executable regardless of what each request asked for (the host slices)
TOP_LOGPROBS_K = 5


def token_logprobs(
    logits: jnp.ndarray,   # [B, V] float32 (post-mask: the real sampling dist)
    tokens: jnp.ndarray,   # [B] int32 chosen ids
    k: int = TOP_LOGPROBS_K,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(chosen logprob [B], top-k ids [B,k], top-k logprobs [B,k]).

    Computed on device inside the decode dispatch: a logsumexp + gather +
    top_k over [B, V] is noise next to the model forward, and returning it
    unconditionally keeps one executable (no logprobs-variant recompiles).
    """
    lse = jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)  # [B,1]
    logp = logits - lse
    chosen = jnp.take_along_axis(logp, tokens[:, None].astype(jnp.int32), axis=-1)[:, 0]
    top_vals, top_ids = jax.lax.top_k(logp, k)
    return chosen, top_ids.astype(jnp.int32), top_vals

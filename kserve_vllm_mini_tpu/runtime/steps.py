"""The serving engine's compiled steps: pure builders of jitted programs.

Each builder takes what its program closes over (``cfg``; ``fwd`` —
``models.llama.forward`` or the pp-sharded drop-in of a pipelined mesh;
``kernels``) and returns the jitted function: no engine, no ``self``.
``Engine._step`` keeps the one cache of them; the prefill lane of
``runtime/disagg.py`` builds its two through ``build_prefill``. What
``forward`` is handed for a model, a cache layout and an adapter bank is
decided in ``forward_kwargs`` and nowhere else.

Read from outside, so not free to change: a program's ``__name__`` (XLA
names it ``jit_<name>``; ``bench/trace_reduce.py`` and ``bench/readers/``
find ``jit_decode``, ``jit_prefill``, ``jit_first`` by it), its
compile-statistics label (``prefill_label``, ``Engine._step``: read by
``bench/run.py``, ``chip_smoke.py``, ``/metrics``) and the order of its
operands (the lowered text, and so a warm compile cache, depends on it).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.models.llama import (
    forward,
    slice_cache_slots,
    update_cache_slots,
)
from kserve_vllm_mini_tpu.runtime.sampling import (
    apply_penalties,
    count_tokens,
    filter_logits,
    sample_tokens,
    token_logprobs,
)


def forward_kwargs(cfg: ModelConfig, kernels: str, *, table=None, lora=None,
                   ids=None, length=None, live=None, stats=False) -> dict[str, Any]:
    """The keyword arguments every step hands ``forward``.

    ``table``: block-table rows of a paged pool (None = dense stripes).
    ``lora``/``ids``: the multi-LoRA bank's layer tree and the adapter index
    of each row (None = base path). ``length`` (a prefill piece's real
    tokens, scalar) or ``live`` (a decode sweep's decoding rows, [S] bool):
    what a hybrid stack must know so that a bucket's padding and a parked
    slot's row leave the recurrent state alone — harmless in a K/V stripe,
    so every other model is handed neither. ``stats``: the caller takes a
    third result from ``forward`` where the model has expert blocks that
    count their routing (``models/moe.py moe_latent_block``). A new kind of
    cache leaf or ``forward`` argument is added here."""
    kw: dict[str, Any] = {"kernels": kernels}
    if stats and cfg.n_expert_layers:
        kw["with_stats"] = True
    if cfg.is_hybrid and length is not None:
        kw["valid_len"] = length[None]
    if cfg.is_hybrid and live is not None:
        kw["valid_len"] = live.astype(jnp.int32)
    if table is not None:
        kw["block_table"] = table
    if lora is not None:
        kw["lora"], kw["lora_ids"] = lora, ids
    return kw


# -- prefill --------------------------------------------------------------

# How a prefill piece reaches its slot's cache, with the operands that follow
# ``(params, cache, tokens, length)`` in the order each program has always
# taken them (a fresh piece has no ``offset``), and the label's prefix.
#   slot:  ``cache`` is the engine's dense stripes, ``addr`` a slot index —
#          the slot is sliced out, run as a batch of one and written back;
#   table: ``cache`` is the paged pool, ``addr`` the slot's block-table row
#          [1, MAXB], which routes the writes to its blocks;
#   whole: ``cache`` is the prefill lane's one-slot staging cache.
_PREFILL_OPERANDS = {
    "slot": ("addr", "offset"),
    "table": ("offset", "addr"),
    "whole": ("offset",),
}
_PREFILL_LABEL = {"slot": "", "table": "paged_", "whole": "disagg_"}


def _prefill_operands(cache_by: str, fresh: bool) -> tuple[str, ...]:
    return tuple(n for n in _PREFILL_OPERANDS[cache_by]
                 if not (fresh and n == "offset"))


def prefill_label(cache_by: str, fresh: bool, bucket: int) -> str:
    """Compile-statistics label of ``build_prefill``'s program at ``bucket``."""
    return (f"{_PREFILL_LABEL[cache_by]}{'' if fresh else 'chunk_'}"
            f"prefill[{bucket}]")


def prefill_bucket(n: int, ecfg) -> int:
    """The power-of-two bucket a piece of ``n`` tokens pads to: one compiled
    prefill program a bucket."""
    b = ecfg.min_prefill_bucket
    while b < n:
        b *= 2
    return min(b, ecfg.max_prefill_len)


def piece_operands(cache_by: str, piece: list[int], bucket: int, pad_id: int,
                   off: int, addr=None) -> tuple:
    """What follows ``(params, cache)`` in a call of ``build_prefill``'s
    program: ``piece`` padded to ``bucket`` as tokens [1, bucket], its real
    length, and the addressing operands (``off`` is dropped from a fresh
    piece's)."""
    tokens = jnp.asarray(piece + [pad_id] * (bucket - len(piece)),
                         dtype=jnp.int32)[None]
    return (tokens, jnp.int32(len(piece)),
            *(addr if n == "addr" else jnp.int32(off)
              for n in _prefill_operands(cache_by, off == 0)))


def build_prefill(cfg: ModelConfig, fwd, kernels: str, cache_by: str,
                  fresh: bool):
    """One prefill piece: ``tokens`` [1, bucket] written at the piece's
    absolute offset inside the slot, the logits of its last real position
    returned ([V] f32; a full [1, bucket, V] tensor is ~2 GB at 128k vocab
    for the server-default 4096 bucket, on the per-request path).

    ``fresh``: the piece starts the prompt — attention runs block-causal
    over the fresh projections (the flash prefill kernel on TPU). A
    continuation writes at ``offset`` and attends the whole cache with
    positional masking, exact for chunked prefill, so prompts longer than
    ``max_prefill_len`` do not truncate. ``cache_by``: see
    ``_PREFILL_OPERANDS``. One program per bucket (the shape of ``tokens``)."""
    operands = _prefill_operands(cache_by, fresh)

    def step(params, cache, tokens, length, *where, lora=None, ids=None):
        at = dict(zip(operands, where, strict=True))
        addr, offset = at.get("addr"), at.get("offset")
        pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
        if not fresh:
            pos = offset + pos
        sub = slice_cache_slots(cache, addr) if cache_by == "slot" else cache
        kw = forward_kwargs(
            cfg, kernels, table=addr if cache_by == "table" else None,
            lora=lora, ids=ids, length=length,
        )
        logits, new = fwd(
            params, cfg, tokens, pos,
            sub, jnp.zeros((1,), jnp.int32) if fresh else offset[None],
            fresh_prefill=fresh,
            logit_index=(length - 1)[None],
            **kw,
        )
        if cache_by == "slot":
            new = update_cache_slots(cache, new, addr)
        return new, logits[0, 0]

    if cache_by == "whole":
        step.__name__ = "fresh" if fresh else "chunk"
    else:
        step.__name__ = "prefill" if fresh else "chunk_prefill"
    return jax.jit(step, donate_argnums=(1,))


# -- decode ---------------------------------------------------------------

def unpack_mask(packed, vocab_size: int):
    """Device-side inverse of np.packbits(..., bitorder='little'):
    [..., ceil(V/8)] uint8 -> [..., V] bool. Grammar masks travel
    host->device EVERY constrained step; packing cuts that transfer 8x
    (~1 MB instead of ~8 MB per token at 64 slots x 128k vocab)."""
    bits = (packed[..., :, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    flat = bits.reshape(*packed.shape[:-1], -1)
    return flat[..., :vocab_size].astype(bool)


def build_decode(cfg: ModelConfig, fwd, kernels: str, n_steps: int = 1,
                 masked: bool = False):
    """Compiled decode of ``n_steps`` sampling steps over all S slots in ONE
    dispatch.

    The scan carries (cache, tokens, lengths, rng, counts) and stacks the
    sampled tokens [n_steps, S]; host state is the source of truth between
    dispatches, so a request finishing mid-chunk just has its surplus
    tokens discarded on the host. Their KV writes stay inside the slot's
    own buffer at positions >= the retained/valid length, where the
    positional attention mask (key j attends iff j <= query position) makes
    them unreachable — with prefix caching a later admission may SKIP
    re-prefilling those rows, so the mask, not overwrite-on-admission, is
    the safety invariant.

    ``masked`` (static; ``n_steps`` must be 1): two more operands follow
    ``freqs`` — bit-packed grammar masks [S, ceil(V/8)] (bit set = token
    allowed) and [S] bool, the rows they apply to. Logprobs come from the
    MASKED logits, the true sampling distribution under the constraint. One
    step per dispatch because the next mask depends on the token just
    emitted (the automaton is host-side; only the mask application rides
    the device), so there is no scan and the rng is used as it is passed."""
    if masked and n_steps != 1:
        raise ValueError("a masked decode step is one step per dispatch")

    def step(params, cache, tokens, lengths, temps, topks, topps, rng,
             counts, pres, freqs, *mask, table=None, lora=None, ids=None,
             live=None):
        # live: [S] bool, hybrid stacks only — the rows whose slot is
        # decoding. Every other row's recurrent state is held through
        # the sweep (its K/V write is harmless, as for any model)
        def one(c, toks, lens, r, cnt):
            kw = forward_kwargs(cfg, kernels, table=table, lora=lora,
                                ids=ids, live=live, stats=True)
            # routed: the expert blocks' counts over the live rows, int32 [4]
            # (nothing for a model without such blocks)
            logits, nc, *routed = fwd(
                params, cfg, toks[:, None], lens[:, None], c, lens, **kw,
            )
            lg = apply_penalties(logits[:, 0, :], cnt, pres, freqs)
            if masked:
                packed_mask, use_mask = mask
                allowed = unpack_mask(packed_mask, cfg.vocab_size)
                lg_masked = jnp.where(allowed, lg, -jnp.inf)
                lg = jnp.where(use_mask[:, None], lg_masked, lg)
            nxt = sample_tokens(lg, r, temps, topks, topps)
            return nc, nxt, (nxt, *token_logprobs(lg, nxt), *routed)

        if masked:
            nc, nxt, ys = one(cache, tokens, lengths, rng, counts)
            # nxt doubles as the on-device token carry (same contract as
            # the plain program), so a constrained sweep keeps the device
            # token buffer warm for the sweeps that follow it
            return nc, count_tokens(counts, nxt), nxt, \
                tuple(y[None] for y in ys)

        def body(carry, _):
            c, toks, lens, r, cnt = carry
            r, sub = jax.random.split(r)
            nc, nxt, ys = one(c, toks, lens, sub, cnt)
            # counts update INSIDE the scan: the next fused step's
            # penalty must see this step's emission
            return (nc, nxt, lens + 1, r, count_tokens(cnt, nxt)), ys

        (c, toks, _, _, cnt), ys = jax.lax.scan(
            body, (cache, tokens, lengths, rng, counts), None,
            length=n_steps,
        )
        # toks is the final carry: the last sampled token per slot,
        # returned ON DEVICE so the next dispatch can feed it without a
        # host round-trip (the double-buffered pipeline's token path)
        # ys: ([n,S], [n,S], [n,S,K], [n,S,K]) and, with expert blocks, [n,3]
        return c, cnt, toks, ys

    step.__name__ = "decode_masked" if masked else "decode"
    return jax.jit(step, donate_argnums=(1, 8))


def build_spec_step_sampled(cfg_t: ModelConfig, cfg_d: ModelConfig, k: int,
                            kernels: str = "auto"):
    """Speculative round with REJECTION SAMPLING (Leviathan/Chen): sampled
    requests speculate too, and the emitted tokens are distributed exactly
    as plain sampling from the target's filtered distribution.

    Per slot (temperature/top-k/top-p as [S] vectors, the continuous-
    batching convention): the drafter SAMPLES k proposals from its own
    filtered distribution q; the target computes its filtered distribution
    p at every position in one T=k forward; draft i is accepted with
    probability min(1, p(d_i)/q(d_i)); at the first rejection the
    replacement is drawn from the residual ``normalize(max(p - q, 0))``,
    and when every draft survives a bonus token is drawn from the last p.

    Temperature-0 rows degenerate EXACTLY to the greedy accept rule (see
    sampling.filter_logits): their distributions are one-hots, so the
    ratio is 1 on an argmax match, 0 otherwise, and the residual is the
    target's argmax — greedy requests emit bit-identical tokens to plain
    greedy decode even through this sampled path, which is why the engine
    can run ONE spec executable for a mixed greedy/sampled batch.

    Reference analog: vLLM's rejection sampler is what lets its spec
    decode serve sampled traffic (the reference benchmarks it via the
    speculative-decoding profile, runners/profiles/speculative-decoding
    .yaml); greedy-only speculation was VERDICT round-4 item 3's gap."""

    def spec_step(params_t, cache_t, params_d, cache_d, last, lengths,
                  temps, topks, topps, rng):
        rng_d, rng_acc, rng_res = jax.random.split(rng, 3)

        # drafter: k SAMPLED proposals + the full proposal distribution per
        # step (the rejection test and the residual both need q)
        def dbody(carry, rng_step):
            c, tok, lens = carry
            logits, nc = forward(
                params_d, cfg_d, tok[:, None], lens[:, None], c, lens,
                **forward_kwargs(cfg_d, kernels),
            )
            q_lg = filter_logits(logits[:, 0, :], temps, topks, topps)
            nxt = jax.random.categorical(rng_step, q_lg).astype(jnp.int32)
            return (nc, nxt, lens + 1), (nxt, jax.nn.softmax(q_lg, axis=-1))

        (cache_d, _, _), (drafts, q_all) = jax.lax.scan(
            dbody, (cache_d, last, lengths), jax.random.split(rng_d, k)
        )
        drafts = drafts.T                                   # [S, k]
        q_all = q_all.transpose(1, 0, 2)                    # [S, k, V]

        fed = jnp.concatenate([last[:, None], drafts[:, :-1]], axis=1)
        pos = lengths[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
        logits, nc_t = forward(params_t, cfg_t, fed, pos, cache_t, lengths,
                               **forward_kwargs(cfg_t, kernels))
        S, V = logits.shape[0], logits.shape[-1]
        p_all = jax.nn.softmax(
            filter_logits(
                logits.reshape(S * k, V),
                jnp.repeat(temps, k), jnp.repeat(topks, k),
                jnp.repeat(topps, k),
            ).reshape(S, k, V),
            axis=-1,
        )                                                   # [S, k, V]

        # rejection test on the k-1 verifiable drafts
        if k > 1:
            dcols = drafts[:, : k - 1, None]
            p_tok = jnp.take_along_axis(p_all[:, : k - 1], dcols, axis=2)[..., 0]
            q_tok = jnp.take_along_axis(q_all[:, : k - 1], dcols, axis=2)[..., 0]
            u = jax.random.uniform(rng_acc, p_tok.shape)
            # u in [0,1): ratio >= 1 always accepts, ratio 0 always rejects
            accept = u * q_tok < p_tok                      # [S, k-1]
            a = jnp.where(
                jnp.all(accept, axis=1),
                k - 1,
                jnp.argmin(accept.astype(jnp.int32), axis=1),
            ).astype(jnp.int32)
        else:
            a = jnp.zeros(last.shape, jnp.int32)

        # token at the stop position: residual max(p-q, 0) on a rejection,
        # plain p for the all-accepted bonus (position k-1 has no verified
        # draft). A numerically-empty residual (p == q) falls back to p —
        # the rejection probability there is 0 anyway.
        p_a = jnp.take_along_axis(
            p_all, a[:, None, None], axis=1
        )[:, 0]                                             # [S, V]
        q_a = jnp.take_along_axis(q_all, a[:, None, None], axis=1)[:, 0]
        residual = jnp.maximum(p_a - q_a, 0.0)
        res_sum = jnp.sum(residual, axis=-1, keepdims=True)
        use_res = (a[:, None] < k - 1) & (res_sum > 0)
        dist = jnp.where(use_res, residual / jnp.maximum(res_sum, 1e-20), p_a)
        stop_tok = jax.random.categorical(
            rng_res, jnp.log(jnp.maximum(dist, 1e-38))
        ).astype(jnp.int32)

        j = jnp.arange(k, dtype=jnp.int32)[None, :]
        emit = jnp.where(
            j < a[:, None], drafts,
            jnp.where(j == a[:, None], stop_tok[:, None], -1),
        )
        return nc_t, cache_d, emit

    return jax.jit(spec_step, donate_argnums=(1, 3))


# -- the small programs ----------------------------------------------------

def build_first(cfg: ModelConfig):
    """First-token sampler over the prefill's last-position logits: mask
    application + sampling + logprobs in one dispatch."""

    def first(last_logits, rng, temp, topk, topp, packed_mask, use_mask):
        lg = last_logits[None, :]
        mask = unpack_mask(packed_mask, cfg.vocab_size)
        lg_masked = jnp.where(mask[None], lg, -jnp.inf)
        lg = jnp.where(use_mask, lg_masked, lg)
        tok = sample_tokens(lg, rng, temp[None], topk[None], topp[None])
        lp, tids, tlps = token_logprobs(lg, tok)
        return tok[0], lp[0], tids[0], tlps[0]

    return jax.jit(first)


def build_reset(carry: bool = False):
    """Admission-time reset of one slot's penalty-count row: zero it, then
    record the first generated token.

    ``carry``: the admission is dispatched behind a sweep in flight
    (docs/DECODE_PIPELINE.md), so ``tok`` is still on the device: a fourth
    operand, the decode token carry [S], comes back with ``tok`` in the
    slot's row, and the next sweep feeds it without the host having seen
    the token."""

    def reset(counts, slot, tok, *tokens):
        row = jnp.zeros((counts.shape[1],), counts.dtype).at[tok].add(1)
        counts = jax.lax.dynamic_update_index_in_dim(counts, row, slot, 0)
        if not carry:
            return counts
        (tokens,) = tokens
        return counts, tokens.at[slot].set(tok.astype(tokens.dtype))

    return jax.jit(reset, donate_argnums=(0,))


def build_cache_write(name: str):
    """``update_cache_slots`` with the cache donated, so XLA updates it in
    place. As ``inject`` it writes a prefill lane's staged stripe back at
    the destination slot (the exact inverse of the lane's staging slice);
    as ``kv_block_write`` it installs one pool block's leaves (tier
    promotion, the migration importer). One executable for every call."""

    def write(cache, sub, index):
        return update_cache_slots(cache, sub, index)

    write.__name__ = name
    return jax.jit(write, donate_argnums=(0,))

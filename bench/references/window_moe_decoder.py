"""The plain reference of a decoder whose every layer is attention over a window
or over the whole context, with or without rotary embedding, and routed
ReLU-gated GLU experts behind a softmax router that reads the layer's input
(SmallThinker).

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no ring, no kernels, no batching, and no import from the program. ``x``
is the residual stream; one layer ``l``::

    r = x W_router                      float32 logits over all the experts, from the layer's
                                        INPUT: before the attention norm
    S = the top moe_num_active_primary_experts of r;  w = softmax(r[S])   (the softmax over all,
                                        its top renormalised: norm_topk_prob)
    h = RMSNorm(x; norm_in)
    q, k, v = W_q h, W_k h, W_v h       no bias, no QK-norm
    rope_layout[l] == 1:                rotary embedding on all head_dim dimensions of q and k
                                        (split-half, rope_theta, no scaling); 0: no position enters
    key j is visible to query i iff j <= i, and, where sliding_window_layout[l] == 1,
                                        i - j < sliding_window_size   (plain masks on absolute positions)
    x = x + W_o softmax(q k^T head_dim^-1/2) v          grouped queries
    g = RMSNorm(x; ffn_norm)
    x = x + sum_{e in S} w_e W_down_e (relu(W_gate_e g) * W_up_e g)       (no shared expert, no dense layer)

then the final RMSNorm and an untied head.

Two things keep an 8704-position sequence of 64 experts inside one chip's
memory and a run's time, and change no number: attention runs one block of
``QUERY_BLOCK`` queries after another against all the keys; and only the
chosen experts are multiplied: the ``T x K`` (token, expert) pairs are sorted
by expert, each expert's run padded to whole blocks of ``PAIR_BLOCK`` rows
(pad rows carry weight 0), and one block after another gathers its expert's
three matrices and its tokens' rows and adds its weighted result to its
tokens.

Departures from the published description, each an assumption the
configuration's file lists: weights are the benchmark's own from ``--seed``
(``bench/references/window_moe_weights.py``); the described "secondary
experts" have no key in the source's config and no weight here; one layer's
float32 weights are held at a time (1.5 GB of experts).

``logits_after(...)`` is the contract of ``bench/references/dense_decoder.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import config

weights = config.load_module("references", "window_moe_weights")

QUERY_BLOCK = 512
PAIR_BLOCK = 128


def _rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _rope(x, theta):
    """x: [T, heads, head_dim]; position of row t is t."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w, *, m: dict, rope: bool, window: int | None):
    """x [T, hidden] (normed) -> [T, hidden]; T a multiple of the query block
    or shorter than one."""
    t = x.shape[0]
    f32 = weights.kind(m).to_float32
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (x @ f32(w["wq"])).reshape(t, nh, hd)
    k = (x @ f32(w["wk"])).reshape(t, nkv, hd)
    v = (x @ f32(w["wv"])).reshape(t, nkv, hd)
    if rope:
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are no whole number of query blocks of {block}")
    j = jnp.arange(t)[None, :]

    def some_queries(args):
        qb, i = args                                   # [block, nh, hd], [block]
        scores = jnp.einsum("tkgd,skd->kgts", qb.reshape(block, nkv, nh // nkv, hd), k)
        visible = j <= i[:, None]
        if window is not None:
            visible = visible & (i[:, None] - j < window)
        probs = jax.nn.softmax(
            jnp.where(visible[None, None], scores / math.sqrt(hd), -jnp.inf), axis=-1)
        return jnp.einsum("kgts,skd->tkgd", probs, v).reshape(block, nh * hd)

    o = jax.lax.map(some_queries, (q.reshape(t // block, block, nh, hd),
                                   jnp.arange(t).reshape(t // block, block)))
    return o.reshape(t, nh * hd) @ f32(w["wo"])


def route(r_in, w, *, m: dict):
    """The layer's input ``r_in`` [T, hidden] -> (weights [T, K] float32,
    expert ids [T, K]): the top K logits, a softmax over them."""
    z = weights.sizes(m)
    logits = r_in @ w["router"]
    top, chosen = jax.lax.top_k(logits, z["top_k"])
    if m.get("norm_topk_prob", True):
        return jax.nn.softmax(top, axis=-1), chosen
    return jnp.exp(top - jax.nn.logsumexp(logits, axis=-1, keepdims=True)), chosen


def routed_part(r_in, g, w, *, m: dict, act=jax.nn.relu):
    """What the chosen experts add for ``g`` [T, hidden], routed from
    ``r_in``: the (token, expert) pairs sorted by expert, one block of pairs
    of one expert after another."""
    f32 = weights.kind(m).to_float32
    z = weights.sizes(m)
    t, n_e, k = g.shape[0], z["experts"], z["top_k"]
    wts, chosen = route(r_in, w, m=m)
    expert = chosen.reshape(-1)                                   # [T K]
    order = jnp.argsort(expert, stable=True)
    expert, token, weight = (expert[order], jnp.repeat(jnp.arange(t), k)[order],
                             wts.reshape(-1)[order])
    count = jnp.bincount(expert, length=n_e)                      # pairs an expert
    padded = -(-count // PAIR_BLOCK) * PAIR_BLOCK                 # its rows, in whole blocks
    row0, pair0 = jnp.cumsum(padded) - padded, jnp.cumsum(count) - count
    rows = -(-(t * k + n_e * (PAIR_BLOCK - 1)) // PAIR_BLOCK) * PAIR_BLOCK
    at = row0[expert] + jnp.arange(t * k) - pair0[expert]         # a pair's row
    token = jnp.full((rows,), t, jnp.int32).at[at].set(token)     # pad rows: the zero row t
    weight = jnp.zeros((rows,), jnp.float32).at[at].set(weight)
    # the expert of each block (blocks past the last expert's repeat it, all weight 0)
    of_block = jnp.clip(jnp.searchsorted(jnp.cumsum(padded), jnp.arange(0, rows, PAIR_BLOCK),
                                         side="right"), 0, n_e - 1)
    g0 = jnp.concatenate([g, jnp.zeros((1, g.shape[1]), g.dtype)])

    def one(acc, blk):
        tok, wt, e = blk
        gate, up, down = (f32(jax.tree_util.tree_map(lambda a: a[e], w[name]))
                          for name in ("w_gate", "w_up", "w_down"))
        xb = g0[tok]
        f = (act(xb @ gate) * (xb @ up)) @ down
        return acc.at[tok].add(wt[:, None] * f), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(g0), (
        token.reshape(-1, PAIR_BLOCK), weight.reshape(-1, PAIR_BLOCK), of_block))
    return acc[:t]


def layer(x, w, *, m: dict, rope: bool, window: int | None):
    """One layer on x [T, hidden] float32."""
    eps = m["rms_norm_eps"]
    x_in = x
    x = x + attention(_rms_norm(x, w["attn"]["norm_in"], eps), w["attn"], m=m,
                      rope=rope, window=window)
    return x + routed_part(x_in, _rms_norm(x, w["experts"]["ffn_norm"], eps), w["experts"], m=m)


def kinds_of(m: dict, li: int) -> tuple[bool, int | None]:
    """(rotary embedding, window) of layer ``li``: the two published layouts."""
    return (bool(m["rope_layout"][li]),
            m["sliding_window_size"] if m["sliding_window_layout"][li] else None)


def _head(x, top, *, m: dict):
    return _rms_norm(x, top["final_norm"], m["rms_norm_eps"]) @ top["lm_head"].astype(jnp.float32).T


def _padded(n: int, to: int) -> int:
    return -(-n // to) * to


def logits_after(seed: int, m: dict, sequences: list[list[int]], first: list[int],
                 pad_sequence_to: int = 256, pad_rows_to: int = 64) -> list[np.ndarray]:
    """For each token sequence, the float32 logits ``[len(seq) - first, vocab]``
    at positions ``first - 1 .. len(seq) - 2``: row ``r`` is the distribution
    from which token ``first + r`` of the sequence was drawn."""
    if m.get("tie_word_embeddings"):
        raise ValueError("this reference has an untied head: the configuration "
                         "must not say tie_word_embeddings")
    root = weights.root_key(seed)
    with jax.default_matmul_precision("highest"):
        gen = jax.jit(partial(weights.layer_weights, m=m))
        run = {kd: jax.jit(partial(layer, m=m, rope=kd[0], window=kd[1]))
               for kd in {kinds_of(m, li) for li in range(m["num_hidden_layers"])}}
        run_head = jax.jit(partial(_head, m=m))
        top = jax.jit(partial(weights.top_weights, m=m))(root)
        xs = []
        for seq in sequences:
            t = _padded(len(seq), pad_sequence_to)
            ids = jnp.asarray(list(seq) + [0] * (t - len(seq)), jnp.int32)
            xs.append(top["embed"][ids].astype(jnp.float32))
        for li in range(m["num_hidden_layers"]):
            w = gen(root, layer=jnp.int32(li))
            xs = [run[kinds_of(m, li)](x, w) for x in xs]
            del w
        out = []
        for x, seq, f in zip(xs, sequences, first):
            rows = _padded(len(seq) - f, pad_rows_to)
            idx = jnp.clip(jnp.arange(rows) + f - 1, 0, len(seq) - 2)
            out.append(np.asarray(run_head(x[idx], top))[: len(seq) - f])
    return out

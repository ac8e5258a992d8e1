"""The plain reference of a decoder whose layers pair a sequence operator with a
feed-forward: gated short convolutions and attention layers in the (irregular)
order ``layer_types`` gives, a dense SwiGLU MLP under the first
``num_dense_layers`` layers and routed SwiGLU experts under the rest (LFM2-MoE).

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels, no batching, and no import from the program. ``x`` is the
residual stream, ``d`` the hidden size, ``L = conv_L_cache``::

    x = embed[tokens]
    for each layer l:
      h = RMSNorm(x; norm_in)
      conv:            [B | C | u] = W_in h   (three streams of width d, in that order, no bias)
                       g_t = B_t * u_t;   v_t = sum_{j<L} taps[j] * g_{t-(L-1)+j}   (g before
                       position 0 is 0; depthwise, the last tap on the current position, no
                       bias, NO activation);   y = W_out (C * v)
      full_attention:  q, k, v = W_q h, W_k h, W_v h (no bias); each head of q and of k through
                       an RMSNorm over its head_dim, ONE gain [head_dim] for every head; rotary
                       embedding on all head_dim dimensions (split-half, rope_theta, no scaling);
                       causal softmax at head_dim^-1/2, grouped queries;   y = W_o o
      x = x + y
      g = RMSNorm(x; ffn_norm)
      l < num_dense_layers:  f = W_down (silu(W_gate g) * W_up g)          (intermediate_size)
      else:  s = sigmoid(g W_r) over all num_experts, float32, no bias; the top
             num_experts_per_tok of s + b are chosen (b the selection bias: it chooses and
             does not weigh); w = s[chosen]; w <- w / (sum w + 1e-6) (norm_topk_prob);
             w <- routed_scaling_factor w;
             f = sum_{e chosen} w_e W_down_e (silu(W_gate_e g) * W_up_e g)  (moe_intermediate_size;
             no shared expert)
      x = x + f
    logits = embed RMSNorm(x_L; final_norm)          (the head is the embedding, transposed)

The sum over the chosen experts runs one expert after another over the experts
held here (all of them unless ``m["share"]`` says otherwise), each token taking
an expert with its weight ``w_e`` and with 0 where it did not choose it.
``routed_part`` of the shares of a layer add up to the uncut layer.

Departures from the published description, each an assumption the
configuration's file lists: weights are the benchmark's own from ``--seed``
(``bench/references/conv_moe_weights.py``), the selection bias among them
(a trained model's is learned); ``use_expert_bias`` true and ``conv_bias``
false are built in, not read; one layer's float32 weights are held at a time
(an expert layer is 1.4 GB).

``logits_after(...)`` is the contract of ``bench/references/dense_decoder.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import config

weights = config.load_module("references", "conv_moe_weights")

NORM_EPS = 1e-6     # in the normalisation over the chosen, as the family's code has it


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


def conv_operator(x, w, *, m: dict):
    """x [T, hidden] (normed) -> [T, hidden]."""
    t, d = x.shape
    f32 = weights.kind(m).to_float32
    proj = x @ f32(w["in_proj"])
    b, c, u = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    taps = w["conv"].astype(jnp.float32)                     # [L, d]
    k = taps.shape[0]
    g = jnp.concatenate([jnp.zeros((k - 1, d), jnp.float32), b * u], axis=0)
    v = sum(taps[j] * g[j:j + t] for j in range(k))
    return (c * v) @ f32(w["out_proj"])


def attention_operator(x, w, *, m: dict):
    t = x.shape[0]
    f32 = weights.kind(m).to_float32
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["norm_eps"]
    q = _rms_norm((x @ f32(w["wq"])).reshape(t, nh, hd), w["q_norm"], eps)
    k = _rms_norm((x @ f32(w["wk"])).reshape(t, nkv, hd), w["k_norm"], eps)
    v = (x @ f32(w["wv"])).reshape(t, nkv, hd)
    q = _rope(q, m["rope_theta"]).reshape(t, nkv, nh // nkv, hd)
    k = _rope(k, m["rope_theta"])
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nh * hd) @ f32(w["wo"])


def dense_ffn(x, w, *, m: dict):
    f32 = weights.kind(m).to_float32
    return (jax.nn.silu(x @ f32(w["w_gate"])) * (x @ f32(w["w_up"]))) @ f32(w["w_down"])


def route(x, w, *, m: dict):
    """x [T, hidden] -> float32 [T, num_experts]: the weight with which each
    token takes each expert of the WHOLE layer, zero where it was not chosen."""
    z = weights.sizes(m)
    s = jax.nn.sigmoid(x @ w["router"])
    _, chosen = jax.lax.top_k(s + w["router_bias"], z["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if m.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + NORM_EPS)
    picked = picked * m["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def routed_part(x, w, *, m: dict):
    """What the experts held here add, one expert after another."""
    f32 = weights.kind(m).to_float32
    z = weights.sizes(m)
    take = route(x, w, m=m)[:, z["expert_offset"]:z["expert_offset"] + z["held"]]   # [T, held]

    def one(acc, e):
        gate, up, down, w_e = e
        f = (jax.nn.silu(x @ f32(gate)) * (x @ f32(up))) @ f32(down)
        return acc + w_e[:, None] * f, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (w["w_gate"], w["w_up"], w["w_down"], take.T))
    return acc


OPERATORS = {"conv": conv_operator, "attn": attention_operator}
FFNS = {"mlp": dense_ffn, "experts": routed_part}


def layer(x, w, *, m: dict, operator: str, ffn: str):
    """One layer on x [T, hidden] float32."""
    eps = m["norm_eps"]
    x = x + OPERATORS[operator](_rms_norm(x, w["operator"]["norm_in"], eps), w["operator"], m=m)
    return x + FFNS[ffn](_rms_norm(x, w["ffn"]["ffn_norm"], eps), w["ffn"], m=m)


def _head(x, top, *, m: dict):
    return _rms_norm(x, top["final_norm"], m["norm_eps"]) @ top["embed"].astype(jnp.float32).T


def _padded(n: int, to: int) -> int:
    return -(-n // to) * to


def logits_after(seed: int, m: dict, sequences: list[list[int]], first: list[int],
                 pad_sequence_to: int = 256, pad_rows_to: int = 64) -> list[np.ndarray]:
    """For each token sequence, the float32 logits ``[len(seq) - first, vocab]``
    at positions ``first - 1 .. len(seq) - 2``: row ``r`` is the distribution
    from which token ``first + r`` of the sequence was drawn."""
    if not m.get("tie_word_embeddings"):
        raise ValueError("this reference ties the head to the embedding: "
                         "the configuration must say tie_word_embeddings")
    root = weights.root_key(seed)
    with jax.default_matmul_precision("highest"):
        pairs = {(weights.operator_of(m, li), weights.ffn_of(m, li))
                 for li in range(m["num_hidden_layers"])}
        gen = {pr: jax.jit(partial(weights.layer_weights, m=m, operator=pr[0], ffn=pr[1]))
               for pr in pairs}
        run = {pr: jax.jit(partial(layer, m=m, operator=pr[0], ffn=pr[1])) for pr in pairs}
        run_head = jax.jit(partial(_head, m=m))
        top = jax.jit(partial(weights.top_weights, m=m))(root)
        xs = []
        for seq in sequences:
            t = _padded(len(seq), pad_sequence_to)
            ids = jnp.asarray(list(seq) + [0] * (t - len(seq)), jnp.int32)
            xs.append(top["embed"][ids].astype(jnp.float32))
        for li in range(m["num_hidden_layers"]):
            pr = (weights.operator_of(m, li), weights.ffn_of(m, li))
            w = gen[pr](root, layer=jnp.int32(li))
            xs = [run[pr](x, w) for x in xs]
            del w
        out = []
        for x, seq, f in zip(xs, sequences, first):
            rows = _padded(len(seq) - f, pad_rows_to)
            idx = jnp.clip(jnp.arange(rows) + f - 1, 0, len(seq) - 2)
            out.append(np.asarray(run_head(x[idx], top))[: len(seq) - f])
    return out

"""The plain reference of a hybrid decoder: gated-delta-rule linear-attention
layers beside full-attention layers, from the published equations.

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels, no batching, no chunks, and no import from the program.
The linear mixer (Yang, Kautz, Hatamizadeh, arXiv:2412.06464) runs its
recurrence as written, one token after another::

    block (both kinds):  h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(W_down(silu(W_gate h) * W_up h))
    full mixer:          q, k, v = W_q x, W_k x, W_v x;  RMSNorm over the whole q and the whole k;
                         RoPE; causal softmax attention at head_dim^-1/2; W_o
    linear mixer:        q~, k~, v~ = W_q x, W_k x, W_v x; each channel through a causal depthwise
                         convolution (width linear_conv_kernel_dim) and SiLU; per head q_t, k_t
                         L2-normalised (q_t times dk^-1/2);
                         b_t = sigmoid(W_b x_t), doubled under linear_allow_neg_eigval;
                         a_t = exp(-exp(A_log) softplus(W_a x_t + dt_bias));
                         S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T,  S_0 = 0;
                         o_t = S_t^T q_t;  y_t = W_o[RMSNorm_dv(o_t) * silu(W_g x_t)]
    logits = W_head RMSNorm(y_L)

Departures from the published description, each an assumption the
configuration's file lists: the source's config names the layer kinds and
the mixer's sizes but not the norm placement (OLMo-2/3's reordered norm and
QK-norm are taken), ``rope_theta`` (OLMo-3's 500000), the activation of the
output gate (SiLU), a bias in the convolution (none), the epsilon of the L2
normalisation (``x / sqrt(sum x^2 + 1e-6)``, as the published kernels have
it), and the order of the taps (the last tap multiplies the current input).
Weights are the benchmark's own from ``--seed``
(``bench/references/hybrid_linear_weights.py``), one layer at a time.

``logits_after(...)`` is the contract of ``bench/references/dense_decoder.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import config

weights = config.load_module("references", "hybrid_linear_weights")
L2_EPS = 1e-6


def _rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _rope(x, theta):
    """x: [T, heads, head_dim]; position of row t is t; split-half convention."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def full_mixer(x, w, *, m: dict):
    t = x.shape[0]
    f32 = weights.kind(m).to_float32
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    q = _rms_norm(x @ f32(w["wq"]), w["q_norm"], eps)
    k = _rms_norm(x @ f32(w["wk"]), w["k_norm"], eps)
    v = (x @ f32(w["wv"])).reshape(t, nkv, hd)
    q = _rope(q.reshape(t, nh, hd), m["rope_theta"])
    k = _rope(k.reshape(t, nkv, hd), m["rope_theta"])
    g = nh // nkv
    scores = jnp.einsum("tkgd,skd->kgts", q.reshape(t, nkv, g, hd), k) / math.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nh * hd)
    return o @ f32(w["wo"])


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_mixer(x, w, *, m: dict):
    t = x.shape[0]
    f32 = weights.kind(m).to_float32
    h, kd, vd, _ = weights.sizes(m)
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    u = jnp.concatenate([x @ f32(w["wq"]), x @ f32(w["wk"]), x @ f32(w["wv"])], axis=-1)
    taps = w["conv"].astype(jnp.float32)                     # [K, C], last tap on the current input
    width = taps.shape[0]
    ext = jnp.concatenate([jnp.zeros((width - 1, u.shape[1]), jnp.float32), u], axis=0)
    y = jax.nn.silu(sum(ext[j:j + t] * taps[j] for j in range(width)))
    q = _l2(y[:, :kd].reshape(t, h, dk)) * dk ** -0.5
    k = _l2(y[:, kd:2 * kd].reshape(t, h, dk))
    v = y[:, 2 * kd:].reshape(t, h, dv)
    beta = jax.nn.sigmoid(x @ w["wb"].astype(jnp.float32))
    if m.get("linear_allow_neg_eigval"):
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(w["A_log"]) * jax.nn.softplus(
        x @ w["wa"].astype(jnp.float32) + w["dt_bias"]))

    def step(s, xs):                                         # s [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[:, None, None] * s
        read = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + b_t[:, None, None] * k_t[:, :, None] * (v_t - read)[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((h, dk, dv), jnp.float32), (q, k, v, alpha, beta))
    o = _rms_norm(o, w["o_norm"], m["rms_norm_eps"]).reshape(t, vd)
    return (o * jax.nn.silu(x @ f32(w["wg"]))) @ f32(w["wo"])


def layer(x, w, *, m: dict, layer_type: str):
    """One decoder layer on x [T, hidden] float32."""
    f32 = weights.kind(m).to_float32
    eps = m["rms_norm_eps"]
    mixer = full_mixer if layer_type == weights.FULL else linear_mixer
    h = x + _rms_norm(mixer(x, w, m=m), w["post_attn_norm"], eps)
    gated = jax.nn.silu(h @ f32(w["w_gate"])) * (h @ f32(w["w_up"]))
    return h + _rms_norm(gated @ f32(w["w_down"]), w["post_mlp_norm"], eps)


def _head(x, top, *, m: dict):
    h = _rms_norm(x, top["final_norm"], m["rms_norm_eps"])
    head = top["embed"] if m.get("tie_word_embeddings") else top["lm_head"]
    return h @ head.astype(jnp.float32).T


def _padded(n: int, to: int) -> int:
    return -(-n // to) * to


def logits_after(seed: int, m: dict, sequences: list[list[int]], first: list[int],
                 pad_sequence_to: int = 256, pad_rows_to: int = 64) -> list[np.ndarray]:
    """For each token sequence, the float32 logits ``[len(seq) - first, vocab]``
    at positions ``first - 1 .. len(seq) - 2``: row ``r`` is the distribution
    from which token ``first + r`` of the sequence was drawn."""
    root = weights.root_key(seed)
    with jax.default_matmul_precision("highest"):
        gen, run = {}, {}
        for kind in (weights.FULL, weights.LINEAR):
            gen[kind] = jax.jit(partial(weights.layer_weights, m=m, layer_type=kind))
            run[kind] = jax.jit(partial(layer, m=m, layer_type=kind))
        run_head = jax.jit(partial(_head, m=m))
        top = jax.jit(partial(weights.top_weights, m=m))(root)
        xs = []
        for seq in sequences:
            t = _padded(len(seq), pad_sequence_to)
            ids = jnp.asarray(list(seq) + [0] * (t - len(seq)), jnp.int32)
            xs.append(top["embed"][ids].astype(jnp.float32))
        for li, kind in enumerate(m["layer_types"]):
            w = gen[kind](root, layer=jnp.int32(li))
            xs = [run[kind](x, w) for x in xs]
        out = []
        for x, seq, f in zip(xs, sequences, first):
            rows = _padded(len(seq) - f, pad_rows_to)
            idx = jnp.clip(jnp.arange(rows) + f - 1, 0, len(seq) - 2)
            out.append(np.asarray(run_head(x[idx], top))[: len(seq) - f])
    return out

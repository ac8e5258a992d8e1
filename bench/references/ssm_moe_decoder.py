"""The plain reference of a decoder made of single-mixer blocks: Mamba-2
state-space blocks, attention blocks and routed latent experts beside a shared
expert, in the order ``hybrid_override_pattern`` gives (``M``, ``*``, ``E``).

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels, no batching, no chunks, and no import from the program.
``x`` is the residual stream. Every block is ``x <- x + Mixer(RMSNorm(x))``
with one mixer; after the last block a final RMSNorm and ``lm_head``
(untied)::

    M  Mamba-2 (Dao and Gu, arXiv:2405.21060), H heads of P channels, G groups, state N:
       [z | xBC | dt~] = W_in x;  xBC through a causal depthwise convolution of width
       conv_kernel WITH bias, then SiLU; xBC = [x [H, P] | B [G, N] | C [G, N]], head h
       reading group h // (H / G);  dt_t = softplus(dt~_t + dt_bias);  A = -exp(A_log);
       S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  S_0 = 0;   y_t = S_t C_t + D x_t;
       gate first, then a grouped norm: y <- RMSNorm_group(y * silu(z)) over G groups of
       H P / G channels, with a gain;  out = W_out y.   The recurrence runs token by token.
    *  attention: q, k, v = W_q x, W_k x, W_v x; causal softmax at head_dim^-1/2; W_o.
       NO rotary embedding: the blocks carry no position.
    E  router in float32: s = sigmoid(x W_r) over all router_width experts; the top
       num_experts_per_tok of s + b (b the selection bias) are chosen; w = s[chosen],
       w <- w / (sum w + 1e-20) over ALL the chosen, w <- routed_scaling_factor w;
       u = x W_in (hidden -> latent); each chosen expert e: f_e(u) = relu(u W1_e)^2 W2_e,
       no gate, no bias; r = (sum_e w_e f_e(u)) W_out (latent -> hidden);
       shared expert at full width: g = relu(x S1)^2 S2.   out = r + g.
    logits = W_head RMSNorm(x_L)

**The share.** The configuration states which experts of each layer this chip
holds (``m["share"]``: ``expert_offset``, and ``n_routed_experts`` the number
held, of a router ``router_width`` wide). The router scores, chooses and
normalises over ALL the experts, as the whole model does; the sum over ``e``
runs over the chosen experts that are held here. What the others would add is
left out, and that partial result goes on to the next block. With every expert
held this is the uncut layer; ``routed_part`` of the four quarters of a layer
add up to it (``tests/test_ssm_moe.py``).

Departures from the published description, each an assumption the
configuration's file lists: the attention blocks apply no rotary embedding
(the family's attention carries none; the source's ``rope_theta`` is not read);
the multi-token-prediction module (``num_nextn_predict_layers`` 1) is not
built: it drafts, and the next-token logits do not depend on it;
``n_group`` 1 / ``topk_group`` 1 make group-limited routing a no-op, so it is
not written; the convolution's last tap multiplies the current input.
``time_step_min/max/floor`` and ``rescale_prenorm_residual`` shape a trained
model's initialisation only; weights here are the benchmark's own from
``--seed`` (``bench/references/ssm_moe_weights.py``), one block at a time: an
expert block is 2.8 GB in float32, so no more than one is ever held.

``logits_after(...)`` is the contract of ``bench/references/dense_decoder.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import config

weights = config.load_module("references", "ssm_moe_weights")


def _rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def mamba_mixer(x, w, *, m: dict):
    """x [T, hidden] (normed) -> [T, hidden]; the recurrence token by token."""
    t = x.shape[0]
    f32 = weights.kind(m).to_float32
    z = weights.sizes(m)
    h, p, n, g, di = z["heads"], z["head_dim"], z["state"], z["groups"], z["d_inner"]
    proj = x @ f32(w["in_proj"])
    gate, xbc, dt = proj[:, :di], proj[:, di:di + z["channels"]], proj[:, di + z["channels"]:]
    taps = w["conv"].astype(jnp.float32)                     # [K, C], last tap on the current input
    k = taps.shape[0]
    ext = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    xbc = jax.nn.silu(sum(ext[j:j + t] * taps[j] for j in range(k))
                      + w["conv_b"].astype(jnp.float32))
    xs = xbc[:, :di].reshape(t, h, p)
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(t, g, n), h // g, axis=1)     # [T, H, N]
    c = jnp.repeat(xbc[:, di + g * n:].reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [T, H]
    a = -jnp.exp(w["A_log"])                                 # [H]

    def step(s, inp):                                        # s [H, P, N]
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32), (xs, b, c, dt))
    y = (y + w["D"][:, None] * xs).reshape(t, di) * jax.nn.silu(gate)
    yg = y.reshape(t, g, di // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + m["rms_norm_eps"])
    return (yg.reshape(t, di) * w["norm"].astype(jnp.float32)) @ f32(w["out_proj"])


def attention_mixer(x, w, *, m: dict):
    t = x.shape[0]
    f32 = weights.kind(m).to_float32
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = (x @ f32(w["wq"])).reshape(t, nkv, nh // nkv, hd)
    k = (x @ f32(w["wk"])).reshape(t, nkv, hd)
    v = (x @ f32(w["wv"])).reshape(t, nkv, hd)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nh * hd) @ f32(w["wo"])


def route(x, w, *, m: dict):
    """x [T, hidden] -> float32 [T, router_width]: the weight with which each
    token takes each expert of the WHOLE layer, zero where it was not chosen."""
    z = weights.sizes(m)
    s = jax.nn.sigmoid(x @ w["router"])
    _, chosen = jax.lax.top_k(s + w["router_bias"], z["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if m.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * m["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def routed_part(x, w, *, m: dict):
    """What the experts held here add: ``(sum_e w_e f_e(x W_in)) W_out`` over the
    chosen experts among ``expert_offset ..``, one expert after another."""
    f32 = weights.kind(m).to_float32
    z = weights.sizes(m)
    take = route(x, w, m=m)[:, z["expert_offset"]:z["expert_offset"] + z["held"]]   # [T, held]
    u = x @ f32(w["w_in"])

    def one(acc, e):
        w1, w2, w_e = e
        return acc + w_e[:, None] * (jnp.square(jax.nn.relu(u @ f32(w1))) @ f32(w2)), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (w["w1"], w["w2"], take.T))
    return acc @ f32(w["w_out"])


def shared_part(x, w, *, m: dict):
    f32 = weights.kind(m).to_float32
    return jnp.square(jax.nn.relu(x @ f32(w["s1"]))) @ f32(w["s2"])


def expert_mixer(x, w, *, m: dict):
    return routed_part(x, w, m=m) + shared_part(x, w, m=m)


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": expert_mixer}


def block(x, w, *, m: dict, ch: str):
    """One block on x [T, hidden] float32."""
    return x + MIXERS[ch](_rms_norm(x, w["norm_in"], m["rms_norm_eps"]), w, m=m)


def _head(x, top, *, m: dict):
    return _rms_norm(x, top["final_norm"], m["rms_norm_eps"]) @ top["lm_head"].astype(jnp.float32).T


def _padded(n: int, to: int) -> int:
    return -(-n // to) * to


def logits_after(seed: int, m: dict, sequences: list[list[int]], first: list[int],
                 pad_sequence_to: int = 256, pad_rows_to: int = 64) -> list[np.ndarray]:
    """For each token sequence, the float32 logits ``[len(seq) - first, vocab]``
    at positions ``first - 1 .. len(seq) - 2``: row ``r`` is the distribution
    from which token ``first + r`` of the sequence was drawn."""
    root = weights.root_key(seed)
    with jax.default_matmul_precision("highest"):
        gen = {ch: jax.jit(partial(weights.layer_weights, m=m, ch=ch)) for ch in MIXERS}
        run = {ch: jax.jit(partial(block, m=m, ch=ch)) for ch in MIXERS}
        run_head = jax.jit(partial(_head, m=m))
        top = jax.jit(partial(weights.top_weights, m=m))(root)
        xs = []
        for seq in sequences:
            t = _padded(len(seq), pad_sequence_to)
            ids = jnp.asarray(list(seq) + [0] * (t - len(seq)), jnp.int32)
            xs.append(top["embed"][ids].astype(jnp.float32))
        for li, ch in enumerate(weights.pattern(m)):
            w = gen[ch](root, layer=jnp.int32(li))
            xs = [run[ch](x, w) for x in xs]
            del w
        out = []
        for x, seq, f in zip(xs, sequences, first):
            rows = _padded(len(seq) - f, pad_rows_to)
            idx = jnp.clip(jnp.arange(rows) + f - 1, 0, len(seq) - 2)
            out.append(np.asarray(run_head(x[idx], top))[: len(seq) - f])
    return out

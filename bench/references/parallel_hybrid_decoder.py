"""The plain reference of a decoder whose every block runs two mixers side by
side: a Mamba-2 state-space mixer and rotary attention read ONE normed input,
their outputs join the residual stream in one add, then a pre-normed SwiGLU MLP;
fixed muP multipliers scale the activations (Falcon-H1).

float32 throughout under ``jax.default_matmul_precision("highest")``, no
cache, no kernels, no batching, no chunks, and no import from the program.
``x`` is the residual stream; the multipliers are the configuration's keys of
those names; H heads of P channels, G groups, state N::

    x0 = embed[tokens] * embedding_multiplier
    per block:
      h = RMSNorm(x; norm_in)
      # state-space mixer (Dao and Gu, arXiv:2405.21060)
      u = (W_in (h * ssm_in_multiplier)) * mup,   mup = ssm_multipliers[0..4] spread over
          the segments z | x | B | C | dt of W_in's output, in that order
      z, xBC, dt~ = split(u);  xBC = silu(causal_conv(xBC) + conv_bias), width mamba_d_conv,
          the last tap on the current input;  xBC = [x [H, P] | B [G, N] | C [G, N]], head h
          reading group h // (H / G)
      dt_t = softplus(dt~_t + dt_bias);  A = -exp(A_log)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,  S_0 = 0;   y_t = S_t C_t + D x_t
      m = W_out(RMSNorm_group(y * silu(z); G groups of H P / G, gate first)) * ssm_out_multiplier
      # attention, on the SAME h
      q, k, v = W_q a, (W_k a) * key_multiplier, W_v a,   a = h * attention_in_multiplier
      q, k = rope(q), rope(k)  (rope_theta, every dimension of the head, split-half);
      t = W_o(causal softmax(q k^T / sqrt(head_dim)) v) * attention_out_multiplier
      x = x + m + t
      # MLP
      g = RMSNorm(x; mlp_norm)
      x = x + W_down( W_up g * silu((W_gate g) * mlp_multipliers[0]) ) * mlp_multipliers[1]
    logits = (W_head RMSNorm(x_L; final_norm)) * lm_head_multiplier

No bias but the convolution's. The recurrence runs token by token.

Points the source's ``config.json`` alone does not decide, each listed in the
configuration's ``assumed``: the gated norm is applied gate first
(``mamba_norm_before_gate`` false) over ``mamba_n_groups`` groups with one gain
of width ``mamba_d_ssm``; ``d_inner`` is ``mamba_d_ssm`` (``mamba_expand`` x
``hidden_size`` is not read); the attention scale is ``head_dim ** -0.5``;
rotary embedding on all ``head_dim`` dimensions in the split-half convention;
``mamba_use_mlp`` true: every block has the MLP; ``attn_layer_indices`` null:
every block has attention.

Weights are the benchmark's own from ``--seed``
(``bench/references/parallel_hybrid_weights.py``), one block at a time: a
block is 1.7 GB in float32, so no more than one is ever held; ``lm_head`` is
multiplied in the row blocks it was drawn in, so that no float32 copy of a
vocabulary table ever stands (5.35 GB at 261120 x 5120).

``logits_after(...)`` is the contract of ``bench/references/dense_decoder.py``.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import config

weights = config.load_module("references", "parallel_hybrid_weights")


def _rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _rope(x, theta):
    """x: [T, heads, head_dim]; position of row t is t; split-half pairs."""
    t, _, hd = x.shape
    # float(): the source writes 100000000000, a whole number past 32 bits
    inv = 1.0 / (float(theta) ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def ssm_mixer(h, w, *, m: dict):
    """h [T, hidden] (normed) -> [T, hidden]; the recurrence token by token."""
    t = h.shape[0]
    f32 = weights.kind(m).to_float32
    z = weights.sizes(m)
    nh, p, n, g, di = z["heads"], z["head_dim"], z["state"], z["groups"], z["d_inner"]
    mup = jnp.concatenate([jnp.full((width,), by, jnp.float32)
                           for width, by in zip(z["segments"].values(), m["ssm_multipliers"])])
    u = ((h * m["ssm_in_multiplier"]) @ f32(w["in_proj"])) * mup
    gate, xbc, dt = u[:, :di], u[:, di:di + z["channels"]], u[:, di + z["channels"]:]
    taps = w["conv"].astype(jnp.float32)                     # [K, C], last tap on the current input
    k = taps.shape[0]
    ext = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
    xbc = jax.nn.silu(sum(ext[j:j + t] * taps[j] for j in range(k))
                      + w["conv_b"].astype(jnp.float32))
    xs = xbc[:, :di].reshape(t, nh, p)
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(t, g, n), nh // g, axis=1)     # [T, H, N]
    c = jnp.repeat(xbc[:, di + g * n:].reshape(t, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [T, H]
    a = -jnp.exp(w["A_log"])                                 # [H]

    def step(s, inp):                                        # s [H, P, N]
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32), (xs, b, c, dt))
    y = (y + w["D"][:, None] * xs).reshape(t, di) * jax.nn.silu(gate)
    yg = y.reshape(t, g, di // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + m["rms_norm_eps"])
    out = (yg.reshape(t, di) * w["norm"].astype(jnp.float32)) @ f32(w["out_proj"])
    return out * m["ssm_out_multiplier"]


def attention_mixer(h, w, *, m: dict):
    t = h.shape[0]
    f32 = weights.kind(m).to_float32
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    a = h * m["attention_in_multiplier"]
    q = _rope((a @ f32(w["wq"])).reshape(t, nh, hd), m["rope_theta"])
    k = _rope(((a @ f32(w["wk"])) * m["key_multiplier"]).reshape(t, nkv, hd), m["rope_theta"])
    v = (a @ f32(w["wv"])).reshape(t, nkv, hd)
    scores = jnp.einsum("tkgd,skd->kgts", q.reshape(t, nkv, nh // nkv, hd), k) / math.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nh * hd)
    return (o @ f32(w["wo"])) * m["attention_out_multiplier"]


def mlp(g, w, *, m: dict):
    f32 = weights.kind(m).to_float32
    by_gate, by_down = m["mlp_multipliers"]
    gated = (g @ f32(w["w_up"])) * jax.nn.silu((g @ f32(w["w_gate"])) * by_gate)
    return (gated @ f32(w["w_down"])) * by_down


def block(x, w, *, m: dict):
    """One block on x [T, hidden] float32."""
    h = _rms_norm(x, w["norm_in"], m["rms_norm_eps"])
    x = x + ssm_mixer(h, w, m=m) + attention_mixer(h, w, m=m)
    return x + mlp(_rms_norm(x, w["mlp_norm"], m["rms_norm_eps"]), w, m=m)


def _embed(ids, top, *, m: dict):
    return top["embed"][ids].astype(jnp.float32) * m["embedding_multiplier"]


def _head(x, top, *, m: dict):
    """logits [R, vocab] from x [R, hidden], ``lm_head`` widened and multiplied
    in the row blocks it was drawn in."""
    h = _rms_norm(x, top["final_norm"], m["rms_norm_eps"])
    v, d = top["lm_head"].shape
    rows = weights.table_block_rows(v)
    parts = jax.lax.map(lambda blk: h @ blk.astype(jnp.float32).T,
                        top["lm_head"].reshape(v // rows, rows, d))      # [blocks, R, rows]
    return jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], v) * m["lm_head_multiplier"]


def _padded(n: int, to: int) -> int:
    return -(-n // to) * to


def logits_after(seed: int, m: dict, sequences: list[list[int]], first: list[int],
                 pad_sequence_to: int = 256, pad_rows_to: int = 64) -> list[np.ndarray]:
    """For each token sequence, the float32 logits ``[len(seq) - first, vocab]``
    at positions ``first - 1 .. len(seq) - 2``: row ``r`` is the distribution
    from which token ``first + r`` of the sequence was drawn."""
    root = weights.root_key(seed)
    with jax.default_matmul_precision("highest"):
        gen = jax.jit(partial(weights.layer_weights, m=m))
        run = jax.jit(partial(block, m=m))
        run_head = jax.jit(partial(_head, m=m))
        embed = jax.jit(partial(_embed, m=m))
        top = jax.jit(partial(weights.top_weights, m=m))(root)
        xs = []
        for seq in sequences:
            t = _padded(len(seq), pad_sequence_to)
            xs.append(embed(jnp.asarray(list(seq) + [0] * (t - len(seq)), jnp.int32), top))
        for li in range(m["num_hidden_layers"]):
            w = gen(root, layer=jnp.int32(li))
            xs = [run(x, w) for x in xs]
            del w
        out = []
        for x, seq, f in zip(xs, sequences, first):
            rows = _padded(len(seq) - f, pad_rows_to)
            idx = jnp.clip(jnp.arange(rows) + f - 1, 0, len(seq) - 2)
            out.append(np.asarray(run_head(x[idx], top))[: len(seq) - f])
    return out

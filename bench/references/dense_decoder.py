"""The plain reference: a decoder's forward pass from the published equations.

float32 throughout, ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bfloat16 passes), no cache, no kernels, no
batching, and no import from the program. RMSNorm, rotary embeddings in the
split-half convention of the published checkpoints, grouped-query attention
under a causal mask and, where the model has one, a sliding window, optional
qkv biases, SwiGLU. Weights come from ``bench/weights.py`` (the benchmark's
own, from ``--seed``), one layer at a time, so it fits beside nothing and
needs only one layer's float32 weights at once.

A reference module is named by the configuration's ``reference`` key and
exports ``logits_after`` and ``weights``, the module that makes this
architecture's weights (``all_weights``, ``matmul_shapes``, ``kind``).

``logits_after(...)`` runs whole sequences (prompt + served tokens) teacher-
forced and returns the logits at the positions whose next token was served.
Every sequence is padded to one length and every block of compared rows to
one height, both given by the traffic file (``check.pad_sequence_to``,
``check.pad_rows_to``: its longest request), so a cell compiles three shapes
in its first run and a new seed compiles nothing.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

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


def layer(x, w, *, m: dict):
    """One decoder layer on x [T, hidden] float32."""
    t = x.shape[0]
    f32 = weights.kind(m).to_float32
    nh, nkv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = h @ f32(w["wq"])
    k = h @ f32(w["wk"])
    v = h @ f32(w["wv"])
    if "bq" in w:
        q = q + w["bq"].astype(jnp.float32)
        k = k + w["bk"].astype(jnp.float32)
        v = v + w["bv"].astype(jnp.float32)
    q = _rope(q.reshape(t, nh, hd), m["rope_theta"])
    k = _rope(k.reshape(t, nkv, hd), m["rope_theta"])
    v = v.reshape(t, nkv, hd)
    g = nh // nkv                          # query heads per key/value head
    qg = q.reshape(t, nkv, g, hd)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k) / math.sqrt(hd)
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = j <= i
    if m.get("sliding_window"):
        mask = mask & (j > i - m["sliding_window"])
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nh * hd)
    x = x + o @ f32(w["wo"])
    h = _rms_norm(x, w["mlp_norm"], eps)
    gated = jax.nn.silu(h @ f32(w["w_gate"])) * (h @ f32(w["w_up"]))
    return x + gated @ f32(w["w_down"])


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
        gen_layer = jax.jit(partial(weights.layer_weights, m=m))
        gen_top = jax.jit(partial(weights.top_weights, m=m))
        run_layer = jax.jit(partial(layer, m=m))
        run_head = jax.jit(partial(_head, m=m))
        top = gen_top(root)
        xs = []
        for seq in sequences:
            t = _padded(len(seq), pad_sequence_to)
            ids = jnp.asarray(list(seq) + [0] * (t - len(seq)), jnp.int32)
            xs.append(top["embed"][ids].astype(jnp.float32))
        for li in range(m["num_hidden_layers"]):
            w = gen_layer(root, layer=jnp.int32(li))
            xs = [run_layer(x, w) for x in xs]
        out = []
        for x, seq, f in zip(xs, sequences, first):
            rows = _padded(len(seq) - f, pad_rows_to)
            idx = jnp.clip(jnp.arange(rows) + f - 1, 0, len(seq) - 2)
            out.append(np.asarray(run_head(x[idx], top))[: len(seq) - f])
    return out

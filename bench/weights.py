"""Weights from ``--seed``, made by the benchmark, in the types they are served in.

The benchmark makes the weights and hands them to the program; the plain
reference (``bench/references/``) makes the same values again, one layer at
a time, from the same seed. Nothing here comes from the program, so the
comparison that decides ``correct`` never checks the program against itself.

Layout (the program's checkpoint tree, which ``bench/systems/engine.py`` hands over as
it is): how a decoder matmul is stored is the configuration's ``quantization``,
and each kind is a file of its own, ``bench/weight_kinds/<quantization>.py``
(``make``, ``to_float32``, ``nbytes``), so a later configuration brings its
kind as a new file. Effective std 0.02, as the published models' initialisers
have it. Embeddings and ``lm_head`` are bfloat16. Norm gains are near 1 and
qkv biases are not zero, so a norm or a bias that is left out shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import config

W_STD = 0.02   # unless the configuration's file gives ``weight_std``
MATMULS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_TAG = {name: i for i, name in enumerate(
    MATMULS + ("attn_norm", "mlp_norm", "bq", "bk", "bv",
               "embed", "lm_head", "final_norm"))}


def root_key(seed: int) -> jax.Array:
    """``--seed`` is any whole number up to a little over 2**31; fold it in
    two 31-bit halves so nothing overflows an int32."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def matmul_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """``[in, out]`` of every decoder matmul, from the published sizes."""
    d, hd = m["hidden_size"], m["head_dim"]
    qd, kvd = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    ff = m["intermediate_size"]
    return {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def _key(root: jax.Array, name: str, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root, _TAG[name]), layer)


def kind(m: dict):
    """The module that makes and reads this configuration's decoder matmuls."""
    return config.load_module("weight_kinds", m["quantization"])


def layer_weights(root: jax.Array, m: dict, layer) -> dict:
    """One decoder layer: its matmuls as the configuration stores them, bf16
    norm gains and, where the model has them, bf16 qkv biases. ``layer`` may
    be traced."""
    make = kind(m).make
    out: dict = {name: make(_key(root, name, layer), shape, m.get("weight_std") or W_STD)
                 for name, shape in matmul_shapes(m).items()}
    d = m["hidden_size"]
    for name in ("attn_norm", "mlp_norm"):
        g = 1.0 + 0.1 * jax.random.normal(_key(root, name, layer), (d,), jnp.float32)
        out[name] = g.astype(jnp.bfloat16)
    if m.get("attention_bias"):
        shapes = matmul_shapes(m)
        for b, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
            v = 0.1 * jax.random.normal(_key(root, b, layer), (shapes[w][1],), jnp.float32)
            out[b] = v.astype(jnp.bfloat16)
    return out


def top_weights(root: jax.Array, m: dict) -> dict:
    """Embedding, ``lm_head`` and the final norm gain, bfloat16."""
    v, d = m["vocab_size"], m["hidden_size"]
    std = m.get("weight_std") or W_STD
    out = {
        "embed": (jax.random.normal(_key(root, "embed", 0), (v, d), jnp.float32)
                  * std).astype(jnp.bfloat16),
        "final_norm": (1.0 + 0.1 * jax.random.normal(
            _key(root, "final_norm", 0), (d,), jnp.float32)).astype(jnp.bfloat16),
    }
    if not m.get("tie_word_embeddings"):
        out["lm_head"] = (jax.random.normal(_key(root, "lm_head", 0), (v, d), jnp.float32)
                          * std).astype(jnp.bfloat16)
    return out


def all_weights(seed: int, m: dict, shardings=None) -> dict:
    """Every weight of the model in ONE jitted call on the device: layers
    stacked on a leading axis (a ``lax.map`` over the layer index, so the
    transient is one layer's float32 draw), plus the top-level tensors.
    ``shardings(tree_of_shapes)`` gives the layout over a mesh, so that a
    model that fits no single chip is never whole on one."""
    n_layers = m["num_hidden_layers"]

    def make(root):
        layers = jax.lax.map(lambda i: layer_weights(root, m, i),
                             jnp.arange(n_layers, dtype=jnp.int32))
        return {"layers": layers, **top_weights(root, m)}

    root = root_key(seed)
    if shardings is None:
        return jax.jit(make)(root)
    return jax.jit(make, out_shardings=shardings(jax.eval_shape(make, root)))(root)

"""Weights from ``--seed`` for a hybrid stack: gated-delta-rule linear-attention
layers beside full-attention layers (``bench/references/hybrid_linear_decoder.py``).

The same contract as ``bench/weights.py`` (whose seed folding and top-level
tensors it reuses; nothing comes from the program): ``all_weights`` lays the
model out as the program's checkpoint tree in one jitted call, here
``layers = {"full": [n_full, ...], "linear": [n_linear, ...]}`` with each
kind stacked in stack order, and the reference draws the same values again,
one layer at a time, from the layer's index in the whole stack.

Stored as the configuration's ``quantization`` says (``bench/weight_kinds/``):
every projection of either mixer and the three MLP matmuls. bfloat16: the
norm gains, the two gate projections ``wa``/``wb`` [hidden, heads] and the
convolution taps ``conv`` [kernel, channels] (last tap on the current
input; channels are q, k, v side by side). float32: ``A_log``, ``dt_bias``.

Small leaves get values that show when they are left out: gains near 1 and
not at 1, taps of std 0.5, and ``A_log`` / ``dt_bias`` drawn so that the
decay ``a = exp(-exp(A_log) softplus(dt_bias))`` at a zero pre-activation
spans 0.46 to 0.993 over the heads. ``wa`` and ``wb`` are drawn at an
eighth and a quarter of the matmuls' std: the post-norm residual stream of
this family grows to an RMS of ~8 with random weights, and at the full std
the decay of half the positions would be ~0 (no memory to check).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as dense_weights

W_STD = dense_weights.W_STD
root_key = dense_weights.root_key
kind = dense_weights.kind
top_weights = dense_weights.top_weights

FULL, LINEAR = "full_attention", "linear_attention"
MLP = ("w_gate", "w_up", "w_down")
_NAMES = ("wq", "wk", "wv", "wo", "wg", *MLP, "wa", "wb", "conv", "A_log", "dt_bias",
          "o_norm", "q_norm", "k_norm", "post_attn_norm", "post_mlp_norm")
_TAG = {name: 100 + i for i, name in enumerate(_NAMES)}


def sizes(m: dict) -> tuple[int, int, int, int]:
    """heads, key width, value width, convolution channels of the linear mixer."""
    h = m["linear_num_key_heads"]
    if m["linear_num_value_heads"] != h:
        raise ValueError("linear_num_key_heads != linear_num_value_heads is not described here")
    kd, vd = h * m["linear_key_head_dim"], h * m["linear_value_head_dim"]
    return h, kd, vd, 2 * kd + vd


def matmul_shapes(m: dict, layer_type: str = LINEAR) -> dict[str, tuple[int, int]]:
    """``[in, out]`` of every stored matmul of one layer of that type."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    mlp = {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    if layer_type == FULL:
        qd = m["num_attention_heads"] * m["head_dim"]
        kvd = m["num_key_value_heads"] * m["head_dim"]
        return {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d), **mlp}
    _, kd, vd, _ = sizes(m)
    return {"wq": (d, kd), "wk": (d, kd), "wv": (d, vd), "wg": (d, vd), "wo": (vd, d), **mlp}


def _key(root, name: str, layer):
    return jax.random.fold_in(jax.random.fold_in(root, _TAG[name]), layer)


def _gain(root, name, layer, width):
    g = 1.0 + 0.1 * jax.random.normal(_key(root, name, layer), (width,), jnp.float32)
    return g.astype(jnp.bfloat16)


def layer_weights(root, m: dict, layer, layer_type: str) -> dict:
    """One layer of the stack; ``layer`` is its index among all layers and
    may be traced, ``layer_type`` is static."""
    std = m.get("weight_std") or W_STD
    make = kind(m).make
    shapes = matmul_shapes(m, layer_type)
    out: dict = {name: make(_key(root, name, layer), shape, std)
                 for name, shape in shapes.items()}
    d = m["hidden_size"]
    out["post_attn_norm"] = _gain(root, "post_attn_norm", layer, d)
    out["post_mlp_norm"] = _gain(root, "post_mlp_norm", layer, d)
    if layer_type == FULL:
        out["q_norm"] = _gain(root, "q_norm", layer, shapes["wq"][1])
        out["k_norm"] = _gain(root, "k_norm", layer, shapes["wk"][1])
        return out
    h, _, _, channels = sizes(m)
    for name, scale in (("wa", std / 8), ("wb", std / 4)):
        out[name] = (jax.random.normal(_key(root, name, layer), (d, h), jnp.float32)
                     * scale).astype(jnp.bfloat16)
    out["conv"] = (0.5 * jax.random.normal(
        _key(root, "conv", layer), (m["linear_conv_kernel_dim"], channels), jnp.float32)
    ).astype(jnp.bfloat16)
    out["A_log"] = jnp.log(jax.random.uniform(
        _key(root, "A_log", layer), (h,), jnp.float32, 1.0, 2.5))
    out["dt_bias"] = jax.random.uniform(
        _key(root, "dt_bias", layer), (h,), jnp.float32, -5.0, -1.0)
    out["o_norm"] = _gain(root, "o_norm", layer, m["linear_value_head_dim"])
    return out


def layers_of(m: dict, layer_type: str) -> list[int]:
    types = m["layer_types"]
    if len(types) != m["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    return [i for i, t in enumerate(types) if t == layer_type]


def all_weights(seed: int, m: dict, shardings=None) -> dict:
    """Every weight in ONE jitted call on the device, each kind of layer
    stacked on its own leading axis (a ``lax.map`` over the layers' indices
    in the whole stack: the transient is one layer's float32 draw)."""

    def make(root):
        def stack(layer_type):
            idx = jnp.asarray(layers_of(m, layer_type), jnp.int32)
            return jax.lax.map(lambda i: layer_weights(root, m, i, layer_type), idx)

        return {"layers": {"full": stack(FULL), "linear": stack(LINEAR)},
                **top_weights(root, m)}

    root = root_key(seed)
    if shardings is None:
        return jax.jit(make)(root)
    return jax.jit(make, out_shardings=shardings(jax.eval_shape(make, root)))(root)

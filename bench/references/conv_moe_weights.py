"""Weights from ``--seed`` for a stack of operator + feed-forward pairs: gated
short convolutions and attention layers in the order ``layer_types`` gives, a
dense SwiGLU MLP under the first ``num_dense_layers`` layers and routed SwiGLU
experts under the rest (``bench/references/conv_moe_decoder.py``).

The contract of ``bench/weights.py`` (whose seed folding, weight kinds and
top-level tensors it reuses; nothing comes from the program): ``all_weights``
lays the model out as the program's checkpoint tree in one jitted call, here
``layers = {"conv": [n_conv, ...], "attn": [n_attn, ...], "mlp":
[num_dense_layers, ...], "experts": [n_expert_layers, ...]}`` with each kind
stacked in stack order, and the reference draws the same values again, one
layer at a time, from the layer's index in the whole stack. An operator's
stack holds its layer's ``norm_in``, a feed-forward's its ``ffn_norm``. The
head is tied to the embedding (``tie_word_embeddings``): no ``lm_head``.

**The share.** ``m["share"]`` (optional; a whole model has none) says which
experts of each layer this device holds: ``expert_offset .. expert_offset +
experts_held`` of the router's ``num_experts``. An expert's weights are drawn
from its index in the WHOLE layer, so the shares of a layer hold different
parts of one set of experts; router and selection bias are the same in every
share.

Stored as the configuration's ``quantization`` says (``bench/weight_kinds/``):
``in_proj`` [hidden, 3 hidden] (columns B | C | x) and ``out_proj``;
``wq``/``wk``/``wv``/``wo``; the dense ``w_gate``/``w_up``/``w_down``; the
expert stacks of the same names, ``[held, hidden, ff]`` twice and ``[held, ff,
hidden]``. bfloat16: the gains (``norm_in``, ``ffn_norm`` [hidden];
``q_norm``, ``k_norm`` [head_dim], one gain for every head) and the
convolution's taps ``conv`` [L, hidden], last tap on the current position.
float32: the ``router`` [hidden, num_experts] and its selection bias
``router_bias``.

Scales: matmuls at the configuration's ``weight_std`` (0.02), gains near 1,
taps of std 0.5 (so that no tap is buried and their order shows), the
selection bias of std 0.05: the sigmoid scores of a normed input through a
std-0.02 router spread by ~0.2, the fourth and fifth best of 32 lie ~0.02
apart, so the bias changes about a third of the choices and a program that
drops it, or weighs by it, shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as dense_weights

W_STD = dense_weights.W_STD
root_key = dense_weights.root_key
kind = dense_weights.kind
top_weights = dense_weights.top_weights

_NAMES = ("norm_in", "ffn_norm", "in_proj", "out_proj", "conv", "wq", "wk", "wv", "wo",
          "q_norm", "k_norm", "w_gate", "w_up", "w_down", "router", "router_bias",
          "e_gate", "e_up", "e_down")
_TAG = {name: 300 + i for i, name in enumerate(_NAMES)}
OPERATORS = {"conv": "conv", "full_attention": "attn"}


def sizes(m: dict) -> dict:
    share = m.get("share") or {}
    return {"taps": m["conv_L_cache"], "ff": m["moe_intermediate_size"],
            "router_width": m["num_experts"], "top_k": m["num_experts_per_tok"],
            "expert_offset": share.get("expert_offset", 0),
            "held": share.get("experts_held", m["num_experts"])}


def operator_of(m: dict, layer: int) -> str:
    """The stack a layer's sequence operator lives in: ``conv`` or ``attn``."""
    return OPERATORS[m["layer_types"][layer]]


def ffn_of(m: dict, layer: int) -> str:
    """The stack a layer's feed-forward lives in: ``mlp`` or ``experts``."""
    return "mlp" if layer < m["num_dense_layers"] else "experts"


def layers_of(m: dict, stack: str) -> list[int]:
    """Indices in the whole model of the layers whose operator or
    feed-forward lies in ``stack``."""
    which = operator_of if stack in OPERATORS.values() else ffn_of
    return [i for i in range(m["num_hidden_layers"]) if which(m, i) == stack]


def matmul_shapes(m: dict, stack: str) -> dict[str, tuple[int, ...]]:
    """Shape of every stored matmul of one layer of that stack (``[in, out]``;
    the expert stacks carry a leading axis of the experts held)."""
    d, x = m["hidden_size"], sizes(m)
    if stack == "conv":
        return {"in_proj": (d, 3 * d), "out_proj": (d, d)}
    if stack == "attn":
        qd = m["num_attention_heads"] * m["head_dim"]
        kvd = m["num_key_value_heads"] * m["head_dim"]
        return {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d)}
    if stack == "mlp":
        ff = m["intermediate_size"]
        return {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    return {"w_gate": (x["held"], d, x["ff"]), "w_up": (x["held"], d, x["ff"]),
            "w_down": (x["held"], x["ff"], d)}


def layer_nbytes(m: dict, stack: str) -> int:
    """Bytes of one layer's stored matmuls in that stack, as the configuration stores them."""
    nbytes = kind(m).nbytes
    return sum((s[0] if len(s) == 3 else 1) * nbytes(s[-2:])
               for s in matmul_shapes(m, stack).values())


def _key(root, name: str, layer):
    return jax.random.fold_in(jax.random.fold_in(root, _TAG[name]), layer)


def _gain(root, name, layer, width):
    g = 1.0 + 0.1 * jax.random.normal(_key(root, name, layer), (width,), jnp.float32)
    return g.astype(jnp.bfloat16)


def expert_stack(root, m: dict, name: str, layer, shape, std):
    """Experts ``expert_offset ..`` of the layer, each drawn from its index in
    the whole layer, stacked: one expert's draw at a time."""
    make, base = kind(m).make, _key(root, name, layer)
    first = sizes(m)["expert_offset"]
    return jax.lax.map(lambda e: make(jax.random.fold_in(base, e), shape[1:], std),
                       first + jnp.arange(shape[0], dtype=jnp.int32))


def stack_weights(root, m: dict, layer, stack: str) -> dict:
    """What one layer keeps in ``stack``; ``layer`` is its index in the whole
    model and may be traced, ``stack`` is static."""
    std = m.get("weight_std") or W_STD
    make, x, d = kind(m).make, sizes(m), m["hidden_size"]
    shapes = matmul_shapes(m, stack)
    if stack == "experts":
        out = {name: expert_stack(root, m, f"e_{name[2:]}", layer, shape, std)
               for name, shape in shapes.items()}
        out["router"] = std * jax.random.normal(
            _key(root, "router", layer), (d, x["router_width"]), jnp.float32)
        out["router_bias"] = 0.05 * jax.random.normal(
            _key(root, "router_bias", layer), (x["router_width"],), jnp.float32)
    else:
        out = {name: make(_key(root, name, layer), shape, std) for name, shape in shapes.items()}
    if stack in ("mlp", "experts"):
        out["ffn_norm"] = _gain(root, "ffn_norm", layer, d)
        return out
    out["norm_in"] = _gain(root, "norm_in", layer, d)
    if stack == "conv":
        out["conv"] = (0.5 * jax.random.normal(
            _key(root, "conv", layer), (x["taps"], d), jnp.float32)).astype(jnp.bfloat16)
    else:
        out["q_norm"] = _gain(root, "q_norm", layer, m["head_dim"])
        out["k_norm"] = _gain(root, "k_norm", layer, m["head_dim"])
    return out


def layer_weights(root, m: dict, layer, operator: str, ffn: str) -> dict:
    """One whole layer for the reference: ``{"operator": ..., "ffn": ...}``."""
    return {"operator": stack_weights(root, m, layer, operator),
            "ffn": stack_weights(root, m, layer, ffn)}


def all_weights(seed: int, m: dict, shardings=None) -> dict:
    """Every weight in ONE jitted call on the device, each of the four stacks
    on its own leading axis (a ``lax.map`` over the layers' indices in the
    whole model: the transient is one expert's float32 draw)."""

    def make(root):
        layers = {}
        for stack in ("conv", "attn", "mlp", "experts"):
            idx = layers_of(m, stack)
            if idx:
                layers[stack] = jax.lax.map(
                    lambda i, stack=stack: stack_weights(root, m, i, stack),
                    jnp.asarray(idx, jnp.int32))
        return {"layers": layers, **top_weights(root, m)}

    root = root_key(seed)
    if shardings is None:
        return jax.jit(make)(root)
    return jax.jit(make, out_shardings=shardings(jax.eval_shape(make, root)))(root)

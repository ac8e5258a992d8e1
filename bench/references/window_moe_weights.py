"""Weights from ``--seed`` for a stack whose every layer is attention over a
window or over the whole context (``sliding_window_layout``), with or without
rotary embedding (``rope_layout``), and routed ReLU-gated GLU experts
(``bench/references/window_moe_decoder.py``).

The contract of ``bench/weights.py`` (whose seed folding, weight kinds and
top-level tensors it reuses; nothing comes from the program): ``all_weights``
lays the model out as the program's checkpoint tree in one jitted call, here
``layers = {"attn": [n_layers, ...], "experts": [n_layers, ...]}`` in stack
order, and the reference draws the same values again, one layer at a time,
from the layer's index. Window and global layers share the attention stack:
the two layouts tell them apart, not the weights. The head is not tied
(``lm_head`` beside ``embed``).

Stored as the configuration's ``quantization`` says (``bench/weight_kinds/``):
``wq``/``wk``/``wv``/``wo`` and the expert stacks ``w_gate``/``w_up``
``[experts, hidden, ff]`` and ``w_down`` ``[experts, ff, hidden]``, every
expert of a layer held (an expert is drawn from its index in the layer).
bfloat16: the gains ``norm_in`` (attention stack) and ``ffn_norm`` (expert
stack). float32: the ``router`` ``[hidden, experts]``, no bias.

Scales: matmuls at the configuration's ``weight_std`` (0.02), gains near 1;
the experts' ``w_down`` at the configuration's ``expert_down_std`` where it
gives one (0.1: a 768-wide expert at 0.02 adds a sixth of what attention adds
to the residual stream, whose attention outputs are then one
position-independent vector from the second layer on, and every greedy answer
repeats one token whatever the precision; the configuration's ``assumed``).
No weight ``intermediate_size`` wide exists: the model has no dense
feed-forward.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as dense_weights

W_STD = dense_weights.W_STD
root_key = dense_weights.root_key
kind = dense_weights.kind
top_weights = dense_weights.top_weights

_NAMES = ("norm_in", "ffn_norm", "wq", "wk", "wv", "wo", "router", "e_gate", "e_up", "e_down")
_TAG = {name: 400 + i for i, name in enumerate(_NAMES)}
STACKS = ("attn", "experts")


def sizes(m: dict) -> dict:
    """The expert layer's sizes under the source's names."""
    return {"ff": m["moe_ffn_hidden_size"], "experts": m["moe_num_primary_experts"],
            "top_k": m["moe_num_active_primary_experts"]}


def matmul_shapes(m: dict, stack: str) -> dict[str, tuple[int, ...]]:
    """Shape of every stored matmul of one layer of that stack (``[in, out]``;
    the expert stacks carry a leading axis of the experts)."""
    d, x = m["hidden_size"], sizes(m)
    if stack == "attn":
        qd = m["num_attention_heads"] * m["head_dim"]
        kvd = m["num_key_value_heads"] * m["head_dim"]
        return {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d)}
    return {"w_gate": (x["experts"], d, x["ff"]), "w_up": (x["experts"], d, x["ff"]),
            "w_down": (x["experts"], x["ff"], d)}


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


def stack_weights(root, m: dict, layer, stack: str) -> dict:
    """What one layer keeps in ``stack``; ``layer`` may be traced, ``stack`` is static."""
    std = m.get("weight_std") or W_STD
    make, d = kind(m).make, m["hidden_size"]
    shapes = matmul_shapes(m, stack)
    if stack == "attn":
        out = {name: make(_key(root, name, layer), shape, std) for name, shape in shapes.items()}
        out["norm_in"] = _gain(root, "norm_in", layer, d)
        return out
    out = {}
    for name, shape in shapes.items():
        base = _key(root, f"e_{name[2:]}", layer)
        at = (m.get("expert_down_std") or std) if name == "w_down" else std
        # one expert's draw at a time, each from its index in the layer
        out[name] = jax.lax.map(lambda e, base=base, shape=shape, at=at: make(
            jax.random.fold_in(base, e), shape[1:], at), jnp.arange(shape[0], dtype=jnp.int32))
    out["router"] = std * jax.random.normal(
        _key(root, "router", layer), (d, sizes(m)["experts"]), jnp.float32)
    out["ffn_norm"] = _gain(root, "ffn_norm", layer, d)
    return out


def layer_weights(root, m: dict, layer) -> dict:
    """One whole layer for the reference: ``{"attn": ..., "experts": ...}``."""
    return {stack: stack_weights(root, m, layer, stack) for stack in STACKS}


def all_weights(seed: int, m: dict, shardings=None) -> dict:
    """Every weight in ONE jitted call on the device, each of the two stacks
    on its own leading axis (a ``lax.map`` over the layers: the transient is
    one expert's float32 draw)."""

    def make(root):
        idx = jnp.arange(m["num_hidden_layers"], dtype=jnp.int32)
        layers = {stack: jax.lax.map(lambda i, stack=stack: stack_weights(root, m, i, stack), idx)
                  for stack in STACKS}
        return {"layers": layers, **top_weights(root, m)}

    root = root_key(seed)
    if shardings is None:
        return jax.jit(make)(root)
    return jax.jit(make, out_shardings=shardings(jax.eval_shape(make, root)))(root)

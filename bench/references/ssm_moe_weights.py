"""Weights from ``--seed`` for a stack of single-mixer blocks: Mamba-2 (``M``),
attention (``*``) and routed latent experts beside a shared expert (``E``), in
the order ``hybrid_override_pattern`` gives
(``bench/references/ssm_moe_decoder.py``).

The contract of ``bench/weights.py`` (whose seed folding, weight kinds and
top-level tensors it reuses; nothing comes from the program): ``all_weights``
lays the model out as the program's checkpoint tree in one jitted call, here
``layers = {"mamba": [n_M, ...], "attn": [n_*, ...], "experts": [n_E, ...]}``
with each kind stacked in stack order, and the reference draws the same
values again, one layer at a time, from the layer's index in the whole stack.

**The share.** ``m["share"]`` says which part of each layer this chip holds:
experts ``expert_offset .. expert_offset + n_routed_experts`` of a router
``router_width`` wide. An expert's weights are drawn from its index in the
WHOLE layer, so four shares of a layer hold four different quarters of one
set of experts; the router, its bias, the latent projections and the shared
expert are the same in every share. The vocabulary slice is a smaller
vocabulary: ``vocab_size`` rows.

Stored as the configuration's ``quantization`` says (``bench/weight_kinds/``):
``in_proj``/``out_proj``, ``wq``/``wk``/``wv``/``wo``, ``w_in``/``w_out``, the
expert stacks ``w1`` [held, latent, ff] / ``w2`` [held, ff, latent] and the
shared ``s1``/``s2``. bfloat16: gains, convolution taps ``conv`` [kernel,
channels] (last tap on the current input; channels are x, B, C side by side)
and bias. float32: ``A_log``, ``D``, ``dt_bias``, the ``router`` [hidden,
router_width] and its selection bias.

Scales are set so that every part of a block shows in the logits (random
weights are not a trained model's): ``in_proj``'s ``dt`` columns at a quarter
of the matmuls' std and ``dt_bias`` in -5.5..-2.5, so that steps span
~0.003-0.15 and, with ``A`` in -16..-1, decays 0.1-0.997 a position; the
shared expert's ``s2`` at a quarter of the std, so that the WHOLE model's 22
routed experts a token and its shared expert add about the same to the
residual stream, and this chip's quarter of them about 0.4 times the shared
expert (at equal std the shared expert is ten times the routed part, and a
wrong routing would not show). The routed part is also what makes the served
tokens diverge from a float32 reference: a router score that bfloat16 rounds
the other way swaps an expert, and the choice is discrete (PERF.md section 2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as dense_weights

W_STD = dense_weights.W_STD
root_key = dense_weights.root_key
kind = dense_weights.kind
top_weights = dense_weights.top_weights

KINDS = {"M": "mamba", "*": "attn", "E": "experts"}
_NAMES = ("in_proj_z", "in_proj_xbc", "in_proj_dt", "out_proj", "conv", "conv_b", "A_log", "D",
          "dt_bias", "norm", "norm_in", "wq", "wk", "wv", "wo", "router", "router_bias",
          "w_in", "w_out", "w1", "w2", "s1", "s2")
_TAG = {name: 200 + i for i, name in enumerate(_NAMES)}


def pattern(m: dict) -> str:
    return m["hybrid_override_pattern"]


def layers_of(m: dict, ch: str) -> list[int]:
    """Indices in the whole stack of the blocks of one kind."""
    return [i for i, c in enumerate(pattern(m)) if c == ch]


def sizes(m: dict) -> dict:
    """The Mamba-2 mixer's and the expert block's sizes from the published keys."""
    h, p, n, g = m["mamba_num_heads"], m["mamba_head_dim"], m["ssm_state_size"], m["n_groups"]
    d_inner = h * p
    return {"heads": h, "head_dim": p, "state": n, "groups": g, "d_inner": d_inner,
            "channels": d_inner + 2 * g * n, "kernel": m["conv_kernel"],
            "latent": m["moe_latent_size"], "ff": m["moe_intermediate_size"],
            "shared_ff": m["moe_shared_expert_intermediate_size"],
            "held": m["n_routed_experts"], "router_width": m["share"]["router_width"],
            "expert_offset": m["share"]["expert_offset"], "top_k": m["num_experts_per_tok"]}


def matmul_shapes(m: dict, ch: str) -> dict[str, tuple[int, ...]]:
    """Shape of every stored matmul of one block of that kind (``[in, out]``;
    the expert stacks carry a leading axis of the experts held)."""
    d, x = m["hidden_size"], sizes(m)
    if ch == "M":
        return {"in_proj": (d, x["d_inner"] + x["channels"] + x["heads"]),
                "out_proj": (x["d_inner"], d)}
    if ch == "*":
        qd = m["num_attention_heads"] * m["head_dim"]
        kvd = m["num_key_value_heads"] * m["head_dim"]
        return {"wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d)}
    return {"w_in": (d, x["latent"]), "w_out": (x["latent"], d),
            "w1": (x["held"], x["latent"], x["ff"]), "w2": (x["held"], x["ff"], x["latent"]),
            "s1": (d, x["shared_ff"]), "s2": (x["shared_ff"], d)}


def block_nbytes(m: dict, ch: str) -> int:
    """Bytes of one block's stored matmuls, as the configuration stores them."""
    nbytes = kind(m).nbytes
    return sum((s[0] if len(s) == 3 else 1) * nbytes(s[-2:]) for s in matmul_shapes(m, ch).values())


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


def layer_weights(root, m: dict, layer, ch: str) -> dict:
    """One block of the stack; ``layer`` is its index among all blocks and may
    be traced, ``ch`` (its kind) is static."""
    std = m.get("weight_std") or W_STD
    make, x, d = kind(m).make, sizes(m), m["hidden_size"]
    shapes = matmul_shapes(m, ch)
    out: dict = {"norm_in": _gain(root, "norm_in", layer, d)}
    if ch == "M":
        parts = [make(_key(root, f"in_proj_{part}", layer), (d, width), s)
                 for part, width, s in (("z", x["d_inner"], std), ("xbc", x["channels"], std),
                                        ("dt", x["heads"], std / 4))]
        out["in_proj"] = jax.tree_util.tree_map(
            lambda *leaves: jnp.concatenate(leaves, axis=-1), *parts)
        out["out_proj"] = make(_key(root, "out_proj", layer), shapes["out_proj"], std)
        out["conv"] = (0.5 * jax.random.normal(
            _key(root, "conv", layer), (x["kernel"], x["channels"]), jnp.float32)
        ).astype(jnp.bfloat16)
        out["conv_b"] = (0.1 * jax.random.normal(
            _key(root, "conv_b", layer), (x["channels"],), jnp.float32)).astype(jnp.bfloat16)
        out["A_log"] = jnp.log(jax.random.uniform(
            _key(root, "A_log", layer), (x["heads"],), jnp.float32, 1.0, 16.0))
        out["D"] = jax.random.uniform(_key(root, "D", layer), (x["heads"],), jnp.float32, 0.5, 1.5)
        out["dt_bias"] = jax.random.uniform(
            _key(root, "dt_bias", layer), (x["heads"],), jnp.float32, -5.5, -2.5)
        out["norm"] = _gain(root, "norm", layer, x["d_inner"])
        return out
    if ch == "*":
        for name, shape in shapes.items():
            out[name] = make(_key(root, name, layer), shape, std)
        return out
    for name, s in (("w_in", std), ("w_out", std), ("s1", std), ("s2", std / 4)):
        out[name] = make(_key(root, name, layer), shapes[name], s)
    for name in ("w1", "w2"):
        out[name] = expert_stack(root, m, name, layer, shapes[name], std)
    out["router"] = std * jax.random.normal(
        _key(root, "router", layer), (d, x["router_width"]), jnp.float32)
    out["router_bias"] = 0.05 * jax.random.normal(
        _key(root, "router_bias", layer), (x["router_width"],), jnp.float32)
    return out


def all_weights(seed: int, m: dict, shardings=None) -> dict:
    """Every weight in ONE jitted call on the device, each kind of block
    stacked on its own leading axis (a ``lax.map`` over the blocks' indices in
    the whole stack: the transient is one expert's float32 draw)."""

    def make(root):
        layers = {}
        for ch, name in KINDS.items():
            idx = layers_of(m, ch)
            if idx:
                layers[name] = jax.lax.map(lambda i, ch=ch: layer_weights(root, m, i, ch),
                                           jnp.asarray(idx, jnp.int32))
        return {"layers": layers, **top_weights(root, m)}

    root = root_key(seed)
    if shardings is None:
        return jax.jit(make)(root)
    return jax.jit(make, out_shardings=shardings(jax.eval_shape(make, root)))(root)

"""Weights from ``--seed`` for a decoder whose every block runs TWO mixers side
by side on one normed input, a Mamba-2 state-space mixer and rotary attention,
then a SwiGLU MLP, with the family's fixed muP multipliers
(``bench/references/parallel_hybrid_decoder.py``).

The contract of ``bench/weights.py`` (whose seed folding and weight kinds it
reuses; nothing comes from the program): ``all_weights`` lays the model out as
the program's checkpoint tree in one jitted call, here ``layers = {"parallel":
[L, ...]}`` with both mixers' and the MLP's leaves in one stack, and the
reference draws the same values again, one block at a time.

Sizes are read from the source's own keys (``mamba_n_heads``, ``mamba_d_head``,
``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``, ``mamba_d_ssm``):
``d_inner`` is ``mamba_d_ssm`` (= heads x head width), NOT ``mamba_expand`` x
``hidden_size``.

Stored as the configuration's ``quantization`` says (``bench/weight_kinds/``):
``in_proj`` [hidden, 2 d_inner + 2 G N + H] (columns z | x | B | C | dt),
``out_proj``, ``wq``/``wk``/``wv``/``wo``, ``w_gate``/``w_up``/``w_down``.
bfloat16: the three gains (``norm_in``, ``norm`` over d_inner, ``mlp_norm``),
convolution taps ``conv`` [kernel, channels] (last tap on the current input;
channels x | B | C) and bias, the embedding and ``lm_head``. float32:
``A_log``, ``D``, ``dt_bias``.

**The multipliers are NOT folded in.** They are scalars of the configuration
that the program and the reference each apply to activations; a weight that
carried one would hide its omission from the comparison, since both sides
share the weights.

**Scales.** A muP model's trained weights are large where a small multiplier
follows them; at std 0.02 throughout, ``attention_out_multiplier`` 0.0375 and
``ssm_out_multiplier`` 0.088 would bury both mixers under the embedding, a
``key_multiplier`` of 0.011 would flatten every attention score to uniform (no
position, no key would show), and the state's own term would vanish beside the
``D`` skip. So each matmul is drawn at the std 0.02 of ``bench/weights.py``
DIVIDED by the product of the fixed multipliers on its path
(``std_of``): with the published multipliers applied, every activation is what
a std-0.02 model without multipliers would see, and leaving any multiplier
out moves the result by its own factor. ``in_proj``'s ``dt`` columns are a
quarter of that and ``dt_bias`` lies in -5.5..-2.5 (steps ~0.003-0.15; with
``A`` in -16..-1 decays 0.1-0.997 a position), as for the state-space stack of
``ssm_moe_weights.py``. The embedding keeps std 0.02 (the multiplier makes the
residual stream's first term 0.113, a tenth of a branch's output, where a
dense decoder's is a fiftieth).

**The vocabulary tables are drawn in row blocks** (``table``): a float32
``[261120, 5120]`` draw is 5.35 GB, two of them beside the decoder's layers do
not fit a 16 GB chip. Each block of ``table_block_rows`` rows comes from the
table's key folded with the block's index; the transient is one block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as dense_weights

W_STD = dense_weights.W_STD
root_key = dense_weights.root_key
kind = dense_weights.kind

STACK = "parallel"     # the program's name for this kind of block's weight stack
SEGMENTS = ("z", "x", "B", "C", "dt")
_NAMES = (tuple(f"in_proj_{seg}" for seg in SEGMENTS)
          + ("out_proj", "conv", "conv_b", "A_log", "D", "dt_bias", "norm", "norm_in", "mlp_norm",
             "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "embed", "lm_head", "final_norm"))
_TAG = {name: 300 + i for i, name in enumerate(_NAMES)}
TABLE_BLOCK_ROWS = 16384     # at most this many rows of a vocabulary table are drawn at once


def sizes(m: dict) -> dict:
    """The state-space mixer's sizes from the source's keys."""
    h, p, n, g = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"], m["mamba_n_groups"]
    d_inner = m["mamba_d_ssm"]
    if d_inner != h * p:
        raise ValueError(f"mamba_d_ssm {d_inner} is not mamba_n_heads x mamba_d_head = {h * p}")
    return {"heads": h, "head_dim": p, "state": n, "groups": g, "d_inner": d_inner,
            "channels": d_inner + 2 * g * n, "kernel": m["mamba_d_conv"],
            "segments": dict(zip(SEGMENTS, (d_inner, d_inner, g * n, g * n, h)))}


def matmul_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """``[in, out]`` of every stored matmul of one block."""
    d, hd, x = m["hidden_size"], m["head_dim"], sizes(m)
    qd, kvd = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    ff = m["intermediate_size"]
    return {"in_proj": (d, x["d_inner"] + x["channels"] + x["heads"]),
            "out_proj": (x["d_inner"], d),
            "wq": (d, qd), "wk": (d, kvd), "wv": (d, kvd), "wo": (qd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def block_nbytes(m: dict) -> int:
    """Bytes of one block's stored matmuls, as the configuration stores them."""
    return sum(kind(m).nbytes(s) for s in matmul_shapes(m).values())


def std_of(m: dict) -> dict[str, float]:
    """Effective std of every matmul (``in_proj`` by segment) and table: the
    base std over the product of the fixed multipliers on the weight's path."""
    std = m.get("weight_std") or W_STD
    a_in, key = m["attention_in_multiplier"], m["key_multiplier"]
    out = {"wq": std / (a_in * key ** 0.5), "wk": std / (a_in * key ** 0.5),
           "wv": std / a_in, "wo": std / m["attention_out_multiplier"],
           "out_proj": std / m["ssm_out_multiplier"],
           "w_gate": std / m["mlp_multipliers"][0], "w_up": std,
           "w_down": std / m["mlp_multipliers"][1],
           "embed": std, "lm_head": std / m["lm_head_multiplier"]}
    for seg, by in zip(SEGMENTS, m["ssm_multipliers"]):
        out[f"in_proj_{seg}"] = std / (m["ssm_in_multiplier"] * by) / (4.0 if seg == "dt" else 1.0)
    return out


def _key(root, name: str, layer):
    return jax.random.fold_in(jax.random.fold_in(root, _TAG[name]), layer)


def _gain(root, name, layer, width):
    g = 1.0 + 0.1 * jax.random.normal(_key(root, name, layer), (width,), jnp.float32)
    return g.astype(jnp.bfloat16)


def layer_weights(root, m: dict, layer) -> dict:
    """One block: both mixers and the MLP. ``layer`` may be traced."""
    make, x, d, std = kind(m).make, sizes(m), m["hidden_size"], std_of(m)
    shapes = matmul_shapes(m)
    parts = [make(_key(root, f"in_proj_{seg}", layer), (d, width), std[f"in_proj_{seg}"])
             for seg, width in x["segments"].items()]
    out: dict = {"in_proj": jax.tree_util.tree_map(
        lambda *leaves: jnp.concatenate(leaves, axis=-1), *parts)}
    for name in ("out_proj", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        out[name] = make(_key(root, name, layer), shapes[name], std[name])
    out["conv"] = (0.5 * jax.random.normal(
        _key(root, "conv", layer), (x["kernel"], x["channels"]), jnp.float32)).astype(jnp.bfloat16)
    out["conv_b"] = (0.1 * jax.random.normal(
        _key(root, "conv_b", layer), (x["channels"],), jnp.float32)).astype(jnp.bfloat16)
    out["A_log"] = jnp.log(jax.random.uniform(
        _key(root, "A_log", layer), (x["heads"],), jnp.float32, 1.0, 16.0))
    out["D"] = jax.random.uniform(_key(root, "D", layer), (x["heads"],), jnp.float32, 0.5, 1.5)
    out["dt_bias"] = jax.random.uniform(
        _key(root, "dt_bias", layer), (x["heads"],), jnp.float32, -5.5, -2.5)
    out["norm"] = _gain(root, "norm", layer, x["d_inner"])
    out["norm_in"] = _gain(root, "norm_in", layer, d)
    out["mlp_norm"] = _gain(root, "mlp_norm", layer, d)
    return out


def table_block_rows(rows: int) -> int:
    """The largest divisor of ``rows`` that is at most ``TABLE_BLOCK_ROWS``."""
    return next(b for b in range(min(rows, TABLE_BLOCK_ROWS), 0, -1) if rows % b == 0)


def table(root, name: str, m: dict, std: float):
    """A bfloat16 ``[vocab_size, hidden_size]`` table, drawn ``table_block_rows``
    rows at a time from the table's key folded with the block's index."""
    v, d = m["vocab_size"], m["hidden_size"]
    rows, base = table_block_rows(v), _key(root, name, 0)
    blocks = jax.lax.map(
        lambda b: (jax.random.normal(jax.random.fold_in(base, b), (rows, d), jnp.float32)
                   * std).astype(jnp.bfloat16),
        jnp.arange(v // rows, dtype=jnp.int32))
    return blocks.reshape(v, d)


def top_weights(root, m: dict) -> dict:
    """Embedding, ``lm_head`` (untied) and the final norm gain, bfloat16."""
    std = std_of(m)
    return {"embed": table(root, "embed", m, std["embed"]),
            "lm_head": table(root, "lm_head", m, std["lm_head"]),
            "final_norm": _gain(root, "final_norm", 0, m["hidden_size"])}


def all_weights(seed: int, m: dict, shardings=None) -> dict:
    """Every weight in ONE jitted call on the device, the blocks stacked on a
    leading axis (a ``lax.map`` over the layer index: the transient is one
    matmul's float32 draw, or one block of a table's rows)."""

    def make(root):
        layers = jax.lax.map(lambda i: layer_weights(root, m, i),
                             jnp.arange(m["num_hidden_layers"], dtype=jnp.int32))
        return {"layers": {STACK: layers}, **top_weights(root, m)}

    root = root_key(seed)
    if shardings is None:
        return jax.jit(make)(root)
    return jax.jit(make, out_shardings=shardings(jax.eval_shape(make, root)))(root)

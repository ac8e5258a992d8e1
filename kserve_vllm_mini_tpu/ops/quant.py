"""Weight-only int8 quantization for the serving path.

The reference treats quantization as an engine flag it sweeps and measures
(sweeps/quantization_sweep.py:40-234, runners/profiles/quantization/*.yaml);
the engines themselves do the work. Here the runtime is in-repo, so the knob
is real: per-output-channel symmetric int8 on every transformer matmul
weight, stored as ``{"q": int8 [..., in, out], "s": f32 [..., out]}``.

TPU-first shape of the trick: the int8 tensor halves HBM traffic vs bf16
(weights are the dominant stream during decode), and the dequantize —
``(x @ q) * s`` — is a trailing elementwise multiply XLA fuses into the
matmul's epilogue on the MXU. Activations stay bf16, so accuracy loss is the
weight rounding only (the usual "W8A16" recipe, cf. AWQ/GPTQ claims at
reference README.md:119-121).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

# A quantized linear leaf is a dict with exactly these keys; the AWQ
# variant (ops/awq.py) adds "a" — the per-INPUT-channel runtime multiplier
# (1/s of the calibration scaling), applied to activations before the
# matmul and folded back by dequantize_weight.
_QKEYS = frozenset({"q", "s"})
_QKEYS_AWQ = frozenset({"q", "s", "a"})


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and set(leaf.keys()) in (_QKEYS, _QKEYS_AWQ)


def quantize_weight(w: jnp.ndarray, bits: int = 8) -> dict[str, jnp.ndarray]:
    """Per-output-channel symmetric int8/int4 over the input (second-to-last)
    axis. Works on [in, out] and layer-stacked [L, in, out] alike: the scale
    is computed over axis -2 and has shape [..., out].

    ``bits=4`` stores the nibbles PACKED two-per-``uint8`` along the output
    axis (``q`` shape [..., in, out//2]) rather than as native ``jnp.int4``
    leaves: in the sessions that built this an S4 array at a jit dispatch
    boundary triggered a relayout ``device_put`` that recursed into jit
    (RecursionError at dispatch) — whether native int4 leaves work on this
    installation is ROADMAP A6's question — while a uint8 leaf crosses
    cleanly and is
    bitcast back to int4 *inside* the compiled program (``_unpack_int4``),
    where XLA's native two-nibbles-per-byte S4 representation takes over.
    HBM still streams half the int8 bytes — the W4A16 recipe; the quality
    cost is what the quantization sweep's fidelity axis measures.
    """
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    qmax = 127.0 if bits == 8 else 7.0
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -qmax, qmax)
    if bits == 4:
        if w.shape[-1] % 2:
            raise ValueError(f"int4 packing needs an even output dim, got {w.shape}")
        n = q.astype(jnp.int8)
        # element 2i -> low nibble of byte i, 2i+1 -> high nibble: the order
        # _unpack_int4's mask/shift unpack restores (pinned by
        # tests/test_quant.py test_int4_unpack_traced_matches_eager, which
        # compares the jitted unpack against the eager one)
        lo = n[..., 0::2] & 0x0F
        hi = n[..., 1::2] & 0x0F
        packed = (lo | (hi << 4)).astype(jnp.uint8)
        return {"q": packed, "s": scale.squeeze(-2).astype(jnp.float32)}
    return {"q": q.astype(jnp.int8), "s": scale.squeeze(-2).astype(jnp.float32)}


def is_packed_int4(qw: dict[str, jnp.ndarray]) -> bool:
    """Packed-int4 leaves are discriminated by dtype: uint8 holds nibble
    pairs, int8 holds plain int8 channels."""
    return qw["q"].dtype == jnp.uint8


def _unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """uint8 [..., out//2] nibble pairs -> [..., out] int8 tensor.

    Arithmetic unpack (mask / shift / sign-extend), identical traced and
    eager. NOT a ``lax.bitcast_convert_type(..., int4)``: on this JAX line
    the sub-byte bitcast keeps the byte shape at abstract-eval time (no
    trailing nibble axis), so the following widen-to-[..., out] reshape is
    a width mismatch — and the lowering fails the MLIR verifier anyway
    (KVM063's sub-byte-bitcast rule pins this). An S4 intermediate at a
    dispatch boundary hit the same relayout recursion (see quantize_weight).
    The arithmetic form still streams only the packed bytes from HBM: XLA
    fuses the mask/shift into the consumer's producer epilogue."""
    lo = (packed & 0x0F).astype(jnp.int8)
    hi = (packed >> 4).astype(jnp.int8)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1)  # [..., out//2, 2]
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def unpacked_q(qw: dict[str, jnp.ndarray]) -> jnp.ndarray:
    """The quantized weight as its logical [..., in, out] integer tensor."""
    return _unpack_int4(qw["q"]) if is_packed_int4(qw) else qw["q"]


def dequantize_weight(qw: dict[str, jnp.ndarray], dtype=jnp.bfloat16) -> jnp.ndarray:
    q = unpacked_q(qw)
    deq = q.astype(jnp.float32) * qw["s"][..., None, :].astype(jnp.float32)
    if "a" in qw:
        # AWQ leaf: the stored integers encode W*s; fold the input scaling
        # back (a = 1/s) so this returns the effective weight
        deq = deq * qw["a"][..., :, None].astype(jnp.float32)
    return deq.astype(dtype)


def linear(x: jnp.ndarray, w: Any, mode: str = "dequant") -> jnp.ndarray:
    """``x @ w`` where ``w`` is a plain array or a quantized dict.

    ``mode`` is the quant_mode axis (ops/qmatmul.py QUANT_MODES):

    - ``"dequant"`` (default, W8A16/W4A16): the matmul runs with the int
      tensor cast to the activation dtype (one fused convert feeding the
      MXU) and the per-channel scale applied to the [..., out] result — an
      epilogue multiply, not a materialized dequantized weight. AWQ leaves
      additionally multiply the activations by the per-input-channel
      compensation (``a``) first — a producer-side elementwise op XLA
      fuses; HBM traffic is unchanged.
    - ``"w8a8"``: activations are quantized per token and the contraction
      runs int8 x int8 on the MXU with an int32 accumulator, scales folded
      post-accumulation (ops/qmatmul.py qdot). Plain (unquantized) weights
      are unaffected by the mode.
    """
    if is_quantized(w):
        if mode == "w8a8":
            from kserve_vllm_mini_tpu.ops.qmatmul import qdot

            return qdot(x, w)
        if "a" in w:
            x = x * w["a"].astype(x.dtype)
        y = x @ unpacked_q(w).astype(x.dtype)
        return y * w["s"].astype(x.dtype)
    return x @ w


# Names of the per-layer matmul weights that quantization applies to, in
# whichever stack of ``layers`` they lie: every row of models/llama.py
# matmul_shapes but the routers. Norms, embeddings, and the lm_head stay
# high-precision — standard practice, and the embed is a gather anyway. So do
# the small leaves of the mixers and the expert blocks: routers and their
# selection biases, convolution taps and biases, the delta rule's wa, wb
# [d, heads], A_log, D, dt_bias and every gain are noise-level bytes that set
# a state's decay, a write's strength or a token's experts. No such leaf
# shares a name with one below (tests/test_layer_plan.py holds that).
QUANTIZABLE = (
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",   # attention; an MLP or GLU experts
    "wg",                                                 # the delta-rule mixer's output gate
    "in_proj", "out_proj",                                # Mamba-2; the short convolution
    "w_in", "w_out", "w1", "w2", "s1", "s2",              # latent experts beside a shared one
)


def quantize_params(params: dict[str, Any], bits: int = 8) -> dict[str, Any]:
    """Quantize every transformer matmul weight of a param tree, whether
    ``layers`` is one flat stack or holds a stack a kind of layer."""
    def stack(layers: dict[str, Any]) -> dict[str, Any]:
        return {k: (quantize_weight(v, bits=bits) if k in QUANTIZABLE
                    else stack(v) if isinstance(v, dict) else v)
                for k, v in layers.items()}

    return {**params, "layers": stack(params["layers"])}


def quantized_bytes(params: dict[str, Any]) -> int:
    """Total parameter bytes, honoring quantized leaves (for /metrics + logs).

    int4 counts as half a byte per element — XLA packs pairs in TPU HBM
    even though host-side ml_dtypes reports itemsize 1."""
    import jax
    import jax.numpy as jnp

    total = 0
    for leaf in jax.tree.leaves(params):
        if leaf.dtype == jnp.int4:
            total += (leaf.size + 1) // 2
        else:
            total += leaf.size * leaf.dtype.itemsize
    return total

"""The gated short convolution: the sequence operator of a ``"conv"`` layer
(LFM2, LFM2-MoE).

``in_proj`` gives three streams of the model's width, ``B | C | x`` in that
order. With ``g_t = B_t * x_t``::

    v_t = sum_{j < L} taps[j] * g_{t-(L-1)+j}        y_t = out_proj(C_t * v_t)

a depthwise causal convolution of ``L = conv_L_cache`` taps (the last on the
current position) with no bias and NO activation, gated on the way in and on
the way out. Its only memory is the last ``L - 1`` values of ``g``: no
recurrent state. That tail has no positions, so like the other mixers'
(models/linear_attention.py, whose ``causal_conv`` this shares) it is cut at
``valid``: a row with ``valid == 0`` keeps its tail bit for bit.
"""

from __future__ import annotations

import jax.numpy as jnp

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.models.linear_attention import causal_conv
from kserve_vllm_mini_tpu.ops.quant import linear


def mixer_weight_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """``[in, out]`` of the operator's two matmuls (both quantized in the
    int8 tree); the taps ``conv`` [L, d] stay as they are."""
    d = cfg.d_model
    return {"in_proj": (d, 3 * d), "out_proj": (d, d)}


def state_shapes(cfg: ModelConfig, batch: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """The one leaf the operator keeps per slot, slot axis at dim 1 like K/V."""
    return {"conv_tail": ((cfg.n_conv_layers, batch, cfg.conv_L_cache - 1, cfg.d_model),
                          cfg.dtype)}


def short_conv_mixer(
    p: dict,                      # one layer's operator leaves
    cfg: ModelConfig,
    x: jnp.ndarray,               # [B, T, D] (already normed)
    tail: jnp.ndarray,            # [B, L-1, D]: the gated inputs before this block
    valid: jnp.ndarray,           # [B] int32: leading positions that are real
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The operator over one block -> (y [B, T, D], new tail)."""
    b, c, u = jnp.split(linear(x, p["in_proj"], mode=cfg.quant_mode), 3, axis=-1)
    v, new_tail = causal_conv(b * u, tail, p["conv"], valid, activation=None)
    return linear(c * v.astype(x.dtype), p["out_proj"], mode=cfg.quant_mode), new_tail

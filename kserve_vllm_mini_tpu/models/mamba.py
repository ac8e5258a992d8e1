"""Mamba-2: the state-space mixer of a ``hybrid_override_pattern`` stack.

Dao and Gu, "Transformers are SSMs" (arXiv:2405.21060). Per head ``h`` with a
state ``S`` [head_dim, d_state], a scalar transition and an input-dependent
step::

    dt_t = softplus(dt~_t + dt_bias)        a_t = exp(dt_t * A),  A = -exp(A_log)
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t    y_t = S_t C_t + D x_t

``in_proj`` gives the gate ``z``, the convolution's input ``xBC`` (x
[heads, head_dim]; B and C [n_groups, d_state], head ``h`` reading group
``h // (heads / n_groups)``) and ``dt~``; ``xBC`` passes a causal depthwise
convolution with bias and SiLU. The output is
``out_proj(RMSNorm_group(y * silu(z)))``, the norm over each of the
``n_groups`` groups of ``d_inner / n_groups`` channels (gate first).

Decode (``T == 1``) is the recurrence as written (``recurrent_step``). As
jnp it is three passes over a layer's state (XLA's update reads and writes
it, a second fusion reads the new state again for ``S C``); on a TPU a
decode step takes ``kvmini_ssm_decode`` (ops/ssm_decode.py): the same update
with a slot's heads held in VMEM, so the state is read once and written
once, inside the cache's layer stack (``ssm_decode_kernel_block`` is the
gate). ``recurrent_step`` runs everywhere else and is the oracle. Prefill is
the chunked (state-space-dual) form, ``chunk_size`` positions at a time:
inside a chunk the outputs are a masked ``C B^T`` product, between chunks
one pass carries the state.

As for the delta rule (models/linear_attention.py, whose ``causal_conv``
this shares): the state has no positions, so every entry point takes
``valid`` [B]. Positions past it get ``dt = 0``, which decays nothing
(``a = 1``) and writes nothing, and the tail is cut at ``valid``; a row with
``valid == 0`` keeps state and tail bit for bit. The state is float32.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.models.linear_attention import causal_conv
from kserve_vllm_mini_tpu.models.llama import scaled
from kserve_vllm_mini_tpu.ops.flash_attention import pallas_interpret
from kserve_vllm_mini_tpu.ops.quant import linear
from kserve_vllm_mini_tpu.ops.ssm_decode import ssm_decode_block, ssm_decode_update

_HI = jax.lax.Precision.HIGHEST


def mixer_weight_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """``[in, out]`` of the mixer's two matmuls (both quantized in the int8
    tree). ``in_proj``'s columns are z, xBC, dt in that order."""
    di, ch = cfg.mamba_d_inner, cfg.mamba_conv_channels
    return {"in_proj": (cfg.d_model, di + ch + cfg.mamba_num_heads),
            "out_proj": (di, cfg.d_model)}


def state_shapes(cfg: ModelConfig, batch: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """The two leaves the mixer keeps per slot, slot axis at dim 1 like K/V."""
    n = cfg.n_ssm_layers
    return {
        "ssm_state": ((n, batch, cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size), "float32"),
        "ssm_conv": ((n, batch, cfg.conv_kernel - 1, cfg.mamba_conv_channels), cfg.dtype),
    }


def recurrent_step(state, x, b, c, dt, a_log_neg):
    """One position. state [B,H,P,N] f32; x [B,H,P]; b, c [B,H,N] (already
    spread from groups to heads); dt [B,H]; a_log_neg = A [H] (negative).
    Elementwise and one reduction: float32 stays float32 on every backend."""
    decay = jnp.exp(dt * a_log_neg)[..., None, None]
    s = state * decay + (dt[..., None] * x)[..., None] * b[..., None, :]
    return s, jnp.sum(s * c[..., None, :], axis=-1)


def ssm_decode_kernel_block(kernels: str, ssm_state: jnp.ndarray, t: int) -> Optional[int]:
    """Heads a grid step of ``kvmini_ssm_decode`` holds where a block of
    ``t`` positions over the cache's state stack ``ssm_state``
    [L, B, H, P, N] takes the kernel, or None where it keeps
    ``recurrent_step`` / ``chunked_scan``. THE gate (the twin of
    ``linear_attention.linear_decode_kernel_block``), read from what the
    call can see: the kernels mode and backend, one position a row, and the
    stack's dtype and tile (``ssm_decode_block``: float32, ``P`` whole
    sublane tiles, ``N`` whole lane tiles, a head block that fits VMEM)."""
    if t != 1 or pallas_interpret(kernels) is None:
        return None
    return ssm_decode_block(*ssm_state.shape[2:], ssm_state.dtype)


def recurrent_scan(state, x, b, c, dt, a):
    """The recurrence token by token over [B,T,H,*] inputs: the tests'
    oracle for ``chunked_scan``, never a serving path."""
    def body(s, xs):
        return recurrent_step(s, *xs, a)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt))
    state, y = jax.lax.scan(body, state, xs)
    return state, jnp.moveaxis(y, 0, 1)


def chunked_scan(state, x, b, c, dt, a, chunk: int):
    """The same recurrence over x [B,T,H,P], b, c [B,T,G,N], dt [B,T,H],
    ``chunk`` positions at a time. With ``l = cumsum(dt * A)`` inside a
    chunk entering with state S0::

        Y = tril(C B^T * e^{l_i - l_j}) (dt X)  +  e^{l} C S0
        S1 = e^{l_end} S0 + (e^{l_end - l} dt X)^T B

    Exponents are differences that are <= 0: nothing overflows. A position
    with ``dt = 0`` writes nothing and decays nothing."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    cs = chunk if t >= chunk else t
    pad = -t % cs
    if pad:
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))) for v in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    nc = (t + pad) // cs
    per = h // g

    def chunks(v):                                   # [B, T, ...] -> [B, NC, C, ...]
        return v.reshape(bsz, nc, cs, *v.shape[2:])

    x, b, c, dt = chunks(x), chunks(b), chunks(c), chunks(dt)
    la = jnp.cumsum(dt * a, axis=2)                  # [B, NC, C, H], <= 0, decreasing
    xdt = x * dt[..., None]
    i, j = jnp.arange(cs)[:, None], jnp.arange(cs)[None, :]
    diff = la[:, :, :, None, :] - la[:, :, None, :, :]             # [B,NC,C,C,H]
    decay = jnp.exp(jnp.where((j <= i)[None, None, :, :, None], diff, -jnp.inf))
    cb = jnp.einsum("bzign,bzjgn->bzijg", c, b, precision=_HI)     # [B,NC,C,C,G]
    scores = jnp.repeat(cb, per, axis=-1) * decay                  # [B,NC,C,C,H]
    y_in = jnp.einsum("bzijh,bzjhp->bzihp", scores, xdt, precision=_HI)
    # what a chunk adds to the state, and how much of the entering state is left
    to_end = jnp.exp(la[:, :, -1:, :] - la)                        # [B,NC,C,H]
    bh = jnp.repeat(b, per, axis=3)                                # [B,NC,C,H,N]
    ch = jnp.repeat(c, per, axis=3)
    add = jnp.einsum("bzjhp,bzjhn->bzhpn", xdt * to_end[..., None], bh, precision=_HI)
    keep = jnp.exp(la[:, :, -1, :])                                # [B,NC,H]
    read = ch * jnp.exp(la)[..., None]                             # [B,NC,C,H,N]

    def body(s, xs):
        add_z, keep_z, read_z = xs
        y_off = jnp.einsum("bihn,bhpn->bihp", read_z, s, precision=_HI)
        return keep_z[..., None, None] * s + add_z, y_off

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (add, keep, read))
    state, y_off = jax.lax.scan(body, state, xs)                   # [NC,B,C,H,P]
    y = y_in + jnp.moveaxis(y_off, 0, 1)
    return state, y.reshape(bsz, nc * cs, h, p)[:, :t]


def grouped_rms_norm(y: jnp.ndarray, gain: jnp.ndarray, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal parts of the last axis, then
    the gain [d_inner]. ``y`` float32."""
    shape = y.shape
    yg = y.reshape(*shape[:-1], groups, shape[-1] // groups)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(shape) * gain.astype(jnp.float32)


def gated_norm(y: jnp.ndarray, z: jnp.ndarray, gain: jnp.ndarray, groups: int, eps: float):
    """The mixer's output norm, gate FIRST (``norm_before_gate`` false in the
    published configs): ``RMSNorm_group(y * silu(z))``."""
    return grouped_rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), gain, groups, eps)


def mamba_mixer(
    p: dict,                      # one layer's mixer leaves
    cfg: ModelConfig,
    x: jnp.ndarray,               # [B, T, D] (already normed)
    state: jnp.ndarray,           # [B, H, P, N] float32; with ``layer``,
                                  # the cache's whole stack [L, B, H, P, N]
    tail: jnp.ndarray,            # [B, K-1, C]
    valid: jnp.ndarray,           # [B] int32: leading positions that are real
    kernels: str = "auto",        # Pallas kernel selection, as ``forward``'s
    layer: Optional[jnp.ndarray] = None,  # scalar int32: ``state`` is the
                                  # stack and this layer of it is updated in
                                  # place by the decode kernel; the caller
                                  # asked ``ssm_decode_kernel_block``
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The mixer over one block -> (y [B,T,D], new state, new tail). ``T ==
    1`` takes the one-token recurrence, anything longer the chunked form.
    Rows with ``valid == 0`` come back with state and tail untouched. With
    ``layer`` the state goes in and comes back as the whole stack: slicing a
    layer out for the kernel and writing it back would cost the two passes
    the kernel saves."""
    bsz, t, _ = x.shape
    h, hp, n, g = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size, cfg.n_groups
    di = cfg.mamba_d_inner
    zxbcdt = linear(scaled(x, cfg.ssm_in_multiplier), p["in_proj"], mode=cfg.quant_mode)
    if cfg.ssm_multipliers is not None:
        # one fixed scalar a segment of in_proj's output: z | x | B | C | dt
        by = jnp.concatenate([jnp.full((width,), m, jnp.float32) for width, m in zip(
            (di, di, g * n, g * n, h), cfg.ssm_multipliers)])
        zxbcdt = (zxbcdt.astype(jnp.float32) * by).astype(zxbcdt.dtype)
    z, xbc, dt = jnp.split(zxbcdt, [di, di + cfg.mamba_conv_channels], axis=-1)
    xbc, new_tail = causal_conv(xbc, tail, p["conv"], valid, bias=p["conv_b"])
    xs = xbc[..., :di].reshape(bsz, t, h, hp)
    b = xbc[..., di:di + g * n].reshape(bsz, t, g, n)
    c = xbc[..., di + g * n:].reshape(bsz, t, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    real = (jnp.arange(t)[None, :] < valid[:, None])[..., None]    # [B, T, 1]
    dt = jnp.where(real, dt, 0.0)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    if layer is not None:           # the kernel holds the rows with valid == 0 itself
        new_state, y = ssm_decode_update(
            state, layer, xs[:, 0], b[:, 0], c[:, 0], dt[:, 0], a, valid,
            interpret=pallas_interpret(kernels))
        y = y[:, None]
    elif t == 1:
        per = h // g
        s, y = recurrent_step(state, xs[:, 0], jnp.repeat(b[:, 0], per, axis=1),
                              jnp.repeat(c[:, 0], per, axis=1), dt[:, 0], a)
        y = y[:, None]
    else:
        s, y = chunked_scan(state, xs, b, c, dt, a, cfg.chunk_size)
    if layer is None:
        new_state = jnp.where((valid > 0)[:, None, None, None], s, state)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    normed = gated_norm(y.reshape(bsz, t, di), z, p["norm"], g, cfg.rms_eps).astype(x.dtype)
    return linear(normed, p["out_proj"], mode=cfg.quant_mode), new_state, new_tail

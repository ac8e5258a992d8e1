"""The gated delta rule: the linear-attention mixer of a hybrid stack.

Yang, Kautz, Hatamizadeh, "Gated Delta Networks" (arXiv:2412.06464); the
chunked form follows Yang et al., "Parallelizing Linear Transformers with
the Delta Rule over Sequence Length" (arXiv:2406.06484). Per head, with a
state ``S`` [dk, dv]::

    S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T      o_t = S_t^T q_t

``q``, ``k``, ``v`` come from the projections through a causal depthwise
convolution and SiLU; ``q`` and ``k`` are L2-normalised per head (``q`` times
dk^-1/2); ``b_t = sigmoid(W_b x_t)`` (doubled under
``linear_allow_neg_eigval``) and ``log a_t = -exp(A_log) softplus(W_a x_t +
dt_bias)``. The output is ``W_o[RMSNorm(o_t) * silu(W_g x_t)]``.

What a serving cache needs of it, and the reason this is a module of its
own: the mixer's memory is not positional. A key/value stripe forgives a
write at a padded or parked position (it is overwritten before anything
attends it); a recurrent state does not. So every entry point takes
``valid`` [B], the number of leading positions of the block that are real,
and positions past it leave ``S`` and the convolution tail bit-for-bit as
they were: their ``b`` is 0 and their ``log a`` is 0, and the tail is cut
at ``valid``. A decode sweep passes 0 for a slot that is parked or between
two pieces of its prefill.

The state is float32 whatever the model's dtype: it is summed into for the
whole life of a sequence.

A decode step (``T == 1``) on a TPU takes ``kvmini_linear_decode``
(ops/linear_decode.py): ``recurrent_step`` with a slot's heads held in VMEM,
so the state is read once and written once, inside the cache's layer stack
(``linear_decode_kernel_block`` is the gate). ``recurrent_step`` runs
everywhere else and is the oracle.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.ops.flash_attention import pallas_interpret
from kserve_vllm_mini_tpu.ops.linear_decode import linear_decode_block, linear_decode_update
from kserve_vllm_mini_tpu.ops.quant import linear
from kserve_vllm_mini_tpu.ops.rmsnorm import rms_norm

CHUNK = 64          # positions the chunk-parallel form solves at once
L2_EPS = 1e-6       # q / sqrt(sum q^2 + eps), as the published kernels normalise

_HI = jax.lax.Precision.HIGHEST


def mixer_weight_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """Per-layer shape of every matmul of the linear mixer, ``[in, out]``.
    The int8 checkpoint tree quantizes the five projections
    (ops/quant.py QUANTIZABLE); wa, wb, the convolution taps,
    A_log, dt_bias and the gains stay as they are."""
    h = cfg.linear_num_key_heads
    kd, vd = h * cfg.linear_key_head_dim, h * cfg.linear_value_head_dim
    d = cfg.d_model
    return {"wq": (d, kd), "wk": (d, kd), "wv": (d, vd), "wg": (d, vd),
            "wo": (vd, d), "wa": (d, h), "wb": (d, h)}


def state_shapes(cfg: ModelConfig, batch: int) -> dict[str, tuple[tuple[int, ...], str]]:
    """The two leaves the mixer keeps per slot, slot axis at dim 1 like K/V:
    the recurrent state (float32) and the last ``kernel - 1`` inputs of the
    convolution (model dtype)."""
    n, h = cfg.n_linear_layers, cfg.linear_num_key_heads
    return {
        "lin_state": ((n, batch, h, cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                      "float32"),
        "lin_conv": ((n, batch, cfg.linear_conv_kernel_dim - 1, cfg.linear_conv_channels),
                     cfg.dtype),
    }


def causal_conv(u: jnp.ndarray, tail: jnp.ndarray, taps: jnp.ndarray,
                valid: jnp.ndarray, bias: jnp.ndarray | None = None,
                activation=jax.nn.silu) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution (plus ``bias`` [C] where the mixer has
    one) then ``activation`` (SiLU for the delta rule and Mamba-2; None for
    the short-convolution operator, which has none). ``u`` [B, T, C] are this block's inputs, ``tail``
    [B, K-1, C] the inputs before it, ``taps`` [K, C] with the last tap on
    the current input. Returns the float32 outputs [B, T, C] and the new
    tail: the K-1 inputs before position ``valid`` (the old tail where
    ``valid`` is 0)."""
    k = taps.shape[0]
    t = u.shape[1]
    ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)       # [B, T+K-1, C]
    w = taps.astype(jnp.float32)
    y = sum(ext[:, j:j + t].astype(jnp.float32) * w[j] for j in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    rows = valid[:, None] + jnp.arange(k - 1)[None, :]             # [B, K-1]
    new_tail = jnp.take_along_axis(ext, rows[:, :, None], axis=1)
    return (y if activation is None else activation(y)), new_tail.astype(tail.dtype)


def _l2(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def recurrent_step(state, q, k, v, g, beta):
    """One position, the recurrence as written. state [B,H,dk,dv] f32;
    q, k [B,H,dk]; v [B,H,dv]; g = log a, beta [B,H]. Elementwise and
    reductions only, so float32 stays float32 on every backend. The read
    ``S_t^T q`` is taken from the decayed state, ``(a S)^T q + u (k.q)``:
    both reductions then sit in one pass over the state and the write in a
    second, instead of a third pass over the new state."""
    s = state * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    o = jnp.sum(s * q[..., None], axis=-2) + u * jnp.sum(k * q, axis=-1, keepdims=True)
    return s + k[..., None] * u[..., None, :], o


def linear_decode_kernel_block(kernels: str, lin_state: jnp.ndarray, t: int) -> Optional[int]:
    """Heads a grid step of ``kvmini_linear_decode`` holds where a block of
    ``t`` positions over the cache's state stack ``lin_state``
    [L, B, H, dk, dv] takes the kernel, or None where it keeps
    ``recurrent_step`` / ``chunked_scan``. THE gate, read from what the call
    can see: the kernels mode and backend, one position a row, and the
    stack's dtype and tile (``linear_decode_block``: float32, whole sublane
    tiles, a head block that fits VMEM)."""
    if t != 1 or pallas_interpret(kernels) is None:
        return None
    return linear_decode_block(*lin_state.shape[2:], lin_state.dtype)


def recurrent_scan(state, q, k, v, g, beta):
    """The recurrence token by token over [B,T,H,*] inputs: the oracle the
    chunked form is tested against, never a serving path."""
    def body(s, xs):
        s, o = recurrent_step(s, *xs)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(body, state, xs)
    return state, jnp.moveaxis(o, 0, 1)


def unit_lower_inverse(a: jnp.ndarray) -> jnp.ndarray:
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., n, n]:
    forward substitution row by row on diagonal blocks of at most 16
    (elementwise float32), then the halves merged with matmuls,
    ``[[T11, 0], [-T22 a21 T11, T22]]``. The backend's triangular solve is
    one custom call a matrix on a TPU (2.4 ms a layer at 1024 positions);
    this is a few fused operations and five small batched matmuls."""
    n = a.shape[-1]
    if n <= 16:
        eye = jnp.eye(n, dtype=a.dtype)
        t = jnp.broadcast_to(eye, a.shape)
        for i in range(1, n):
            row = eye[i] - jnp.sum(a[..., i, :i, None] * t[..., :i, :], axis=-2)
            t = t.at[..., i, :].set(row)
        return t
    h = n // 2
    t11, t22 = unit_lower_inverse(a[..., :h, :h]), unit_lower_inverse(a[..., h:, h:])
    t21 = -jnp.einsum("...ij,...jk,...kl->...il", t22, a[..., h:, :h], t11, precision=_HI)
    top = jnp.concatenate([t11, jnp.zeros_like(a[..., :h, h:])], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([t21, t22], axis=-1)], axis=-2)


def chunked_scan(state, q, k, v, g, beta, chunk: int = CHUNK):
    """The same recurrence over [B,T,H,*] inputs, ``chunk`` positions at a
    time. Inside a chunk with entering state S0 and G_i = sum_{j<=i} g_j::

        (I + A) U = diag(beta) (V - diag(e^G) K S0)     A_ij = beta_i e^{G_i-G_j} k_i.k_j  (j < i)
        O = diag(e^G) Q S0 + tril(Q K^T * e^{G_i-G_j}) U
        S1 = e^{G_C} S0 + (K * e^{G_C-G})^T U

    ``(I + A)^-1`` (``unit_lower_inverse``) is applied to ``beta V`` and
    ``beta e^G K`` for every chunk at once, so the only sequential
    part is one pass over the T / chunk chunks carrying S. Decays enter as
    exponentials of differences that are <= 0: nothing overflows. A
    position with ``beta = 0`` and ``g = 0`` contributes nothing and decays
    nothing."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    c = chunk if t >= chunk else t
    pad = -t % c
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    n = (t + pad) // c

    def heads_first(a):                      # [B, T, H, d] -> [B, H, N, C, d]
        return jnp.moveaxis(a.reshape(b, n, c, h, -1), 3, 1)

    q, k, v = heads_first(q), heads_first(k), heads_first(v)
    g = jnp.moveaxis(g.reshape(b, n, c, h), 3, 1)                  # [B, H, N, C]
    beta = jnp.moveaxis(beta.reshape(b, n, c, h), 3, 1)
    cum = jnp.cumsum(g, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]                   # G_i - G_j
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(j <= i, diff, -jnp.inf))             # lower incl. diagonal
    kk = jnp.einsum("bhnid,bhnjd->bhnij", k, k, precision=_HI)
    a = jnp.where(j < i, beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(cum))[..., None] * k], axis=-1)
    solved = jnp.einsum("bhnij,bhnjd->bhnid", unit_lower_inverse(a), rhs, precision=_HI)
    w_v, w_k = solved[..., :dv], solved[..., dv:]                  # U = w_v - w_k S0
    qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(cum)[..., None]                             # reads S0
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]            # writes S1
    carry_decay = jnp.exp(cum[..., -1])                            # [B, H, N]

    def body(s, xs):
        w_v, w_k, qk, q_in, k_out, dec = xs
        u = w_v - jnp.einsum("bhik,bhkv->bhiv", w_k, s, precision=_HI)
        o = (jnp.einsum("bhik,bhkv->bhiv", q_in, s, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk, u, precision=_HI))
        s = dec[..., None, None] * s + jnp.einsum(
            "bhik,bhiv->bhkv", k_out, u, precision=_HI)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w_v, w_k, qk, q_in, k_out, carry_decay))
    state, o = jax.lax.scan(body, state, xs)                       # o [N, B, H, C, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)[:, :, :t]
    return state, jnp.moveaxis(o, 1, 2)                            # [B, T, H, dv]


def linear_mixer(
    p: dict,                      # one layer's mixer leaves
    cfg: ModelConfig,
    x: jnp.ndarray,               # [B, T, D]
    state: jnp.ndarray,           # [B, H, dk, dv] float32; with ``layer``,
                                  # the cache's whole stack [L, B, H, dk, dv]
    tail: jnp.ndarray,            # [B, K-1, C]
    valid: jnp.ndarray,           # [B] int32: leading positions that are real
    kernels: str = "auto",        # Pallas kernel selection, as ``forward``'s
    layer: Optional[jnp.ndarray] = None,  # scalar int32: ``state`` is the
                                  # stack and this layer of it is updated in
                                  # place by the decode kernel; the caller
                                  # asked ``linear_decode_kernel_block``
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The mixer over one block -> (y [B,T,D], new state, new tail).
    ``T == 1`` takes the one-token recurrence, anything longer the
    chunk-parallel form. Rows of the batch with ``valid == 0`` come back
    with state and tail untouched, bit for bit. With ``layer`` the state
    goes in and comes back as the whole stack: slicing a layer out for the
    kernel and writing it back would cost the two passes the kernel saves."""
    b, t, _ = x.shape
    h, dk, dv = cfg.linear_num_key_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    qm = cfg.quant_mode
    u = jnp.concatenate(
        [linear(x, p["wq"], mode=qm), linear(x, p["wk"], mode=qm),
         linear(x, p["wv"], mode=qm)], axis=-1)
    y, new_tail = causal_conv(u, tail, p["conv"], valid)
    q = _l2(y[..., : h * dk].reshape(b, t, h, dk)) * dk ** -0.5
    k = _l2(y[..., h * dk: 2 * h * dk].reshape(b, t, h, dk))
    v = y[..., 2 * h * dk:].reshape(b, t, h, dv)
    xf = x.astype(jnp.float32)
    beta = jax.nn.sigmoid(xf @ p["wb"].astype(jnp.float32))
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        xf @ p["wa"].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    real = (jnp.arange(t)[None, :] < valid[:, None])[..., None]    # [B, T, 1]
    beta, g = jnp.where(real, beta, 0.0), jnp.where(real, g, 0.0)
    if t > 1:
        s, o = chunked_scan(state, q, k, v, g, beta)
    else:
        one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        if layer is None:
            s, o = recurrent_step(state, *one)
        else:       # the kernel holds the rows with valid == 0 itself
            new_state, o = linear_decode_update(
                state, layer, *one, valid, interpret=pallas_interpret(kernels))
        o = o[:, None]
    if layer is None:
        new_state = jnp.where((valid > 0)[:, None, None, None], s, state)
    o = rms_norm(o, p["o_norm"], cfg.rms_eps)
    gate = jax.nn.silu(linear(x, p["wg"], mode=qm).astype(jnp.float32))
    gated = (o.reshape(b, t, h * dv) * gate).astype(x.dtype)
    return linear(gated, p["wo"], mode=qm), new_state, new_tail

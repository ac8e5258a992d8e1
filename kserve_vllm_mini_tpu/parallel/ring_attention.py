"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

The reference has no sequence parallelism at all (SURVEY.md §5.7 — sequence
length is just a config knob handed to external engines). The TPU build owns
the runtime, so long context is real work: the sequence axis is sharded over
``sp``, each device holds a [B, H, T/sp, D] block of Q/K/V, and K/V blocks
rotate around the ring via ``jax.lax.ppermute`` while a numerically-stable
online-softmax accumulator (flash-attention style m/l/acc triplet) folds in
one block per step. Peak memory per device is O(T/sp) instead of O(T), and
the ppermute rides ICI neighbor links.

Causality is positional: absolute position ids travel with each K block, so
the mask never depends on ring step index and uneven/rotated layouts stay
correct.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kserve_vllm_mini_tpu.ops.attention import repeat_kv


def _block_accumulate(q, k, v, q_pos, k_pos, m, l, acc, scale):
    """Fold one K/V block into the online-softmax state.

    q: [B,H,Tq,D]; k,v: [B,H,Tk,D]; *_pos: [B,Tq]/[B,Tk];
    m,l: [B,H,Tq]; acc: [B,H,Tq,D] (all f32).
    """
    logits = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) * scale
    mask = (k_pos[:, None, None, :] <= q_pos[:, None, :, None])
    logits = jnp.where(mask, logits, -jnp.inf)
    m_blk = jnp.max(logits, axis=-1)                      # [B,H,Tq]
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (m_new == -inf): contribute nothing
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(logits - safe_m[..., None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhts,bhsd->bhtd", p.astype(v.dtype), v
    ).astype(jnp.float32)
    return m_new, l_new, acc_new


def _ring_attention_local(q, k, v, q_pos, k_pos, axis_name: str, scale: float,
                          sp: int):
    """Per-device body run under shard_map. Shapes are the local blocks.

    ``sp`` is the ring size, passed statically from the mesh (the perm
    list needs a Python int)."""
    n_rep = q.shape[1] // k.shape[1]
    if n_rep > 1:
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
    B, H, Tq, D = q.shape
    # the accumulators are logically device-varying over the ring axis from
    # step 1 on; shard_map's typing requires declaring that up front or the
    # fori_loop carry types mismatch.
    def varying(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    m = varying(jnp.full((B, H, Tq), -jnp.inf, dtype=jnp.float32))
    l = varying(jnp.zeros((B, H, Tq), dtype=jnp.float32))
    acc = varying(jnp.zeros((B, H, Tq, D), dtype=jnp.float32))

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(i, carry):
        m, l, acc, k, v, k_pos = carry
        m, l, acc = _block_accumulate(q, k, v, q_pos, k_pos, m, l, acc, scale)
        # rotate K/V (and their positions) to the next ring neighbor
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        k_pos = jax.lax.ppermute(k_pos, axis_name, perm)
        return m, l, acc, k, v, k_pos

    m, l, acc, *_ = jax.lax.fori_loop(0, sp, step, (m, l, acc, k, v, k_pos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,       # [B, H, T, D], T sharded over sp
    k: jnp.ndarray,       # [B, KVH, T, D]
    v: jnp.ndarray,       # [B, KVH, T, D]
    positions: jnp.ndarray,  # [B, T] absolute positions, sharded with T
    mesh: Mesh,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Causal ring attention over the mesh's ``sp`` axis. Returns [B, H, T, D]
    with the same sequence sharding as q."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    seq = P(None, None, "sp", None)
    pos_spec = P(None, "sp")
    fn = shard_map(
        partial(_ring_attention_local, axis_name="sp", scale=scale,
                sp=int(mesh.shape["sp"])),
        mesh=mesh,
        in_specs=(seq, seq, seq, pos_spec, pos_spec),
        out_specs=seq,
    )
    return fn(q, k, v, positions, positions)

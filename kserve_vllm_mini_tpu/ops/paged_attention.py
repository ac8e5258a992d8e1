"""Pallas TPU paged-attention decode kernel.

The gather-based paged read (models/llama.py ``_read_layer``) materializes
the batch's blocks into a contiguous [B, KVH, S, D] tensor before the
attention matmuls — 3x the KV bytes in HBM traffic (pool read + gather
write + attention read). This kernel is the TPU-native version of the trick
vLLM's namesake PagedAttention kernel does on GPU: the BLOCK TABLE is a
scalar-prefetch argument whose values drive each grid step's BlockSpec
index map, so the pool block a slot needs is DMA'd straight from HBM into
VMEM — per slot, per kv head, per block — and attention runs on it in
place. No gathered copy exists, and HBM sees exactly one read of the live
KV prefix.

Layout: grid (slots, kv_heads, max_blocks); the online-softmax m/l/acc
recurrence lives in VMEM scratch and persists across the block sweep (the
innermost grid axis, same structure as ops/flash_attention.py). GQA comes
in pre-grouped: q is [S, KVH, G, D] so each grid step contracts a [G, D]
query tile against the [BLK, D] key block on the MXU.

Blocks past the slot's live length are skipped (``pl.when``) — their DMA
still happens (the grid is static), reading whatever block their table
entry names. The engine parks freed/unwritten table rows on its scratch
block (runtime/engine.py ``_paged_release``), which is what concentrates
the dead traffic; the ``jnp.clip`` below is only bounds safety for ids
outside [0, P).

The kernel takes the LAYER-STACKED pool ([L, P, KVH, BLK, D]) plus the
layer index as a scalar-prefetch value folded into the index map: slicing
one layer out before the call would hand XLA a dynamic-slice feeding a
custom call, which materializes the whole layer pool in HBM per step —
exactly the copy this kernel exists to avoid.

The jnp gather path is the correctness oracle; tests compare in interpret
mode on CPU (tests/test_paged_kernel.py). The serving path dispatches to
the kernel on TPU for plain-causal, bf16-KV configs and keeps the exact
gather path elsewhere (models/llama.py run_cached_layers).

``dense_decode_attention`` is the DENSE-cache twin for the int8-KV
layout: same shared online-softmax block body, same ``k_scale``/
``v_scale`` dequant-in-kernel convention, but the key-block sweep walks
the per-slot [L, B, KVH, S, D] cache stripes directly (no table) — so the
eager read path's materialized bf16 [B, KVH, S, D] dequantized tensor
never exists (models/llama.py ``_read_layer`` remains the fallback
oracle).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_block_body(
    qpos,        # scalar int32: this slot's query position
    q_ref,       # [1, 1, G, D] this slot/head's query tile
    k_ref,       # [1, 1, 1, BLK, D] this grid step's key block
    v_ref,       # [1, 1, 1, BLK, D]
    rest,        # [k_s_ref, v_s_ref,] o_ref, m_ref, l_ref, acc_ref —
                 # int8-KV mode carries [1, 1, KVH, BLK] per-position scale
                 # blocks for ALL kv heads (see ``_scale_spec``)
    block_k: int,
    scale: float,
    quantized: bool,
):
    """One key-block step of the online-softmax decode recurrence — the
    body BOTH decode kernels share (paged: the block arrived via the table
    index map; dense: via the sequential S sweep). The m/l/acc scratch
    persists across the innermost grid axis."""
    if quantized:
        k_s_ref, v_s_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(2)
    nb = pl.num_programs(2)
    # this grid step's kv head: the scale block spans every head (a
    # one-head block is not a legal TPU tile), so the row is picked here
    head = pl.ds(pl.program_id(1), 1)

    @pl.when(b == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # keys j of block b sit at positions b*BLK + j; the decode query at
    # position qpos attends j <= qpos, so a block starting past qpos is
    # all-masked — skip its FLOPs entirely
    run = b * block_k <= qpos

    @pl.when(run)
    def _accumulate():
        q = q_ref[0, 0]                      # [G, D]
        k = k_ref[0, 0, 0]                   # [BLK, D] (int8 when quantized)
        v = v_ref[0, 0, 0]
        logits = jax.lax.dot_general(
            q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                            # [G, BLK]
        if quantized:
            # per-position dequant folds into the [G, BLK] intermediates:
            # (q . k_j s_j) = (q . k_j) * s_j, and p @ (v s) = (p * s) @ v
            logits = logits * k_s_ref[0, 0, head, :]       # [1, BLK]
        kpos = b * block_k + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1
        )
        logits = jnp.where(kpos <= qpos, logits, _NEG_INF)
        m_prev = m_ref[:]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            pv = (p * v_s_ref[0, 0, head, :]).astype(jnp.float32)
            vv = v.astype(jnp.float32)
        else:
            pv = p.astype(v.dtype)
            vv = v
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            pv, vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(b == nb - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _paged_decode_kernel(
    layer_ref,   # [1] int32 layer index (scalar prefetch; used in index maps)
    table_ref,   # [S, MAXB] int32 (scalar prefetch)
    qpos_ref,    # [S] int32 query positions (scalar prefetch)
    q_ref,
    k_ref,       # the table-selected pool block
    v_ref,
    *rest,
    block_k: int,
    scale: float,
    quantized: bool,
):
    _decode_block_body(
        qpos_ref[pl.program_id(0)], q_ref, k_ref, v_ref, rest,
        block_k=block_k, scale=scale, quantized=quantized,
    )


def _dense_decode_kernel(
    layer_ref,   # [1] int32 layer index (scalar prefetch; used in index maps)
    qpos_ref,    # [B] int32 query positions (scalar prefetch)
    q_ref,
    k_ref,       # this slot's b-th BLK-position stripe of the dense cache
    v_ref,
    *rest,
    block_k: int,
    scale: float,
    quantized: bool,
):
    _decode_block_body(
        qpos_ref[pl.program_id(0)], q_ref, k_ref, v_ref, rest,
        block_k=block_k, scale=scale, quantized=quantized,
    )


def paged_decode_attention(
    q: jnp.ndarray,        # [S, KVH, G, D] decode queries, GQA pre-grouped
    k_pool: jnp.ndarray,   # [L, P, KVH, BLK, D] layer-stacked key pool
                           # (or [P, KVH, BLK, D] for a single layer)
    v_pool: jnp.ndarray,
    table: jnp.ndarray,    # [S, MAXB] int32 block ids (position order)
    qpos: jnp.ndarray,     # [S] int32 current query position per slot
    layer: jnp.ndarray | int = 0,  # which layer of the stacked pool
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [L, P, KVH, BLK] f32: int8-KV
    v_scale: Optional[jnp.ndarray] = None,  # per-position dequant scales
    interpret: bool = False,  # Pallas interpreter: tests only
) -> jnp.ndarray:
    """Returns [S, KVH, G, D] attention outputs, reading each slot's live
    blocks straight from the pool (table-driven DMA, no gather copy). The
    layer index rides the index map so the caller never slices the pool.
    With ``k_scale``/``v_scale`` the pools hold int8 values dequantized
    in-kernel (the scaled-int8 KV cache layout, models/llama.py)."""
    if k_pool.ndim == 4:
        k_pool = k_pool[None]
        v_pool = v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    quantized = k_scale is not None
    S, KVH, G, D = q.shape
    L, P, _, BLK, _ = k_pool.shape
    MAXB = table.shape[1]
    scale = scale if scale is not None else D ** -0.5

    # bounds safety only: dead-but-in-range ids DMA whatever they name
    # (the engine's scratch-row convention concentrates that traffic)
    safe_table = jnp.clip(table, 0, P - 1).astype(jnp.int32)
    qpos = qpos.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape((1,))

    def _pool_spec():
        return pl.BlockSpec(
            (1, 1, 1, BLK, D),
            lambda s, h, b, layer, table, qpos: (
                layer[0], table[s, b], h, 0, 0
            ),
        )

    def _scale_spec():
        # all KVH heads of the block: the TPU lowering wants the last two
        # block dims tile-divisible (8, 128) or equal to the array's, and a
        # one-head (1, BLK) block over [KVH, BLK] is neither — the kernel
        # body picks its head's row
        return pl.BlockSpec(
            (1, 1, KVH, BLK),
            lambda s, h, b, layer, table, qpos: (layer[0], table[s, b], 0, 0),
        )

    in_specs = [
        pl.BlockSpec(
            (1, 1, G, D),
            lambda s, h, b, layer, table, qpos: (s, h, 0, 0),
        ),
        _pool_spec(),
        _pool_spec(),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [_scale_spec(), _scale_spec()]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, KVH, MAXB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, G, D), lambda s, h, b, layer, table, qpos: (s, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_decode_kernel, block_k=BLK, scale=scale, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, KVH, G, D), q.dtype),
        interpret=interpret,
        name="kvmini_paged_decode",
    )(layer_arr, safe_table, qpos, *operands)


def dense_decode_block(seq_len: int) -> Optional[int]:
    """Key-block size the dense decode kernel sweeps ``seq_len`` with, or
    None when no supported block divides it (the caller then keeps the
    eager read path). Powers of two down to 8: the sweep grid must tile
    the cache's S axis exactly — Pallas pads partial blocks with whatever
    HBM holds, and while the positional mask would zero those scores, a
    dense cache length that is not even 8-aligned is a test-only shape
    not worth the kernel."""
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if seq_len % cand == 0:
            return cand
    return None


def dense_decode_attention(
    q: jnp.ndarray,        # [B, KVH, G, D] decode queries, GQA pre-grouped
    k_cache: jnp.ndarray,  # [L, B, KVH, S, D] layer-stacked dense cache
                           # (or [B, KVH, S, D] for a single layer)
    v_cache: jnp.ndarray,
    qpos: jnp.ndarray,     # [B] int32 current query position per slot
    layer: jnp.ndarray | int = 0,  # which layer of the stacked cache
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [L, B, KVH, S] f32: int8-KV
    v_scale: Optional[jnp.ndarray] = None,  # per-position dequant scales
    interpret: bool = False,  # Pallas interpreter: tests only
) -> jnp.ndarray:
    """Decode attention straight over the DENSE per-slot cache: the twin of
    ``paged_decode_attention`` for ``kv_layout="dense"``.

    The eager int8-KV read path (models/llama.py ``_read_layer``)
    dequantizes the whole [B, KVH, S, D] stripe into a materialized bf16
    tensor before attention — 3x the live-KV bytes in HBM traffic (int8
    read + bf16 write + attention read) plus a full dequantized copy in
    HBM. Here each BLK-position stripe is DMA'd int8 from HBM into VMEM
    and dequantized in-register inside the online-softmax sweep (the same
    shared block body as the paged kernel, same ``k_scale``/``v_scale``
    layout), so the bf16 KV tensor never exists. The layer index rides the
    index map so the caller never slices the stacked cache (a dynamic-
    slice operand feeding a custom call would materialize the whole layer
    in HBM — the copy this kernel exists to avoid).

    Blocks past a slot's live length are skipped by the block body's
    ``run`` guard; their DMA still happens (static grid) but reads the
    slot's own dead cache tail, never another slot's data."""
    if k_cache.ndim == 4:
        k_cache = k_cache[None]
        v_cache = v_cache[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    quantized = k_scale is not None
    B, KVH, G, D = q.shape
    L, _, _, S, _ = k_cache.shape
    BLK = dense_decode_block(S)
    if BLK is None:
        raise ValueError(
            f"dense decode kernel needs a power-of-two-tileable seq axis "
            f"(>= 8); got S={S} — use the eager read path"
        )
    scale = scale if scale is not None else D ** -0.5

    qpos = qpos.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape((1,))
    nb = S // BLK

    def _cache_spec():
        return pl.BlockSpec(
            (1, 1, 1, BLK, D),
            lambda s, h, b, layer, qpos: (layer[0], s, h, b, 0),
        )

    def _scale_spec():
        # all KVH heads per stripe (same tile rule as the paged kernel's
        # scale block); the kernel body picks its head's row
        return pl.BlockSpec(
            (1, 1, KVH, BLK),
            lambda s, h, b, layer, qpos: (layer[0], s, 0, b),
        )

    in_specs = [
        pl.BlockSpec(
            (1, 1, G, D),
            lambda s, h, b, layer, qpos: (s, h, 0, 0),
        ),
        _cache_spec(),
        _cache_spec(),
    ]
    operands = [q, k_cache, v_cache]
    if quantized:
        in_specs += [_scale_spec(), _scale_spec()]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, G, D), lambda s, h, b, layer, qpos: (s, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _dense_decode_kernel, block_k=BLK, scale=scale, quantized=quantized
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        interpret=interpret,
        name="kvmini_dense_decode",
    )(layer_arr, qpos, *operands)

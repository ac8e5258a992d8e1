"""Pallas TPU flash attention (prefill path).

Blocked causal attention with the online-softmax m/l/acc recurrence held in
VMEM scratch. Grid is (batch, q_heads, q_blocks, kv_blocks); TPU iterates the
last grid axis innermost, so scratch accumulators persist across the
kv-block sweep for one (b, h, q_block) output tile. GQA is handled in the
BlockSpec index map (query head h reads kv head h // n_rep) so kv blocks are
never materialized repeated.

The jnp reference (ops/attention.py) is the correctness oracle; tests compare
against it in interpret mode on CPU. The serving path reaches the kernel via
``prefill_attention`` below (models/llama.py ``forward(fresh_prefill=True)``,
called by runtime/engine.py's prefill step), which compiles the kernel on TPU
— the MXU sees [block_q, d] x [d, block_k] bf16 tiles — and takes the jnp
oracle on other backends. chip_smoke.py asserts every prefill executable
holds a tpu_custom_call.

``cached_prefill_attention`` below is the CONTINUATION-chunk variant: a
chunk's queries attend the slot's whole dense cache stripe (earlier
chunks' cached KV plus the chunk's own just-written rows) with positional
masking, reading int8-KV stripes with in-kernel dequant — the prefill
twin of ops/paged_attention.py ``dense_decode_attention``, sharing its
``k_scale``/``v_scale`` conventions. The eager ``_read_layer`` dequant
path (models/llama.py) is the consistency oracle and the path off-TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
# a fresh piece of LONG_PIECE positions or more tiles by LONG_PIECE_BLOCK
LONG_PIECE = 2048
LONG_PIECE_BLOCK = 512
_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, block_q: int, block_k: int, causal: bool,
    window: Optional[int] = None,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: kv block strictly after the q block contributes nothing.
    run = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)
    if window is not None:
        # Window: key j is visible to query i iff i - window < j <= i. A kv
        # block whose LAST key lies at or before the tile's FIRST query's
        # lower bound is wholly outside every query's window.
        run = run & (ki * block_k + block_k - 1 > qi * block_q - window)

    @pl.when(run)
    def _accumulate():
        q = q_ref[0, 0]                      # [BQ, D]
        k = k_ref[0, 0]                      # [BK, D]
        v = v_ref[0, 0]                      # [BK, D]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                            # [BQ, BK]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            visible = kpos <= qpos
            if window is not None:
                visible = visible & (kpos > qpos - window)
            logits = jnp.where(visible, logits, _NEG_INF)
        m_prev = m_ref[:]                    # [BQ, 1]... stored as [BQ, 128] lanes
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,   # [B, H, T, D]
    k: jnp.ndarray,   # [B, KVH, S, D]
    v: jnp.ndarray,   # [B, KVH, S, D]
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,  # Pallas interpreter: tests only
    window: Optional[int] = None,  # sliding window (causal only): key j is
                              # visible to query i iff i - window < j <= i.
                              # Key blocks wholly outside it are neither
                              # computed nor fetched; the edge blocks are masked
) -> jnp.ndarray:
    """Causal flash attention. T and S must be multiples of the block sizes
    (the runtime pads sequences to bucket boundaries anyway)."""
    B, H, T, D = q.shape
    _, KVH, S, _ = k.shape
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    if T % block_q or S % block_k:
        raise ValueError(f"T={T}, S={S} must be multiples of blocks ({block_q},{block_k})")
    n_rep = H // KVH
    scale = scale if scale is not None else D ** -0.5

    if window is not None and (not causal or window >= S):
        window = None  # wider than the sequence: the lower bound never binds

    grid = (B, H, T // block_q, S // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        window=window,
    )

    def kv_block(qi, ki):
        if window is None:
            return ki
        # the kv blocks a q tile computes on, [first, last]: a step outside
        # names the nearest of them again, and an unchanged block index
        # issues no DMA (the rule of ops/paged_attention.py fetched_block)
        first = jnp.maximum(qi * block_q - (window - 1), 0) // block_k
        last = (qi * block_q + block_q - 1) // block_k
        return jnp.clip(ki, first, last)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // n_rep, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h // n_rep, kv_block(qi, ki), 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="kvmini_flash_prefill",
    )(q, k, v)


def _cached_prefill_kernel(
    layer_ref,   # [1] int32 layer index (scalar prefetch; used in index maps)
    offset_ref,  # [B] int32 absolute position of each row's first query
    q_ref,       # [1, 1, BQ, D] this (b, h, qi) query tile
    k_ref,       # [1, 1, 1, BK, D] this grid step's cache stripe (int8 when
    v_ref,       #                  quantized)
    *rest,       # [k_s_ref, v_s_ref,] o_ref, m_ref, l_ref, acc_ref
    scale: float,
    block_q: int,
    block_k: int,
    n_rep: int,
    quantized: bool,
):
    """One key-block step of the CACHED-prefill online-softmax recurrence:
    a chunk of queries at absolute positions offset..offset+T-1 attends the
    slot's whole cache stripe (earlier chunks' KV plus this chunk's own
    just-written rows) with positional masking. Same m/l/acc scratch
    persistence across the innermost grid axis — and the same per-position
    scale-dequant convention — as ``_decode_block_body``
    (ops/paged_attention.py): (q . k_j s_j) = (q . k_j) * s_j and
    p @ (v s) = (p * s) @ v, so the int8 stripes stream straight from HBM
    and the materialized bf16 KV tensor of the eager read never exists."""
    if quantized:
        k_s_ref, v_s_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    off = offset_ref[b]
    # this query head's kv head: the [1, 1, KVH, BK] scale block spans every
    # kv head (``_scale_spec``), so the row is picked here
    head = pl.ds(pl.program_id(1) // n_rep, 1)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # key j of cache block ki sits at absolute position ki*BK + j; the
    # tile's LAST query sits at off + qi*BQ + BQ - 1 — a block starting
    # past it is all-masked, skip its FLOPs entirely
    run = ki * block_k <= off + qi * block_q + block_q - 1

    @pl.when(run)
    def _accumulate():
        q = q_ref[0, 0]                      # [BQ, D]
        k = k_ref[0, 0, 0]                   # [BK, D] (int8 when quantized)
        v = v_ref[0, 0, 0]
        logits = jax.lax.dot_general(
            q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                            # [BQ, BK]
        if quantized:
            logits = logits * k_s_ref[0, 0, head, :]       # [1, BK]
        qpos = off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
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

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def cached_prefill_blocks(t: int, s: int) -> Optional[tuple[int, int]]:
    """(block_q, block_k) the cached-prefill kernel tiles (T, S) with, or
    None when either axis has no supported tiling (the caller keeps the
    eager read path). Same alignment contract as ``prefill_attention``:
    the chunk axis T is a power-of-two bucket >= 16 or a multiple of the
    full 128 block; the cache axis S must tile by a power of two >= 8
    (Pallas pads partial blocks with whatever HBM holds — the positional
    mask would zero the scores, but an unvalidated ragged block shape is
    not worth handing Mosaic)."""
    pow2 = t & (t - 1) == 0
    if t < 16 or not (pow2 or t % DEFAULT_BLOCK_Q == 0):
        return None
    bq = min(DEFAULT_BLOCK_Q, t)
    for bk in (DEFAULT_BLOCK_K, 64, 32, 16, 8):
        if s % bk == 0:
            return bq, bk
    return None


def cached_prefill_attention(
    q: jnp.ndarray,        # [B, H, T, D] chunk queries
    k_cache: jnp.ndarray,  # [L, B, KVH, S, D] layer-stacked dense cache
                           # (or [B, KVH, S, D] for a single layer)
    v_cache: jnp.ndarray,
    offsets: jnp.ndarray,  # [B] int32 absolute position of each row's
                           # first query (the chunk's cache offset)
    layer: jnp.ndarray | int = 0,  # which layer of the stacked cache
    scale: Optional[float] = None,
    k_scale: Optional[jnp.ndarray] = None,  # [L, B, KVH, S] f32: int8-KV
    v_scale: Optional[jnp.ndarray] = None,  # per-position dequant scales
    interpret: bool = False,  # Pallas interpreter: tests only
) -> jnp.ndarray:
    """Flash prefill OVER THE CACHE: a continuation chunk's T queries
    attend the slot's whole dense cache stripe — earlier chunks' cached
    KV plus this chunk's own just-written rows — with positional masking,
    streaming int8 stripes and dequantizing in-kernel when
    ``k_scale``/``v_scale`` are given (the scaled-int8 KV layout,
    models/llama.py). The prefill-side twin of ``dense_decode_attention``:
    the eager read path (models/llama.py ``_read_layer``) materializes the
    dequantized bf16 [B, KVH, S, D] tensor before attention — 3x the live
    KV bytes in HBM traffic; here that tensor never exists. The layer
    index rides the index map so the caller never slices the stacked
    cache. GQA is handled in the BlockSpec index map (query head h reads
    kv head h // n_rep). The jnp gather/dequant path is the correctness
    oracle; tests compare in interpret mode on CPU."""
    if k_cache.ndim == 4:
        k_cache = k_cache[None]
        v_cache = v_cache[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    quantized = k_scale is not None
    B, H, T, D = q.shape
    L, _, KVH, S, _ = k_cache.shape
    blocks = cached_prefill_blocks(T, S)
    if blocks is None:
        raise ValueError(
            f"cached prefill kernel needs tileable (T={T}, S={S}) — use "
            "the eager read path (cached_prefill_blocks)"
        )
    bq, bk = blocks
    n_rep = H // KVH
    scale = scale if scale is not None else D ** -0.5

    offsets = offsets.astype(jnp.int32)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape((1,))

    def _cache_spec():
        return pl.BlockSpec(
            (1, 1, 1, bk, D),
            lambda b, h, qi, ki, layer, off: (layer[0], b, h // n_rep, ki, 0),
        )

    def _scale_spec():
        # all KVH heads per stripe: a one-head (1, bk) block over [KVH, S]
        # is not a legal TPU tile (same rule as ops/paged_attention.py)
        return pl.BlockSpec(
            (1, 1, KVH, bk),
            lambda b, h, qi, ki, layer, off: (layer[0], b, 0, ki),
        )

    in_specs = [
        pl.BlockSpec(
            (1, 1, bq, D), lambda b, h, qi, ki, layer, off: (b, h, qi, 0)
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
        grid=(B, H, T // bq, S // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, 1, bq, D), lambda b, h, qi, ki, layer, off: (b, h, qi, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _cached_prefill_kernel, scale=scale, block_q=bq, block_k=bk,
        n_rep=n_rep, quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
        name="kvmini_cached_prefill",
    )(layer_arr, offsets, *operands)


def pallas_interpret(kernels: str) -> Optional[bool]:
    """Resolve a ``kernels`` argument (models/llama.py ``forward``) into
    the ``interpret`` flag its Pallas calls get, or None for the jnp paths:

    - ``"auto"``: compiled kernels iff the default backend is a TPU;
    - ``"off"``: jnp paths only — a GSPMD-partitioned jit cannot hold a
      Mosaic call ("Mosaic kernels cannot be automatically partitioned");
    - ``"interpret"``: kernels through the Pallas interpreter. Tests only:
      nothing on the serving path passes it, so a serving process cannot
      land in interpret mode."""
    if kernels == "auto":
        return False if jax.default_backend() == "tpu" else None
    if kernels == "off":
        return None
    if kernels == "interpret":
        return True
    raise ValueError(f"unknown kernels mode {kernels!r}; known: auto, off, interpret")


def prefill_attention(
    q: jnp.ndarray,   # [B, H, T, D]
    k: jnp.ndarray,   # [B, KVH, T, D]
    v: jnp.ndarray,   # [B, KVH, T, D]
    kernels: str = "auto",
    window: Optional[int] = None,  # a window layer's: i - window < j <= i
) -> jnp.ndarray:
    """Serving-prefill attention over a freshly projected block.

    The engine's prefill writes a new request's whole prompt at cache offset
    0, so block-causal attention over (q, k, v) themselves is exact — no
    cache readback, and attention cost is T x T instead of T x max_seq.

    Dispatch (``pallas_interpret``): the compiled Pallas kernel on TPU
    (prompts pad to power-of-two buckets, so shapes are always
    block-aligned), the jnp oracle elsewhere.
    """
    T = q.shape[2]
    # pieces longer than any 128-block program was tuned on run 512-wide
    # tiles: a grid step costs ~0.35 us whatever it multiplies, and a
    # [128, 128] x [128, D] tile is 8 MFLOP, 40 ns of a v5e
    block = LONG_PIECE_BLOCK if T >= LONG_PIECE else DEFAULT_BLOCK_Q
    bq = min(block, T)
    bk = min(block, T)
    # tile-aligned block shapes only: T a power of two >= 16 (the engine's
    # bucket sizes) or a multiple of the full 128 block — anything else
    # (e.g. a clamped 99-wide bucket) takes the jnp path rather than handing
    # Mosaic an unvalidated block shape
    pow2 = T & (T - 1) == 0
    aligned = (
        (T >= 16)
        and (pow2 or T % DEFAULT_BLOCK_Q == 0)
        and q.shape[1] % k.shape[1] == 0
    )
    interpret = pallas_interpret(kernels)
    if interpret is not None and aligned:
        return flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                               interpret=interpret, window=window)
    from kserve_vllm_mini_tpu.ops.attention import attention, causal_mask

    mask = causal_mask(T, T)
    if window is not None:
        pos = jnp.arange(T)
        mask = mask & (pos[None, :] > pos[:, None] - window)
    return attention(q, k, v, mask[None, None])

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
still happens (the paged grid is static and its index map follows the
table), reading whatever block their table entry names. The engine parks
freed/unwritten table rows on its scratch block (runtime/engine.py
``_paged_release``), which is what concentrates the dead traffic; the
``jnp.clip`` below is only bounds safety for ids outside [0, P).

The kernel takes the LAYER-STACKED pool ([L, P, KVH, BLK, D]) plus the
layer index as a scalar-prefetch value folded into the index map: slicing
one layer out before the call would hand XLA a dynamic-slice feeding a
custom call, which materializes the whole layer pool in HBM per step —
exactly the copy this kernel exists to avoid.

``dense_decode_attention`` is the DENSE-cache twin, for bf16 and int8 KV
alike: the same online-softmax block update and the same ``k_scale``/
``v_scale`` dequant-in-kernel convention, over the per-slot
[L, B, KVH, S, D] cache stripes (no table). Its index maps read the
prefetched query positions: a slot fetches only the key blocks between
the first and the last key it can reach (``live_block_range`` — its
position, and the model's window if it has one), and the slot's other
grid steps name the first live block again, which issues no DMA
(``fetched_block``). A decode step therefore reads the live KV, not the
reserved stripes, and neither the eager path's full-stripe attention nor
its materialized bf16 dequantized tensor exists (models/llama.py
``_read_layer`` remains the fallback and the oracle).

The jnp paths are the correctness oracle; tests compare in interpret mode
on CPU (tests/test_paged_kernel.py, tests/test_qmatmul.py,
tests/test_tpu_lowering.py). On a TPU the serving path takes the paged
kernel for plain-causal configs under ``kv_layout="paged"`` and the dense
kernel for every dense-layout config without a softcap or a per-layer
(alternating) window whose cache ``dense_decode_block`` has a block for
(heads a multiple of 128 wide; the block sized to VMEM), and keeps the
exact eager path elsewhere (models/llama.py ``run_cached_layers``,
``dense_decode_kernel_block``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def live_block_range(qpos, window: Optional[int], block_k: int):
    """(first, last) key-block indices a decode query at ``qpos`` can reach:
    the blocks that hold a key j with ``max(qpos - window + 1, 0) <= j <=
    qpos`` (``window=None``: from 0). THE block-selection rule of the dense
    decode kernel — its index maps, its ``run`` guard and the engine's
    ``decode_kv_blocks_fetched`` counter all call this one function, on
    traced scalars, numpy arrays and plain ints alike."""
    xp = jnp if isinstance(qpos, jax.Array) else np
    last = qpos // block_k
    if window is None:
        return xp.zeros_like(last), last
    return xp.maximum(qpos - (window - 1), 0) // block_k, last


def fetched_block(step, n_steps: int, first, last):
    """(block, runs): the key block the dense decode kernel's ``step``-th
    of ``n_steps`` grid steps names for a slot whose live range is
    ``[first, last]``, and whether the step computes on it. Block ``first``
    through the slot's dead steps (``runs`` False), then the live range in
    order, so that the LAST step fetches ``last``.

    An unchanged block index issues no new DMA, so a slot's dead blocks
    cost neither bytes nor FLOPs. The dead steps come FIRST because the
    Pallas pipeline prefetches exactly one grid step ahead: this way a
    slot's first block is requested during the previous slot's last live
    step and lands behind that step's matmuls, where dead steps at the end
    (nothing to fetch, nothing to compute) would leave every slot's first
    DMA exposed (a v5e, mistral-7b's 32 layers x 16 slots at 80-850
    positions: 3.7 ms that way, 2.7 ms this way; PERF.md section 6, PR 27)."""
    xp = jnp if isinstance(first, jax.Array) else np
    live_step = step - (n_steps - (last - first + 1))
    return first + xp.maximum(live_step, 0), live_step >= 0


def _decode_block_update(
    qpos,        # scalar int32: this slot's query position
    kpos0,       # scalar int32: absolute position of the block's first key
    q,           # [G, D] this slot/head's query tile
    k,           # [BLK, D] key block (int8 when quantized)
    v,           # [BLK, D]
    k_s,         # [1, BLK] f32 per-position dequant scales, or None
    v_s,
    m_ref,       # [H', G, 1] f32 running max   } online-softmax scratch;
    l_ref,       # [H', G, 1] f32 running sum   } row ``h`` is this head's
    acc_ref,     # [H', G, D] f32 running out   }
    h: int,
    scale: float,
    window: Optional[int],
):
    """One key block of one kv head's online-softmax decode recurrence —
    the step BOTH decode kernels share (paged: the block arrived via the
    table index map, one head a grid step; dense: via ``fetched_block``,
    every head of the block in one grid step). A key at position j is
    visible iff ``qpos - window < j <= qpos``."""
    logits = jax.lax.dot_general(
        q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                            # [G, BLK]
    if k_s is not None:
        # per-position dequant folds into the [G, BLK] intermediates:
        # (q . k_j s_j) = (q . k_j) * s_j, and p @ (v s) = (p * s) @ v
        logits = logits * k_s
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    visible = kpos <= qpos
    if window is not None:
        visible = visible & (kpos > qpos - window)
    logits = jnp.where(visible, logits, _NEG_INF)
    m_prev = m_ref[h]
    m_cur = jnp.max(logits, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
    if v_s is not None:
        pv = (p * v_s).astype(jnp.float32)
        vv = v.astype(jnp.float32)
    else:
        pv = p.astype(v.dtype)
        vv = v
    acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
        pv, vv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[h] = m_new


def _init_softmax_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _paged_decode_kernel(
    layer_ref,   # [1] int32 layer index (scalar prefetch; used in index maps)
    table_ref,   # [S, MAXB] int32 (scalar prefetch)
    qpos_ref,    # [S] int32 query positions (scalar prefetch)
    q_ref,       # [1, 1, G, D] this slot/head's query tile
    k_ref,       # [1, 1, 1, BLK, D] the table-selected pool block
    v_ref,
    *rest,       # [k_s_ref, v_s_ref,] o_ref, m_ref, l_ref, acc_ref —
                 # int8-KV mode carries [1, 1, KVH, BLK] per-position scale
                 # blocks for ALL kv heads (see ``_scale_spec``)
    block_k: int,
    scale: float,
    quantized: bool,
):
    if quantized:
        k_s_ref, v_s_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    qpos = qpos_ref[pl.program_id(0)]
    b = pl.program_id(2)
    # this grid step's kv head: the scale block spans every head (a
    # one-head block is not a legal TPU tile), so the row is picked here
    head = pl.ds(pl.program_id(1), 1)

    @pl.when(b == 0)
    def _init():
        _init_softmax_state(m_ref, l_ref, acc_ref)

    # keys j of block b sit at positions b*BLK + j; the decode query at
    # position qpos attends j <= qpos, so a block starting past qpos is
    # all-masked — skip its FLOPs entirely
    @pl.when(b * block_k <= qpos)
    def _accumulate():
        _decode_block_update(
            qpos, b * block_k, q_ref[0, 0], k_ref[0, 0, 0], v_ref[0, 0, 0],
            k_s_ref[0, 0, head, :] if quantized else None,
            v_s_ref[0, 0, head, :] if quantized else None,
            m_ref, l_ref, acc_ref, 0, scale, None,
        )

    @pl.when(b == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[0] / jnp.maximum(l_ref[0], 1e-30)).astype(o_ref.dtype)


def _dense_decode_kernel(
    layer_ref,   # [1] int32 layer index (scalar prefetch; used in index maps)
    qpos_ref,    # [B] int32 query positions (scalar prefetch)
    q_ref,       # [1, KVH, G, D] this slot's query tiles, every kv head
    k_ref,       # [1, 1, KVH, BLK, D] the slot's ``fetched_block`` stripe
    v_ref,
    *rest,       # [k_s_ref, v_s_ref,] o_ref, m_ref, l_ref, acc_ref; the
                 # int8-KV scale blocks are [1, 1, KVH, BLK]
    block_k: int,
    scale: float,
    window: Optional[int],
    quantized: bool,
):
    if quantized:
        k_s_ref, v_s_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    qpos = qpos_ref[pl.program_id(0)]
    b, nb = pl.program_id(1), pl.num_programs(1)
    blk, runs = fetched_block(b, nb, *live_block_range(qpos, window, block_k))

    @pl.when(b == 0)
    def _init():
        _init_softmax_state(m_ref, l_ref, acc_ref)

    # the slot's dead steps come first (fetched_block): they re-name its
    # first live block, nothing was fetched for them and nothing runs
    @pl.when(runs)
    def _accumulate():
        for h in range(q_ref.shape[1]):
            _decode_block_update(
                qpos, blk * block_k, q_ref[0, h], k_ref[0, 0, h], v_ref[0, 0, h],
                k_s_ref[0, 0, h:h + 1, :] if quantized else None,
                v_s_ref[0, 0, h:h + 1, :] if quantized else None,
                m_ref, l_ref, acc_ref, h, scale, window,
            )

    @pl.when(b == nb - 1)
    def _finalize():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


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
            pltpu.VMEM((1, G, 1), jnp.float32),
            pltpu.VMEM((1, G, 1), jnp.float32),
            pltpu.VMEM((1, G, D), jnp.float32),
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


# Mosaic's default scoped-VMEM limit on a v5e is 16 MiB a kernel. The dense
# decode kernel's pipelined operands (K and V blocks, int8-KV scale blocks,
# two buffers each) may take half of it; the query/output tiles, the
# softmax scratch and the body's [G, BLK] / [BLK, D] temporaries live in the
# other half.
_DENSE_BLOCK_VMEM_BUDGET = 8 << 20


def dense_block_vmem_bytes(
    block_k: int, kv_heads: int, head_dim: int, kv_dtype
) -> int:
    """VMEM the dense decode kernel's pipelined cache operands occupy at
    key-block size ``block_k``: the K and V blocks of [KVH, BLK, D], plus
    the two [KVH, BLK] f32 scale blocks of an int8 cache, double-buffered."""
    itemsize = jnp.dtype(kv_dtype).itemsize
    per_key = head_dim * itemsize + (4 if itemsize == 1 else 0)
    return 2 * 2 * kv_heads * block_k * per_key


def dense_decode_block(
    seq_len: int, kv_heads: int, head_dim: int, kv_dtype
) -> Optional[int]:
    """Key-block size the dense decode kernel sweeps a [.., KVH, S, D]
    cache of ``kv_dtype`` with, or None when it has none (the caller then
    keeps the eager read path): the largest power of two from 512 down to
    8 that divides ``seq_len`` and whose all-heads blocks fit the VMEM
    budget (``dense_block_vmem_bytes``) — 512 for 8 bf16 heads of 128,
    256 for 32 of them.

    The sweep grid must tile the cache's S axis exactly — Pallas pads
    partial blocks with whatever HBM holds, and while the positional mask
    would zero those scores, a dense cache length that is not even
    8-aligned is a test-only shape not worth the kernel. A head size that
    is not a multiple of 128 lanes has none either: XLA keeps such a cache
    in a layout the Mosaic call does not take, and copies the WHOLE cache
    to feed it, every step. The cap of 512 is a reading, not a guess:
    smaller blocks fetch fewer dead positions in a slot's last live block,
    but on a v5e a 256-position step of 8 heads costs 2.3 us for 1 MB where
    a 512-position one costs 2.9 us for 2 MB, and 512 won every length mix
    tried (PERF.md section 6, PR 27)."""
    if head_dim % 128:
        return None
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if (
            seq_len % cand == 0
            and dense_block_vmem_bytes(cand, kv_heads, head_dim, kv_dtype)
            <= _DENSE_BLOCK_VMEM_BUDGET
        ):
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
    window: Optional[int] = None,  # sliding window: key j is visible iff
                           # qpos - window < j <= qpos (None: plain causal)
    interpret: bool = False,  # Pallas interpreter: tests only
) -> jnp.ndarray:
    """Decode attention straight over the DENSE per-slot cache, reading
    from HBM only the key/value blocks a slot's query can reach: the twin
    of ``paged_decode_attention`` for ``kv_layout="dense"``, for bf16 and
    int8 KV alike.

    The eager read path (models/llama.py ``_read_layer``) attends over the
    whole [B, KVH, S, D] stripe every step whatever is live (and for int8
    KV dequantizes it into a materialized bf16 tensor first: 3x the bytes).
    Here the grid is (slots, S/BLK) and each step's BlockSpec index map
    names ``fetched_block`` of the slot's ``live_block_range`` — the first
    of the blocks between ``max(qpos - window + 1, 0)`` and ``qpos`` for
    as many steps as the slot has dead blocks, then those blocks in order.
    The Pallas pipeline issues a DMA only when a step's block index
    differs from the previous step's, so a slot at position 300 of 2048
    costs one 512-position block of HBM traffic, not four; the body's
    guard skips the repeated steps.

    One block carries EVERY kv head ([KVH, BLK, D], BLK from
    ``dense_decode_block``: the largest that fits VMEM), so a layer is
    B * S/BLK grid steps rather than B * KVH * S/BLK: a dead step still
    costs ~0.35 us, and at 8 heads x 512 positions a live one is bound by
    its DMA (2.9 us for 2 MB of bf16 K+V), where 256-position blocks took
    2.3 us for half the bytes. int8 blocks are dequantized in-register
    inside the online-softmax sweep (the same shared block update as the
    paged kernel, same ``k_scale``/``v_scale`` layout). The layer index rides
    the index map so the caller never slices the stacked cache (a
    dynamic-slice operand feeding a custom call would materialize the
    whole layer in HBM)."""
    if k_cache.ndim == 4:
        k_cache = k_cache[None]
        v_cache = v_cache[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    quantized = k_scale is not None
    B, KVH, G, D = q.shape
    L, _, _, S, _ = k_cache.shape
    BLK = dense_decode_block(S, KVH, D, k_cache.dtype)
    if BLK is None:
        raise ValueError(
            f"dense decode kernel has no key block for S={S}, KVH={KVH}, "
            f"D={D}, {k_cache.dtype} (dense_decode_block) — use the eager "
            f"read path"
        )
    scale = scale if scale is not None else D ** -0.5
    if window is not None and window >= S:
        window = None  # wider than the cache: the lower bound never binds

    # bounds safety only: the engine never feeds a position past S - 1
    qpos = jnp.clip(qpos.astype(jnp.int32), 0, S - 1)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape((1,))

    def _block(s, b, qpos):
        return fetched_block(b, S // BLK, *live_block_range(qpos[s], window, BLK))[0]

    def _cache_spec():
        return pl.BlockSpec(
            (1, 1, KVH, BLK, D),
            lambda s, b, layer, qpos: (layer[0], s, 0, _block(s, b, qpos), 0),
        )

    def _scale_spec():
        return pl.BlockSpec(
            (1, 1, KVH, BLK),
            lambda s, b, layer, qpos: (layer[0], s, 0, _block(s, b, qpos)),
        )

    def _q_spec():
        return pl.BlockSpec(
            (1, KVH, G, D), lambda s, b, layer, qpos: (s, 0, 0, 0)
        )

    in_specs = [_q_spec(), _cache_spec(), _cache_spec()]
    operands = [q, k_cache, v_cache]
    if quantized:
        in_specs += [_scale_spec(), _scale_spec()]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, S // BLK),
        in_specs=in_specs,
        out_specs=_q_spec(),
        scratch_shapes=[
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _dense_decode_kernel, block_k=BLK, scale=scale, window=window,
        quantized=quantized,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        interpret=interpret,
        name="kvmini_dense_decode",
    )(layer_arr, qpos, *operands)

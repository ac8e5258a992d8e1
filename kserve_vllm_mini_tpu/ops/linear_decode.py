"""Pallas TPU kernel: one decode step of the gated delta rule, in place.

``models/linear_attention.py recurrent_step`` is two reductions over the
state (``S^T k``, ``S^T q``) and one rank-one update that needs the first
reduction's result. XLA cannot keep a tile of ``S`` on the chip between a
reduction over it and the update, so its program reads the layer's state,
reads it again and writes it: three passes where the work needs two. Here
one grid step holds a slot's block of heads in VMEM, takes both reductions
from the tile and writes the updated tile back to where it came from.

The kernel takes the LAYER-STACKED state ``[L, B, H, dk, dv]`` with the
layer index as a scalar-prefetch value folded into the index map, and
returns the stack through ``input_output_aliases``: slicing the layer out
before the call and putting it back after would be a copy out and a copy
back around an opaque call, four passes (the same reason
``ops/paged_attention.py`` takes its stacked pools).

Layout: grid (slots, H / Hb). The state tile is ``[Hb, dk, dv]`` float32
with ``dk`` on sublanes and ``dv`` on lanes, so the ``dv``-long vectors
(``v``, ``u``, the two reductions, the output) are rows that broadcast
down the sublanes for free, and ``k`` and ``q`` must be COLUMNS: they come
in transposed a head block at a time, ``[B, H / Hb, dk, 2 Hb]`` (the
block's ``j``-th head has its ``k`` in lane ``j`` and its ``q`` in lane
``Hb + j``), and a column is broadcast along the lanes. ``v`` and the
output are blocked the same way, ``[B, H / Hb, Hb, dv]``, so the body is
``Hb`` copies of one head's update at static lanes and rows, whichever
block the grid step holds. The per-head scalars (``exp(g)``, ``beta``,
``k.q``) ride in SMEM. Everything is float32 elementwise products and sums
in ``recurrent_step``'s order: on a v5e the result is the jnp path's bit
for bit.

Every slot's state is read and written whatever is live (a row with
``valid == 0`` gets its own tile back through a select), so the time does
not follow the live count. On a v5e the call is bound by its DMAs: a layer
of ``[16, 30, 96, 192]`` takes 0.144 ms in the decode program, the same
with or without the select and for live or parked rows, and within 1.5% for
10 to 30 heads a grid step (3 heads: +12%, the steps' own overhead; PERF.md
section 6, PR 33). That is 657 GB/s over the bytes the chip really moves:
the 192-wide rows are padded to 256 lanes in HBM, and Mosaic takes no DMA of
part of a lane tile.

``recurrent_step`` stays the fallback and the oracle
(tests/test_linear_decode_kernel.py compares in interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Of Mosaic's default 16 MiB of scoped VMEM a kernel, the pipelined state
# tiles (in and out, two buffers each) may take half, as the dense decode
# kernel's K/V blocks do (ops/paged_attention.py); the k/q columns, the v
# and output rows and the body's temporaries live in the other half.
_STATE_TILE_VMEM_BUDGET = 8 << 20


def state_tile_vmem_bytes(heads_block: int, dk: int, dv: int) -> int:
    """VMEM the pipelined state tiles occupy at ``heads_block`` heads a grid
    step: ``[Hb, dk, dv]`` float32 padded to whole (8, 128) tiles, in and
    out, double-buffered."""
    return 2 * 2 * heads_block * (-(-dk // 8) * 8) * (-(-dv // 128) * 128) * 4


def linear_decode_block(heads: int, dk: int, dv: int, dtype) -> Optional[int]:
    """Heads of one slot a grid step of the kernel holds, or None where it
    has no block (the caller keeps ``recurrent_step``): the largest divisor
    of ``heads`` whose tiles fit the VMEM budget (``state_tile_vmem_bytes``)
    — 15 of 30 heads of 96 x 192. The state must be float32 (the kernel
    computes in the state's precision and the jnp path's is float32) and
    ``dk`` a whole number of 8-row sublane tiles."""
    if jnp.dtype(dtype) != jnp.float32 or dk % 8:
        return None
    for cand in range(heads, 0, -1):
        if heads % cand == 0 and state_tile_vmem_bytes(cand, dk, dv) <= _STATE_TILE_VMEM_BUDGET:
            return cand
    return None


def _linear_decode_kernel(
    layer_ref,   # [1] int32 layer index (scalar prefetch; used in index maps)
    valid_ref,   # [B] int32 (scalar prefetch): 0 = leave this slot's state alone
    scal_ref,    # [B, 3H] f32 in SMEM: exp(g) | beta | k.q, a head each
    s_ref,       # [1, 1, Hb, dk, dv] this slot's block of heads
    cols_ref,    # [1, 1, dk, 2Hb] the block's k | q as columns
    v_ref,       # [1, 1, Hb, dv] the block's v rows
    s_out_ref,   # the same tile of the same (aliased) stack
    o_ref,       # [1, 1, Hb, dv] the block's outputs
    *,
    heads: int,
):
    b = pl.program_id(0)
    live = valid_ref[b] > 0
    heads_block = s_ref.shape[2]
    first = pl.program_id(1) * heads_block
    # unrolled: a head's lane in ``cols_ref`` and row in ``v_ref`` / ``o_ref``
    # must be static (Mosaic takes no dynamic lane index)
    for j in range(heads_block):
        decay, beta, kq = (scal_ref[b, i * heads + first + j] for i in range(3))
        old = s_ref[0, 0, j]                                         # [dk, dv]
        k_col = cols_ref[0, 0, :, j:j + 1]                           # [dk, 1]
        q_col = cols_ref[0, 0, :, heads_block + j:heads_block + j + 1]
        s = old * decay
        u = beta * (v_ref[0, 0, j:j + 1, :] - jnp.sum(s * k_col, axis=0, keepdims=True))
        o_ref[0, 0, j:j + 1, :] = jnp.sum(s * q_col, axis=0, keepdims=True) + u * kq
        s_out_ref[0, 0, j] = jnp.where(live, s + k_col * u, old)


def linear_decode_update(
    state: jnp.ndarray,    # [L, B, H, dk, dv] float32, the layer-stacked state
    layer: jnp.ndarray | int,  # which layer of the stack this step updates
    q: jnp.ndarray,        # [B, H, dk] float32
    k: jnp.ndarray,        # [B, H, dk]
    v: jnp.ndarray,        # [B, H, dv]
    g: jnp.ndarray,        # [B, H] log decay
    beta: jnp.ndarray,     # [B, H]
    valid: jnp.ndarray,    # [B]: rows with 0 keep their state bit for bit
    heads_block: Optional[int] = None,  # tests only: a divisor of H other
                           # than ``linear_decode_block``'s
    interpret: bool = False,  # Pallas interpreter: tests only
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``recurrent_step`` on layer ``layer`` of the stack -> (the stack with
    that layer's state updated in place, o [B, H, dv]). The other layers'
    state is not touched: the output aliases the input and the grid visits
    this layer's tiles alone."""
    L, B, H, dk, dv = state.shape
    hb = heads_block or linear_decode_block(H, dk, dv, state.dtype)
    if hb is None or H % hb:
        raise ValueError(
            f"linear decode kernel has no head block for H={H}, dk={dk}, "
            f"dv={dv}, {state.dtype} (linear_decode_block) — use recurrent_step"
        )
    f32, n = jnp.float32, H // hb
    scal = jnp.concatenate(
        [jnp.exp(g.astype(f32)), beta.astype(f32), jnp.sum(k * q, axis=-1).astype(f32)],
        axis=-1)                                                     # [B, 3H]

    def columns(x):                                                  # [B, n, dk, Hb]
        return jnp.swapaxes(x.astype(f32).reshape(B, n, hb, dk), 2, 3)

    cols = jnp.concatenate([columns(k), columns(q)], axis=-1)        # [B, n, dk, 2Hb]
    layer_arr = jnp.asarray(layer, jnp.int32).reshape((1,))

    def _state_spec():
        return pl.BlockSpec(
            (1, 1, hb, dk, dv), lambda b, i, layer, valid: (layer[0], b, i, 0, 0))

    def _block_spec(rows: int, width: int):
        return pl.BlockSpec((1, 1, rows, width), lambda b, i, layer, valid: (b, i, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _state_spec(),
            _block_spec(dk, 2 * hb),
            _block_spec(hb, dv),
        ],
        # the state first: a trace names the call by its result and first
        # operands, and bench/hybrid_roofline.py finds it by the state's shape
        out_specs=[_state_spec(), _block_spec(hb, dv)],
    )
    new_state, o = pl.pallas_call(
        functools.partial(_linear_decode_kernel, heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, n, hb, dv), f32)],
        # operand 3 (after layer, valid, scal) is the stack: updated in place
        input_output_aliases={3: 0},
        interpret=interpret,
        name="kvmini_linear_decode",
    )(layer_arr, valid.astype(jnp.int32), scal, state, cols,
      v.astype(f32).reshape(B, n, hb, dv))
    return new_state, o.reshape(B, H, dv)

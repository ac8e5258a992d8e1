"""Pallas TPU kernel: one decode step of the Mamba-2 recurrence, in place.

``models/mamba.py recurrent_step`` is an update of the state (``S * a + (dt
x) (x) B``) and a reduction over the NEW state (``S . C``). XLA fuses the
update into one pass that reads and writes the layer's state and then reads
the new state again for the reduction: three passes where the work needs
two. Here one grid step holds a slot's block of heads in VMEM, updates the
tile, takes the reduction from the same tile and writes it back to where it
came from.

A sibling of ``kvmini_linear_decode`` (ops/linear_decode.py), and the same
shape of call: the kernel takes the LAYER-STACKED state ``[L, B, H, P, N]``
with the layer index as a scalar-prefetch value folded into the index map
and returns the stack through ``input_output_aliases`` (a layer sliced out
for an opaque call is copied out and copied back: four passes).

Layout: grid (slots, H / Hb). The state tile is ``[Hb, P, N]`` float32 with
``P`` on sublanes and ``N`` on lanes, so ``B`` and ``C`` are rows that
broadcast down the sublanes (all ``n_groups`` of a slot come in as one small
block ``[2 G, N]``; a head reads its group's two rows at a dynamic sublane),
and ``dt x`` and the output, which run along ``P``, are COLUMNS: they come
in and go out transposed a slot at a time, ``[B, P, H]`` (head ``h`` in lane
``h``). The body is a loop over the block's heads, a few at a time, not
``Hb`` copies: a head's tile is a dynamic index on the tile's leading axis,
its column is picked out of the lanes by a mask and a lane reduction (Mosaic
takes no dynamic lane index), its output column is put into its lane by a
select. ``exp(dt A)`` rides in SMEM.

The update is float32 products and sums on the VPU, ``recurrent_step``'s own
(on a v5e the new state is the jnp path's bit for bit). The reduction over
``N`` runs over LANES, and a lane reduction a state vreg is more than the
XLU gives inside the DMA time where ``N`` is one lane tile (0.75 ms a layer
of ``[32, 128, 64, 128]`` against the DMAs' 0.42). So the sum is the MXU's:
the float32 products ``s * C`` are split EXACTLY into three bfloat16 parts
(8 + 8 + 8 bits of mantissa: ``hi + mid + lo == s * C``), laid side by side
along the contraction and multiplied by ones, so the matmul adds the same
float32 products in float32, in its own order (``y`` differs from the jnp
path's by ~1e-7 relative, as two orders of one float32 sum do). A matmul
streams the products of ``_MATMUL_ROWS / P`` heads at once, because a short
one is all weight load (PERF.md section 6, PR 37).

Every slot's state is read and written whatever is live (a row with
``valid == 0`` gets its own tile back through a select), so the time does
not follow the live count.

``recurrent_step`` stays the fallback and the oracle
(tests/test_ssm_decode_kernel.py compares in interpret mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kserve_vllm_mini_tpu.ops.linear_decode import linear_decode_block

# Rows of ``s * C`` one matmul of the reduction streams. The ones it multiplies
# by are loaded into the MXU for every matmul: over 64 rows a layer of
# ``[32, 128, 64, 128]`` takes 1.27 ms, over 128 0.70, over 256 0.44, over 512
# 0.42, which is what the DMAs alone take (my chip runs, PR 37).
_MATMUL_ROWS = 512


def ssm_decode_block(heads: int, p: int, n: int, dtype) -> Optional[int]:
    """Heads of one slot a grid step of the kernel holds, or None where it
    has no block (the caller keeps ``recurrent_step``): the delta rule's
    block rule over a ``[P, N]`` tile (``linear_decode_block``: a float32
    state, ``P`` whole sublane tiles, the largest divisor of ``heads`` whose
    pipelined tiles fit the VMEM budget: 64 of 128 heads of 64 x 128, 16 of
    32 of 128 x 256), and ``N`` a whole number of 128-lane tiles, because the
    reduction runs over the lanes and a padded lane would be summed."""
    if n % 128:
        return None
    return linear_decode_block(heads, p, n, dtype)


def _ssm_decode_kernel(
    layer_ref,   # [1] int32 layer index (scalar prefetch; used in index maps)
    valid_ref,   # [B] int32 (scalar prefetch): 0 = leave this slot's state alone
    decay_ref,   # [B, H] f32 in SMEM: exp(dt A), a head each
    s_ref,       # [1, 1, Hb, P, N] this slot's block of heads
    cols_ref,    # [1, P, H] the slot's dt x, head h the column in lane h
    bc_ref,      # [1, 2G, N] the slot's B rows, then its C rows, a group each
    s_out_ref,   # the same tile of the same (aliased) stack
    y_ref,       # [1, P, H] the slot's outputs, head h the column in lane h
):
    slot = pl.program_id(0)
    live = valid_ref[slot] > 0
    heads_block, _, n = s_ref.shape[2:]
    p, heads = cols_ref.shape[1:]
    groups = bc_ref.shape[1] // 2
    per = heads // groups
    first = pl.program_id(1) * heads_block
    # heads whose products one matmul sums: a divisor of the block
    together = max(d for d in range(1, heads_block + 1)
                   if heads_block % d == 0 and (d == 1 or d * p <= _MATMUL_ROWS))
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, heads), 1)
    cols = cols_ref[0]
    f32, bf16 = jnp.float32, jnp.bfloat16
    ones = jnp.ones((3 * n, 128), bf16)

    def some_heads(k, y_cols):
        at = first + k * together
        prods = []
        for u in range(together):
            h, j = at + u, k * together + u
            g = h // per
            old = s_ref[0, 0, j]                                     # [P, N]
            b_row = bc_ref[0, pl.ds(g, 1), :]                        # [1, N]
            c_row = bc_ref[0, pl.ds(groups + g, 1), :]
            dtx = jnp.sum(jnp.where(lane == h, cols, 0.0), axis=1, keepdims=True)  # [P, 1]
            s = old * decay_ref[slot, h] + dtx * b_row
            s_out_ref[0, 0, j] = jnp.where(live, s, old)
            prods.append(s * c_row)
        sc = jnp.concatenate(prods, axis=0)                          # [together P, N]
        hi = sc.astype(bf16)
        rest = sc - hi.astype(f32)
        mid = rest.astype(bf16)
        lo = (rest - mid.astype(f32)).astype(bf16)
        sums = jnp.dot(jnp.concatenate([hi, mid, lo], axis=1), ones,
                       preferred_element_type=f32)                   # [together P, 128], lanes alike
        for u in range(together):
            y_cols = jnp.where(lane == at + u, sums[u * p:(u + 1) * p, :1], y_cols)
        return y_cols

    y_cols = jax.lax.fori_loop(
        0, heads_block // together, some_heads, jnp.zeros((p, heads), f32))
    # the slot's output block stays in VMEM over its H / Hb grid steps: each
    # writes its own heads' lanes
    mine = (lane >= first) & (lane < first + heads_block)
    y_ref[0] = jnp.where(mine, y_cols, y_ref[0])


# jitted, with the layer index an operand: a walk's ten or twelve call sites are
# then one traced and lowered body called with another scalar (each pallas_call
# traced and lowered on its own is 0.14 s of every set-up, warm cache or not),
# and XLA inlines the calls, so the stack is still updated in place
@functools.partial(jax.jit, static_argnames=("heads_block", "interpret"))
def ssm_decode_update(
    state: jnp.ndarray,    # [L, B, H, P, N] float32, the layer-stacked state
    layer: jnp.ndarray | int,  # which layer of the stack this step updates
    x: jnp.ndarray,        # [B, H, P] float32
    b: jnp.ndarray,        # [B, G, N], a row a group (not spread to heads)
    c: jnp.ndarray,        # [B, G, N]
    dt: jnp.ndarray,       # [B, H]
    a: jnp.ndarray,        # [H], negative
    valid: jnp.ndarray,    # [B]: rows with 0 keep their state bit for bit
    heads_block: Optional[int] = None,  # tests only: a divisor of H other
                           # than ``ssm_decode_block``'s
    interpret: bool = False,  # Pallas interpreter: tests only
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``recurrent_step`` on layer ``layer`` of the stack -> (the stack with
    that layer's state updated in place, y [B, H, P]). The other layers'
    state is not touched: the output aliases the input and the grid visits
    this layer's tiles alone."""
    L, B, H, P, N = state.shape
    G = b.shape[1]
    hb = heads_block or ssm_decode_block(H, P, N, state.dtype)
    if hb is None or H % hb or H % G:
        raise ValueError(
            f"ssm decode kernel has no head block for H={H}, P={P}, N={N}, "
            f"G={G}, {state.dtype} (ssm_decode_block) — use recurrent_step"
        )
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))                              # [B, H]
    cols = jnp.swapaxes(dt[..., None] * x.astype(f32), 1, 2)         # [B, P, H]
    rows = jnp.concatenate([b.astype(f32), c.astype(f32)], axis=1)   # [B, 2G, N]
    layer_arr = jnp.asarray(layer, jnp.int32).reshape((1,))

    def _state_spec():
        return pl.BlockSpec(
            (1, 1, hb, P, N), lambda s, i, layer, valid: (layer[0], s, i, 0, 0))

    def _slot_spec(rows: int, width: int):
        return pl.BlockSpec((1, rows, width), lambda s, i, layer, valid: (s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H // hb),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _state_spec(),
            _slot_spec(P, H),
            _slot_spec(2 * G, N),
        ],
        # the state first: a trace names the call by its result and first
        # operands, and the roofline modules under bench/ find it by the
        # state's shape
        out_specs=[_state_spec(), _slot_spec(P, H)],
    )
    new_state, y = pl.pallas_call(
        _ssm_decode_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, P, H), f32)],
        # operand 3 (after layer, valid, decay) is the stack: updated in place
        input_output_aliases={3: 0},
        interpret=interpret,
        name="kvmini_ssm_decode",
    )(layer_arr, valid.astype(jnp.int32), decay, state, cols, rows)
    return new_state, jnp.swapaxes(y, 1, 2)

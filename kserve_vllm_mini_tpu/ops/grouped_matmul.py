"""Pallas TPU kernels: the experts' matmuls over their weight stacks, read
where they lie. ``grouped_matmul`` (``gmm``), a long piece's rows grouped by
expert; ``expert_sweep`` (``emm``), a decode step's touched experts one after
the other (the last section).

``models/moe.py``'s dense dispatch multiplies every held expert by every
token of a piece. For a long prefill piece that arithmetic is the program
(32 experts where a token chose 4). Here the piece's token-expert
assignments come SORTED by expert, each expert's group padded to a whole
number of row tiles (``group_rows``), and one call multiplies each row tile
by its own expert's weights: the arithmetic is the chosen experts' plus the
padding, and every expert's weights are read once.

The shape of the call is ``kvmini_ssm_decode``'s (ops/ssm_decode.py): the
kernel takes the LAYER-STACKED leaf ``[L, E, in, out]`` with the layer index
and each row tile's expert as scalar-prefetch values folded into the
weight's index map, so no layer and no expert is sliced out of the stack for
the call (a slice handed to an opaque call is a copy), and it sits behind one
``jax.jit`` whose operands are the stack, the layer index and the rows: a
walk's 12 or 22 expert layers are one traced and lowered body a shape.

Layout: grid (out / tn, row tiles), the row tiles innermost, so a block of an
expert's weights is fetched once for all of that expert's consecutive tiles
(Pallas skips the copy of a block whose index did not change) and, where the
whole output width fits (``tn == out``: every configuration of the
benchmark), once a call. An int8 leaf is dequantised as ``ops/quant.linear``
does, W8A16: the int8 block is converted to the rows' dtype into a VMEM
scratch when the expert CHANGES, not a tile (the conversion of a
``[2048, 1792]`` block costs about what a 128-row tile's matmul does), the
matmul accumulates in float32, and the float32 scale per output channel is
the epilogue. With two stacks the call is the gated pair (``act(x W_a) *
(x W_b)``: the row tile is read once for both) and the activation runs in
float32 on the scaled products; the result is cast to the rows' dtype once.

Row tiles past the used count (the bound on the rows is static, their use
is not) map to the last used tile's blocks, so nothing is fetched for them,
and do nothing.

**A decode step** (``expert_sweep``) has a row or two an expert, so there is
nothing to sort: the grid is the experts held, one step an expert, the
touched experts first (their indices a scalar-prefetch value folded into the
stacks' index maps beside the layer, as above; a step past their count stays
on the last one's blocks and does nothing, so an untouched expert is never
fetched and the call's shape does not depend on the routing). A step
multiplies ALL the rows by its expert's whole blocks: the first matmul or
gated pair with the activation in float32, then the second matmul on the
``[rows, mid]`` intermediate, which stays in a VMEM scratch; the product
times the expert's column of the rows' routing weights (zero where a row did
not choose it) is added to the float32 result, which is resident until the
last step and written once. An int8 block is converted ``SWEEP_CHUNK`` rows
at a time on its way into the matmul (W8A16 as above; no converted copy is
kept: every block is used once). The call is bound by the touched experts'
stream: on a v5e 700-740 GB/s over them from half the experts up, 580-650 at
an eighth to a quarter, where the first block's fetch, which nothing hides,
and ~0.5 us a skipped step show (my chip run, PR 46).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kserve_vllm_mini_tpu.ops.quant import is_quantized

# What a call's blocks may take of a v5e core's 128 MiB of VMEM: the int8
# blocks of a gated pair double-buffered and their converted copies are 29 MB
# at LFM2-MoE's 2048 x 1792
VMEM_BUDGET = 64 << 20
ROW_TILE = 128          # the MXU's height: a shorter tile leaves it part empty
_SUBLANES = {1: 32, 2: 16, 4: 8}   # rows of one tile by the dtype's bytes

# each looked up in ``jax.nn`` when it is called: a test swaps one for another
ACTIVATIONS = {
    None: lambda a: a,
    "silu": lambda a: jax.nn.silu(a),
    "relu": lambda a: jax.nn.relu(a),
    "relu2": lambda a: jnp.square(jax.nn.relu(a)),
}


def _vmem_bytes(n_in: int, tm: int, tn: int, w_bytes: int, x_bytes: int, n_w: int) -> int:
    """Bytes of one grid step's blocks: the weights' double-buffered (and
    their converted copy where they are int8), the row tile's and the
    result's, and the float32 products."""
    weights = n_w * n_in * tn * (2 * w_bytes + (x_bytes if w_bytes == 1 else 0))
    return weights + 2 * tm * (n_in + tn) * x_bytes + (n_w + 1) * tm * tn * 4


def _dtypes_served(w_dtype, x_dtype) -> bool:
    """int8 weights or the rows' own dtype, under bfloat16 or float32 rows."""
    return ((w_dtype == jnp.int8 or w_dtype == x_dtype)
            and jnp.issubdtype(x_dtype, jnp.floating) and x_dtype.itemsize in (2, 4))


def grouped_matmul_block(n_in: int, n_out: int, w_dtype, x_dtype,
                         n_weights: int = 1) -> Optional[tuple[int, int]]:
    """(rows of a tile, output columns of a block), or None where the kernel
    has no block (the caller keeps the dense dispatch): int8 weights or the
    rows' own floating dtype, ``in`` whole sublane tiles of both dtypes and
    ``out`` whole lane tiles. The row tile is the MXU's height whatever the
    rows: twice that pads more and was no faster where groups are long (768
    rows an expert: the gated pair 2.55 against 2.57 ms, the down projection
    1.40 against 1.50; my chip run, PR 45). The output block is the widest
    that divides ``out`` into lane tiles and fits ``VMEM_BUDGET``: the whole
    width wherever it fits, so that each expert's weights are read once."""
    w_dtype, x_dtype = jnp.dtype(w_dtype), jnp.dtype(x_dtype)
    if not _dtypes_served(w_dtype, x_dtype):
        return None
    if n_out % 128 or n_in % _SUBLANES[w_dtype.itemsize] or n_in % _SUBLANES[x_dtype.itemsize]:
        return None
    lanes = n_out // 128
    for d in sorted((d for d in range(1, lanes + 1) if lanes % d == 0), reverse=True):
        if _vmem_bytes(n_in, ROW_TILE, 128 * d, w_dtype.itemsize, x_dtype.itemsize,
                       n_weights) <= VMEM_BUDGET:
            return ROW_TILE, 128 * d
    return None


def group_rows(expert: jnp.ndarray, groups: int, tile: int,
               ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Lay ``expert`` [A] (each assignment's group, ``0 .. groups - 1``, or
    ``groups`` for one that belongs to none) out as rows sorted by group, each
    group padded to whole tiles of ``tile`` rows ->

    - ``source`` [R] int32: the assignment each row holds, ``A`` for a row of
      padding; ``R = A + groups * (tile - 1)`` rounded up to whole tiles, the
      most any routing needs (no capacity: a routing that sends everything to
      one group fits), so the shape is static;
    - ``tiles`` [1 + R / tile] int32: the number of tiles in use, then each
      tile's group (``groups`` past the used ones);
    - ``row`` [A] int32: the row each assignment went to. An assignment of no
      group goes past the used tiles, where nothing is computed.

    One stable sort over the assignments and every group's ``tile - 1``
    possible rows of padding (those a group does not need sort to the end),
    and one more to invert it: a scatter of 4-49 thousand scalars would run
    them one by one."""
    n = expert.shape[0]
    ids = jnp.arange(groups, dtype=jnp.int32)
    counts = jnp.sum(expert[:, None] == ids[None, :], axis=0, dtype=jnp.int32)
    spare = jnp.arange(tile - 1, dtype=jnp.int32)[None, :] < ((-counts) % tile)[:, None]
    padding = jnp.where(spare, ids[:, None], groups).reshape(-1)
    total = -(-(n + padding.shape[0]) // tile) * tile
    keys = jnp.concatenate([expert.astype(jnp.int32), padding,
                            jnp.full((total - n - padding.shape[0],), groups, jnp.int32)])
    vals = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                            jnp.full((total - n,), n, jnp.int32)])
    keys, source = jax.lax.sort((keys, vals), num_keys=1, is_stable=True)
    tile_group = keys[::tile]
    used = jnp.sum(tile_group < groups, dtype=jnp.int32)
    _, row = jax.lax.sort((source, jnp.arange(total, dtype=jnp.int32)), num_keys=1)
    return source, jnp.concatenate([used[None], tile_group]), row[:n]


def _grouped_matmul_kernel(meta_ref, *refs, n_w: int, quantized: bool, act: str):
    """``meta_ref``: [2 + tiles] int32 in SMEM: the layer, the tiles in use,
    each tile's expert. ``refs``: the ``n_w`` weight blocks [in, tn], then
    (int8) the scales of all experts [E, tn] a stack, the row tile [tm, in],
    the result [tm, tn], then (int8) a converted copy of each weight block."""
    w_refs, refs = refs[:n_w], refs[n_w:]
    s_refs, refs = (refs[:n_w], refs[n_w:]) if quantized else ((), refs)
    x_ref, o_ref, *scratch = refs
    t = pl.program_id(1)
    expert = meta_ref[2 + t]

    @pl.when(t < meta_ref[1])
    def _():
        if quantized:
            n_in = w_refs[0].shape[0]
            step = math.gcd(n_in, 256)

            # the expert's blocks are converted when the expert changes: the
            # tiles of one expert follow each other
            @pl.when((t == 0) | (expert != meta_ref[2 + jnp.maximum(t - 1, 0)]))
            def _():
                def some_rows(c, carry):
                    at = pl.ds(pl.multiple_of(c * step, step), step)
                    for w_ref, wide in zip(w_refs, scratch):
                        wide[at, :] = w_ref[at, :].astype(wide.dtype)
                    return carry

                jax.lax.fori_loop(0, n_in // step, some_rows, 0)

        x = x_ref[...]
        products = []
        for i in range(n_w):
            w = scratch[i][...] if quantized else w_refs[i][...]
            y = jnp.dot(x, w, preferred_element_type=jnp.float32)
            if quantized:
                y = y * s_refs[i][pl.ds(expert, 1), :]
            products.append(y)
        out = ACTIVATIONS[act](products[0])
        if n_w == 2:
            out = out * products[1]
        o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block", "interpret"))
def grouped_matmul(
    rows: jnp.ndarray,      # [R, in], sorted by expert, groups padded to the row tile
    tiles: jnp.ndarray,     # [1 + R / tm] int32: tiles in use, each tile's expert (group_rows)
    layer: jnp.ndarray | int,   # which layer of the stacks
    *stacks: Any,           # one or two leaves [L, E, in, out]: plain, or int8 {"q", "s" [L, E, out]}
    act: Optional[str] = None,  # ACTIVATIONS: on the first stack's product
    block: tuple[int, int],     # (tm, tn): grouped_matmul_block
    interpret: bool = False,    # Pallas interpreter: tests only
) -> jnp.ndarray:
    """``act(rows @ W_a[layer, e]) [* rows @ W_b[layer, e]]`` -> [R, out] in
    the rows' dtype, ``e`` each row tile's expert. Rows of tiles past the
    used count are not written."""
    tm, tn = block
    quantized = is_quantized(stacks[0])
    qs = [w["q"] if quantized else w for w in stacks]
    _, n_e, n_in, n_out = qs[0].shape
    total = rows.shape[0]
    n_w = len(stacks)
    if quantized and any("a" in w for w in stacks):
        raise ValueError("grouped matmul: an AWQ leaf's input scaling is the dense dispatch's")
    if total % tm or tiles.shape[0] != 1 + total // tm or n_out % tn:  # kvmini: static-shape
        raise ValueError(f"grouped matmul: {total} rows, {tiles.shape[0] - 1} tiles of {tm}, "
                         f"out {n_out} in blocks of {tn}")
    meta = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape((1,)),
                            tiles.astype(jnp.int32)])

    def tile_of(t, meta):       # tiles past the used ones stay on the last used
        return jnp.maximum(jnp.minimum(t, meta[1] - 1), 0)

    def weight_at(j, t, meta):
        return meta[0], jnp.minimum(meta[2 + tile_of(t, meta)], n_e - 1), 0, j

    # the stacks before the rows: a trace names a call by its result and its
    # first operands, and the roofline modules under bench/ find the experts'
    # matmuls by the stack's shape
    in_specs = [pl.BlockSpec((None, None, n_in, tn), weight_at) for _ in qs]
    operands = list(qs)
    if quantized:
        in_specs += [pl.BlockSpec((None, n_e, tn), lambda j, t, meta: (meta[0], 0, j))
                     for _ in stacks]
        operands += [w["s"].astype(jnp.float32) for w in stacks]
    in_specs.append(pl.BlockSpec((tm, n_in), lambda j, t, meta: (tile_of(t, meta), 0)))
    operands.append(rows)

    need = _vmem_bytes(n_in, tm, tn, qs[0].dtype.itemsize, rows.dtype.itemsize, n_w)
    return pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, n_w=n_w, quantized=quantized, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_out // tn, total // tm),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, meta: (tile_of(t, meta), j)),
            scratch_shapes=[pltpu.VMEM((n_in, tn), rows.dtype) for _ in qs] if quantized else [],
        ),
        out_shape=jax.ShapeDtypeStruct((total, n_out), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            # the converted copy is carried from a tile to the next
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(need + (16 << 20), 32 << 20),
        ),
        interpret=interpret,
        # a short name: a trace cuts a call's name at 96 characters, and the
        # stack's shape, after the result's and the first operand, must fit
        name="gmm",
    )(meta, *operands)


# -- a decode step: the touched experts, one after the other ------------------

SWEEP_ROWS = 32     # the rows a sweep multiplies every touched expert by
SWEEP_CHUNK = 512   # rows of an int8 block converted and multiplied at once


def _chunk_of(n: int, most: int = SWEEP_CHUNK) -> int:
    """The largest whole number of lane tiles that divides ``n`` and is at
    most ``most`` (``n`` itself where it is no more)."""
    lanes = n // 128
    return 128 * max(d for d in range(1, lanes + 1) if lanes % d == 0 and 128 * d <= most)


def expert_sweep_fits(n_in: int, n_mid: int, n_out: int, w_dtype, x_dtype,
                      n_first: int, rows: int) -> bool:
    """Whether ``expert_sweep`` has a block for the body ``[in, mid]`` (one
    or two stacks) then ``[mid, out]`` over ``rows`` rows: at most
    ``SWEEP_ROWS`` of them, int8 weights or the rows' own floating dtype,
    every width whole lane tiles, and one expert's blocks, double-buffered,
    inside ``VMEM_BUDGET`` (the width is never cut: an expert's ``[rows,
    mid]`` intermediate has to stay in VMEM)."""
    w_dtype, x_dtype = jnp.dtype(w_dtype), jnp.dtype(x_dtype)
    if rows > SWEEP_ROWS or not _dtypes_served(w_dtype, x_dtype):
        return False
    if n_in % 128 or n_mid % 128 or n_out % 128:
        return False
    return _sweep_vmem_bytes(n_in, n_mid, n_out, w_dtype.itemsize, x_dtype.itemsize,
                             n_first) <= VMEM_BUDGET


def _sweep_vmem_bytes(n_in: int, n_mid: int, n_out: int, w_bytes: int, x_bytes: int,
                      n_first: int) -> int:
    """Bytes of one grid step of ``expert_sweep``: the expert's blocks
    double-buffered, two converted chunks on their way into a matmul, and
    the rows, the float32 products and the result."""
    weights = 2 * (n_first * n_in * n_mid + n_mid * n_out) * w_bytes
    wide = max(_chunk_of(n_in) * n_mid, _chunk_of(n_mid) * n_out) * x_bytes
    small = SWEEP_ROWS * (2 * n_in * x_bytes + (n_first + 2) * n_mid * 4 + 3 * n_out * 4)
    return weights + 2 * wide + small


def _expert_sweep_kernel(meta_ref, *refs, n_first: int, quantized: bool, act: str):
    """One grid step: one touched expert times all the rows. ``meta_ref``
    [2 + E] int32 in SMEM: the layer, the experts touched, their indices.
    ``refs``: the expert's ``n_first`` blocks [in, mid] and its block [mid,
    out], then (int8) each stack's scales of all experts [E, .], the rows
    [S, in], their weights for every expert [S, E] float32, the result
    [S, out] float32 (resident: every step adds to it), the scratch of the
    intermediate [S, mid]."""
    w_refs, refs = refs[:n_first + 1], refs[n_first + 1:]
    s_refs, refs = (refs[:n_first + 1], refs[n_first + 1:]) if quantized else ((), refs)
    x_ref, take_ref, o_ref, mid_ref = refs
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t < meta_ref[1])
    def _():
        expert = meta_ref[2 + t]

        def product(rows_ref, i):
            """``rows @ W_i`` in float32, an int8 block converted a chunk of
            rows at a time (W8A16: the converted chunk never leaves VMEM),
            times the expert's float32 scale per output channel."""
            w_ref = w_refs[i]
            n_in = w_ref.shape[0]
            step = _chunk_of(n_in) if quantized else n_in
            y = None
            for at in range(0, n_in, step):
                w = w_ref[at:at + step, :]
                part = jnp.dot(rows_ref[:, at:at + step],
                               w.astype(rows_ref.dtype) if quantized else w,
                               preferred_element_type=jnp.float32)
                y = part if y is None else y + part
            return y * s_refs[i][pl.ds(expert, 1), :] if quantized else y

        mid = ACTIVATIONS[act](product(x_ref, 0))
        if n_first == 2:
            mid = mid * product(x_ref, 1)
        mid_ref[...] = mid.astype(mid_ref.dtype)
        out = product(mid_ref, n_first)
        # the expert's column of the weights: zero where a row did not choose it
        lane = jax.lax.broadcasted_iota(jnp.int32, take_ref.shape, 1)
        share = jnp.sum(jnp.where(lane == expert, take_ref[...], 0.0), axis=1, keepdims=True)
        o_ref[...] += share * out


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def expert_sweep(
    rows: jnp.ndarray,      # [S, in]: a decode step's rows
    take: jnp.ndarray,      # [S, E] float32: each row's weight for each expert, zero where not chosen
    touched: jnp.ndarray,   # [E] bool: the experts to visit (every nonzero column of take)
    layer: jnp.ndarray | int,   # which layer of the stacks
    *stacks: Any,           # two or three leaves [L, E, ., .]: the first matmul's, then the second's
    act: Optional[str] = None,  # ACTIVATIONS: on the first stack's product
    interpret: bool = False,    # Pallas interpreter: tests only
) -> jnp.ndarray:
    """``sum_e take[:, e] * (act(rows @ W_a[layer, e]) [* rows @ W_b[layer,
    e]]) @ W_c[layer, e]`` over the ``touched`` experts -> [S, out] float32.

    The grid is the experts held, one step an expert, and the experts in use
    come first: a step past their count stays on the last one's blocks, so
    nothing is fetched for an expert no row chose, and does nothing. The
    shape of the call does not depend on the routing."""
    *first, last = stacks
    quantized = is_quantized(last)
    qs = [w["q"] if quantized else w for w in stacks]
    _, n_e, n_in, n_mid = qs[0].shape
    n_out = qs[-1].shape[-1]
    n_rows = rows.shape[0]
    if quantized and any("a" in w for w in stacks):
        raise ValueError("expert sweep: an AWQ leaf's input scaling is the dense dispatch's")
    if take.shape != (n_rows, n_e) or touched.shape != (n_e,):
        raise ValueError(f"expert sweep: weights {take.shape} and touched {touched.shape} "
                         f"for {n_rows} rows and {n_e} experts")
    # whole sublane tiles of rows (a padded row has no weight anywhere)
    pad = -n_rows % _SUBLANES[rows.dtype.itemsize]
    rows = jnp.pad(rows, ((0, pad), (0, 0)))
    take = jnp.pad(take.astype(jnp.float32), ((0, pad), (0, 0)))
    # the touched experts' indices in order, without a sort: expert e is the
    # (number of touched experts before it)-th
    ids = jnp.arange(n_e, dtype=jnp.int32)
    place = jnp.cumsum(touched, dtype=jnp.int32) - 1
    order = jnp.sum(jnp.where(touched[None, :] & (place[None, :] == ids[:, None]),
                              ids[None, :], 0), axis=1, dtype=jnp.int32)
    meta = jnp.concatenate([jnp.asarray(layer, jnp.int32).reshape((1,)),
                            jnp.sum(touched, dtype=jnp.int32)[None], order])

    def weight_at(t, meta):     # steps past the used ones stay on the last used
        return meta[0], meta[2 + jnp.maximum(jnp.minimum(t, meta[1] - 1), 0)], 0, 0

    def whole(t, meta):
        return 0, 0

    # the stacks before the rows, as in ``grouped_matmul``: the roofline
    # modules under bench/ find the experts' matmuls by the stack's shape
    in_specs = [pl.BlockSpec((None, None, *q.shape[2:]), weight_at) for q in qs]
    operands = list(qs)
    if quantized:
        in_specs += [pl.BlockSpec((None, n_e, w["s"].shape[-1]), lambda t, meta: (meta[0], 0, 0))
                     for w in stacks]
        operands += [w["s"].astype(jnp.float32) for w in stacks]
    in_specs += [pl.BlockSpec(rows.shape, whole), pl.BlockSpec(take.shape, whole)]
    operands += [rows, take]

    need = _sweep_vmem_bytes(n_in, n_mid, n_out, qs[0].dtype.itemsize, rows.dtype.itemsize,
                             len(first))
    out = pl.pallas_call(
        functools.partial(_expert_sweep_kernel, n_first=len(first), quantized=quantized,
                          act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_e,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((rows.shape[0], n_out), whole),
            scratch_shapes=[pltpu.VMEM((rows.shape[0], n_mid), rows.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], n_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),     # every step adds to the one result
            vmem_limit_bytes=max(need + (16 << 20), 32 << 20),
        ),
        interpret=interpret,
        name="emm",     # short: see ``grouped_matmul``
    )(meta, *operands)
    return out[:n_rows]

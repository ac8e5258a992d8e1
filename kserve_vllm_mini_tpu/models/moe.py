"""Sparse mixture-of-experts layers, drop-free, for a device that holds some
or all of the experts.

Four layers share one rule of dispatch:

- ``moe_mlp`` (Mixtral): a softmax router, the top ``n_experts_per_tok``
  renormalised, SwiGLU experts at the model's width, every expert held. The
  expert axis shards over the mesh's ``ep`` axis (parallel/sharding.py).
- ``moe_latent_block`` (Nemotron-H's ``E`` block): a float32 sigmoid router
  over ``n_routed_experts`` with a selection bias, ``num_experts_per_tok``
  chosen by ``score + bias`` and weighted by their scores, normalised over
  all the chosen and scaled by ``routed_scaling_factor``; un-gated ReLU^2
  experts in a latent of ``moe_latent_size`` between a down- and an
  up-projection; one shared expert at the model's width. The device is TOLD
  which experts it holds (``cfg.expert_offset``, ``cfg.experts_held``): it
  routes over all of them, weights as the whole model would, and computes
  the part of the result its own experts give. What the absent experts would
  add is left out; on one device nothing stands in for their exchange.
- ``moe_glu_block`` (LFM2-MoE's feed-forward past ``num_dense_layers``): the
  same float32 sigmoid router and selection bias, the chosen scores
  normalised with the family's ``+ 1e-6`` and scaled; Mixtral's SwiGLU
  experts (``glu_experts``, the one body both run) ``moe_intermediate_size``
  wide at the model's width, no latent and no shared expert. The device is
  told which experts it holds exactly as above.
- ``moe_glu_block`` again, for a ``sliding_window_layout`` stack
  (SmallThinker): a float32 softmax router without bias that reads the
  layer's INPUT (``route_from``, the residual stream before the attention
  norm) while the experts read the post-attention norm; the chosen
  probabilities renormalised; the same GLU body with ReLU on the gate.

**Three dispatches, chosen by what the piece is** (``grouped_blocks``), and
none can drop a token at any routing, because nothing is bounded by a
capacity.

*Dense: every held expert runs every token of the block, and the routing
weight (zero where the token did not choose the expert) is applied at the
combine.* No sort, scatter, buffer or gather, so no latency-bound operation
between the router and the matmuls, and the int8 expert stacks are read where
they lie (one batched matmul over the expert axis, at 92% of the HBM peak on
a v5e). The price is the stream and the arithmetic of every held expert,
chosen or not. This is what runs a piece of 33 tokens up to one row tile
(128: a prefill piece that short touches every expert and its arithmetic
still hides under the weight stream), ``quant_mode="w8a8"`` (the int8
contraction has its own arithmetic, and it is the control of the benchmark's
``correct``), any piece without the Pallas kernels (the CPU; a mesh, so
``moe_mlp`` under an ``ep`` axis), packed int4 and AWQ leaves, shapes the
kernels have no block for, and a decode step whose caller has only a layer's
slice of the stacks (``moe_mlp`` under Mixtral's layer scan: a slice handed to
an opaque call is a copy of the slice). There, pieces longer than
``TOKEN_BLOCK`` run block by block (unrolled) so that the ``[experts, tokens,
width]`` intermediates stay bounded; pieces longer than ``LONG_PIECE`` in
blocks of ``LONG_TOKEN_BLOCK``: since the grouped dispatch only those
fallbacks reach these blocks.

*Swept: a decode step visits the held experts that a LIVE row chose, one
after the other, and every other expert is never fetched.* A decode step is
bound by the experts' weight stream, and its few rows choose few experts: the
benchmark's cells read 49% of 32 held at ~8 live rows (LFM2-MoE), 15% of 64
at ~3 (SmallThinker), 51% of 128 with 32 of 32 live (Nemotron-H) (ledger,
PR 45, ``*_expert_touch_share``), so the dense step streamed twice to six
times what it needed. A piece of up to 32 rows (a decode step; a prompt's
shortest piece takes it too, where it costs what the dense dispatch does) on
one TPU, over int8 leaves in ``dequant`` mode or plain stacks, handed the
LAYER STACKS with their index: ``ops/grouped_matmul.py expert_sweep``, ONE
Pallas call a layer whose grid is the experts held. Each step multiplies one
touched expert's blocks by ALL the rows (the gated pair or the first matmul,
the activation in float32, the second matmul: the ``[rows, mid]``
intermediate never leaves VMEM) and adds the product times that expert's
column of ``held_weights`` (zero where a row did not choose it) to a float32
result that stays in VMEM until the last step. No sort, gather or combine:
the dense dispatch's arithmetic over fewer experts, W8A16 as
``ops/quant.linear``. The touched experts come first in the grid and a step
past their count stays on the last one's blocks, so the call's shape is the
same at every routing (all rows on one expert; no row live) and nothing is
fetched for an expert no live row chose. A parked row (``real``) chooses
nothing; its result, which nothing reads, is zero. ``visited`` (the fourth
of ``routing_stats``) says how many held experts a step's dispatch
multiplied: the touched count here, every held expert in the dense step.

*Grouped: each expert runs the rows that chose it.* A piece longer than one
row tile of the kernel (128 tokens: a group is padded to whole tiles, so up
to there an expert with one token costs what the dense dispatch's every
expert does, and past it the dense arithmetic, 2 FLOP a token for every
int8 byte of every held expert, is more than a v5e does while it reads that
byte), on one TPU, over int8 leaves in ``dequant`` mode or plain stacks:
256-8192 tokens times 32 or 64 experts where a token chose 4 or 6 is
arithmetic no weight stream hides (it was four fifths of the prefill
programs at a twentieth of the MXU's peak). ``_run_grouped`` sorts the
piece's ``S x K`` assignments that land on a held expert by expert, each
expert's group padded to whole row tiles (``ops/grouped_matmul.py
group_rows``: at most ``S K + held (tile - 1)`` rows, a static bound that
holds every routing, all tokens on one expert included; assignments that
land elsewhere are dropped from the groups and from nothing else), gathers
the rows, runs the body as two calls of one Pallas kernel (``gmm``: the
first matmul or gated pair with its activation, then the second) over the
LAYER-STACKED leaves with the layer index and each tile's expert folded into
the weights' index maps, so the stacks are read where they lie, and sums
each token's ``K`` rows times their routing weights in float32: the dense
dispatch's result up to the order of a float32 sum (and one rounding where
the dense one rounds each product). It is one jitted function whose operands
are the stacks and the layer index, so a walk's expert layers are one traced
and lowered body a shape; the walks pass the stack and the index down
(``moe_glu_block(..., layer=)``).

Quantization: expert stacks quantize per output channel like dense weights
(ops/quant.py works on any [..., in, out] stack); routers and the selection
bias stay unquantized (noise-level bytes, and they decide the routing).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.ops.flash_attention import pallas_interpret
from kserve_vllm_mini_tpu.ops.grouped_matmul import (
    ACTIVATIONS,
    expert_sweep,
    expert_sweep_fits,
    group_rows,
    grouped_matmul,
    grouped_matmul_block,
)
from kserve_vllm_mini_tpu.ops.quant import is_quantized, linear, unpacked_q

TOKEN_BLOCK = 256   # tokens the held experts run at once in the dense dispatch
# A piece of more than LONG_PIECE tokens that still runs the dense dispatch
# (no kernels, ``w8a8``: the benchmark's control) runs LONG_TOKEN_BLOCK at
# once: the unrolled blocks are what a prefill program's compile time grows
# with (an 8192-token piece of 12 expert layers is 384 of them at 256 and
# takes 146 s to compile, 96 at 1024)
LONG_PIECE = 1024
LONG_TOKEN_BLOCK = 1024
_HI = jax.lax.Precision.HIGHEST


def _expert_linear(x: jnp.ndarray, w: Any, mode: str = "dequant") -> jnp.ndarray:
    """``[E, S, in] @ [E, in, out]`` (``x`` [S, in] is every expert's
    input alike); ``w`` is a plain stack or an int8 leaf whose scale is a
    fused epilogue (the contract of ops.quant.linear). ``mode="w8a8"``
    contracts in int8 with the expert axis as the batch dimension."""
    eq = "sd,edf->esf" if x.ndim == 2 else "esd,edf->esf"
    if not is_quantized(w):
        return jnp.einsum(eq, x, w)
    if mode == "w8a8":
        from kserve_vllm_mini_tpu.ops.qmatmul import qdot

        n_e = w["s"].shape[0]
        return qdot(jnp.broadcast_to(x, (n_e, *x.shape)) if x.ndim == 2 else x,
                    w, batch_dims=1)
    y = jnp.einsum(eq, x, unpacked_q(w).astype(x.dtype))
    return y * w["s"].astype(x.dtype)[:, None, :]


def held_weights(weights: jnp.ndarray, chosen: jnp.ndarray, offset: int,
                 held: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``[S, K]`` routing weights and expert ids -> ``[S, held]`` float32,
    the weight with which each token takes each expert held here (experts
    ``offset .. offset + held``; zero where it did not choose it), and
    ``[S, held]`` bool, whether it chose it."""
    hit = (chosen[:, :, None] - offset) == jnp.arange(held)[None, None, :]
    take = jnp.sum(jnp.where(hit, weights[:, :, None].astype(jnp.float32), 0.0), axis=1)
    return take, jnp.any(hit, axis=1)


# An expert body as data, so that both dispatches run the same one: the
# stacks of the first matmul (two: a gate and an up projection, multiplied
# after the gate's activation), the activation (ops/grouped_matmul.py
# ACTIVATIONS; in float32, cast back), the stack of the second matmul.
GLU_SILU = (("w_gate", "w_up"), "silu", "w_down")    # Mixtral, LFM2-MoE
GLU_RELU = (("w_gate", "w_up"), "relu", "w_down")    # SmallThinker
LATENT_RELU2 = (("w1",), "relu2", "w2")              # Nemotron-H's latent experts


def experts_of(p: dict[str, Any], body, qm: str):
    """The dense expert body over ``p``'s stacks [E, in, mid] / [E, mid, out]:
    ``x`` [S, in] -> [E, S, out], every expert on every token of the block."""
    first, act, last = body

    def experts(xb):
        mid = ACTIVATIONS[act](
            _expert_linear(xb, p[first[0]], qm).astype(jnp.float32)).astype(xb.dtype)
        if len(first) == 2:
            mid = mid * _expert_linear(xb, p[first[1]], qm)
        return _expert_linear(mid, p[last], qm)

    return experts


SWEEP = "sweep"     # ``grouped_blocks``: a short piece, every touched expert times all its rows


def grouped_blocks(x: jnp.ndarray, p: dict[str, Any], body, qm: str, kernels: str,
                   stacked: bool = False):
    """How the piece ``x`` [S, in] runs over the stacks ``p`` (each
    [..., E, in, out]): None, the dense dispatch; ``SWEEP``, the touched
    experts one after the other (``ops/grouped_matmul.py expert_sweep``);
    or the grouped dispatch's blocks. Read off the input alone.

    Either kernel needs Pallas on (a TPU, one device: under a mesh the
    engine passes ``"off"``), stacks that are int8 leaves in ``dequant`` mode
    or plain arrays of the activations' dtype (``w8a8`` contracts in int8 and
    packed or AWQ leaves have their own arithmetic: the dense dispatch's),
    and shapes the kernel has a block for. Then by the piece's rows:

    - up to ``SWEEP_ROWS`` of the kernel's (32: a decode step, a prompt's
      shortest piece) the sweep, where one expert's blocks fit its VMEM
      budget and the caller passed the LAYER STACKS with their index
      (``stacked``): a layer's slice handed to an opaque call is a copy of
      the whole slice, 350-705 MB of expert stacks here, so a caller that
      has only a slice keeps the dense dispatch;
    - more, up to one row tile of the grouped kernel: dense (a group's
      padding costs what every expert on every token does, and that
      arithmetic hides under the weight stream);
    - longer: grouped."""
    first, _, last = body
    names = (*first, last)
    if pallas_interpret(kernels) is None:
        return None
    quantized = [is_quantized(p[n]) for n in names]
    plain_int8 = all(quantized) and qm == "dequant" and all(
        set(p[n]) == {"q", "s"} and p[n]["q"].dtype == jnp.int8 for n in names)
    if any(quantized) and not plain_int8:  # kvmini: static-shape
        return None
    w_first, w_last = (p[n]["q"] if quantized[0] else p[n] for n in (first[0], last))
    if stacked and expert_sweep_fits(*w_first.shape[-2:], w_last.shape[-1], w_first.dtype,
                                     x.dtype, len(first), x.shape[0]):
        return SWEEP
    blocks = [grouped_matmul_block(w.shape[-2], w.shape[-1], w.dtype, x.dtype, n_w)
              for w, n_w in ((w_first, len(first)), (w_last, 1))]
    if (None in blocks or blocks[0][0] != blocks[1][0]  # kvmini: static-shape
            or x.shape[0] <= blocks[0][0]):
        return None
    return blocks[0], blocks[1]


def grouped_prefill(cfg: ModelConfig, layers: dict[str, Any], tokens: int,
                    kernels: str) -> bool:
    """Whether a prefill piece of ``tokens`` tokens takes the grouped
    dispatch in the model's expert layers: what the walks decide, from the
    same inputs (``layers`` the parameter tree's layer stacks), for the
    engine's counter. False for a model without routed experts."""
    e = next((e for e in cfg.layer_plan if e.ffn in ("glu", "latent", "moe")), None)
    if e is None:
        return False
    if e.ffn == "glu":
        # the body follows what the router reads, as in ``moe_glu_block``
        p, body = layers[e.ffn_stack], GLU_RELU if e.route_from_input else GLU_SILU
        width = cfg.d_model
    elif e.ffn == "latent":
        p, body, width = layers[e.ffn_stack], LATENT_RELU2, cfg.moe_latent_size
    else:
        p, body, width = layers, GLU_SILU, cfg.d_model
    x = jax.ShapeDtypeStruct((tokens, width), cfg.jnp_dtype)
    return grouped_blocks(x, p, body, cfg.quant_mode, kernels, True) not in (None, SWEEP)


@functools.partial(jax.jit, static_argnames=("offset", "body", "blocks", "interpret"))
def _run_grouped(x, weights, chosen, stacks, layer, *, offset, body, blocks, interpret):
    """The grouped dispatch of one piece, behind ONE jit: a walk's expert
    layers call it with another ``layer`` of the same ``stacks`` and are one
    traced and lowered body (a call site traced and lowered on its own is
    0.1-0.3 s of every set-up, warm compile cache or not; XLA inlines the
    calls). ``x`` [S, in], ``weights`` / ``chosen`` [S, K] over all the
    routed experts, ``stacks`` the body's LAYER-STACKED leaves
    [L, held, ., .] -> [S, out] float32."""
    first, act, last = body
    (tm, _), _ = blocks
    k = chosen.shape[1]
    held = jax.tree_util.tree_leaves(stacks[last])[0].shape[1]
    local = chosen - offset
    landed = (local >= 0) & (local < held)                                  # [S, K]
    # assignments that land elsewhere are dropped from the groups, and from
    # nothing else: the router weighted them as the whole model would
    source, tiles, row = group_rows(jnp.where(landed, local, held).reshape(-1), held, tm)
    rows = jnp.take(x, source // k, axis=0, mode="clip")                    # [R, in]
    mid = grouped_matmul(rows, tiles, layer, *(stacks[n] for n in first), act=act,
                         block=blocks[0], interpret=interpret)
    out = grouped_matmul(mid, tiles, layer, stacks[last], block=blocks[1],
                         interpret=interpret)                               # [R, out]
    # each token's K rows times their routing weights: ONE gather, the rows
    # in choice-major order, so that the sum runs over the leading axis of
    # [K, S, out] (whole tiles added to whole tiles). A gathered row costs
    # ~40 ns on a v5e whether one gather takes S K rows or K gathers S each
    # (1.0 ms a layer at 4096 tokens). A row past the used tiles was never written
    mine = jnp.take(out, row.reshape(chosen.shape).T.reshape(-1), axis=0)
    mine = mine.reshape(k, x.shape[0], -1).astype(jnp.float32)              # [K, S, out]
    share = weights.astype(jnp.float32).T[:, :, None]
    return jnp.sum(jnp.where(landed.T[:, :, None], share * mine, 0.0), axis=0)


def _run_held(x: jnp.ndarray, weights: jnp.ndarray, chosen: jnp.ndarray, offset: int,
              p: dict[str, Any], body, qm: str, kernels: str = "off",
              layer: Optional[int] = None, real: Optional[jnp.ndarray] = None,
              ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The held experts' part of ``sum_k weights[s, k] * expert_chosen[s, k](x[s])``
    -> ([S, out] float32; ``landed`` [S, held], whether a token chose each
    held expert; ``visited``, int32, the held experts whose weights the
    dispatch multiplied). ``p`` holds the body's stacks [held, ., .], or with
    ``layer`` the layer stacks [L, held, ., .] of which this is layer
    ``layer``. ``real`` [S] marks the rows that are read: only the sweep of a
    short piece looks at it, to leave out the experts that none but a parked
    row chose (such a row's result is then zero). ``grouped_blocks`` says
    which dispatch; the dense one runs every held expert on every token, in
    blocks of ``TOKEN_BLOCK`` (``LONG_TOKEN_BLOCK`` past ``LONG_PIECE``
    tokens)."""
    names = (*body[0], body[2])
    p = {n: p[n] for n in names}
    held = jax.tree_util.tree_leaves(p[names[-1]])[0].shape[-3]
    take, landed = held_weights(weights, chosen, offset, held)
    how = grouped_blocks(x, p, body, qm, kernels, layer is not None)
    if how == SWEEP:  # kvmini: static-shape
        chose = landed
        if real is not None:    # a parked row chooses nothing, and reads zero
            chose, take = landed & real[:, None], jnp.where(real[:, None], take, 0.0)
        touched = jnp.any(chose, axis=0)
        out = expert_sweep(x, take, touched, jnp.int32(layer), *(p[n] for n in names),
                           act=body[1], interpret=pallas_interpret(kernels))
        return out, landed, jnp.sum(touched, dtype=jnp.int32)
    if how is not None:
        if layer is None:
            p, layer = jax.tree_util.tree_map(lambda a: a[None], p), 0
        out = _run_grouped(x, weights, chosen, p, jnp.int32(layer), offset=offset, body=body,
                           blocks=how, interpret=pallas_interpret(kernels))
        return out, landed, jnp.sum(jnp.any(landed, axis=0), dtype=jnp.int32)
    if layer is not None:
        p = jax.tree_util.tree_map(lambda a: a[layer], p)
    experts = experts_of(p, body, qm)

    def block(xb, tb):
        return jnp.einsum("se,esd->sd", tb, experts(xb),
                          preferred_element_type=jnp.float32)

    n = x.shape[0]
    size = TOKEN_BLOCK if n <= LONG_PIECE else LONG_TOKEN_BLOCK
    if n <= size:
        return block(x, take), landed, jnp.int32(held)
    # Unrolled, not a ``lax.map``: a loop's operands must be buffers of their
    # own, so XLA sliced every expert stack out of its layer stack and copied
    # it (transposed) before the loop: 117 MB a stack, 6.9 GB of temporaries
    # in a 1024-token prefill of 22 layers x 32 experts (chipless compile).
    pad = -n % size
    xs = jnp.pad(x, ((0, pad), (0, 0)))
    ts = jnp.pad(take, ((0, pad), (0, 0)))
    out = jnp.concatenate([block(xs[i:i + size], ts[i:i + size])
                           for i in range(0, n + pad, size)])[:n]
    return out, landed, jnp.int32(held)


def moe_mlp(p: dict[str, Any], cfg: ModelConfig, h: jnp.ndarray,
            kernels: str = "off") -> jnp.ndarray:
    """Mixtral's routed SwiGLU MLP. ``h`` is the normed hidden [B, T, D];
    returns the MLP delta [B, T, D] (the caller adds the residual).
    ``p`` holds this layer's ``router`` [D, E] and the expert stacks
    ``w_gate``/``w_up`` [E, D, F], ``w_down`` [E, F, D]: a slice of the layer
    scan's, which is a copy, so a decode step keeps the dense dispatch
    (``grouped_blocks``; no cell of the benchmark runs this layer)."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    gates, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.n_experts_per_tok)
    gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)
    out, _, _ = _run_held(x, gates, chosen, 0, p, GLU_SILU, cfg.quant_mode, kernels)
    return out.astype(h.dtype).reshape(B, T, D)


NORM_EPS_LATENT = 1e-20   # Nemotron-H: sum(w) + 1e-20
NORM_EPS_GLU = 1e-6       # LFM2-MoE: sum(w) + 1e-6


def route_sigmoid(x: jnp.ndarray, router: jnp.ndarray, bias: jnp.ndarray,
                  cfg: ModelConfig, norm_eps: float = NORM_EPS_LATENT,
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The biased sigmoid router on x [S, D] -> (weights [S, K] float32,
    expert ids [S, K]) over ALL ``n_routed_experts``: chosen by score +
    bias, weighted by score, normalised over all the chosen (``norm_eps`` is
    the family's constant in that sum), scaled. Float32 at the highest
    precision: a rounded score picks another expert."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32), precision=_HI)
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return w * cfg.routed_scaling_factor, chosen


def route_softmax(x: jnp.ndarray, router: jnp.ndarray, cfg: ModelConfig,
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The softmax router on x [S, D] -> (weights [S, K] float32, expert ids
    [S, K]) over ALL ``n_routed_experts``, no bias: the top
    ``num_experts_per_tok`` logits chosen and a softmax over the chosen,
    which is the softmax over all, its top renormalised (``norm_topk_prob``;
    without it the chosen keep their share of the whole). Float32 at the
    highest precision, as ``route_sigmoid``."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32), precision=_HI)
    top, chosen = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        return jax.nn.softmax(top, axis=-1), chosen
    return jnp.exp(top - jax.nn.logsumexp(logits, axis=-1, keepdims=True)), chosen


def routing_stats(landed: jnp.ndarray, real: Optional[jnp.ndarray],
                  visited: jnp.ndarray) -> jnp.ndarray:
    """int32 [4] from ``landed`` [S, held] (whether a token chose a held
    expert), over the positions ``real`` [B, T] marks (all when None):
    assignments that landed on an expert held here, held experts with at
    least one token, and the fullest held expert's tokens; then ``visited``,
    the held experts whose weights the dispatch multiplied (``_run_held``)."""
    if real is not None:
        landed = landed & real.reshape(-1, 1)
    load = jnp.sum(landed, axis=0, dtype=jnp.int32)                        # [held]
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32), jnp.max(load),
                      visited])


def _of_layer(p: dict[str, Any], layer: Optional[int], *names: str) -> dict[str, Any]:
    """The leaves ``names`` of ``p``, of its layer ``layer`` where ``p`` is a
    layer stack: the small ones a block reads beside its expert stacks."""
    if layer is None:
        return {n: p[n] for n in names}
    return {n: jax.tree_util.tree_map(lambda a: a[layer], p[n]) for n in names}


def moe_glu_block(
    p: dict[str, Any], cfg: ModelConfig, h: jnp.ndarray,
    real: Optional[jnp.ndarray] = None,
    route_from: Optional[jnp.ndarray] = None,
    kernels: str = "off",
    layer: Optional[int] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A routed GLU feed-forward on the normed hidden ``h`` [B, T, D]
    -> (delta [B, T, D], stats as ``routing_stats``). ``p``: ``router``
    [D, n_routed] float32; the held experts' stacks ``w_gate``/``w_up``
    [held, D, F], ``w_down`` [held, F, D]. With ``layer``, ``p`` is the whole
    LAYER STACK of such leaves and this is its layer ``layer``: a walk passes
    the stack and the index down, so that the kernels of a decode step and
    of a long piece read the expert stacks where they lie.

    LFM2-MoE's: the biased sigmoid router (``router_bias`` [n_routed]) on
    ``h`` itself, SiLU on the gate. A ``sliding_window_layout`` stack's
    (``route_from`` [B, T, D], the layer's input): the softmax router on
    THAT tensor, ReLU on the gate."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    if route_from is None:
        small = _of_layer(p, layer, "router", "router_bias")
        w, chosen = route_sigmoid(x, small["router"], small["router_bias"], cfg, NORM_EPS_GLU)
        body = GLU_SILU
    else:
        router = _of_layer(p, layer, "router")["router"]
        w, chosen = route_softmax(route_from.reshape(B * T, D), router, cfg)
        body = GLU_RELU
    out, landed, visited = _run_held(x, w, chosen, cfg.expert_offset, p, body, cfg.quant_mode,
                                     kernels, layer, None if real is None else real.reshape(-1))
    return out.astype(h.dtype).reshape(B, T, D), routing_stats(landed, real, visited)


def relu2(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(x.dtype)


def moe_latent_block(
    p: dict[str, Any], cfg: ModelConfig, h: jnp.ndarray,
    real: Optional[jnp.ndarray] = None,
    kernels: str = "off",
    layer: Optional[int] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The ``E`` block's mixer on the normed hidden ``h`` [B, T, D] ->
    (delta [B, T, D], stats). ``p``: ``router`` [D, n_routed] and
    ``router_bias`` [n_routed] float32; ``w_in`` [D, latent], ``w_out``
    [latent, D]; the held experts' stacks ``w1`` [held, latent, F], ``w2``
    [held, F, latent]; the shared expert ``s1`` [D, Fs], ``s2`` [Fs, D].
    With ``layer``, ``p`` is the LAYER STACK of the ``E`` blocks and this is
    its block ``layer`` (``moe_glu_block``).

    ``stats`` is ``routing_stats`` over the positions ``real`` marks."""
    B, T, D = h.shape
    dt, qm = h.dtype, cfg.quant_mode
    x = h.reshape(B * T, D)
    q = _of_layer(p, layer, "router", "router_bias", "w_in", "w_out", "s1", "s2")
    w, chosen = route_sigmoid(x, q["router"], q["router_bias"], cfg)
    u = linear(x, q["w_in"], mode=qm)                                      # [S, latent]
    routed, landed, visited = _run_held(u, w, chosen, cfg.expert_offset, p, LATENT_RELU2, qm,
                                        kernels, layer, None if real is None else real.reshape(-1))
    routed = linear(routed.astype(dt), q["w_out"], mode=qm)
    shared = linear(relu2(linear(x, q["s1"], mode=qm)), q["s2"], mode=qm)
    return (routed + shared).reshape(B, T, D), routing_stats(landed, real, visited)

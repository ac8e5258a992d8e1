"""Sparse mixture-of-experts layers, drop-free, for a device that holds some
or all of the experts.

Four layers share one dispatch:

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

**Dispatch: every held expert runs every token of the block, and the routing
weight (zero where the token did not choose the expert) is applied at the
combine.** No token can be dropped at any routing, because nothing is
bounded by a capacity: there is no sort, scatter, buffer or gather, so no
latency-bound operation between the router and the matmuls, and the int8
expert stacks are read where they lie (one batched matmul over the expert
axis). The price is arithmetic: held experts / chosen-and-held experts times
what the chosen tokens need. A decode step is bound by the experts' weight
stream, which a sweep over 32 slots x 22 choices touches three quarters of
anyway, so there the arithmetic is free; a prefill piece of 128 positions
touches every expert and its arithmetic still hides under the weight stream;
longer pieces pay it (ROADMAP: a grouped matmul over tokens sorted by
expert, which would also skip the experts a decode step does not touch).
Pieces longer than ``TOKEN_BLOCK`` run block by block (unrolled) so that
the ``[experts, tokens, width]`` intermediates stay bounded; pieces longer
than ``LONG_PIECE`` in blocks of ``LONG_TOKEN_BLOCK``.

Quantization: expert stacks quantize per output channel like dense weights
(ops/quant.py works on any [..., in, out] stack); routers and the selection
bias stay unquantized (noise-level bytes, and they decide the routing).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.ops.quant import is_quantized, linear, unpacked_q

TOKEN_BLOCK = 256   # tokens the held experts run at once
# A piece of more than LONG_PIECE tokens (longer than any piece the programs
# above were measured on) runs LONG_TOKEN_BLOCK at once: the unrolled blocks
# are what a prefill program's compile time grows with (an 8192-token piece of
# 12 expert layers is 384 of them at 256 and takes 146 s to compile, 96 at 1024)
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


def _run_held(x: jnp.ndarray, take: jnp.ndarray, body) -> jnp.ndarray:
    """``sum_e take[s, e] * body(x)[e, s]`` -> [S, out] float32, the tokens
    in blocks of ``TOKEN_BLOCK`` (``LONG_TOKEN_BLOCK`` past ``LONG_PIECE`` tokens)."""
    def block(args):
        xb, tb = args
        return jnp.einsum("se,esd->sd", tb, body(xb),
                          preferred_element_type=jnp.float32)

    n = x.shape[0]
    size = TOKEN_BLOCK if n <= LONG_PIECE else LONG_TOKEN_BLOCK
    if n <= size:
        return block((x, take))
    # Unrolled, not a ``lax.map``: a loop's operands must be buffers of their
    # own, so XLA sliced every expert stack out of its layer stack and copied
    # it (transposed) before the loop: 117 MB a stack, 6.9 GB of temporaries
    # in a 1024-token prefill of 22 layers x 32 experts (chipless compile).
    pad = -n % size
    xs = jnp.pad(x, ((0, pad), (0, 0)))
    ts = jnp.pad(take, ((0, pad), (0, 0)))
    return jnp.concatenate([block((xs[i:i + size], ts[i:i + size]))
                            for i in range(0, n + pad, size)])[:n]


def glu_experts(p: dict[str, Any], qm: str, act=jax.nn.silu):
    """The gated expert body over the stacks ``w_gate``/``w_up`` [E, D, F]
    and ``w_down`` [E, F, D]: ``x`` [S, D] -> [E, S, D], every expert on
    every token of the block. ``act`` is the gate's activation: SiLU
    (Mixtral, LFM2-MoE) or ReLU (SmallThinker); one body."""
    def experts(xb):
        gated = act(
            _expert_linear(xb, p["w_gate"], qm).astype(jnp.float32)
        ).astype(xb.dtype) * _expert_linear(xb, p["w_up"], qm)
        return _expert_linear(gated, p["w_down"], qm)

    return experts


def moe_mlp(p: dict[str, Any], cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Mixtral's routed SwiGLU MLP. ``h`` is the normed hidden [B, T, D];
    returns the MLP delta [B, T, D] (the caller adds the residual).
    ``p`` holds this layer's ``router`` [D, E] and the expert stacks
    ``w_gate``/``w_up`` [E, D, F], ``w_down`` [E, F, D]."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    gates, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.n_experts_per_tok)
    gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)
    out = _run_held(x, held_weights(gates, chosen, 0, cfg.n_experts)[0],
                    glu_experts(p, cfg.quant_mode))
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


def routing_stats(landed: jnp.ndarray, real: Optional[jnp.ndarray]) -> jnp.ndarray:
    """int32 [3] from ``landed`` [S, held] (whether a token chose a held
    expert), over the positions ``real`` [B, T] marks (all when None):
    assignments that landed on an expert held here, held experts with at
    least one token, and the fullest held expert's tokens."""
    if real is not None:
        landed = landed & real.reshape(-1, 1)
    load = jnp.sum(landed, axis=0, dtype=jnp.int32)                        # [held]
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0, dtype=jnp.int32), jnp.max(load)])


def moe_glu_block(
    p: dict[str, Any], cfg: ModelConfig, h: jnp.ndarray,
    real: Optional[jnp.ndarray] = None,
    route_from: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """A routed GLU feed-forward on the normed hidden ``h`` [B, T, D]
    -> (delta [B, T, D], stats as ``routing_stats``). ``p``: ``router``
    [D, n_routed] float32; the held experts' stacks ``w_gate``/``w_up``
    [held, D, F], ``w_down`` [held, F, D].

    LFM2-MoE's: the biased sigmoid router (``router_bias`` [n_routed]) on
    ``h`` itself, SiLU on the gate. A ``sliding_window_layout`` stack's
    (``route_from`` [B, T, D], the layer's input): the softmax router on
    THAT tensor, ReLU on the gate."""
    B, T, D = h.shape
    x = h.reshape(B * T, D)
    if route_from is None:
        w, chosen = route_sigmoid(x, p["router"], p["router_bias"], cfg, NORM_EPS_GLU)
        act = jax.nn.silu
    else:
        w, chosen = route_softmax(route_from.reshape(B * T, D), p["router"], cfg)
        act = jax.nn.relu
    take, landed = held_weights(w, chosen, cfg.expert_offset, cfg.experts_held)
    out = _run_held(x, take, glu_experts(p, cfg.quant_mode, act))
    return out.astype(h.dtype).reshape(B, T, D), routing_stats(landed, real)


def relu2(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(jax.nn.relu(x.astype(jnp.float32))).astype(x.dtype)


def moe_latent_block(
    p: dict[str, Any], cfg: ModelConfig, h: jnp.ndarray,
    real: Optional[jnp.ndarray] = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The ``E`` block's mixer on the normed hidden ``h`` [B, T, D] ->
    (delta [B, T, D], stats). ``p``: ``router`` [D, n_routed] and
    ``router_bias`` [n_routed] float32; ``w_in`` [D, latent], ``w_out``
    [latent, D]; the held experts' stacks ``w1`` [held, latent, F], ``w2``
    [held, F, latent]; the shared expert ``s1`` [D, Fs], ``s2`` [Fs, D].

    ``stats`` is ``routing_stats`` over the positions ``real`` marks."""
    B, T, D = h.shape
    dt, qm = h.dtype, cfg.quant_mode
    x = h.reshape(B * T, D)
    w, chosen = route_sigmoid(x, p["router"], p["router_bias"], cfg)
    take, landed = held_weights(w, chosen, cfg.expert_offset, cfg.experts_held)  # [S, held]
    u = linear(x, p["w_in"], mode=qm)                                      # [S, latent]

    def experts(ub):
        return _expert_linear(relu2(_expert_linear(ub, p["w1"], qm)), p["w2"], qm)

    routed = linear(_run_held(u, take, experts).astype(dt), p["w_out"], mode=qm)
    shared = linear(relu2(linear(x, p["s1"], mode=qm)), p["s2"], mode=qm)
    stats = routing_stats(landed, real)
    return (routed + shared).reshape(B, T, D), stats

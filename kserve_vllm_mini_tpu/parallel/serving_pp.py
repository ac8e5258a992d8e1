"""Serving-side pipeline parallelism: a pp-sharded drop-in for forward().

The reference serves multi-stage models by passing PIPELINE_PARALLEL_SIZE to
Triton (/root/reference/runners/backends/triton/deploy.sh:84-86); here the
mechanism is owned end-to-end. parallel/pipeline.py covers training; this
module covers the *serving* engine: ``make_pp_forward(cfg, mesh)`` returns a
function with forward()'s exact contract (tokens/positions/cache/offsets/
fresh_prefill/logit_index -> logits, cache), so every engine path — flash
prefill, chunked-prefill continuation, fused decode, grammar-masked decode —
runs over a pp mesh unchanged.

TPU-native design:

- **Layer-range sharding**: params["layers"] and the KV cache shard their
  leading L axis over ``pp`` (the cache memory — the serving-scale reason
  for PP — is actually split across stages). Everything else is replicated.
- **SPMD ring, one ppermute per tick**: inside ``shard_map`` each stage
  runs its local ``run_cached_layers`` every tick; activations move to the
  next stage with a single collective-permute. Tick t's compute is real on
  stage t and garbage elsewhere — the standard SPMD bubble.
- **Gated cache writes**: inactive ticks must not corrupt a stage's cache,
  and a full-cache select per tick would copy gigabytes. Instead
  ``run_cached_layers(write_gate=...)`` gathers the existing values at the
  scatter indices and writes them back when the stage is inactive — the
  no-op write stays O(B·KVH·T·D), the same traffic as the real write
  (models/llama.py).
- **Latency model**: a P-stage forward costs P stage-times + (P-1) hops.
  Serving PP buys HBM capacity (each chip holds L/P layers + L/P of the
  cache), not latency — the validator/docs state this tradeoff.

Composition with other axes: ``shard_map`` runs in full-manual mode over
every mesh axis, with non-pp axes unused by the specs (size-1 in serving
topologies this module targets). tp-within-stage composes at the GSPMD
level instead — run tp=1 per stage here, or use the training executor's
explicit-collective route; the validator only advertises pp x dp serving
meshes.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.models.llama import (
    embed_tokens,
    final_logits,
    run_cached_layers,
)

from kserve_vllm_mini_tpu.ops.rope import rope_frequencies


def _pp_param_specs(params: dict[str, Any]) -> dict[str, Any]:
    """Layer stack over pp, everything else replicated (same shape as
    pipeline._pipeline_specs, duplicated here to keep the serving module
    free of the training executor's imports)."""
    def walk(node, under_layers):
        if isinstance(node, dict):
            return {k: walk(v, under_layers or k == "layers") for k, v in node.items()}
        if under_layers:
            return P("pp", *([None] * (jnp.ndim(node) - 1)))
        return P(*([None] * jnp.ndim(node)))

    return {k: walk(v, k == "layers") for k, v in params.items()}


def _cache_specs(cache: dict[str, Any]) -> dict[str, Any]:
    return {k: P("pp", *([None] * (v.ndim - 1))) for k, v in cache.items()}


def make_pp_forward(cfg: ModelConfig, mesh: Mesh, microbatches: int = 1):
    """Build a pp-sharded function with models.llama.forward's signature.

    Requires cfg.n_layers % pp == 0. The returned function must be called
    with a cache (the serving engine always has one) whose leading axis is
    the full n_layers — shard_map hands each stage its L/pp block.

    ``microbatches > 1`` splits the batch's SLOT axis into M groups and
    pipelines them GPipe-style: M + P - 1 ticks instead of M * P, so the
    per-step bubble shrinks from (P-1)/P toward (P-1)/(M+P-1) — decode
    throughput approaches the single-stage rate while the memory split
    stays. Each group writes its own cache slot range
    (run_cached_layers slot_base) and inactive ticks no-op via the write
    gate. Calls whose batch does not divide M (the engine's B=1 prefills)
    fall back to M=1 at trace time.
    """
    n_pp = int(mesh.shape["pp"])
    if cfg.n_layers % n_pp:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={n_pp}")
    other = {a: s for a, s in mesh.shape.items() if a != "pp" and s > 1}
    if other:
        # the specs below replicate every non-pp axis: a dp>1 mesh would
        # all-gather the dp-sharded cache every forward and duplicate work
        raise ValueError(
            f"serving PP runs on pure-pp meshes; got extra axes {other} — "
            "scale replicas at the deployment layer (Knative dp) instead"
        )
    perm = [(i, (i + 1) % n_pp) for i in range(n_pp)]

    def pp_forward(
        params: dict[str, Any],
        cfg_: ModelConfig,
        tokens: jnp.ndarray,
        positions: jnp.ndarray,
        kv_cache: Optional[dict[str, Any]] = None,
        cache_offsets: Optional[jnp.ndarray] = None,
        fresh_prefill: bool = False,
        logit_index: Optional[jnp.ndarray] = None,
        kernels: str = "auto",
    ):
        if cfg_ is not cfg:
            raise ValueError(
                "pp_forward was built for one specific config (its rope "
                "tables and stage split are baked in); got a different cfg"
            )
        if kv_cache is None:
            raise ValueError("pp_forward is the serving executor — cache required "
                             "(training uses parallel/pipeline.py)")
        B, T = tokens.shape
        if cache_offsets is None:
            cache_offsets = jnp.zeros((B,), dtype=jnp.int32)
        # trace-time microbatch choice: B=1 prefills (and any batch that
        # does not divide M) run unpipelined
        M = microbatches if microbatches > 1 and B % microbatches == 0 else 1
        mb = B // M

        p_specs = _pp_param_specs(params)
        c_specs = _cache_specs(kv_cache)
        rep = P(None, None)

        has_li = logit_index is not None
        li = logit_index if has_li else jnp.zeros((B,), dtype=jnp.int32)

        @partial(jax.jit, donate_argnums=(3,))
        def run(params, tokens, positions, cache, offsets, li):
            @partial(
                shard_map,
                mesh=mesh,
                in_specs=(p_specs, rep, rep, c_specs, P(None), P(None)),
                out_specs=(P(None, None, None), c_specs),
                check_vma=False,
            )
            def inner(params, tokens, positions, cache, offsets, li):
                stage = jax.lax.axis_index("pp")
                cos, sin = rope_frequencies(
                    cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta, cfg.rope_scaling
                )
                x = embed_tokens(params, cfg, tokens)         # [B, T, D]
                mbs = x.reshape(M, mb, T, -1)
                pos_mb = positions.reshape(M, mb, T)
                off_mb = offsets.reshape(M, mb)

                def tick(carry, t):
                    state, cache_l, outs = carry
                    m = t - stage                  # this stage's microbatch
                    m_idx = jnp.clip(m, 0, M - 1)
                    active = (m >= 0) & (m < M)
                    # stage 0 ingests microbatch t while any remain
                    h_in = jnp.where(
                        (stage == 0) & (t < M),
                        jax.lax.dynamic_index_in_dim(
                            mbs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
                        ),
                        state,
                    )
                    if M == 1:
                        # keep the unpipelined path free of the per-layer
                        # dynamic K/V slices slot_base implies (trace-time
                        # branch; bit-identical to the pre-microbatch code)
                        pos_t, off_t, base_t = positions, offsets, None
                    else:
                        pos_t = jax.lax.dynamic_index_in_dim(
                            pos_mb, m_idx, 0, keepdims=False
                        )
                        off_t = jax.lax.dynamic_index_in_dim(
                            off_mb, m_idx, 0, keepdims=False
                        )
                        base_t = m_idx * mb
                    h_out, cache_l = run_cached_layers(
                        params["layers"], cfg, h_in, pos_t,
                        cos, sin, cache_l, off_t,
                        fresh_prefill=fresh_prefill,
                        write_gate=active,
                        slot_base=base_t,
                        # global index of this stage's first layer: the
                        # alt-sliding-window phase follows GLOBAL parity
                        layer_offset=stage * (cfg.n_layers // n_pp),
                        kernels=kernels,
                    )
                    # last stage emits microbatch t-(P-1) once the pipe fills
                    out_idx = t - (n_pp - 1)
                    emitted = jax.lax.dynamic_update_index_in_dim(
                        outs, h_out, jnp.clip(out_idx, 0, M - 1), axis=0
                    )
                    outs = jnp.where(
                        (stage == n_pp - 1) & (out_idx >= 0), emitted, outs
                    )
                    state = jax.lax.ppermute(h_out, "pp", perm)
                    return (state, cache_l, outs), None

                outs0 = jnp.zeros((M, mb, T, x.shape[-1]), dtype=x.dtype)
                (_, cache_out, outs), _ = jax.lax.scan(
                    tick, (jnp.zeros_like(mbs[0]), cache, outs0),
                    jnp.arange(M + n_pp - 1),
                )
                # only the last stage holds real outputs; broadcast, then
                # every stage computes identical (replicated) logits
                outs = jax.lax.psum(
                    jnp.where(stage == n_pp - 1, outs, jnp.zeros_like(outs)), "pp"
                )
                h = outs.reshape(B, T, -1)
                if has_li:
                    h = h[jnp.arange(B)[:, None], li[:, None]]
                # shared family epilogue (phi bias, gemma (1+w) norm +
                # softcap): executor-local head code drifts silently
                logits = final_logits(params, cfg, h)
                # shard_map has no donation knob — the enclosing jit (run,
                # donate_argnums=(3,)) owns the cache  # kvmini: buffer-ok
                return logits, cache_out

            return inner(params, tokens, positions, cache, offsets, li)

        return run(params, tokens, positions, kv_cache, cache_offsets, li)

    pp_forward.n_pp = n_pp
    return pp_forward

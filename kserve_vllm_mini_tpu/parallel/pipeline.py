"""Pipeline parallelism: stage-partitioned execution with microbatching.

The reference only *passes through* a PIPELINE_PARALLEL_SIZE knob to Triton
(/root/reference/runners/backends/triton/deploy.sh:84-86); here the
mechanism is owned. TPU-native design:

- The stacked layer axis [L, ...] shards over the ``pp`` mesh axis, so each
  stage holds ``L / pp`` contiguous layers — *layer-range sharding*, not an
  annotation: inside ``shard_map`` each device literally has only its own
  stage's weights.
- A GPipe-style schedule runs ``M`` microbatches through ``P`` stages in
  ``M + P - 1`` ticks. Every tick each stage applies its local layers
  (a ``lax.scan`` over them) to its current activation buffer, then hands
  the result to the next stage with a single ``lax.ppermute`` — the
  activation transfer rides ICI, once per tick, instead of every layer
  (which is what naively scanning pp-sharded layers would do).
- The schedule is SPMD: all stages execute the same program each tick;
  stage identity comes from ``lax.axis_index("pp")``. Warmup/drain bubbles
  process don't-care data that is never emitted.
- Everything is differentiable (``ppermute`` transposes to the inverse
  permutation), so the same executor serves the training step used by the
  multi-chip dry-run and drafter fine-tuning.

Embedding / final norm / lm head are replicated across stages (they are
small next to the layer stack); the layer weights — the bulk of the model —
are stage-partitioned.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.models.llama import (
    embed_tokens,
    final_logits,
    layer_forward,
)

from kserve_vllm_mini_tpu.ops.rope import rope_frequencies


def _pipeline_specs(params: dict[str, Any]) -> dict[str, Any]:
    """shard_map partition specs: layer stack over pp, everything else
    replicated (dp handled on the token spec)."""

    def leaf_spec(path: tuple, leaf) -> P:
        if path and path[0] == "layers":
            return P("pp", *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    def walk(node, path=()):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return leaf_spec(path, node)

    return walk(params)


def pipeline_loss_fn(
    params: dict[str, Any],
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T+1]
    mesh: Mesh,
    n_microbatches: int = 2,
) -> jnp.ndarray:
    """Next-token NLL computed through the pipelined executor."""
    n_pp = mesh.shape["pp"]
    if cfg.n_layers % n_pp:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pp={n_pp}")
    n_dp = mesh.shape.get("dp", 1)
    B = tokens.shape[0]
    if B % (n_dp * n_microbatches):
        raise ValueError(
            f"batch {B} must divide dp*microbatches = {n_dp}*{n_microbatches}"
        )

    p_specs = _pipeline_specs(params)
    tok_spec = P("dp", None)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(p_specs, tok_spec),
        out_specs=P(),
        check_vma=False,
    )
    def spmd_loss(params, tokens):
        inp, tgt = tokens[:, :-1], tokens[:, 1:]
        b, T = inp.shape
        M = n_microbatches
        mb = b // M
        stage = jax.lax.axis_index("pp")
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (mb, T))
        cos, sin = rope_frequencies(
            cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta, cfg.rope_scaling
        )
        layers_local = params["layers"]  # [L/P, ...] — this stage's range only

        x = embed_tokens(params, cfg, inp)             # [b, T, D]
        mbs = x.reshape(M, mb, T, cfg.d_model)

        def run_stage(h):
            # global layer indices: alt-sliding-window masks follow global
            # parity, and this stage owns layers [stage*L/P, (stage+1)*L/P)
            lbase = stage * (cfg.n_layers // n_pp)

            def body(carry, xs):
                p, li = xs
                return layer_forward(
                    p, cfg, carry, positions, cos, sin, layer_idx=li
                ), None

            out, _ = jax.lax.scan(
                body, h,
                (layers_local, lbase + jnp.arange(cfg.n_layers // n_pp)),
            )
            return out

        perm = [(i, (i + 1) % n_pp) for i in range(n_pp)]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t while any remain; other stages
            # (and the drain phase) use what the previous tick handed over
            h_in = jnp.where(
                (stage == 0) & (t < M),
                jax.lax.dynamic_index_in_dim(
                    mbs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
                ),
                state,
            )
            h_out = run_stage(h_in)
            # last stage emits microbatch t-(P-1) once the pipe is full
            out_idx = t - (n_pp - 1)
            emitted = jax.lax.dynamic_update_index_in_dim(
                outputs, h_out, jnp.clip(out_idx, 0, M - 1), axis=0
            )
            outputs = jnp.where((stage == n_pp - 1) & (out_idx >= 0), emitted, outputs)
            state = jax.lax.ppermute(h_out, "pp", perm)
            return (state, outputs), None

        state0 = jnp.zeros((mb, T, cfg.d_model), dtype=x.dtype)
        outputs0 = jnp.zeros((M, mb, T, cfg.d_model), dtype=x.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (state0, outputs0), jnp.arange(M + n_pp - 1)
        )

        # only the last stage holds real outputs; broadcast over the pp ring
        outputs = jax.lax.psum(
            jnp.where(stage == n_pp - 1, outputs, jnp.zeros_like(outputs)), "pp"
        )
        h = outputs.reshape(b, T, cfg.d_model)
        # family epilogues (phi bias, gemma (1+w) norm + softcap) live in
        # ONE place — an executor with its own head code drifts silently
        logits = final_logits(params, cfg, h)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return jax.lax.pmean(jnp.mean(nll), "dp")

    return spmd_loss(params, tokens)


def make_pipeline_train_step(
    cfg: ModelConfig, mesh: Mesh, lr: float = 1e-3, n_microbatches: int = 2
):
    """jitted SGD step over the pipelined loss; params stay pp-sharded."""
    from kserve_vllm_mini_tpu.parallel.sharding import _axis

    def to_named(spec_tree):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P),
        )

    tok_sh = NamedSharding(mesh, P(_axis(mesh, "dp"), None))

    def step(params, tokens):
        loss, grads = jax.value_and_grad(pipeline_loss_fn)(
            params, cfg, tokens, mesh, n_microbatches=n_microbatches
        )
        new_params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32)).astype(p.dtype),
            params,
            grads,
        )
        return new_params, loss

    def compile_for(params):
        p_sh = to_named(_pipeline_specs(params))
        return jax.jit(
            step,
            in_shardings=(p_sh, tok_sh),
            out_shardings=(p_sh, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        )

    return compile_for


def shard_params_for_pipeline(params: dict[str, Any], mesh: Mesh) -> dict[str, Any]:
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        _pipeline_specs(params),
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.device_put(params, shardings)


def dryrun_pipeline(n_devices: int) -> None:
    """pp>=2 stage-partitioned execution on a dp x pp mesh: compile, run one
    train step, verify the loss matches the non-pipelined forward."""
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import init_params
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.train import loss_fn

    cfg = get_config("llama-tiny")
    pp = 2
    while pp * 2 <= min(cfg.n_layers, n_devices // 2) and cfg.n_layers % (pp * 2) == 0:
        pp *= 2
    dp = n_devices // pp
    spec = MeshSpec(dp=dp, sp=1, pp=pp, tp=1)
    mesh = make_mesh(spec)

    params = shard_params_for_pipeline(init_params(jax.random.PRNGKey(0), cfg), mesh)
    M = 2
    B, T = dp * M, 32
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, T + 1), 0, cfg.vocab_size, dtype=jnp.int32
    )

    step = make_pipeline_train_step(cfg, mesh, n_microbatches=M)(params)
    ref = float(loss_fn(jax.device_get(params), cfg, tokens))
    params, loss = step(params, tokens)
    loss.block_until_ready()
    got = float(loss)
    assert abs(got - ref) < 5e-2 * max(1.0, abs(ref)), (got, ref)
    print(
        f"dryrun_pipeline ok: mesh dp={dp} pp={pp} (n={n_devices}), "
        f"microbatches={M}, loss={got:.4f} (unpipelined {ref:.4f})"
    )

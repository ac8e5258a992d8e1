"""Model-family oracle tests: sliding window (mistral), attention biases
(qwen2), and sparse MoE (mixtral) — the reference's other engine-profile
families (/root/reference/profiles/tensorrt-llm/{mistral-7b,codellama-7b}.yaml
and the MoE/EP axis the TPU build adds on top).

Each architecture axis gets a mathematical oracle, not a smoke test:
- window: cached decode == full forward; the window provably binds.
- bias: zero biases reproduce the bias-free model exactly.
- MoE: identical experts == dense SwiGLU (gates sum to 1, so routing must
  cancel); capacity drops degrade gracefully; EP-sharded == unsharded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Model-oracle suite: compile-heavy (gemma's sandwich-norm/softcap graphs
# alone cost ~7 min of XLA:CPU compiles), so it runs in the slow lane with
# its peers (test_model/test_runtime) — the fast tier is the harness lane
# (round-4 verdict #9: fast tier must stay under 3 minutes).
pytestmark = pytest.mark.slow

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import (
    forward,
    init_kv_cache,
    init_params,
)


def _tok_pos(cfg, B, T, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return toks, pos


# ---------------------------------------------------------------- mistral --

def test_sliding_window_binds():
    """With T > window, windowed logits must differ from full-causal logits
    (the mask actually cuts context), and dropping the window reproduces
    llama-tiny exactly (same weights, same math when the window is off)."""
    cfg = get_config("mistral-tiny")          # window = 16
    T = 48
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 2, T)
    lg_win, _ = forward(p, cfg, toks, pos)
    lg_full, _ = forward(p, cfg.scaled(sliding_window=None), toks, pos)
    # positions < window see identical context -> identical logits
    np.testing.assert_allclose(
        np.asarray(lg_win[:, : cfg.sliding_window]),
        np.asarray(lg_full[:, : cfg.sliding_window]),
        rtol=1e-5, atol=1e-5,
    )
    # beyond the window the mask must change the result
    assert not np.allclose(
        np.asarray(lg_win[:, -1]), np.asarray(lg_full[:, -1]), atol=1e-4
    )


def test_sliding_window_cached_decode_matches_full_forward():
    """Prefill+decode through the cache reproduces the cache-free windowed
    forward position-for-position (the cached mask applies the same window
    against absolute cache slots)."""
    cfg = get_config("mistral-tiny")
    T, steps = 24, 8                          # crosses the 16-token window
    p = init_params(jax.random.PRNGKey(0), cfg)
    total = T + steps
    toks, pos = _tok_pos(cfg, 1, total)
    ref, _ = forward(p, cfg, toks, pos)       # full windowed forward

    cache = init_kv_cache(cfg, 1, max_seq=64)
    _, cache = forward(
        p, cfg, toks[:, :T], pos[:, :T], cache,
        jnp.zeros((1,), jnp.int32), fresh_prefill=True,
    )
    for i in range(steps):
        t = T + i
        lg, cache = forward(
            p, cfg, toks[:, t : t + 1], pos[:, t : t + 1],
            cache, jnp.full((1,), t, jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(lg[0, 0]), np.asarray(ref[0, t]), rtol=2e-2, atol=2e-2
        )


def test_windowed_prefill_beyond_window_uses_masked_path():
    """fresh_prefill with T > window must still be windowed-exact (the flash
    kernel is block-causal only; forward must fall back to the masked path)."""
    cfg = get_config("mistral-tiny")
    T = 32                                    # > window=16
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 2, T)
    ref, _ = forward(p, cfg, toks, pos)
    cache = init_kv_cache(cfg, 2, max_seq=64)
    lg, _ = forward(
        p, cfg, toks, pos, cache, jnp.zeros((2,), jnp.int32), fresh_prefill=True
    )
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_ring_attention_rejects_window():
    cfg = get_config("mistral-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 1, 16)
    with pytest.raises(ValueError, match="sliding-window"):
        forward(p, cfg, toks, pos, attention_fn=lambda q, k, v, pp: q)


# ------------------------------------------------------------------ qwen2 --

def test_qwen_zero_bias_equals_no_bias():
    """Init biases are zero, so qwen-tiny must reproduce the identical
    bias-free architecture bit-for-bit; a nonzero bias must change logits."""
    cfg = get_config("qwen-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 2, 16)
    lg_bias, _ = forward(p, cfg, toks, pos)

    cfg_nb = cfg.scaled(attn_bias=False)
    p_nb = {k: v for k, v in p.items()}
    p_nb["layers"] = {k: v for k, v in p["layers"].items() if k not in ("bq", "bk", "bv")}
    lg_nb, _ = forward(p_nb, cfg_nb, toks, pos)
    np.testing.assert_array_equal(np.asarray(lg_bias), np.asarray(lg_nb))

    p2 = dict(p)
    p2["layers"] = dict(p["layers"])
    p2["layers"]["bq"] = jnp.ones_like(p["layers"]["bq"]) * 0.5
    lg2, _ = forward(p2, cfg, toks, pos)
    assert not np.allclose(np.asarray(lg2), np.asarray(lg_bias), atol=1e-4)


# ---------------------------------------------------------------- mixtral --

def test_moe_identical_experts_equals_dense():
    """When every expert holds the same weights, top-k routing with
    renormalized gates must reproduce the dense SwiGLU MLP (whatever the
    router picks, the result is the same expert output weighted by gates
    summing to 1)."""
    cfg = get_config("mixtral-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    # copy expert 0 into all experts, each layer
    layers = dict(p["layers"])
    for name in ("w_gate", "w_up", "w_down"):
        w = layers[name]                       # [L, E, in, out]
        layers[name] = jnp.broadcast_to(w[:, :1], w.shape)
    p_same = dict(p, layers=layers)

    dense_cfg = get_config("llama-tiny").scaled(
        vocab_size=cfg.vocab_size, d_ff=cfg.d_ff, max_seq_len=cfg.max_seq_len,
        rope_theta=cfg.rope_theta,
    )
    dense_layers = {
        k: (v[:, 0] if k in ("w_gate", "w_up", "w_down") else v)
        for k, v in layers.items()
        if k != "router"
    }
    p_dense = dict(p_same, layers=dense_layers)

    toks, pos = _tok_pos(cfg, 2, 16)
    lg_moe, _ = forward(p_same, cfg, toks, pos)
    lg_dense, _ = forward(p_dense, dense_cfg, toks, pos)
    np.testing.assert_allclose(
        np.asarray(lg_moe), np.asarray(lg_dense), rtol=2e-2, atol=2e-2
    )


def _moe_layer(cfg, seed=0):
    """One layer's MoE leaves of the preset, float32."""
    p = init_params(jax.random.PRNGKey(seed), cfg.scaled(dtype="float32"))
    return {k: p["layers"][k][0] for k in ("router", "w_gate", "w_up", "w_down")}


def _token_by_token(p, cfg, x):
    """The layer as its equations read: each token through its chosen
    experts alone, weighted by its renormalised gates."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    out = np.zeros(x.shape, np.float32)
    for s in range(x.shape[0]):
        top = np.argsort(-np.asarray(probs[s]))[: cfg.n_experts_per_tok]
        gates = np.asarray(probs[s])[top] / np.asarray(probs[s])[top].sum()
        for g, e in zip(gates, top):
            h = jax.nn.silu(x[s] @ p["w_gate"][e]) * (x[s] @ p["w_up"][e])
            out[s] += g * np.asarray(h @ p["w_down"][e])
    return out


@pytest.mark.parametrize("routing", ["random", "all_to_two_experts", "one_token"])
def test_moe_drops_no_token_at_any_routing(routing):
    """No capacity bounds an expert: with every token of the block sent to
    the same two experts (a capacity-factor dispatch would keep the first
    few and drop the rest) the layer is still each token through its own
    chosen experts."""
    from kserve_vllm_mini_tpu.models.moe import moe_mlp

    cfg = get_config("mixtral-tiny").scaled(dtype="float32")
    p = _moe_layer(cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1 if routing == "one_token" else 48,
                                                          cfg.d_model)), jnp.float32)
    if routing == "all_to_two_experts":
        x = x + 2.0                   # a component every token shares, for the router to read
        p["router"] = jnp.zeros((cfg.d_model, cfg.n_experts)).at[:, 1].set(0.05).at[:, 3].set(0.04)
    got = moe_mlp(p, cfg, x[None])[0]
    want = _token_by_token(p, cfg, x)
    if routing == "all_to_two_experts":
        chosen = np.argsort(-np.asarray(x @ p["router"]), axis=-1)[:, :2]
        assert (np.sort(chosen, axis=-1) == [1, 3]).all()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_moe_ep_sharded_matches_unsharded():
    """Expert-parallel sharding over the ``ep`` mesh axis must be a pure
    layout change: logits equal to the single-device run."""
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.sharding import shard_params

    cfg = get_config("mixtral-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 4, 16)
    ref, _ = forward(p, cfg, toks, pos)

    mesh = make_mesh(MeshSpec(dp=2, ep=2, tp=2))
    p_sharded = shard_params(p, cfg, mesh)
    lg, _ = jax.jit(lambda pp, t, ps: forward(pp, cfg, t, ps))(p_sharded, toks, pos)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_moe_quantized_init_runs():
    from kserve_vllm_mini_tpu.models.llama import init_params_quantized

    cfg = get_config("mixtral-tiny")
    pq = init_params_quantized(jax.random.PRNGKey(0), cfg)
    # router must stay full precision; experts must be int8
    assert pq["layers"]["router"].dtype == cfg.jnp_dtype
    assert pq["layers"]["w_gate"]["q"].dtype == jnp.int8
    toks, pos = _tok_pos(cfg, 2, 16)
    lg, _ = forward(pq, cfg, toks, pos)
    assert bool(jnp.isfinite(lg).all())


# -------------------------------------------------------------- codellama --

def test_codellama_preset_is_llama2_shaped():
    cfg = get_config("codellama-7b")
    assert cfg.n_kv_heads == cfg.n_heads        # MHA
    assert cfg.rope_theta == 1_000_000.0
    assert cfg.vocab_size == 32_016


# ------------------------------------------------------------ loader maps --

def test_loader_roundtrip_new_families(tmp_path):
    """save_checkpoint -> load_hf_checkpoint is the identity for each new
    family (bias, window, and MoE leaves all survive the HF name mapping)."""
    from kserve_vllm_mini_tpu.models.loader import load_hf_checkpoint, save_checkpoint

    for name in ("mistral-tiny", "qwen-tiny", "mixtral-tiny", "phi-tiny",
                 "gemma-tiny"):
        cfg = get_config(name)
        p = init_params(jax.random.PRNGKey(3), cfg)
        if cfg.attn_bias:  # exercise nonzero biases through the roundtrip
            p["layers"]["bq"] = p["layers"]["bq"] + 0.25
        out = tmp_path / name
        save_checkpoint(p, cfg, out)
        p2, cfg2 = load_hf_checkpoint(out)
        assert cfg2.sliding_window == cfg.sliding_window
        assert cfg2.attn_bias == cfg.attn_bias
        assert cfg2.n_experts == cfg.n_experts
        assert cfg2.block == cfg.block
        if cfg.block == "gemma2":
            assert cfg2.explicit_head_dim == cfg.explicit_head_dim
            assert cfg2.attn_softcap == cfg.attn_softcap
            assert cfg2.final_softcap == cfg.final_softcap
            assert cfg2.query_pre_attn_scalar == cfg.query_pre_attn_scalar
            assert cfg2.alt_sliding_window
        for path, leaf in jax.tree_util.tree_leaves_with_path(p):
            leaf2 = p2
            for k in path:
                leaf2 = leaf2[k.key]
            np.testing.assert_allclose(
                np.asarray(leaf, dtype=np.float32),
                np.asarray(leaf2, dtype=np.float32),
                rtol=1e-2, atol=1e-2,
                err_msg=f"{name}: {path}",
            )


# -------------------------------------------------------------------- phi --

def _naive_phi_layer(pl, cfg, x, cos, sin):
    """Independent straight-line phi block (no scan, no shared helpers
    beyond rope): LayerNorm -> {attention, GELU MLP} in parallel -> residual.
    The oracle the production path must match."""
    from kserve_vllm_mini_tpu.ops.rope import apply_rope

    B, T, D = x.shape
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    h = ((xf - mean) / jnp.sqrt(var + cfg.rms_eps)
         * pl["attn_norm"].astype(jnp.float32)
         + pl["attn_norm_b"].astype(jnp.float32)).astype(x.dtype)

    hd, rd = cfg.head_dim, cfg.rotary_dim
    q = (h @ pl["wq"] + pl["bq"]).reshape(B, T, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = (h @ pl["wk"] + pl["bk"]).reshape(B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = (h @ pl["wv"] + pl["bv"]).reshape(B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    q = jnp.concatenate([apply_rope(q[..., :rd], pos, cos, sin), q[..., rd:]], -1)
    k = jnp.concatenate([apply_rope(k[..., :rd], pos, cos, sin), k[..., rd:]], -1)

    logits = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) * hd ** -0.5
    mask = jnp.tril(jnp.ones((T, T), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1).astype(x.dtype)
    o = jnp.einsum("bhts,bhsd->bhtd", probs, v)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, D)
    attn_out = o @ pl["wo"] + pl["bo"]

    up = (h @ pl["w_up"] + pl["b_up"]).astype(jnp.float32)
    mlp_out = (jax.nn.gelu(up, approximate=True).astype(x.dtype) @ pl["w_down"]
               + pl["b_down"])
    return x + attn_out + mlp_out


def test_phi_forward_matches_naive_block():
    """Production forward (scan, shared helpers) == straight-line oracle."""
    from kserve_vllm_mini_tpu.ops.rope import rope_frequencies

    cfg = get_config("phi-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    B, T = 2, 16
    toks, pos = _tok_pos(cfg, B, T)
    got, _ = forward(p, cfg, toks, pos)

    cos, sin = rope_frequencies(cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta)
    x = p["embed"][toks]
    for li in range(cfg.n_layers):
        pl = {k: v[li] for k, v in p["layers"].items()}
        x = _naive_phi_layer(pl, cfg, x, cos, sin)
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    x = ((xf - mean) / jnp.sqrt(var + cfg.rms_eps)
         * p["final_norm"].astype(jnp.float32)
         + p["final_norm_b"].astype(jnp.float32)).astype(cfg.jnp_dtype)
    want = (x @ p["lm_head"].T).astype(jnp.float32) + p["lm_head_b"].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_phi_cached_decode_matches_full_forward():
    cfg = get_config("phi-tiny")
    T, steps = 16, 6
    p = init_params(jax.random.PRNGKey(0), cfg)
    total = T + steps
    toks, pos = _tok_pos(cfg, 1, total)
    ref, _ = forward(p, cfg, toks, pos)

    cache = init_kv_cache(cfg, 1, max_seq=64)
    _, cache = forward(
        p, cfg, toks[:, :T], pos[:, :T], cache,
        jnp.zeros((1,), jnp.int32), fresh_prefill=True,
    )
    for i in range(steps):
        t = T + i
        lg, cache = forward(
            p, cfg, toks[:, t : t + 1], pos[:, t : t + 1],
            cache, jnp.full((1,), t, jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(lg[0, 0]), np.asarray(ref[0, t]), rtol=2e-2, atol=2e-2
        )


def test_phi_partial_rotary_binds():
    """partial_rotary_factor must matter: full-rotary logits differ."""
    cfg = get_config("phi-tiny")                # prf = 0.5
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 2, 16)
    a, _ = forward(p, cfg, toks, pos)
    b, _ = forward(p, cfg.scaled(partial_rotary_factor=1.0), toks, pos)
    assert not np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_phi_tp_sharded_matches_unsharded():
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.sharding import shard_params

    cfg = get_config("phi-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 4, 16)
    ref, _ = forward(p, cfg, toks, pos)
    mesh = make_mesh(MeshSpec(dp=2, tp=4))
    p_sharded = shard_params(p, cfg, mesh)
    lg, _ = jax.jit(lambda pp, t, ps: forward(pp, cfg, t, ps))(p_sharded, toks, pos)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_phi_quantized_init_runs():
    from kserve_vllm_mini_tpu.models.llama import init_params_quantized

    cfg = get_config("phi-tiny")
    pq = init_params_quantized(jax.random.PRNGKey(0), cfg)
    assert pq["layers"]["w_up"]["q"].dtype == jnp.int8
    toks, pos = _tok_pos(cfg, 2, 16)
    lg, _ = forward(pq, cfg, toks, pos)
    assert bool(jnp.isfinite(lg).all())


def test_phi_pipeline_executor_matches_forward():
    """The pipelined executor must run the same math as forward() for the
    phi block too (rotary_dim-width rope tables, biased final LayerNorm,
    lm_head bias)."""
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.pipeline import pipeline_loss_fn

    cfg = get_config("phi-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    B, T = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T + 1), 0, cfg.vocab_size)

    mesh = make_mesh(MeshSpec(dp=2, pp=2))
    loss_pp = pipeline_loss_fn(p, cfg, tokens, mesh, n_microbatches=2)

    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, _ = forward(p, cfg, inp, pos)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(
        float(loss_pp), float(jnp.mean(nll)), rtol=2e-2, atol=2e-2
    )


# ----------------------------------------------------------------- gemma --

def _gnorm(t, w, eps):
    tf = t.astype(jnp.float32)
    var = (tf * tf).mean(-1, keepdims=True)
    return (tf / jnp.sqrt(var + eps) * (1.0 + w.astype(jnp.float32))).astype(
        t.dtype
    )


def _naive_gemma_layer(pl, cfg, x, cos, sin, layer_idx):
    """Independent straight-line gemma-2 block: sandwich (1+w)-RMSNorms,
    GeGLU, query_pre_attn scaling, tanh-capped attention scores, and a
    local mask on even layers — the oracle the production scan must match."""
    from kserve_vllm_mini_tpu.ops.rope import apply_rope

    B, T, D = x.shape
    hd = cfg.head_dim
    h = _gnorm(x, pl["attn_norm"], cfg.rms_eps)
    q = (h @ pl["wq"]).reshape(B, T, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = (h @ pl["wk"]).reshape(B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = (h @ pl["wv"]).reshape(B, T, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    q = apply_rope(q, pos, cos, sin)
    k = apply_rope(k, pos, cos, sin)
    g = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)

    scale = (cfg.query_pre_attn_scalar or float(hd)) ** -0.5
    logits = jnp.einsum("bhtd,bhsd->bhts", q, k).astype(jnp.float32) * scale
    logits = jnp.tanh(logits / cfg.attn_softcap) * cfg.attn_softcap
    qi = jnp.arange(T)[:, None]
    kj = jnp.arange(T)[None, :]
    mask = kj <= qi
    if layer_idx % 2 == 0:
        mask &= kj > qi - cfg.sliding_window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1).astype(x.dtype)
    o = jnp.einsum("bhts,bhsd->bhtd", probs, v)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, cfg.n_heads * hd)
    x = x + _gnorm(o @ pl["wo"], pl["post_attn_norm"], cfg.rms_eps)

    h2 = _gnorm(x, pl["mlp_norm"], cfg.rms_eps)
    gate = jax.nn.gelu(
        (h2 @ pl["w_gate"]).astype(jnp.float32), approximate=True
    ).astype(x.dtype)
    mlp = (gate * (h2 @ pl["w_up"])) @ pl["w_down"]
    return x + _gnorm(mlp, pl["post_mlp_norm"], cfg.rms_eps)


def test_gemma_forward_matches_naive_block():
    """Production forward (scan, shared helpers, alternating masks,
    softcaps, tied head) == straight-line oracle, at T past the window so
    both mask phases bind."""
    from kserve_vllm_mini_tpu.ops.rope import rope_frequencies

    cfg = get_config("gemma-tiny")
    T = 24                                     # > window (16)
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 2, T)
    cos, sin = rope_frequencies(
        cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta, cfg.rope_scaling
    )
    x = p["embed"][toks] * jnp.asarray(cfg.d_model ** 0.5, cfg.jnp_dtype)
    for i in range(cfg.n_layers):
        pl = {k: v[i] for k, v in p["layers"].items()}
        x = _naive_gemma_layer(pl, cfg, x, cos, sin, i)
    x = _gnorm(x, p["final_norm"], cfg.rms_eps)
    ref = (x @ p["embed"].T).astype(jnp.float32)
    ref = jnp.tanh(ref / cfg.final_softcap) * cfg.final_softcap

    lg, _ = forward(p, cfg, toks, pos)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


def test_gemma_cached_decode_matches_full_forward():
    """Prefill+decode through the cache reproduces the cache-free forward
    position-for-position — across the window boundary, both mask phases,
    and the capped-score paths."""
    cfg = get_config("gemma-tiny")
    T, steps = 20, 8                           # crosses the 16-token window
    p = init_params(jax.random.PRNGKey(0), cfg)
    total = T + steps
    toks, pos = _tok_pos(cfg, 1, total)
    ref, _ = forward(p, cfg, toks, pos)

    cache = init_kv_cache(cfg, 1, max_seq=64)
    _, cache = forward(
        p, cfg, toks[:, :T], pos[:, :T], cache,
        jnp.zeros((1,), jnp.int32), fresh_prefill=True,
    )
    for i in range(steps):
        t = T + i
        lg, cache = forward(
            p, cfg, toks[:, t : t + 1], pos[:, t : t + 1],
            cache, jnp.full((1,), t, jnp.int32),
        )
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(ref[:, t]), rtol=3e-2, atol=3e-2,
            err_msg=f"decode step {i}",
        )


def test_gemma_alternating_window_binds():
    """The alternation itself must matter: alternating logits differ from
    both all-local (alt off, window kept) and all-global (window off) at
    T > window — i.e. both phases are actually running."""
    cfg = get_config("gemma-tiny")
    T = 48
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 2, T)
    alt, _ = forward(p, cfg, toks, pos)
    all_local, _ = forward(p, cfg.scaled(alt_sliding_window=False), toks, pos)
    all_global, _ = forward(
        p, cfg.scaled(sliding_window=None, alt_sliding_window=False), toks, pos
    )
    assert not np.allclose(np.asarray(alt[:, -1]),
                           np.asarray(all_local[:, -1]), atol=1e-4)
    assert not np.allclose(np.asarray(alt[:, -1]),
                           np.asarray(all_global[:, -1]), atol=1e-4)


def test_gemma_softcaps_bind():
    """Final logits live strictly inside (-cap, cap), and disabling the
    attention cap changes the result (the cap is really applied)."""
    cfg = get_config("gemma-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 2, 16)
    lg, _ = forward(p, cfg, toks, pos)
    assert float(jnp.max(jnp.abs(lg))) < cfg.final_softcap
    lg_nocap, _ = forward(p, cfg.scaled(attn_softcap=None), toks, pos)
    delta = float(np.max(np.abs(
        np.asarray(lg, dtype=np.float32) - np.asarray(lg_nocap, np.float32)
    )))
    if delta <= 1e-3:
        # Tiny-init attention scores sit deep in tanh's linear region and
        # the model runs bf16, so on some backend builds the cap is
        # numerically INVISIBLE end-to-end (delta can be exactly 0.0 —
        # PR 8's minimal-container failure was this coin flip landing
        # heads). "The cap is really applied" is then a STRUCTURAL
        # property: the capped program must carry the extra per-layer
        # tanh the uncapped one lacks. (The cap's math is pinned
        # numerically by test_gemma_attn_softcap_matches_reference.)
        def _tanh_count(c):
            jp = jax.make_jaxpr(lambda t, q: forward(p, c, t, q))(toks, pos)
            return str(jp).count("tanh")

        assert _tanh_count(cfg) > _tanh_count(cfg.scaled(attn_softcap=None))
        pytest.skip(
            f"attn-softcap delta {delta:.1e} is at the bf16 noise floor "
            "on this backend build; cap verified present in the traced "
            "program instead"
        )
    assert not np.allclose(np.asarray(lg), np.asarray(lg_nocap), atol=1e-5)


def test_gemma_explicit_head_dim():
    """head_dim 48 != d_model/n_heads (32): projections must be shaped by
    the explicit value."""
    cfg = get_config("gemma-tiny")
    assert cfg.head_dim == 48
    p = init_params(jax.random.PRNGKey(0), cfg)
    assert p["layers"]["wq"].shape == (cfg.n_layers, cfg.d_model, 4 * 48)
    assert p["layers"]["wo"].shape == (cfg.n_layers, 4 * 48, cfg.d_model)
    assert "lm_head" not in p                  # tied embeddings


def test_gemma_tp_sharded_matches_unsharded():
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.sharding import shard_params

    cfg = get_config("gemma-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    toks, pos = _tok_pos(cfg, 4, 16)
    ref, _ = forward(p, cfg, toks, pos)
    mesh = make_mesh(MeshSpec(dp=4, tp=2))     # kv heads = 2 -> tp = 2
    p_sharded = shard_params(p, cfg, mesh)
    lg, _ = jax.jit(lambda pp, t, ps: forward(pp, cfg, t, ps))(p_sharded, toks, pos)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_gemma_quantized_init_runs():
    from kserve_vllm_mini_tpu.models.llama import init_params_quantized

    cfg = get_config("gemma-tiny")
    pq = init_params_quantized(jax.random.PRNGKey(0), cfg)
    assert pq["layers"]["w_up"]["q"].dtype == jnp.int8
    toks, pos = _tok_pos(cfg, 2, 16)
    lg, _ = forward(pq, cfg, toks, pos)
    assert bool(jnp.isfinite(lg).all())


def test_gemma_engine_serves_greedy_oracle():
    """The serving engine (continuous batching, cached decode, first-token
    sampler) produces the sequential greedy tokens for a gemma model."""
    from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest

    cfg = get_config("gemma-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 42, 7]
    n_new = 8
    toks = list(prompt)
    for _ in range(n_new):
        arr = jnp.asarray(toks, jnp.int32)[None]
        pos = jnp.arange(len(toks), dtype=jnp.int32)[None]
        lg, _ = forward(p, cfg, arr, pos)
        toks.append(int(jnp.argmax(lg[0, -1])))
    ref = toks[len(prompt):]

    eng = Engine(
        p, cfg,
        EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                     min_prefill_bucket=16),
    )
    eng.start()
    try:
        h = eng.submit(GenRequest(prompt_tokens=prompt, max_new_tokens=n_new))
        got = []
        while True:
            kind, *rest = h.events.get(timeout=120)
            if kind == "token":
                got.append(rest[0])
            else:
                break
        assert got == ref
    finally:
        eng.stop()


def test_gemma_pipeline_executor_matches_forward():
    """The pipelined training executor must reproduce forward()'s loss for
    gemma too: sqrt(d_model) embeddings, global-parity alternating masks
    across stages, (1+w) final norm, capped logits (the shared
    embed_tokens/final_logits helpers are what keep executors honest)."""
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.pipeline import pipeline_loss_fn

    cfg = get_config("gemma-tiny")             # 4 layers -> 2 per stage
    p = init_params(jax.random.PRNGKey(0), cfg)
    B, T = 4, 24                               # T > window: both phases bind
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, T + 1), 0, cfg.vocab_size)

    mesh = make_mesh(MeshSpec(dp=2, pp=2))
    loss_pp = pipeline_loss_fn(p, cfg, tokens, mesh, n_microbatches=2)

    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    logits, _ = forward(p, cfg, inp, pos)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(
        float(loss_pp), float(jnp.mean(nll)), rtol=2e-2, atol=2e-2
    )


def test_gemma_serving_pp_matches_single_device_engine():
    """Gemma through the serving-PP engine emits the same greedy tokens as
    the single-device engine — alternating masks keep GLOBAL layer parity
    across the stage split, and the pp head applies gemma's epilogues."""
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.sharding import shard_params
    from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest

    cfg = get_config("gemma-tiny")
    p = init_params(jax.random.PRNGKey(0), cfg)
    prompt = [5, 9, 42, 7, 13]
    n_new = 8

    def run(engine):
        engine.start()
        try:
            h = engine.submit(GenRequest(prompt_tokens=prompt, max_new_tokens=n_new))
            got = []
            while True:
                kind, *rest = h.events.get(timeout=180)
                if kind == "token":
                    got.append(rest[0])
                else:
                    break
            return got
        finally:
            engine.stop()

    ecfg = EngineConfig(max_slots=2, max_seq_len=64, max_prefill_len=32,
                        min_prefill_bucket=16)
    ref = run(Engine(p, cfg, ecfg))

    mesh = make_mesh(MeshSpec(pp=2))
    got = run(Engine(shard_params(p, cfg, mesh), cfg, ecfg, mesh=mesh))
    assert got == ref

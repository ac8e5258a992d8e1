"""Int8 weight-only quantization: math, model parity, sharding compat."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_params
from kserve_vllm_mini_tpu.ops.quant import (
    dequantize_weight,
    is_quantized,
    linear,
    quantize_params,
    quantize_weight,
    quantized_bytes,
)


def test_quantize_roundtrip_error_small():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    qw = quantize_weight(w)
    assert qw["q"].dtype == jnp.int8
    assert qw["s"].shape == (32,)
    back = dequantize_weight(qw, dtype=jnp.float32)
    # per-channel symmetric int8: max error is half a step = amax/254
    amax = np.max(np.abs(np.asarray(w)), axis=0)
    err = np.max(np.abs(np.asarray(back) - np.asarray(w)), axis=0)
    assert np.all(err <= amax / 254.0 + 1e-6)


def test_quantize_weight_stacked_layers():
    w = jnp.ones((3, 8, 4)) * jnp.arange(1, 5)  # distinct per-out-channel scales
    qw = quantize_weight(w)
    assert qw["q"].shape == (3, 8, 4)
    assert qw["s"].shape == (3, 4)
    np.testing.assert_allclose(np.asarray(qw["s"]), np.tile(np.arange(1, 5) / 127.0, (3, 1)))


def test_linear_dispatch():
    x = jnp.ones((2, 8), dtype=jnp.float32)
    w = jnp.full((8, 4), 0.5, dtype=jnp.float32)
    plain = linear(x, w)
    quant = linear(x, quantize_weight(w))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(quant), rtol=1e-2)


def test_zero_weight_channel_no_nan():
    w = jnp.zeros((8, 4))
    qw = quantize_weight(w)
    assert np.all(np.isfinite(np.asarray(qw["s"])))
    assert np.all(np.asarray(dequantize_weight(qw)) == 0)


def test_quantized_forward_close_to_dense():
    cfg = get_config("llama-tiny", max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params)
    assert is_quantized(qparams["layers"]["wq"])
    assert not is_quantized(qparams["layers"]["attn_norm"])

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(16), (1, 16)).astype(jnp.int32)
    logits, _ = forward(params, cfg, tokens, positions)
    qlogits, _ = forward(qparams, cfg, tokens, positions)
    # top-1 agreement on most positions is the practical bar for W8A16
    top = jnp.argmax(logits, -1)
    qtop = jnp.argmax(qlogits, -1)
    agree = float(jnp.mean((top == qtop).astype(jnp.float32)))
    assert agree >= 0.75, f"greedy agreement {agree}"


def test_init_params_quantized_matches_quantize_after_init():
    """The layer-wise int8 init (which never materializes the bf16 stack —
    the round-2 8B OOM fix) must equal quantize-after-init to within one
    quantization LSB. The weights drawn are bit-identical (same per-layer
    keys); XLA may fuse the bf16-cast → f32 quantize chain at a different
    rounding boundary in the two programs, which can flip q by ±1 on a
    ~1e-4 fraction of elements, so exact bit-equality is not portable
    across backends/fusion contexts."""
    from kserve_vllm_mini_tpu.models.llama import init_params_quantized

    cfg = get_config("llama-tiny", max_seq_len=64)
    key = jax.random.PRNGKey(3)
    oracle = quantize_params(init_params(key, cfg))
    direct = init_params_quantized(key, cfg)

    assert jax.tree.structure(oracle) == jax.tree.structure(direct)
    for name in ("embed", "final_norm", "lm_head"):
        np.testing.assert_array_equal(
            np.asarray(oracle[name]), np.asarray(direct[name]), err_msg=name
        )
    for lname, a in oracle["layers"].items():
        b = direct["layers"][lname]
        if not is_quantized(a):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=lname)
            continue
        assert a["q"].dtype == b["q"].dtype and a["q"].shape == b["q"].shape
        np.testing.assert_allclose(
            np.asarray(a["s"]), np.asarray(b["s"]), rtol=1e-5, err_msg=lname
        )
        dq = np.abs(np.asarray(a["q"]).astype(np.int32) - np.asarray(b["q"]).astype(np.int32))
        assert dq.max() <= 1, f"{lname}: max |dq| {dq.max()}"
        assert (dq != 0).mean() <= 1e-3, f"{lname}: {100 * (dq != 0).mean():.3f}% differ"


def test_logit_index_matches_full_forward():
    """logit_index (the prefill HBM saver) must pick exactly the row the
    full forward computes — including ragged per-sequence positions."""
    cfg = get_config("llama-tiny", max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(16), (2, 16)).astype(jnp.int32)
    full, _ = forward(params, cfg, tokens, positions)
    idx = jnp.asarray([15, 7], dtype=jnp.int32)  # ragged: per-sequence last
    picked, _ = forward(params, cfg, tokens, positions, logit_index=idx)
    assert picked.shape == (2, 1, cfg.vocab_size)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(full[b, int(idx[b])]), np.asarray(picked[b, 0]),
            rtol=1e-5, atol=1e-5,
        )


def test_quantized_bytes_smaller():
    cfg = get_config("llama-tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert quantized_bytes(quantize_params(params)) < quantized_bytes(params)


def test_shard_quantized_params():
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh
    from kserve_vllm_mini_tpu.parallel.sharding import shard_params

    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = get_config("llama-tiny")
    mesh = make_mesh(MeshSpec.fill(4, tp=4))
    qparams = quantize_params(init_params(jax.random.PRNGKey(0), cfg))
    sharded = shard_params(qparams, cfg, mesh)
    # q sharded like the weight; s sharded along out — and still computes
    tokens = jnp.zeros((1, 8), dtype=jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(8), (1, 8)).astype(jnp.int32)
    logits, _ = forward(sharded, cfg, tokens, positions)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_int8_kv_cache_forward_close_to_bf16():
    """Scaled int8 KV: cached decode logits must track the bf16-cache path
    (per-position amax scales bound the relative rounding error)."""
    from kserve_vllm_mini_tpu.models.llama import init_kv_cache

    cfg = get_config("llama-tiny", max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(12), (2, 12)).astype(jnp.int32)
    offs = jnp.zeros((2,), jnp.int32)

    cache_bf = init_kv_cache(cfg, 2, max_seq=64)
    cache_q = init_kv_cache(cfg, 2, max_seq=64, quantized=True)
    assert cache_q["k"].dtype == jnp.int8 and cache_q["k_s"].dtype == jnp.float32

    lb, cache_bf = forward(params, cfg, tokens, positions, cache_bf, offs)
    lq, cache_q = forward(params, cfg, tokens, positions, cache_q, offs)
    agree = float(jnp.mean((jnp.argmax(lb, -1) == jnp.argmax(lq, -1)).astype(jnp.float32)))
    assert agree >= 0.9, f"prefill top-1 agreement {agree}"

    # decode one step against each cache
    nxt = jnp.argmax(lb[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
    pos1 = jnp.full((2, 1), 12, dtype=jnp.int32)
    db, _ = forward(params, cfg, nxt, pos1, cache_bf, jnp.full((2,), 12, jnp.int32))
    dq, _ = forward(params, cfg, nxt, pos1, cache_q, jnp.full((2,), 12, jnp.int32))
    # distributions must be close in the bulk
    pb = jax.nn.softmax(db[:, 0], -1)
    pq = jax.nn.softmax(dq[:, 0], -1)
    tv = float(0.5 * jnp.sum(jnp.abs(pb - pq), axis=-1).max())
    assert tv < 0.15, f"total-variation distance {tv}"


def test_int8_kv_cache_memory_halves():
    from kserve_vllm_mini_tpu.models.llama import init_kv_cache

    cfg = get_config("llama-tiny", max_seq_len=64)
    bf = init_kv_cache(cfg, 4, max_seq=64)
    q = init_kv_cache(cfg, 4, max_seq=64, quantized=True)
    bf_bytes = sum(a.size * a.dtype.itemsize for a in bf.values())
    q_bytes = sum(a.size * a.dtype.itemsize for a in q.values())
    assert q_bytes < 0.6 * bf_bytes


def test_int4_quantize_roundtrip_error_bounded():
    """int4 per-channel roundtrip error stays within one quantization step
    (amax/7 per output channel)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kserve_vllm_mini_tpu.ops.quant import dequantize_weight, quantize_weight

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32) * 0.1
    qw = quantize_weight(w, bits=4)
    # packed representation: nibble pairs in uint8, output axis halved
    # (native S4 leaves recurse at the dispatch relayout — see quantize_weight)
    assert qw["q"].dtype == jnp.uint8
    assert qw["q"].shape == (64, 16)
    err = np.abs(np.asarray(dequantize_weight(qw, jnp.float32)) - np.asarray(w))
    step = np.asarray(qw["s"])[None, :]
    assert (err <= step * 0.75 + 1e-6).all()


def test_int4_unpack_traced_matches_eager():
    """The traced bitcast branch and the eager host branch of _unpack_int4
    must agree element-for-element — this pins the nibble order the packer
    assumes (low nibble = even element) against the backend's
    bitcast_convert_type semantics."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kserve_vllm_mini_tpu.ops.quant import _unpack_int4

    packed = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, size=(5, 8), dtype=np.uint8)
    )
    eager = np.asarray(_unpack_int4(packed), np.int32)
    traced = np.asarray(
        jax.jit(lambda p: _unpack_int4(p).astype(jnp.int8))(packed), np.int32
    )
    assert eager.shape == traced.shape == (5, 16)
    np.testing.assert_array_equal(eager, traced)


def test_int4_init_equals_quantize_after_init():
    """bits=4 layer-wise init == quantize_params(init_params(...), bits=4)
    (same per-layer keys, same scale math — the int8 oracle at 4 bits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import (
        forward,
        init_params,
        init_params_quantized,
    )
    from kserve_vllm_mini_tpu.ops.quant import quantize_params

    cfg = get_config("llama-tiny")
    direct = init_params_quantized(jax.random.PRNGKey(0), cfg, bits=4)
    after = quantize_params(init_params(jax.random.PRNGKey(0), cfg), bits=4)
    from kserve_vllm_mini_tpu.ops.quant import _unpack_int4

    for a, b in zip(jax.tree.leaves(direct), jax.tree.leaves(after)):
        if a.dtype == jnp.uint8:  # packed int4 nibbles — compare unpacked
            ua = np.asarray(_unpack_int4(a), np.int32)
            ub = np.asarray(_unpack_int4(b), np.int32)
            assert np.abs(ua - ub).max() <= 1  # +-1 LSB from the cast boundary
        else:
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-2, atol=2e-4,
            )

    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    lg, _ = forward(direct, cfg, toks, pos)
    assert bool(jnp.isfinite(lg).all())


def test_quantized_bytes_counts_int4_as_half():
    import jax
    import jax.numpy as jnp

    from kserve_vllm_mini_tpu.ops.quant import quantized_bytes

    tree = {"a": jnp.zeros((10, 10), jnp.int4), "b": jnp.zeros((10,), jnp.float32)}
    assert quantized_bytes(tree) == 50 + 40


# -- AWQ-style activation-aware int4 (ops/awq.py) ----------------------------


def _outlier_model():
    """llama-tiny with a few 8x-hot norm channels — the real-model
    activation-outlier phenomenon AWQ exists for (random iid weights have
    no outliers, so plain and calibrated int4 tie there)."""
    cfg = get_config("llama-tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    spread = np.ones(cfg.d_model, np.float32)
    spread[::16] = 8.0
    for nm in ("attn_norm", "mlp_norm"):
        params["layers"][nm] = params["layers"][nm] * jnp.asarray(spread)
    return cfg, params


def test_awq_stats_cover_all_targets():
    from kserve_vllm_mini_tpu.ops.awq import (
        calibration_tokens,
        collect_activation_stats,
    )
    from kserve_vllm_mini_tpu.ops.quant import QUANTIZABLE

    cfg, params = _outlier_model()
    cal = calibration_tokens(cfg.vocab_size, None, n_tokens=64, seed=1)
    stats = collect_activation_stats(params, cfg, cal)
    assert set(stats) == set(QUANTIZABLE) & set(params["layers"])
    for name, a in stats.items():
        assert a.shape[0] == cfg.n_layers
        assert a.ndim == 2 and (a >= 0).all(), name
    # the engineered outliers must be visible in the attn-input stats
    ratio = stats["wq"][:, ::16].mean() / stats["wq"].mean()
    assert ratio > 2.0


def test_awq_leaf_linear_matches_dequant():
    from kserve_vllm_mini_tpu.ops.awq import quantize_weight_awq
    from kserve_vllm_mini_tpu.ops.quant import dequantize_weight, is_quantized, linear

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    a = np.abs(np.random.default_rng(0).normal(size=(64,))).astype(np.float32) + 0.1
    a[::8] *= 10.0
    leaf = quantize_weight_awq(w, a, bits=4)
    assert is_quantized(leaf) and set(leaf) == {"q", "s", "a"}
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 64), jnp.float32)
    y = linear(x, leaf)
    y_ref = x @ dequantize_weight(leaf, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-2, atol=1e-3)


def test_awq_beats_plain_int4_on_outlier_model():
    """The round-4 verdict's acceptance criterion: calibrated int4 beats
    plain int4 on the likelihood axis (same speed by construction — the
    runtime op differs only by a fused elementwise multiply)."""
    from kserve_vllm_mini_tpu.ops.awq import (
        calibration_tokens,
        collect_activation_stats,
        quantize_params_awq,
    )
    from kserve_vllm_mini_tpu.ops.quant import quantize_params

    cfg, params = _outlier_model()
    cal = calibration_tokens(cfg.vocab_size, None, n_tokens=128, seed=1)
    stats = collect_activation_stats(params, cfg, cal)
    p_awq = quantize_params_awq(params, cfg, stats=stats, bits=4)
    p_int4 = quantize_params(params, bits=4)

    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))
    lg_fp, _ = forward(params, cfg, toks, pos)
    lg_awq, _ = forward(p_awq, cfg, toks, pos)
    lg_i4, _ = forward(p_int4, cfg, toks, pos)

    mse_awq = float(jnp.mean((lg_awq - lg_fp) ** 2))
    mse_i4 = float(jnp.mean((lg_i4 - lg_fp) ** 2))
    assert mse_awq < mse_i4, (mse_awq, mse_i4)

    def avg_ll(lg):
        lp = jax.nn.log_softmax(lg[:, :-1].astype(jnp.float32), axis=-1)
        tgt = toks[:, 1:]
        return float(jnp.mean(jnp.take_along_axis(lp, tgt[..., None], axis=-1)))

    ll_fp = avg_ll(lg_fp)
    assert abs(avg_ll(lg_awq) - ll_fp) < abs(avg_ll(lg_i4) - ll_fp)


def test_awq_alpha_grid_includes_plain_fallback():
    """alpha=0 (s=1, i.e. plain quantization) is in the search grid, so on
    a model with NO outliers the search objective can never score worse
    than plain int4's."""
    from kserve_vllm_mini_tpu.ops.awq import DEFAULT_ALPHAS, awq_scales

    assert 0.0 in DEFAULT_ALPHAS
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 32, 16), jnp.float32)
    a = np.ones((3, 32), np.float32)  # flat activations: s must be ~1
    s = awq_scales(w, a, bits=4)
    np.testing.assert_allclose(np.asarray(s), 1.0, rtol=1e-5)


def test_awq_engine_generates():
    """build_engine(quantization='int4-awq') calibrates from the embedded
    corpus and serves finite tokens end-to-end."""
    from kserve_vllm_mini_tpu.runtime.engine import GenRequest
    from kserve_vllm_mini_tpu.runtime.server import build_engine

    engine, tok, _name = build_engine(
        model="llama-tiny", quantization="int4-awq", max_slots=2,
        max_seq_len=128,
    )
    engine.start()
    try:
        h = engine.submit(GenRequest(
            prompt_tokens=tok.encode("hello there"), max_new_tokens=8,
        ))
        out = []
        while True:
            kind, *rest = h.events.get(timeout=120)
            if kind != "token":
                break
            out.append(rest[0])
        assert len(out) == 8
    finally:
        engine.stop()

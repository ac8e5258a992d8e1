"""W8A8 native-quantized hot path (ops/qmatmul.py + the dense int8-KV
decode kernel): activation-quant math, qdot-vs-dequant equivalence, the
llama-tiny W8A8 oracle (logits tolerance + byte-identical greedy engine
streams), dense-vs-paged int8-KV kernel consistency, the perplexity gate
tripping on a seeded numerics break, and the compiled-bytes acceptance pin
(w8a8+int8KV decode <= 60% of the bf16 dequant path's bytes on the
8-device CPU-mesh proxy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_params
from kserve_vllm_mini_tpu.ops.qmatmul import (
    int8_dot,
    qdot,
    quantize_activations,
    validate_quant_mode,
)
from kserve_vllm_mini_tpu.ops.quant import linear, quantize_params, quantize_weight


def test_validate_quant_mode():
    assert validate_quant_mode("dequant") == "dequant"
    assert validate_quant_mode("w8a8") == "w8a8"
    with pytest.raises(ValueError, match="quant_mode"):
        validate_quant_mode("int8")


def test_quantize_activations_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 64), jnp.float32)
    q, s = quantize_activations(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert q.shape == x.shape and s.shape == (4, 7, 1)
    back = q.astype(jnp.float32) * s
    # symmetric int8 per row: error <= half a step = row_amax / 254
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert (err <= amax / 254.0 + 1e-6).all()


def test_quantize_activations_zero_row_no_nan():
    x = jnp.zeros((3, 16), jnp.float32)
    q, s = quantize_activations(x)
    assert np.asarray(q).max() == 0
    assert np.all(np.asarray(s) == 1.0)  # scale 1.0, never 0/NaN


def test_quantize_activations_pre_scale_folds():
    """AWQ compensation folds into the SAME quant pass: quantizing (x * a)
    directly equals quantize_activations(x, pre_scale=a)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 32), jnp.float32)
    a = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (32,))) + 0.1
    q1, s1 = quantize_activations(x * a)
    q2, s2 = quantize_activations(x, pre_scale=a)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


def test_int8_dot_accumulates_in_int32():
    """The KVM064 convention, checked dynamically: a contraction long
    enough to wrap an int8 accumulator must come back exact in int32."""
    xq = jnp.full((1, 1024), 100, jnp.int8)
    wq = jnp.full((1024, 1), 100, jnp.int8)
    out = int8_dot(xq, wq)
    assert out.dtype == jnp.int32
    assert int(out[0, 0]) == 1024 * 100 * 100  # wraps at int8/int16 widths


def test_qdot_matches_dequant_linear_int8():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 64), jnp.float32)
    qw = quantize_weight(w)
    y_deq = linear(x, qw)
    y_w8 = qdot(x, qw)
    assert y_w8.dtype == x.dtype
    # activation rounding adds <= ~1/254 relative per element
    denom = float(jnp.max(jnp.abs(y_deq)))
    assert float(jnp.max(jnp.abs(y_w8 - y_deq))) / denom < 0.02
    # and linear() dispatches to the same path
    np.testing.assert_array_equal(
        np.asarray(linear(x, qw, mode="w8a8")), np.asarray(y_w8)
    )


def test_qdot_matches_dequant_linear_int4_packed():
    """Packed-int4 leaves feed the int8 contraction through the prologue
    unpack — the packed uint8 tensor is the only weight operand."""
    from kserve_vllm_mini_tpu.ops.quant import is_packed_int4

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), jnp.float32)
    qw = quantize_weight(w, bits=4)
    assert is_packed_int4(qw)
    y_deq = linear(x, qw)
    y_w8 = linear(x, qw, mode="w8a8")
    denom = float(jnp.max(jnp.abs(y_deq)))
    assert float(jnp.max(jnp.abs(y_w8 - y_deq))) / denom < 0.02


def test_qdot_awq_leaf_matches_dequant():
    from kserve_vllm_mini_tpu.ops.awq import quantize_weight_awq

    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32), jnp.float32)
    a = np.abs(np.random.default_rng(0).normal(size=(64,))).astype(np.float32) + 0.1
    a[::8] *= 10.0
    leaf = quantize_weight_awq(w, jnp.asarray(a), bits=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 64), jnp.float32)
    y_deq = linear(x, leaf)
    y_w8 = linear(x, leaf, mode="w8a8")
    denom = float(jnp.max(jnp.abs(y_deq)))
    assert float(jnp.max(jnp.abs(y_w8 - y_deq))) / denom < 0.03


def test_qdot_batched_expert_contraction():
    """MoE shape: [E, C, in] @ [E, in, out] with the expert axis as the
    dot_general batch dim (models/moe.py _expert_linear w8a8 branch)."""
    we = jax.random.normal(jax.random.PRNGKey(2), (4, 48, 16), jnp.float32)
    xe = jax.random.normal(jax.random.PRNGKey(3), (4, 6, 48), jnp.float32)
    qe = quantize_weight(we)
    y = qdot(xe, qe, batch_dims=1)
    ref = jnp.einsum("ecd,edf->ecf", xe, we)
    assert y.shape == ref.shape
    denom = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(y - ref))) / denom < 0.03


def test_qdot_traced_matches_eager():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 16), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32), jnp.float32)
    qw = quantize_weight(w)
    eager = np.asarray(qdot(x, qw))
    traced = np.asarray(jax.jit(lambda x: qdot(x, qw))(x))
    np.testing.assert_allclose(traced, eager, rtol=1e-6, atol=1e-6)


# -- the llama-tiny W8A8 oracle ----------------------------------------------


def test_w8a8_forward_close_to_dequant():
    """Full-model logits under quant_mode=w8a8 track the dequant path
    within activation-quant tolerance, with top-1 agreement on most
    positions (the W8A16 bar of tests/test_quant.py, held by W8A8)."""
    cfg = get_config("llama-tiny", max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params(params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16)).astype(jnp.int32)

    lg_deq, _ = forward(qparams, cfg, toks, pos)
    lg_w8, _ = forward(qparams, cfg.scaled(quant_mode="w8a8"), toks, pos)
    # distributions stay close in the bulk
    pd = jax.nn.softmax(lg_deq, -1)
    pw = jax.nn.softmax(lg_w8, -1)
    tv = float(0.5 * jnp.sum(jnp.abs(pd - pw), axis=-1).max())
    assert tv < 0.15, f"total-variation distance {tv}"
    agree = float(jnp.mean(
        (jnp.argmax(lg_deq, -1) == jnp.argmax(lg_w8, -1)).astype(jnp.float32)
    ))
    assert agree >= 0.75, f"greedy agreement {agree}"


def test_w8a8_engine_streams_byte_identical_to_dequant():
    """The engine-level oracle: greedy streams under quant_mode=w8a8 are
    byte-identical to the dequant path's on llama-tiny int8 (fixed seeds;
    CPU execution is deterministic, so this is a fixed outcome — a flip
    here means the w8a8 numerics moved)."""
    from kserve_vllm_mini_tpu.runtime.engine import GenRequest
    from kserve_vllm_mini_tpu.runtime.server import build_engine

    def run(mode):
        engine, tok, _ = build_engine(
            model="llama-tiny", quantization="int8", quant_mode=mode,
            max_slots=2, max_seq_len=128,
        )
        assert engine.cfg.quant_mode == mode
        assert engine.ecfg.quant_mode == mode
        engine.start()
        try:
            outs = []
            for prompt in ("hello there", "the quick brown fox"):
                h = engine.submit(GenRequest(
                    prompt_tokens=tok.encode(prompt), max_new_tokens=12,
                ))
                toks = []
                while True:
                    kind, *rest = h.events.get(timeout=120)
                    if kind != "token":
                        break
                    toks.append(rest[0])
                outs.append(toks)
        finally:
            engine.stop()
        return outs

    assert run("dequant") == run("w8a8")


# -- dense int8-KV decode kernel ----------------------------------------------


def test_dense_kernel_matches_eager_oracle():
    """Direct kernel-vs-oracle in f32: in-kernel dequant over the dense
    [L, B, KVH, S, D] cache equals dequantize-then-attend."""
    from kserve_vllm_mini_tpu.ops.attention import attention
    from kserve_vllm_mini_tpu.ops.paged_attention import dense_decode_attention

    rng = np.random.default_rng(4)
    L, B, KVH, G, D, S = 2, 3, 2, 2, 128, 64
    kq = jnp.asarray(rng.integers(-127, 128, size=(L, B, KVH, S, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=(L, B, KVH, S, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, size=(L, B, KVH, S)).astype(np.float32))
    vs = jnp.asarray(rng.uniform(0.01, 0.1, size=(L, B, KVH, S)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(B, KVH, G, D)).astype(np.float32))
    # positions inside block 0, mid-sweep, and the last valid position
    qpos = jnp.asarray([5, 23, 63], jnp.int32)

    out = dense_decode_attention(q, kq, vq, qpos, layer=1,
                                 k_scale=ks, v_scale=vs, interpret=True)
    kf = kq[1].astype(jnp.float32) * ks[1][..., None]
    vf = vq[1].astype(jnp.float32) * vs[1][..., None]
    qh = q.reshape(B, KVH * G, 1, D)
    mask = jnp.arange(S)[None, None, None, :] <= qpos[:, None, None, None]
    ref = attention(qh, kf, vf, mask).reshape(B, KVH, G, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_dense_kernel_block_helper():
    from kserve_vllm_mini_tpu.ops.paged_attention import dense_decode_block

    bf16 = jnp.bfloat16
    assert dense_decode_block(1024, 8, 128, bf16) == 512
    assert dense_decode_block(64, 8, 128, bf16) == 64
    assert dense_decode_block(24, 8, 128, bf16) == 8
    assert dense_decode_block(7, 8, 128, bf16) is None  # not 8-aligned: eager fallback


# (preset, kv heads, head size) of every shape the presets bring; the block
# the rule gives a 2048-long bf16 / int8 cache, None = eager read
_PRESET_BLOCKS = [
    ("mistral-7b", 8, 128, 512, 512),     # llama-3.x, mixtral alike
    ("qwen2-7b", 4, 128, 512, 512),
    ("codellama-7b", 32, 128, 256, 256),  # MHA: four 4 MiB buffers at 512
    ("phi-2.7b", 32, 80, None, None),     # } a head that is not a multiple
    ("llama-1b", 8, 64, None, None),      # } of 128 lanes: XLA would copy
    ("smoke-125m", 12, 64, None, None),   # } the whole cache to feed the call
    ("gemma-2-9b", 8, 256, 512, 512),     # (its softcap keeps it eager anyway)
]


@pytest.mark.parametrize("preset,kvh,d,blk_bf16,blk_int8", _PRESET_BLOCKS)
def test_dense_kernel_block_fits_vmem(preset, kvh, d, blk_bf16, blk_int8):
    """The block rule adapts to what it sees (KVH, D, itemsize): every
    preset's shape gets the largest block whose double-buffered K and V
    (and scale) blocks stay inside half of Mosaic's 16 MiB scoped VMEM, or
    keeps the eager read. KVH 32 / D 128 bf16 at 512 is the shape a v5e
    compile refuses (16.05 MiB scoped)."""
    from kserve_vllm_mini_tpu.ops.paged_attention import (
        dense_block_vmem_bytes,
        dense_decode_block,
    )

    cfg = get_config(preset)
    assert (cfg.n_kv_heads, cfg.head_dim) == (kvh, d)
    for dtype, want in ((jnp.bfloat16, blk_bf16), (jnp.int8, blk_int8)):
        blk = dense_decode_block(2048, kvh, d, dtype)
        assert blk == want
        if blk is None:
            continue
        assert dense_block_vmem_bytes(blk, kvh, d, dtype) <= 8 << 20
        # and the next size up would not have fitted, where there is one
        if blk < 512:
            assert dense_block_vmem_bytes(2 * blk, kvh, d, dtype) > 8 << 20
    assert dense_block_vmem_bytes(512, 32, 128, jnp.bfloat16) == 16 << 20


def test_model_dense_kernel_matches_eager_path():
    """The dense kernel (interpreted) through the model's int8-KV decode
    path agrees with the eager dequantize-on-read path (which rounds in
    bf16 — same tolerance contract as the paged kernel's model test)."""
    from kserve_vllm_mini_tpu.models.llama import init_kv_cache

    # 128-wide heads: narrower ones keep the eager read (dense_decode_block)
    cfg = get_config("llama-tiny", max_seq_len=64).scaled(explicit_head_dim=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12)).astype(jnp.int32)
    offs = jnp.zeros((2,), jnp.int32)

    def one_step(kernels):
        cache = init_kv_cache(cfg, 2, max_seq=64, quantized=True)
        lg, cache = forward(params, cfg, toks, pos, cache, offs,
                            fresh_prefill=True)
        nxt = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
        lens = jnp.full((2,), 12, jnp.int32)
        lg2, _ = forward(params, cfg, nxt[:, None], lens[:, None],
                         cache, lens, kernels=kernels)
        return np.asarray(lg2[:, 0, :])

    eager = one_step("off")
    kernel = one_step("interpret")
    # eager dequantizes in model dtype (bf16), the kernel in f32
    np.testing.assert_allclose(kernel, eager, rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(eager.argmax(-1), kernel.argmax(-1))


def test_dense_vs_paged_kernel_consistency():
    """The two kernels see the SAME int8-KV stream through different
    layouts: a dense-cache decode (dense kernel) and a paged-pool decode
    (paged kernel) over the same token stream must produce the same
    greedy tokens, and logits within kernel-vs-kernel rounding (both
    dequantize in f32 in-kernel; only the sweep order differs)."""
    from kserve_vllm_mini_tpu.models.llama import init_kv_cache, init_paged_kv_cache

    cfg = get_config("llama-tiny", max_seq_len=64).scaled(explicit_head_dim=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, T, BLK = 2, 16, 8
    table = jnp.asarray(
        [[3, 17, 5, 9, 11, 2, 16, 19], [7, 0, 14, 6, 12, 8, 13, 1]], jnp.int32
    )
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)

    def run(paged):
        if paged:
            cache = init_paged_kv_cache(cfg, 20, BLK, quantized=True)
            kw = {"block_table": table}
        else:
            cache = init_kv_cache(cfg, B, max_seq=64, quantized=True)
            kw = {}
        lg, cache = forward(params, cfg, toks, pos, cache, zero,
                            fresh_prefill=True, **kw)
        nxt = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
        lens = jnp.full((B,), T, jnp.int32)
        steps = []
        for _ in range(4):
            lg2, cache = forward(params, cfg, nxt[:, None], lens[:, None],
                                 cache, lens, kernels="interpret", **kw)
            nxt = jnp.argmax(lg2[:, 0, :], -1).astype(jnp.int32)
            steps.append(np.asarray(nxt))
            lens = lens + 1
        return np.stack(steps), np.asarray(lg2[:, 0, :])

    toks_d, lg_d = run(paged=False)
    toks_p, lg_p = run(paged=True)
    np.testing.assert_array_equal(toks_d, toks_p)
    np.testing.assert_allclose(lg_d, lg_p, rtol=3e-2, atol=3e-2)


def test_dense_kernel_gate_excludes_unsupported_shapes():
    """Softcapped / alternating-window models keep the eager path even
    when kernels are on — the gate, not the caller's mode, owns
    correctness (the kernel has no softcap and one mask for every layer).
    A uniform window no longer excludes: see
    tests/test_tpu_lowering.py::test_model_decode_takes_dense_kernel."""
    from kserve_vllm_mini_tpu.models.llama import (
        dense_decode_kernel_block,
        init_kv_cache,
    )

    # softcap + alternating window; 128-wide heads, so that it is the
    # attention and not the head size that the gate turns away
    cfg = get_config("gemma-tiny", max_seq_len=64).scaled(explicit_head_dim=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24)).astype(jnp.int32)
    offs = jnp.zeros((2,), jnp.int32)
    cache = init_kv_cache(cfg, 2, max_seq=64, quantized=True)
    assert dense_decode_kernel_block(cfg, "interpret", cache, False) is None
    plain = cfg.scaled(attn_softcap=None, alt_sliding_window=False)
    assert dense_decode_kernel_block(plain, "interpret", cache, False) == 64
    assert dense_decode_kernel_block(plain, "interpret", cache, True) is None
    assert dense_decode_kernel_block(plain, "off", cache, False) is None
    lg, cache = forward(params, cfg, toks, pos, cache, offs,
                        fresh_prefill=True)
    lens = jnp.full((2,), 24, jnp.int32)
    nxt = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
    # the gate must route to the masked eager path — bit-equal to the
    # kernels-off run
    lg_on, _ = forward(params, cfg, nxt[:, None], lens[:, None], cache, lens,
                       kernels="interpret")
    lg_off, _ = forward(params, cfg, nxt[:, None], lens[:, None], cache, lens,
                        kernels="off")
    np.testing.assert_array_equal(np.asarray(lg_on), np.asarray(lg_off))


# -- the dense decode kernel reads only what a slot can reach ------------------

_D = 128
# (G, KVH, S): the two benchmark head groupings at two 512-key blocks a
# slot, and an MHA shape whose 32 heads the block rule gives 256-key blocks
_HEADS = {"G4-KVH8": (4, 8, 1024), "G7-KVH4": (7, 4, 1024), "G1-KVH32": (1, 32, 512)}
# a window the last slot's position is well past / one wider than the cache
_WINDOWS = {"causal": None, "window<S": 300, "window>S": 5000}


def _dense_blk(kv, heads):
    from kserve_vllm_mini_tpu.ops.paged_attention import dense_decode_block

    _, KVH, S = _HEADS[heads]
    blk = dense_decode_block(S, KVH, _D, jnp.int8 if kv == "int8" else jnp.bfloat16)
    assert S // blk >= 2  # the sweep crosses a block boundary
    return blk


def _dense_qpos(kv, heads):
    """One batch mixes the positions at which the block rule changes."""
    blk = _dense_blk(kv, heads)
    return [0, blk - 1, blk, _HEADS[heads][2] - 1]


def _dense_case(seed, kv, heads):
    """q, cache operands (keyword form) and the dequantized f32 K/V of
    layer 1 of a two-layer dense cache, four slots."""
    rng = np.random.default_rng(seed)
    G, KVH, S = _HEADS[heads]
    B, L = 4, 2
    shape = (L, B, KVH, S, _D)
    q = jnp.asarray(rng.normal(size=(B, KVH, G, _D)).astype(np.float32))
    if kv == "int8":
        k = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, size=shape[:-1]).astype(np.float32))
        vs = jnp.asarray(rng.uniform(0.01, 0.1, size=shape[:-1]).astype(np.float32))
        ops = {"k_cache": k, "v_cache": v, "k_scale": ks, "v_scale": vs}
        kf = k[1].astype(jnp.float32) * ks[1][..., None]
        vf = v[1].astype(jnp.float32) * vs[1][..., None]
    else:
        k = jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal(shape, np.float32), jnp.bfloat16)
        ops = {"k_cache": k, "v_cache": v}
        kf, vf = k[1].astype(jnp.float32), v[1].astype(jnp.float32)
    return q, ops, kf, vf


def _visible(qpos, window, S):
    kpos = np.arange(S)[None, :]
    qp = np.asarray(qpos)[:, None]
    vis = kpos <= qp
    if window is not None:
        vis &= kpos > qp - window
    return vis                                            # [B, S]


@pytest.mark.parametrize("heads", list(_HEADS))
@pytest.mark.parametrize("window", list(_WINDOWS))
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_dense_kernel_parity_with_eager_oracle(kv, window, heads):
    """bf16 and int8 KV x {no window, a window the last slot's position is
    past, a window wider than the cache} x both benchmark head groupings
    and an MHA one, positions 0 / BLK-1 / BLK / S-1 in one batch, against
    masked eager attention over the whole stripe."""
    from kserve_vllm_mini_tpu.ops.attention import attention
    from kserve_vllm_mini_tpu.ops.paged_attention import dense_decode_attention

    G, KVH, S = _HEADS[heads]
    W = _WINDOWS[window]
    q, ops, kf, vf = _dense_case(11, kv, heads)
    qpos = _dense_qpos(kv, heads)
    out = dense_decode_attention(q, qpos=jnp.asarray(qpos, jnp.int32), layer=1,
                                 window=W, interpret=True, **ops)
    mask = jnp.asarray(_visible(qpos, W, S))[:, None, None, :]
    B = len(qpos)
    ref = attention(q.reshape(B, KVH * G, 1, _D), kf, vf, mask)
    # bf16 KV: the kernel rounds the softmax weights to bf16 before p @ v,
    # as the paged kernel does; int8 KV dequantizes in f32 (up to 1024
    # keys summed block by block against all at once)
    tol = 2e-2 if kv == "bf16" else 2e-4
    np.testing.assert_allclose(np.asarray(out).reshape(ref.shape),
                               np.asarray(ref), rtol=tol, atol=tol)


@pytest.mark.parametrize("window", ["causal", "window<S"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_dense_kernel_ignores_poisoned_dead_positions(kv, window):
    """NaN in every key (and key scale) a slot cannot reach — past ``qpos``
    and before ``qpos - W + 1`` — and in every value of a block outside
    the live range: the output is finite and bit-equal to the clean run.
    (A dead VALUE inside a live block gets a huge finite number instead:
    its softmax weight is exactly 0, and 0 * NaN is NaN in any p @ v, the
    eager oracle's included — the cache never holds NaN, only stale rows.)"""
    from kserve_vllm_mini_tpu.ops.paged_attention import (
        dense_decode_attention,
        live_block_range,
    )

    W = _WINDOWS[window]
    heads = "G4-KVH8"
    S, blk = _HEADS[heads][2], _dense_blk(kv, heads)
    q, ops, _, _ = _dense_case(12, kv, heads)
    qpos = np.asarray(_dense_qpos(kv, heads), np.int32)
    dead = ~_visible(qpos, W, S)                          # [B, S]
    first, last = live_block_range(qpos, W, blk)
    blk_of = np.arange(S) // blk
    dead_blk = (blk_of[None] < first[:, None]) | (blk_of[None] > last[:, None])

    def poison(a, where, value):
        """``a`` with ``value`` at the [B, S] positions ``where``, in every
        layer, head and channel."""
        m = where.reshape((1, len(qpos), 1, S) + (1,) * (a.ndim - 4))
        return jnp.where(jnp.asarray(m), jnp.asarray(value, a.dtype), a)

    # int8 cannot hold NaN: there the poison rides the per-position scales
    kn, vn = ("k_scale", "v_scale") if kv == "int8" else ("k_cache", "v_cache")
    bad = dict(ops)
    bad[kn] = poison(ops[kn], dead, np.nan)
    bad[vn] = poison(poison(ops[vn], dead, 1e30), dead_blk, np.nan)
    kw = dict(qpos=jnp.asarray(qpos), layer=1, window=W, interpret=True)
    clean = np.asarray(dense_decode_attention(q, **kw, **ops))
    out = np.asarray(dense_decode_attention(q, **kw, **bad))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, clean)


@pytest.mark.parametrize("window", [None, 1, 24, 64, 100])
@pytest.mark.parametrize("blk", [16, 64])
def test_dense_kernel_block_selection_rule(window, blk):
    """The index map as a plain function: for every position of a 64-long
    stripe the grid steps name the first reachable block through the dead
    steps and then exactly the blocks between the first and the last
    reachable key, in order, ending on the last — never a block outside
    ``[first_live, last_live]`` — and traced scalars follow the same rule
    as numpy arrays."""
    from kserve_vllm_mini_tpu.ops.paged_attention import (
        fetched_block,
        live_block_range,
    )

    _S = 64
    nb = _S // blk
    qpos = np.arange(_S, dtype=np.int32)
    first, last = live_block_range(qpos, window, blk)
    lo_key = qpos if window is None else np.maximum(qpos - window + 1, 0)
    np.testing.assert_array_equal(first, 0 if window is None else lo_key // blk)
    np.testing.assert_array_equal(last, qpos // blk)
    steps = [fetched_block(b, nb, first, last) for b in range(nb)]
    named = np.stack([blk for blk, _ in steps])           # [nb, S]
    runs = np.stack([run for _, run in steps])
    assert (named >= first).all() and (named <= last).all()
    for p in range(_S):
        live = list(range(first[p], last[p] + 1))
        assert named[:, p].tolist() == [first[p]] * (nb - len(live)) + live
        assert runs[:, p].tolist() == [False] * (nb - len(live)) + [True] * len(live)
        # a change of index is a DMA: exactly one per live block
        assert 1 + int((np.diff(named[:, p]) != 0).sum()) == len(live)
    traced = jax.jit(lambda p, b: fetched_block(b, nb, *live_block_range(p, window, blk))[0])
    for p, b in [(0, 0), (blk - 1, nb - 1), (_S - 1, 0), (_S - 1, nb - 1)]:
        assert int(traced(jnp.int32(p), jnp.int32(b))) == named[b, p]


# -- perplexity gate -----------------------------------------------------------


def test_perplexity_gate_trips_on_dropped_activation_scale(monkeypatch):
    """The seeded numerics break: dropping the per-row activation scale
    (returning scale=1 from quantize_activations) must blow the w8a8 NLL
    past the sweep gate's threshold, while the CORRECT w8a8 path stays
    well under it — the gate separates quantization noise from broken
    math.

    A pure random-init model sits AT chance (NLL ~= log V), so no break
    can move its NLL — the oracle model needs predictive structure. Tied
    embeddings give it one for free: with 0.02-std layer weights the
    residual stream stays ~= the input embedding, so logits = x @ E^T
    predict "next token = current token" — strong (below-chance NLL) on
    repetitive text, and exactly the structure a broken quantized matmul
    destroys (the corrupted branch output swamps the residual identity
    and NLL collapses back to chance)."""
    from kserve_vllm_mini_tpu.ops import qmatmul
    from kserve_vllm_mini_tpu.quality.perplexity import eval_text_nll
    from kserve_vllm_mini_tpu.runtime.tokenizer import load_tokenizer
    from kserve_vllm_mini_tpu.sweeps.quantization import (
        PERPLEXITY_GATE_MAX_NLL_DELTA,
    )

    tok = load_tokenizer(None)
    cfg = get_config("llama-tiny", max_seq_len=256).scaled(
        vocab_size=max(512, tok.vocab_size), tie_embeddings=True,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    # sharper self-logits (x @ E^T ~ |E_t|^2): the identity prediction
    # drops the baseline well below chance, widening the band the gate
    # discriminates over. Embeddings are not a quantized leaf.
    params["embed"] = params["embed"] * 4.0
    qparams = quantize_params(params)
    texts = [
        "aaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaaa" * 3,
        "the the the the the the the the the the " * 3,
    ]

    base = eval_text_nll(qparams, cfg, tok, texts=texts)["nll_per_token"]
    assert base < float(np.log(cfg.vocab_size)) - 1.0  # real structure
    w8 = eval_text_nll(qparams, cfg.scaled(quant_mode="w8a8"), tok,
                       texts=texts)["nll_per_token"]
    # correct w8a8 is quantization NOISE, far under the gate
    assert abs(w8 - base) < 0.1

    real_quantize = qmatmul.quantize_activations

    def dropped_scale(x, pre_scale=None):
        q, s = real_quantize(x, pre_scale=pre_scale)
        return q, jnp.ones_like(s)  # the seeded break: scale dropped

    monkeypatch.setattr(qmatmul, "quantize_activations", dropped_scale)
    broken = eval_text_nll(qparams, cfg.scaled(quant_mode="w8a8"), tok,
                           texts=texts)["nll_per_token"]
    assert broken - base > PERPLEXITY_GATE_MAX_NLL_DELTA, (broken, base)


def test_sweep_gate_fails_cell_past_threshold(tmp_path):
    """run_quantization's gate column: a cell whose NLL exceeds the
    baseline's by more than the threshold FAILS with a perplexity-gate
    error; an in-tolerance cell records its delta and stays ok."""
    from kserve_vllm_mini_tpu.sweeps.quantization import run_quantization

    def bench(cfg):
        nll = {"none": 2.0, "int8": 2.1, "int4": 9.0}[cfg["quantization"]]
        return {
            "p50_ms": 100.0, "p95_ms": 200.0, "tokens_per_sec": 1000.0,
            "error_rate": 0.0, "cost_per_1k_tokens": 0.01,
            "quality_score": 90.0, "quality_nll_per_token": nll,
            "quality_perplexity": float(np.exp(nll)),
        }

    rows = run_quantization(
        {}, tmp_path,
        space={"quantization": ["none", "int8", "int4"],
               "kv_cache_dtype": ["model"], "decoding": ["greedy"],
               "quant_mode": ["w8a8"]},
        bench_fn=bench,
    )
    by_q = {r["quantization"]: r for r in rows}
    # the unquantized baseline keeps quant_mode=dequant (duplicate filter)
    assert by_q["none"]["quant_mode"] in (None, "dequant")
    assert by_q["none"]["status"] == "ok"
    assert by_q["int8"]["status"] == "ok"
    assert by_q["int8"]["quality_perplexity_delta_vs_baseline"] == 0.1
    assert by_q["int4"]["status"] == "failed"
    assert "perplexity gate" in by_q["int4"]["error"]

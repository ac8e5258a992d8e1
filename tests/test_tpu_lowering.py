"""Every Pallas kernel must LOWER for TPU, checked on the CPU box.

Interpret-mode tests (test_flash_attention.py, test_paged_kernel.py,
test_qmatmul.py) skip the Pallas TPU lowering rules entirely, so a
BlockSpec the TPU lowering refuses — e.g. a (1, BLK) scale block over
[KVH, BLK], neither tile-divisible nor the full dims — passes them and
then cannot trace on a chip. ``jax.export`` with ``platforms=["tpu"]`` runs
those rules (no libtpu, no device, seconds). It stops at the Mosaic module:
whether Mosaic COMPILES it is chip_smoke.py's kernels leg.

Shapes: KVH 8 / G 4 (the GQA grouping of the 7B/8B presets), head_dim 128
and 64, the engine's default blocks (kv_block_size 64, dense stripe 512);
the dense decode kernel also at qwen2's KVH 4 / G 7 and with a window.

The model- and engine-level tests at the end pin which configurations take
``kvmini_dense_decode`` in a decode step, and that taking it changes
neither the logits nor the greedy tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.ops.flash_attention import (
    cached_prefill_attention,
    flash_attention,
)
from kserve_vllm_mini_tpu.ops.paged_attention import (
    dense_decode_attention,
    paged_decode_attention,
)

KVH, G, L = 8, 4, 2
H = KVH * G
BF, I8, F32, I32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _lowers_for_tpu(fn, *args) -> None:
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


def _kv(shape, quantized):
    """(k, v[, k_scale, v_scale]) abstract operands for a cache of
    ``shape`` [..., S|BLK, D]."""
    kv = [_sds(shape, I8 if quantized else BF)] * 2
    return kv + ([_sds(shape[:-1], F32)] * 2 if quantized else [])


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("t", [128, 512])
def test_flash_prefill_lowers(d, t):
    _lowers_for_tpu(
        flash_attention,
        _sds((1, H, t, d), BF), _sds((1, KVH, t, d), BF), _sds((1, KVH, t, d), BF),
    )


@pytest.mark.parametrize("t,window,block", [(2048, 512, 128), (8192, 4096, 512), (4096, 1000, 512)])
def test_flash_prefill_under_a_window_lowers(t, window, block):
    """A window layer's fresh piece (28 / 4 heads of 128): the key-block
    index clamped into the q tile's live range, the long pieces' 512 tiles."""
    _lowers_for_tpu(
        lambda q, k, v: flash_attention(q, k, v, block_q=block, block_k=block, window=window),
        _sds((1, 28, t, 128), BF), _sds((1, 4, t, 128), BF), _sds((1, 4, t, 128), BF),
    )


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
def test_paged_decode_lowers(d, quantized):
    slots, pool, blk, maxb = 8, 65, 64, 32

    def fn(q, table, qpos, layer, k, v, ks=None, vs=None):
        return paged_decode_attention(q, k, v, table, qpos, layer=layer,
                                      k_scale=ks, v_scale=vs)

    _lowers_for_tpu(
        fn, _sds((slots, KVH, G, d), BF), _sds((slots, maxb), I32),
        _sds((slots,), I32), _sds((), I32),
        *_kv((L, pool, KVH, blk, d), quantized),
    )


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
def test_dense_decode_lowers(d, quantized):
    """128-wide heads lower; 64-wide ones are turned away before any
    lowering (``dense_decode_block``: XLA would copy the whole cache to
    feed the call), and the model's gate keeps them on the eager read."""
    slots, seq = 8, 2048

    def fn(q, qpos, layer, k, v, ks=None, vs=None):
        return dense_decode_attention(q, k, v, qpos, layer=layer,
                                      k_scale=ks, v_scale=vs)

    args = (
        fn, _sds((slots, KVH, G, d), BF), _sds((slots,), I32), _sds((), I32),
        *_kv((L, slots, KVH, seq, d), quantized),
    )
    if d % 128:
        with pytest.raises(ValueError, match="no key block"):
            _lowers_for_tpu(*args)
    else:
        _lowers_for_tpu(*args)


@pytest.mark.parametrize("heads", [(8, 4), (4, 7), (32, 1)],
                         ids=["KVH8-G4", "KVH4-G7", "KVH32-G1"])
@pytest.mark.parametrize("window", [None, 1024], ids=["causal", "window"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
def test_dense_decode_all_heads_block_lowers(heads, window, quantized):
    """The benchmark's shapes and codellama's MHA one: 16 slots x 2048, the
    [KVH, BLK, 128] block of every kv head (BLK 512, 256 for 32 heads),
    index maps that read ``qpos`` (and the window)."""
    kvh, g = heads
    slots, seq, d = 16, 2048, 128

    def fn(q, qpos, layer, k, v, ks=None, vs=None):
        return dense_decode_attention(q, k, v, qpos, layer=layer,
                                      k_scale=ks, v_scale=vs, window=window)

    _lowers_for_tpu(
        fn, _sds((slots, kvh, g, d), BF), _sds((slots,), I32), _sds((), I32),
        *_kv((L, slots, kvh, seq, d), quantized),
    )


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
def test_cached_prefill_lowers(d, quantized):
    t, seq = 256, 2048  # one slot's sub-cache, as the engine's chunk step slices it

    def fn(q, offsets, layer, k, v, ks=None, vs=None):
        return cached_prefill_attention(q, k, v, offsets, layer=layer,
                                        k_scale=ks, v_scale=vs)

    _lowers_for_tpu(
        fn, _sds((1, H, t, d), BF), _sds((1,), I32), _sds((), I32),
        *_kv((L, 1, KVH, seq, d), quantized),
    )


@pytest.mark.parametrize("stack", [(24, 16, 30, 96, 192), (6, 4, 4, 24, 48)],
                         ids=["published", "tiny"])
def test_linear_decode_lowers(stack):
    """The hybrid's state stack at the benchmark's size (15 of 30 heads a
    grid step) and the tiny preset's: the aliased stack, the SMEM scalars,
    the transposed k | q columns and the per-slot v / output rows."""
    from kserve_vllm_mini_tpu.ops.linear_decode import linear_decode_update

    _, b, h, dk, dv = stack
    _lowers_for_tpu(
        linear_decode_update, _sds(stack, F32), _sds((), I32),
        _sds((b, h, dk), F32), _sds((b, h, dk), F32), _sds((b, h, dv), F32),
        _sds((b, h), F32), _sds((b, h), F32), _sds((b,), I32),
    )


@pytest.mark.parametrize("stack,groups", [((10, 32, 128, 64, 128), 8), ((12, 16, 32, 128, 256), 2),
                                          ((3, 4, 8, 16, 128), 2)],
                         ids=["single-mixer", "two-mixer", "small"])
def test_ssm_decode_lowers(stack, groups):
    """The two pattern stacks' state stacks at the benchmark's sizes (64 of
    128 heads and 16 of 32 a grid step) and a small one: the aliased stack,
    the SMEM decays, the slot's transposed ``dt x`` columns and ``B | C``
    rows, the bfloat16 matmul that sums over the lanes."""
    from kserve_vllm_mini_tpu.ops.ssm_decode import ssm_decode_update

    _, b, h, p, n = stack
    _lowers_for_tpu(
        ssm_decode_update, _sds(stack, F32), _sds((), I32), _sds((b, h, p), F32),
        _sds((b, groups, n), F32), _sds((b, groups, n), F32), _sds((b, h), F32),
        _sds((h,), F32), _sds((b,), I32),
    )


@pytest.mark.parametrize("shape,tokens,top_k", [
    ((22, 32, 2048, 1792), 1024, 4), ((12, 64, 2560, 768), 4096, 6), ((1, 8, 4096, 14336), 512, 2)],
    ids=["lfm2", "smallthinker", "mixtral"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_grouped_matmul_lowers(shape, tokens, top_k, quantized):
    """The expert stacks of the two cells that prefill long pieces and
    Mixtral's (whose width takes more than one output block), as the grouped
    dispatch calls the kernel: the gated pair with its activation, then the
    down projection, at the blocks the rule gives; the layer and each tile's
    expert scalar-prefetch values; int8 blocks converted into a scratch."""
    from kserve_vllm_mini_tpu.ops.grouped_matmul import grouped_matmul, grouped_matmul_block

    n_l, n_e, d, f = shape
    rows = tokens * top_k

    def leaf(n_in, n_out):
        q = _sds((n_l, n_e, n_in, n_out), I8 if quantized else BF)
        return {"q": q, "s": _sds((n_l, n_e, n_out), F32)} if quantized else q

    for (n_in, n_out), n_w, act in (((d, f), 2, "silu"), ((f, d), 1, None)):
        block = grouped_matmul_block(n_in, n_out, I8 if quantized else BF, BF, n_w)
        total = -(-(rows + n_e * (block[0] - 1)) // block[0]) * block[0]
        _lowers_for_tpu(
            lambda x, tiles, layer, *w, act=act, block=block: grouped_matmul(
                x, tiles, layer, *w, act=act, block=block),
            _sds((total, n_in), BF), _sds((1 + total // block[0],), I32), _sds((), I32),
            *[leaf(n_in, n_out)] * n_w)


# -- which decode steps take the dense kernel ----------------------------------


def _decode_step(preset, quantized, kernels, **over):
    """(jaxpr text, logits) of one T == 1 step after a 24-token prefill."""
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import forward, init_kv_cache, init_params

    # 128-wide heads: the tiny presets' 32 would keep the eager read
    cfg = get_config(preset, max_seq_len=64).scaled(**{"explicit_head_dim": 128, **over})
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24)).astype(I32)
    cache = init_kv_cache(cfg, 2, max_seq=64, quantized=quantized)
    lg, cache = forward(params, cfg, toks, pos, cache, jnp.zeros((2,), I32),
                        fresh_prefill=True)
    nxt = jnp.argmax(lg[:, -1, :], -1).astype(I32)
    lens = jnp.full((2,), 24, I32)

    def step(c):
        return forward(params, cfg, nxt[:, None], lens[:, None], c, lens,
                       kernels=kernels)[0]

    return str(jax.make_jaxpr(step)(cache)), np.asarray(step(cache)[:, 0, :])


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("preset", ["mistral-tiny", "qwen-tiny"])
def test_model_decode_takes_dense_kernel(preset, quantized):
    """A mistral-like (uniform window 16, which the step at position 24 is
    past) and a qwen2-like (no window, qkv bias) configuration take
    ``kvmini_dense_decode`` in a T == 1 step, bf16 and int8 KV through the
    same gate, and give the logits of the eager path."""
    jaxpr_on, lg_on = _decode_step(preset, quantized, "interpret")
    jaxpr_off, lg_off = _decode_step(preset, quantized, "off")
    assert "kvmini_dense_decode" in jaxpr_on
    assert "kvmini_dense_decode" not in jaxpr_off
    # the two differ by where bf16 rounds (eager: the dequantized stripe
    # and the softmax weights; kernel: the weights only, int8 in f32)
    np.testing.assert_allclose(lg_on, lg_off, rtol=3e-2, atol=3e-2)
    np.testing.assert_array_equal(lg_on.argmax(-1), lg_off.argmax(-1))


@pytest.mark.parametrize("case", ["softcap+alt-window", "alt-window", "softcap"])
def test_model_decode_keeps_eager_path(case):
    """A softcap or a per-layer mask keeps the eager read: bit-equal
    logits with kernels on and off, no Pallas call in the step."""
    over = {
        "softcap+alt-window": {},
        "alt-window": {"attn_softcap": None},
        "softcap": {"alt_sliding_window": False},
    }[case]
    jaxpr_on, lg_on = _decode_step("gemma-tiny", False, "interpret", **over)
    _, lg_off = _decode_step("gemma-tiny", False, "off", **over)
    assert "kvmini_dense_decode" not in jaxpr_on
    np.testing.assert_array_equal(lg_on, lg_off)


def _serve_greedy(kernels):
    """A 4-slot mistral-tiny engine (window 16, 128-wide heads, 1024
    positions = two key blocks a slot) after serving three staggered greedy
    requests, and their tokens. In f32: a random-init model's near-ties
    flip on where bf16 rounds (the kernel and the eager read differ there),
    and the test is about which keys are read, not about rounding."""
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import init_params
    from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    cfg = get_config("mistral-tiny", max_seq_len=1024).scaled(
        explicit_head_dim=128, dtype="float32")
    tok = ByteTokenizer()
    engine = Engine(
        init_params(jax.random.PRNGKey(0), cfg), cfg,
        EngineConfig(max_slots=4, max_seq_len=1024, max_prefill_len=64,
                     min_prefill_bucket=16),
        pad_id=tok.pad_id,
    )
    # what a TPU backend makes of the engine's "auto": the interpreter
    # stands in for the compiled kernel (nothing in the program picks it)
    engine._kernels = kernels
    engine.start()
    try:
        handles = [
            engine.submit(GenRequest(prompt_tokens=tok.encode(p), max_new_tokens=n))
            for p, n in (("hello there", 30), ("the quick brown fox jumps", 12),
                         ("a", 20))
        ]
        outs = []
        for h in handles:
            toks = []
            while True:
                kind, *rest = h.events.get(timeout=300)
                if kind != "token":
                    break
                toks.append(rest[0])
            outs.append(toks)
    finally:
        engine.stop()
    return outs, engine


def test_engine_greedy_tokens_and_kv_block_counters(monkeypatch):
    """Greedy streams are identical with the kernel on and off; the two
    counters advance by the kernel's block rule over the lengths each
    decode step was dispatched with (free slots parked on row 0), and
    stay 0 where the eager path runs."""
    from kserve_vllm_mini_tpu.runtime.engine import Engine

    outs_off, eager = _serve_greedy("off")
    assert eager.stats["decode_kv_blocks"] == eager.stats["decode_kv_blocks_fetched"] == 0

    seen = []  # (lengths, steps) of every decode dispatch
    count = Engine._count_decode_kv_blocks

    def spy(self, lengths, steps):
        seen.append((np.array(lengths), steps))
        count(self, lengths, steps)

    monkeypatch.setattr(Engine, "_count_decode_kv_blocks", spy)
    outs_on, engine = _serve_greedy("interpret")
    assert outs_on == outs_off
    assert [len(o) for o in outs_on] == [30, 12, 20]

    blk = engine._dense_decode_blk
    assert eager._dense_decode_blk is None and blk == 512
    nb, W = 1024 // blk, engine.cfg.sliding_window
    assert nb >= 2
    fetched = total = 0
    for lengths, steps in seen:
        for t in range(steps):
            hi = np.minimum(lengths + t, 1023)
            lo = np.maximum(hi - W + 1, 0)
            fetched += int((hi // blk - lo // blk + 1).sum())
            total += 4 * nb
    assert seen and engine.stats["decode_kv_blocks"] == total > 0
    assert engine.stats["decode_kv_blocks_fetched"] == fetched
    assert 0 < fetched < total
    # every slot is free again and parked on row 0, not on its stale length
    assert engine._slot_len == [0, 0, 0, 0]

"""Pallas paged-attention decode kernel (ops/paged_attention.py): the
table-driven block-DMA kernel must match the gather+masked-attention
oracle in interpret mode, across lengths that start, split, and fill
blocks, for MHA and GQA, and through the model's paged decode path
(models/llama.py forward ``kernels="interpret"``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.ops.attention import attention
from kserve_vllm_mini_tpu.ops.paged_attention import paged_decode_attention

pytestmark = pytest.mark.slow


def _oracle(q, kp, vp, table, qpos):
    S, KVH, G, D = q.shape
    MAXB, BLK = table.shape[1], kp.shape[2]
    kg = kp[table].transpose(0, 2, 1, 3, 4).reshape(S, KVH, MAXB * BLK, D)
    vg = vp[table].transpose(0, 2, 1, 3, 4).reshape(S, KVH, MAXB * BLK, D)
    qh = q.reshape(S, KVH * G, 1, D)
    mask = (
        jnp.arange(MAXB * BLK)[None, None, None, :]
        <= qpos[:, None, None, None]
    )
    return attention(qh, kg, vg, mask).reshape(S, KVH, G, D)


def _case(seed, S, KVH, G, D, BLK, MAXB, P, qpos):
    rng = np.random.default_rng(seed)
    kp = jnp.asarray(rng.normal(size=(P, KVH, BLK, D)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(P, KVH, BLK, D)).astype(np.float32))
    # scattered, per-row-unique block ids
    table = jnp.asarray(
        rng.permutation(P)[: S * MAXB].reshape(S, MAXB), jnp.int32
    )
    q = jnp.asarray(rng.normal(size=(S, KVH, G, D)).astype(np.float32))
    qpos = jnp.asarray(qpos, jnp.int32)
    out = paged_decode_attention(q, kp, vp, table, qpos, interpret=True)
    ref = _oracle(q, kp, vp, table, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_matches_oracle_gqa():
    # positions: inside block 0, mid-block, last valid position
    _case(0, S=3, KVH=2, G=4, D=32, BLK=8, MAXB=6, P=20, qpos=[5, 23, 47])


def test_kernel_matches_oracle_mha():
    _case(1, S=2, KVH=4, G=1, D=16, BLK=16, MAXB=4, P=12, qpos=[0, 63])


def test_kernel_block_boundaries():
    # qpos exactly at block edges: last of a block, first of the next
    _case(2, S=4, KVH=1, G=2, D=32, BLK=8, MAXB=4, P=20, qpos=[7, 8, 15, 16])


def test_kernel_ignores_dead_table_entries():
    """Blocks past the live length may point ANYWHERE (scratch ids, stale
    ids, out-of-range ids get clamped) — they must not affect the output."""
    rng = np.random.default_rng(3)
    S, KVH, G, D, BLK, MAXB, P = 2, 2, 2, 32, 8, 4, 10
    kp = jnp.asarray(rng.normal(size=(P, KVH, BLK, D)).astype(np.float32))
    vp = jnp.asarray(rng.normal(size=(P, KVH, BLK, D)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(S, KVH, G, D)).astype(np.float32))
    qpos = jnp.asarray([5, 10], jnp.int32)  # live blocks: 1 and 2
    base = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    junk = jnp.asarray([[1, 999, -5, 0], [5, 6, 42, 999]], jnp.int32)
    out_base = paged_decode_attention(q, kp, vp, base, qpos, interpret=True)
    out_junk = paged_decode_attention(q, kp, vp, junk, qpos, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_base), np.asarray(out_junk))


def test_kernel_int8_kv_dequant():
    """int8-KV mode: the kernel's in-kernel dequant (scales folded into
    the [G, BLK] intermediates) must match dequantize-then-attend."""
    rng = np.random.default_rng(4)
    S, KVH, G, D, BLK, MAXB, P = 2, 2, 2, 32, 8, 4, 12
    kq = jnp.asarray(rng.integers(-127, 128, size=(P, KVH, BLK, D)), jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, size=(P, KVH, BLK, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.1, size=(P, KVH, BLK)).astype(np.float32))
    vs = jnp.asarray(rng.uniform(0.01, 0.1, size=(P, KVH, BLK)).astype(np.float32))
    table = jnp.asarray(rng.permutation(P)[: S * MAXB].reshape(S, MAXB), jnp.int32)
    q = jnp.asarray(rng.normal(size=(S, KVH, G, D)).astype(np.float32))
    qpos = jnp.asarray([10, 27], jnp.int32)

    out = paged_decode_attention(q, kq, vq, table, qpos,
                                 k_scale=ks, v_scale=vs, interpret=True)
    kf = kq.astype(jnp.float32) * ks[..., None]
    vf = vq.astype(jnp.float32) * vs[..., None]
    ref = _oracle(q, kf, vf, table, qpos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_model_paged_decode_path_int8_kv():
    """The kernel through the model's paged int8-KV decode path agrees
    with the gather path (which dequantizes to model dtype on read)."""
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import (
        forward,
        init_paged_kv_cache,
        init_params,
    )

    cfg = get_config("llama-tiny", max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, T, BLK = 2, 16, 8
    table = jnp.asarray(
        [[3, 17, 5, 9, 11, 2, 16, 19], [7, 0, 14, 6, 12, 8, 13, 1]], jnp.int32
    )
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)

    def step(kernels):
        pool = init_paged_kv_cache(cfg, 20, BLK, quantized=True)
        # the prefill stays on the jnp path in both runs: only the decode
        # step's kernel is under test
        lg, pool = forward(params, cfg, toks, pos, pool, zero,
                           fresh_prefill=True, block_table=table,
                           kernels="off")
        nxt = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
        lens = jnp.full((B,), T, jnp.int32)
        lg2, _ = forward(params, cfg, nxt[:, None], lens[:, None], pool, lens,
                         block_table=table, kernels=kernels)
        return np.asarray(lg2[:, 0, :])

    gather = step("off")
    kernel = step("interpret")
    # gather dequantizes in model dtype, the kernel in f32 — bf16-level drift
    np.testing.assert_allclose(kernel, gather, rtol=3e-2, atol=3e-2)


def test_model_paged_decode_path_uses_kernel():
    """Run the kernel through the model's paged decode path and check
    the logits agree with the gather path within kernel tolerance."""
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import (
        forward,
        init_paged_kv_cache,
        init_params,
    )

    cfg = get_config("llama-tiny", max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    B, T, BLK = 2, 16, 8
    table = jnp.asarray(
        [[3, 17, 5, 9, 11, 2, 16, 19], [7, 0, 14, 6, 12, 8, 13, 1]], jnp.int32
    )
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T)).astype(jnp.int32)
    zero = jnp.zeros((B,), jnp.int32)

    def prefill_and_step(kernels):
        pool = init_paged_kv_cache(cfg, 20, BLK)
        lg, pool = forward(params, cfg, toks, pos, pool, zero,
                           fresh_prefill=True, block_table=table,
                           kernels="off")
        nxt = jnp.argmax(lg[:, -1, :], -1).astype(jnp.int32)
        lens = jnp.full((B,), T, jnp.int32)
        lg2, _ = forward(params, cfg, nxt[:, None], lens[:, None], pool, lens,
                         block_table=table, kernels=kernels)
        return np.asarray(lg2[:, 0, :])

    gather = prefill_and_step("off")
    kernel = prefill_and_step("interpret")
    # the model runs bf16: two summation orders differ at bf16 rounding
    np.testing.assert_allclose(kernel, gather, rtol=3e-2, atol=3e-2)
    # and the distributions agree where it matters: same top token
    np.testing.assert_array_equal(gather.argmax(-1), kernel.argmax(-1))

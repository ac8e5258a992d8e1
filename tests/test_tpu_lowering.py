"""Every Pallas kernel must LOWER for TPU, checked on the CPU box.

Interpret-mode tests (test_flash_attention.py, test_paged_kernel.py,
test_qmatmul.py) skip the Pallas TPU lowering rules entirely, so a
BlockSpec the TPU lowering refuses — e.g. a (1, BLK) scale block over
[KVH, BLK], neither tile-divisible nor the full dims — passes them and
then cannot trace on a chip. ``jax.export`` with ``platforms=["tpu"]`` runs
those rules (no libtpu, no device, seconds). It stops at the Mosaic module:
whether Mosaic COMPILES it is chip_smoke.py's kernels leg.

Shapes: KVH 8 / G 4 (the GQA grouping of the 7B/8B presets), head_dim 128
and 64, the engine's default blocks (kv_block_size 64, dense stripe 512).
"""

import jax
import jax.numpy as jnp
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
    slots, seq = 8, 2048

    def fn(q, qpos, layer, k, v, ks=None, vs=None):
        return dense_decode_attention(q, k, v, qpos, layer=layer,
                                      k_scale=ks, v_scale=vs)

    _lowers_for_tpu(
        fn, _sds((slots, KVH, G, d), BF), _sds((slots,), I32), _sds((), I32),
        *_kv((L, slots, KVH, seq, d), quantized),
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

"""``kvmini_linear_decode`` (ops/linear_decode.py): the delta rule's decode
update with a slot's heads held in VMEM, in place in the cache's layer stack,
against ``models/linear_attention.py recurrent_step`` in interpret mode: at
the tiny preset's head sizes (24 x 48) and the published ones (96 x 192), for
every layer of a stack, every head block the rule can give, rows that must
not move, and through the model's decode step (``forward``
``kernels="interpret"``). Whether the call LOWERS for a TPU is
test_tpu_lowering.py's; whether Mosaic compiles it in place,
test_weight_copy_preflight.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.linear_attention import (
    linear_decode_kernel_block,
    recurrent_step,
)
from kserve_vllm_mini_tpu.models.llama import forward, init_kv_cache, init_params
from kserve_vllm_mini_tpu.ops.linear_decode import (
    linear_decode_block,
    linear_decode_update,
    state_tile_vmem_bytes,
)

F32 = jnp.float32
SIZES = {"tiny": (4, 24, 48), "published": (3, 96, 192)}   # heads, dk, dv


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


def step_inputs(seed, steps, slots, heads, dk, dv):
    """``steps`` positions of mixer inputs as ``linear_mixer`` makes them:
    unit keys, queries scaled by dk^-1/2, log decays <= 0, beta in [0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)   # noqa: E731
    q = unit(jax.random.normal(ks[0], (steps, slots, heads, dk), F32)) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (steps, slots, heads, dk), F32))
    v = jax.random.normal(ks[2], (steps, slots, heads, dv), F32)
    g = -jax.nn.softplus(jax.random.normal(ks[3], (steps, slots, heads), F32))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (steps, slots, heads), F32))
    return q, k, v, g, beta


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_sixty_four_steps_on_one_state_are_the_recurrence(size, layer):
    """64 consecutive decode steps on layer ``layer`` of a 3-layer stack:
    state and outputs within 1e-6 (relative L2) of ``recurrent_step``'s, the
    two other layers' state bit for bit what it was."""
    heads, dk, dv = SIZES[size]
    slots, steps = 3, 64
    stack = jax.random.normal(jax.random.PRNGKey(layer), (3, slots, heads, dk, dv), F32)
    xs = step_inputs(10 + layer, steps, slots, heads, dk, dv)
    valid = jnp.ones((slots,), jnp.int32)

    @jax.jit
    def kernel_run(stack):
        return jax.lax.scan(
            lambda s, x: linear_decode_update(s, jnp.int32(layer), *x, valid, interpret=True),
            stack, xs)

    @jax.jit
    def oracle_run(state):
        return jax.lax.scan(lambda s, x: recurrent_step(s, *x), state, xs)

    got_stack, got_o = kernel_run(stack)
    want_state, want_o = oracle_run(stack[layer])
    assert rel_l2(got_stack[layer], want_state) <= 1e-6
    assert rel_l2(got_o, want_o) <= 1e-6
    for other in {0, 1, 2} - {layer}:
        assert np.array_equal(bits(got_stack[other]), bits(stack[other]))


@pytest.mark.parametrize("size", sorted(SIZES))
def test_rows_with_valid_zero_keep_their_state_bit_for_bit(size):
    """A parked row's tile comes back through the kernel's select, not
    through ``S * 1 + k * 0``: that sum turns a ``-0.0`` of the state into
    ``+0.0`` (and a TPU flushes a denormal), so the row holds both, and its
    ``g`` and ``beta`` are NOT zeroed here as ``linear_mixer`` zeroes them."""
    heads, dk, dv = SIZES[size]
    stack = jax.random.normal(jax.random.PRNGKey(3), (2, 3, heads, dk, dv), F32)
    stack = stack.at[1, 1, :, 0, :4].set(jnp.asarray([-0.0, 1e-40, -1e-40, 0.0], F32))
    q, k, v, g, beta = (x[0] for x in step_inputs(4, 1, 3, heads, dk, dv))
    valid = jnp.asarray([1, 0, 2], jnp.int32)
    got, _ = linear_decode_update(stack, 1, q, k, v, g, beta, valid, interpret=True)
    assert np.array_equal(bits(got[1, 1]), bits(stack[1, 1]))
    want, _ = recurrent_step(stack[1], q, k, v, g, beta)
    for row in (0, 2):
        assert rel_l2(got[1, row], want[row]) <= 1e-6
        assert not np.array_equal(bits(got[1, row]), bits(stack[1, row]))
    assert np.array_equal(bits(got[0]), bits(stack[0]))


# every value the block rule gives over these shapes: the divisors of the
# head count, largest that fits first
BLOCKS = [(30, 96, 192, 15), (3, 96, 192, 3), (4, 24, 48, 4), (32, 128, 128, 32),
          (64, 128, 256, 16), (7, 128, 1024, 1), (6, 96, 192, 6)]


@pytest.mark.parametrize("heads,dk,dv,want", BLOCKS)
def test_block_rule_is_the_largest_divisor_that_fits(heads, dk, dv, want):
    assert linear_decode_block(heads, dk, dv, F32) == want
    assert state_tile_vmem_bytes(want, dk, dv) <= 8 << 20
    bigger = [c for c in range(want + 1, heads + 1) if heads % c == 0]
    assert all(state_tile_vmem_bytes(c, dk, dv) > 8 << 20 for c in bigger)


@pytest.mark.parametrize("heads_block", [1, 2, 3, 6])
def test_every_head_block_gives_the_same_update(heads_block):
    """6 heads of 96 x 192 a slot, held 1, 2, 3 or 6 a grid step: the head a
    row or a lane belongs to follows the block's place in the slot."""
    heads, dk, dv = 6, 96, 192
    stack = jax.random.normal(jax.random.PRNGKey(5), (2, 2, heads, dk, dv), F32)
    q, k, v, g, beta = (x[0] for x in step_inputs(6, 1, 2, heads, dk, dv))
    got, got_o = linear_decode_update(
        stack, 0, q, k, v, g, beta, jnp.ones((2,), jnp.int32),
        heads_block=heads_block, interpret=True)
    want, want_o = recurrent_step(stack[0], q, k, v, g, beta)
    assert rel_l2(got[0], want) <= 1e-6 and rel_l2(got_o, want_o) <= 1e-6
    assert np.array_equal(bits(got[1]), bits(stack[1]))


def test_the_gate_reads_mode_length_dtype_and_tile():
    stack = jax.ShapeDtypeStruct((6, 3, 4, 24, 48), F32)
    assert linear_decode_kernel_block("interpret", stack, 1) == 4
    assert linear_decode_kernel_block("off", stack, 1) is None
    assert linear_decode_kernel_block("auto", stack, 1) is None      # a CPU here
    assert linear_decode_kernel_block("interpret", stack, 2) is None
    bf16 = jax.ShapeDtypeStruct(stack.shape, jnp.bfloat16)
    assert linear_decode_kernel_block("interpret", bf16, 1) is None
    odd = jax.ShapeDtypeStruct((6, 3, 4, 20, 48), F32)               # 2.5 sublane tiles
    assert linear_decode_kernel_block("interpret", odd, 1) is None
    with pytest.raises(ValueError, match="no head block"):
        linear_decode_update(jnp.zeros(bf16.shape, jnp.bfloat16), 0, *(
            x[0] for x in step_inputs(0, 1, 3, 4, 24, 48)), jnp.ones((3,), jnp.int32),
            interpret=True)


def _model_step(cfg, kernels, t):
    params = init_params(jax.random.PRNGKey(7), cfg)
    cache = init_kv_cache(cfg, 3, max_seq=64)
    cache["lin_state"] = jax.random.normal(
        jax.random.PRNGKey(8), cache["lin_state"].shape, cache["lin_state"].dtype)
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 500, (3, t)), jnp.int32)
    off = jnp.asarray([5, 9, 0], jnp.int32)
    valid = jnp.asarray([t, t, 0], jnp.int32)
    fn = jax.jit(lambda p, c: forward(
        p, cfg, toks, off[:, None] + jnp.arange(t)[None], c, off,
        kernels=kernels, valid_len=valid))
    return fn, params, cache


@pytest.mark.parametrize("case", ["decode", "off", "two-positions"])
def test_the_models_decode_step_takes_the_kernel_and_only_it(case):
    """``forward`` over the tiny hybrid: a one-token step under
    ``kernels="interpret"`` holds one ``kvmini_linear_decode`` a linear
    layer of the period and gives the jnp path's logits and state (a parked
    row's bit for bit); ``kernels="off"`` and a two-position block hold none
    and run ``recurrent_step`` / ``chunked_scan`` (a bfloat16 state, which
    no program keeps, is turned away at the gate: the test above)."""
    cfg = get_config("olmo-hybrid-tiny", dtype="float32")
    kernels, t = ("off", 1) if case == "off" else ("interpret", 2 if case == "two-positions" else 1)
    fn, params, cache = _model_step(cfg, kernels, t)
    calls = str(jax.make_jaxpr(fn)(params, cache)).count("kvmini_linear_decode")
    if case != "decode":
        assert calls == 0
        return
    assert calls == cfg.layer_period - 1
    logits, new = fn(params, cache)
    want_fn, _, _ = _model_step(cfg, "off", t)
    want_logits, want = want_fn(params, cache)
    assert rel_l2(logits[:2], want_logits[:2]) <= 1e-5
    assert rel_l2(new["lin_state"][:, :2], want["lin_state"][:, :2]) <= 1e-6
    assert np.array_equal(bits(new["lin_state"][:, 2]), bits(cache["lin_state"][:, 2]))
    assert np.array_equal(bits(new["lin_conv"][:, 2].astype(F32)),
                          bits(cache["lin_conv"][:, 2].astype(F32)))

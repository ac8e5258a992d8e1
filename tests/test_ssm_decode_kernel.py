"""``kvmini_ssm_decode`` (ops/ssm_decode.py): the Mamba-2 decode update with a
slot's heads held in VMEM, in place in the cache's layer stack, against
``models/mamba.py recurrent_step`` in interpret mode: at both benchmark cells'
tile shapes (64 x 128 over 8 groups; 128 x 256 over 2; cut in slots and heads
only), for every layer of a stack, head blocks other than the rule's, rows
that must not move, the gate's refusals, and through the model's decode step
and the engine (``kernels="interpret"``). Whether the call LOWERS for a TPU is
test_tpu_lowering.py's; whether Mosaic compiles it in place,
test_weight_copy_preflight.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_kv_cache, init_params
from kserve_vllm_mini_tpu.models.mamba import recurrent_step, ssm_decode_kernel_block
from kserve_vllm_mini_tpu.ops.linear_decode import state_tile_vmem_bytes
from kserve_vllm_mini_tpu.ops.ssm_decode import ssm_decode_block, ssm_decode_update
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig
from tests.test_hybrid_engine import run_requests

F32 = jnp.float32
# heads, head_dim P, state N, groups: the two cells' tiles, 16 heads a group as published
SIZES = {"nemotron": (32, 64, 128, 2), "falcon": (16, 128, 256, 1), "falcon-2g": (4, 128, 256, 2)}


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def bits(a) -> np.ndarray:
    return np.asarray(a).view(np.int32)


def step_inputs(seed, steps, slots, heads, p, n, groups):
    """``steps`` positions of mixer inputs as ``mamba_mixer`` makes them:
    x, B, C after the convolution's SiLU, dt after softplus, A negative."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.nn.silu(jax.random.normal(ks[0], (steps, slots, heads, p), F32))
    b = jax.nn.silu(jax.random.normal(ks[1], (steps, slots, groups, n), F32))
    c = jax.nn.silu(jax.random.normal(ks[2], (steps, slots, groups, n), F32))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (steps, slots, heads), F32) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[4], (heads,), F32, 0.0, 2.0))
    return (x, b, c, dt), a


def oracle(state, x, b, c, dt, a):
    per = x.shape[1] // b.shape[1]
    return recurrent_step(state, x, jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1), dt, a)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("size", sorted(SIZES))
def test_consecutive_steps_on_one_state_are_the_recurrence(size, layer):
    """16 consecutive decode steps on layer ``layer`` of a 3-layer stack:
    state and outputs within 1e-6 (relative L2) of ``recurrent_step``'s, the
    two other layers' state bit for bit what it was."""
    heads, p, n, groups = SIZES[size]
    slots, steps = 2, 16
    stack = jax.random.normal(jax.random.PRNGKey(layer), (3, slots, heads, p, n), F32)
    xs, a = step_inputs(10 + layer, steps, slots, heads, p, n, groups)
    valid = jnp.ones((slots,), jnp.int32)

    @jax.jit
    def kernel_run(stack):
        return jax.lax.scan(
            lambda s, x: ssm_decode_update(s, jnp.int32(layer), *x, a, valid, interpret=True),
            stack, xs)

    @jax.jit
    def oracle_run(state):
        return jax.lax.scan(lambda s, x: oracle(s, *x, a), state, xs)

    got_stack, got_y = kernel_run(stack)
    want_state, want_y = oracle_run(stack[layer])
    assert rel_l2(got_stack[layer], want_state) <= 1e-6
    assert rel_l2(got_y, want_y) <= 1e-6
    for other in {0, 1, 2} - {layer}:
        assert np.array_equal(bits(got_stack[other]), bits(stack[other]))


@pytest.mark.parametrize("size", sorted(SIZES))
def test_rows_with_valid_zero_keep_their_state_bit_for_bit(size):
    """A parked row's tile comes back through the kernel's select, not
    through ``S * 1 + 0``: that sum turns a ``-0.0`` of the state into
    ``+0.0`` (and a TPU flushes a denormal), so the row holds both, and its
    ``dt`` is NOT zeroed here as ``mamba_mixer`` zeroes it."""
    heads, p, n, groups = SIZES[size]
    stack = jax.random.normal(jax.random.PRNGKey(3), (2, 3, heads, p, n), F32)
    stack = stack.at[1, 1, :, 0, :4].set(jnp.asarray([-0.0, 1e-40, -1e-40, 0.0], F32))
    xs, a = step_inputs(4, 1, 3, heads, p, n, groups)
    xs = tuple(v[0] for v in xs)
    valid = jnp.asarray([1, 0, 2], jnp.int32)
    got, _ = ssm_decode_update(stack, 1, *xs, a, valid, interpret=True)
    assert np.array_equal(bits(got[1, 1]), bits(stack[1, 1]))
    want, _ = oracle(stack[1], *xs, a)
    for row in (0, 2):
        assert rel_l2(got[1, row], want[row]) <= 1e-6
        assert not np.array_equal(bits(got[1, row]), bits(stack[1, row]))
    assert np.array_equal(bits(got[0]), bits(stack[0]))


# the block rule over these shapes: the largest divisor of the head count whose
# pipelined tiles fit, None where the tile is not whole lane and sublane tiles
BLOCKS = [(128, 64, 128, 64), (32, 128, 256, 16), (8, 16, 128, 8), (24, 64, 256, 24),
          (7, 128, 1024, 1), (8, 16, 16, None), (32, 128, 192, None), (8, 20, 128, None)]


@pytest.mark.parametrize("heads,p,n,want", BLOCKS)
def test_block_rule_is_the_largest_divisor_that_fits_whole_tiles(heads, p, n, want):
    assert ssm_decode_block(heads, p, n, F32) == want
    assert ssm_decode_block(heads, p, n, jnp.bfloat16) is None
    if want is not None:
        assert state_tile_vmem_bytes(want, p, n) <= 8 << 20
        bigger = [c for c in range(want + 1, heads + 1) if heads % c == 0]
        assert all(state_tile_vmem_bytes(c, p, n) > 8 << 20 for c in bigger)


@pytest.mark.parametrize("size,heads_block", [("nemotron", 1), ("nemotron", 8), ("nemotron", 16),
                                              ("falcon", 2), ("falcon", 8), ("falcon-2g", 1)])
def test_every_head_block_gives_the_same_update(size, heads_block):
    """A slot's heads held 1 to 16 a grid step, a block inside one group,
    one group or two: the lane a head's column lies in and the group's row
    it reads follow its place in the slot, not in the block."""
    heads, p, n, groups = SIZES[size]
    stack = jax.random.normal(jax.random.PRNGKey(5), (2, 2, heads, p, n), F32)
    xs, a = step_inputs(6, 1, 2, heads, p, n, groups)
    xs = tuple(v[0] for v in xs)
    got, got_y = ssm_decode_update(
        stack, 0, *xs, a, jnp.ones((2,), jnp.int32), heads_block=heads_block, interpret=True)
    want, want_y = oracle(stack[0], *xs, a)
    assert rel_l2(got[0], want) <= 1e-6 and rel_l2(got_y, want_y) <= 1e-6
    assert np.array_equal(bits(got[1]), bits(stack[1]))


REFUSALS = {
    "off": ("off", (6, 3, 8, 16, 128), F32, 1),
    "auto-on-a-cpu": ("auto", (6, 3, 8, 16, 128), F32, 1),
    "two-positions": ("interpret", (6, 3, 8, 16, 128), F32, 2),
    "bfloat16-state": ("interpret", (6, 3, 8, 16, 128), jnp.bfloat16, 1),
    "half-a-lane-tile": ("interpret", (6, 3, 8, 16, 192), F32, 1),
    "tiny-preset-state": ("interpret", (6, 3, 8, 16, 16), F32, 1),
    "half-a-sublane-tile": ("interpret", (6, 3, 8, 20, 128), F32, 1),
}


@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_the_gate_reads_mode_length_dtype_and_tile(why):
    kernels, shape, dtype, t = REFUSALS[why]
    assert ssm_decode_kernel_block("interpret", jax.ShapeDtypeStruct((6, 3, 8, 16, 128), F32), 1) == 8
    assert ssm_decode_kernel_block(kernels, jax.ShapeDtypeStruct(shape, dtype), t) is None
    if kernels == "interpret" and t == 1:   # a tile the kernel itself turns away
        heads, p, n = shape[2:]
        xs, a = step_inputs(0, 1, 3, heads, p, n, 2)
        with pytest.raises(ValueError, match="no head block"):
            ssm_decode_update(jnp.zeros(shape, dtype), 0, *(v[0] for v in xs), a,
                              jnp.ones((3,), jnp.int32), interpret=True)


# the tiny presets keep a 16- or 32-wide state, which the gate turns away: a
# state of one whole lane tile under 16-row heads takes the kernel ("M*EME": all
# three kinds of single-mixer block; "PPP": a state-space mixer and attention
# side by side in every block)
STACKS = {"single-mixer": ("nemotron-h-tiny", "M*EME"), "two-mixer": ("falcon-h1-tiny", "PPP")}


def _stack_cfg(stack, state=128):
    preset, pattern = STACKS[stack]
    return get_config(preset, dtype="float32").scaled(
        hybrid_override_pattern=pattern, ssm_state_size=state, mamba_head_dim=16)


def _model_step(cfg, kernels, t):
    params = init_params(jax.random.PRNGKey(7), cfg)
    cache = init_kv_cache(cfg, 3, max_seq=64)
    cache["ssm_state"] = jax.random.normal(
        jax.random.PRNGKey(8), cache["ssm_state"].shape, cache["ssm_state"].dtype)
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 500, (3, t)), jnp.int32)
    off = jnp.asarray([5, 9, 0], jnp.int32)
    valid = jnp.asarray([t, t, 0], jnp.int32)
    fn = jax.jit(lambda p, c: forward(
        p, cfg, toks, off[:, None] + jnp.arange(t)[None], c, off,
        kernels=kernels, valid_len=valid))
    return fn, params, cache


@pytest.mark.parametrize("case", ["decode", "off", "two-positions", "narrow-state"])
@pytest.mark.parametrize("stack", sorted(STACKS))
def test_the_models_decode_step_takes_the_kernel_and_only_it(stack, case):
    """``forward`` over both pattern stacks: a one-token step under
    ``kernels="interpret"`` holds one ``kvmini_ssm_decode`` a state-space
    mixer and gives the jnp path's logits, state and tails (a parked row's
    bit for bit); ``kernels="off"``, a two-position block and the
    presets' own narrow state hold none and run ``recurrent_step`` /
    ``chunked_scan``."""
    cfg = _stack_cfg(stack, state=32 if case == "narrow-state" else 128)
    kernels, t = ("off", 1) if case == "off" else ("interpret", 2 if case == "two-positions" else 1)
    fn, params, cache = _model_step(cfg, kernels, t)
    text = str(jax.make_jaxpr(fn)(params, cache))
    if case != "decode":
        assert "kvmini_ssm_decode" not in text
        return
    # the call sites share one traced body (the layer index is an operand):
    # the kernel is printed once or twice, its jitted wrapper once a call
    assert "kvmini_ssm_decode" in text
    assert text.count("name=ssm_decode_update") == cfg.n_ssm_layers
    logits, new = fn(params, cache)
    want_fn, _, _ = _model_step(cfg, "off", t)
    want_logits, want = want_fn(params, cache)
    assert rel_l2(logits[:2], want_logits[:2]) <= 1e-5
    assert rel_l2(new["ssm_state"][:, :2], want["ssm_state"][:, :2]) <= 1e-6
    assert np.array_equal(bits(new["ssm_state"][:, 2]), bits(cache["ssm_state"][:, 2]))
    assert rel_l2(new["ssm_conv"][:, :2], want["ssm_conv"][:, :2]) <= 1e-5
    assert np.array_equal(bits(new["ssm_conv"][:, 2].astype(F32)),
                          bits(cache["ssm_conv"][:, 2].astype(F32)))


@jax.jit
def _padded_forward(params, toks, n):
    return forward(params, _stack_cfg("two-mixer"), toks, jnp.arange(toks.shape[1])[None],
                   valid_len=n)[0]


@pytest.mark.parametrize("ecfg", [{"prefill_chunk": 32}, {"decode_chunk": 4}],
                         ids=["pieces32", "fused4"])
def test_engine_streams_with_the_state_updated_by_the_decode_kernel(ecfg):
    """Six requests on four slots of the two-mixer stack with the engine's
    decode steps through ``kvmini_ssm_decode`` (interpreted: what a TPU
    backend makes of the engine's "auto"; nothing in the program picks the
    interpreter). The state is updated inside the cache's stack, parked and
    mid-prefill rows held by the kernel's select: every stream is still the
    cache-free forward's, and the gauge says which program ran."""
    cfg = _stack_cfg("two-mixer")
    params = init_params(jax.random.PRNGKey(0), cfg)

    def make():
        return Engine(params, cfg, EngineConfig(
            max_slots=4, max_seq_len=256, max_prefill_len=128, **ecfg))

    eng = make()
    eng._kernels = "interpret"
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (70, 23, 100, 45, 17, 90)]
    try:
        outs = run_requests(eng, prompts)
    finally:
        eng.stop()
    for prompt, out in zip(prompts, outs):
        seq = prompt + out
        toks = jnp.asarray([seq + [0] * (128 - len(seq))], jnp.int32)
        lg = _padded_forward(params, toks, jnp.asarray([len(seq)], jnp.int32))
        assert out == [int(jnp.argmax(lg[0, i])) for i in range(len(prompt) - 1, len(seq) - 1)]
    s = eng.snapshot_stats()
    assert s["ssm_state_kernel"] == 1 and s["linear_state_kernel"] == 0
    assert 0 < s["linear_state_held_slot_steps"] < s["linear_state_slot_steps"]
    eager = make()
    eager.start()
    eager.stop()
    assert eager.snapshot_stats()["ssm_state_kernel"] == 0       # "auto" on a CPU

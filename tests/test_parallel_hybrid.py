"""A stack of two-mixer blocks (a Mamba-2 mixer and rotary attention side by
side on one normed input, then an MLP, with fixed muP multipliers): the program
against the benchmark's plain reference, logits not tokens, at tiny widths on
the CPU; what must hold of a cache that keeps K/V, a state and a tail for
EVERY layer; and what the comparison must see, every multiplier among it."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import config as bench_config
from kserve_vllm_mini_tpu.models import mamba
from kserve_vllm_mini_tpu.models.config import ModelConfig, get_config
from kserve_vllm_mini_tpu.models.llama import (
    forward,
    init_kv_cache,
    init_paged_kv_cache,
    init_params,
    init_params_quantized,
    slice_cache_slots,
    update_cache_slots,
)
from kserve_vllm_mini_tpu.ops.quant import is_quantized, quantize_params
# one padded prefill (or pieces of it) then token-by-token decode through the
# cache, with a jitted step of its own where a test patches the program
from tests.test_hybrid import make_step, prefill_then_decode

reference = bench_config.load_module("references", "parallel_hybrid_decoder")
system = bench_config.load_module("systems", "engine")
CELL = "falcon-h1-34b.decode-sat"
BENCH_FILES = ("mistral-7b-int8", "qwen2-7b-int8", "olmo-hybrid-7b-int8",
               "nemotron-3-super-ep4-int8")
TINY = get_config("falcon-h1-tiny", dtype="float32")


def tiny_model(quantization="int8", **keys):
    """The benchmark's rehearsal sizes of the configuration (three blocks,
    hidden 128, attention 4 / 2 heads x 16, state 8 x 12 x 32 in 2 groups)
    with the published multipliers, and ``attention_in_multiplier`` moved off
    its published 1 so that leaving it out shows too. ``keys`` override."""
    m = bench_config.load_cell(CELL, rehearse=True)["config"]["model"]
    keys = {"attention_in_multiplier": 0.8, **keys}
    extra = {k: v for k, v in keys.items() if k in m["model_extra"]}
    return {**m, "quantization": quantization, **keys,
            "model_extra": {**m["model_extra"], **extra}}


def served_weights(seed, m):
    """The benchmark's weights as the program is handed them, the bfloat16
    leaves widened (the values are the same): the program runs in float32."""
    params = reference.weights.all_weights(seed, m)
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params)


def f32_config(m, **kw):
    return system.model_config({**m, "torch_dtype": "float32"}).scaled(**kw)


def a_sequence(m, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, m["vocab_size"], n)]


def off_by(got, want):
    """The widest difference of two sets of logits, as a share of the largest."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# Float32 on both sides. The sound program reads 5.6e-7 to 8.0e-7 of the largest
# logit (~0.95) off the reference over the eight shapes of the first test, up to
# 6.9e-7 in pieces and 9.1e-7 in a ragged batch (jax 0.9.0, CPU), so the tolerance is
# 1e-5 of it: eleven times the widest sound reading, the rounding of two float32
# programs that order their sums differently. It has to be this tight for the
# state's precision to show: a state rounded to bfloat16 after every update reads
# 3.3e-5 (its error averages out over 32 state columns and 12 channels a head), and
# every case of ``test_the_comparison_sees_what_it_must``
# (tests/test_parallel_hybrid_controls.py) has to read over SEEN =
# 2.5e-5, 2.5 times the tolerance. Readings: ``bf16_state`` 3.3e-5 (1.3 x SEEN),
# ``no_ssm_multiplier_dt`` 8.0e-4 (32 x), ``rope_theta_1e4`` 3.5e-3 and ``no_rope``
# 3.9e-3 (140 x), ``no_ssm_multiplier_C`` 9.4e-3, ``w8a8`` 2.5e-2 (1000 x),
# ``no_attention_in_multiplier`` 5.2e-2, ``no_ssm_multiplier_B`` 5.4e-2,
# ``mixers_sequential_not_parallel`` 0.16, ``attention_on_residual_not_normed_input``
# 0.19, ``no_key_multiplier`` 0.26, ``ssm_multipliers_z_x_swapped`` 0.35,
# ``no_ssm_multiplier_z`` 0.46, ``no_mlp_gate_multiplier`` 0.60,
# ``no_embedding_multiplier`` 0.65, ``no_ssm_out_multiplier`` 0.74, ``no_conv_bias``
# 0.86, ``pad_in_state`` and ``no_ssm_multiplier_x`` 0.99, ``no_ssm_in_multiplier``
# 1.03, ``norm_before_gate`` 1.17, ``no_attention_out_multiplier`` 1.27,
# ``no_mlp_down_multiplier`` 1.32, ``no_lm_head_multiplier`` 127.
TOLERANCE, SEEN = 1e-5, 2.5e-5


@pytest.mark.parametrize("quantization", ["int8", "none"])
@pytest.mark.parametrize("n_prompt,bucket", [(50, 64), (70, 128), (23, 32), (97, 128)])
def test_prefill_then_decode_against_the_references_full_forward(quantization, n_prompt, bucket):
    """One piece, prompt lengths that are no multiple of the chunk (32) or the bucket."""
    m = tiny_model(quantization)
    seq = a_sequence(m, n_prompt + 12, n_prompt)
    want = reference.logits_after(7, m, [seq], [1], 128, 64)[0]
    got, _ = prefill_then_decode(served_weights(7, m), f32_config(m), seq, n_prompt, bucket)
    assert got.shape[0] == len(seq)
    assert off_by(got[:-1], want) < TOLERANCE


@pytest.mark.parametrize("pieces", [[32, 32, 6], [64, 6], [17, 53], [1, 64, 5]])
def test_a_prompt_in_pieces_against_the_reference_and_the_prompt_at_once(pieces):
    """Several pieces, pieces that split a chunk, a piece of one token."""
    m = tiny_model()
    params, cfg = served_weights(3, m), f32_config(m)
    seq = a_sequence(m, 76, 1)
    want = reference.logits_after(3, m, [seq], [1], 128, 64)[0]
    whole, c1 = prefill_then_decode(params, cfg, seq, 70, 128)
    split, c2 = prefill_then_decode(params, cfg, seq, 70, 64, pieces=pieces)
    assert off_by(split[:-1], want) < TOLERANCE and off_by(split, whole) < TOLERANCE
    for leaf in ("ssm_state", "ssm_conv"):
        assert np.max(np.abs(c1[leaf] - c2[leaf])) < 1e-4
    for leaf in ("k", "v"):       # the 76 positions written; past them a bucket's padding differs
        assert np.max(np.abs(c1[leaf][:, :, :, :76] - c2[leaf][:, :, :, :76])) < 1e-4


def test_a_batch_with_ragged_valid_lengths_is_each_row_alone():
    """Three rows of one padded prefill, each told its own length, then one
    decode sweep in which the middle row is parked: every real position's
    logits are the reference's for that row's sequence alone."""
    m = tiny_model()
    params, cfg = served_weights(5, m), f32_config(m)
    lens = [64, 23, 41]
    seqs = [a_sequence(m, n + 2, 10 + i) for i, n in enumerate(lens)]
    want = reference.logits_after(5, m, seqs, [1, 1, 1], 128, 64)
    toks = jnp.asarray([s[:n] + [0] * (64 - n) for s, n in zip(seqs, lens)], jnp.int32)
    step = make_step()
    cache = init_kv_cache(cfg, 3, max_seq=256)
    lg, cache = step(params, cfg, toks, jnp.zeros((3,), jnp.int32), cache,
                     jnp.asarray(lens, jnp.int32), fresh=True)
    for row, n in enumerate(lens):
        assert off_by(np.asarray(lg[row, :n]), want[row][:n]) < TOLERANCE
    before = {k: np.asarray(v) for k, v in cache.items()}
    nxt = jnp.asarray([[s[n]] for s, n in zip(seqs, lens)], jnp.int32)
    lg, cache = step(params, cfg, nxt, jnp.asarray(lens, jnp.int32), cache,
                     jnp.asarray([1, 0, 1], jnp.int32))
    for row in (0, 2):
        assert off_by(np.asarray(lg[row, 0]), want[row][lens[row]]) < TOLERANCE
    for leaf in ("ssm_state", "ssm_conv"):
        assert np.array_equal(np.asarray(cache[leaf])[:, 1], before[leaf][:, 1])
        assert not np.array_equal(np.asarray(cache[leaf])[:, 0], before[leaf][:, 0])


def scan_inputs(t, seed=0, b=2, h=4, p=8, g=2, n=256):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.0, 0.3, (b, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    return f(b, h, p, n), f(b, t, h, p), f(b, t, g, n) / 4, f(b, t, g, n) / 4, dt, a


@pytest.mark.parametrize("t", [1, 7, 32, 33, 100, 256])
def test_chunked_scan_is_the_recurrence_at_state_256_in_two_groups(t):
    s0, x, b, c, dt, a = scan_inputs(t, seed=t)
    per = x.shape[2] // b.shape[2]
    s1, y1 = mamba.recurrent_scan(s0, x, jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2), dt, a)
    s2, y2 = jax.jit(partial(mamba.chunked_scan, chunk=32))(s0, x, b, c, dt, a)
    assert np.max(np.abs(s1 - s2)) < 1e-4 and np.max(np.abs(y1 - y2)) < 1e-4


def test_padded_rows_and_parked_slots_leave_kv_beyond_valid_state_and_tail():
    """A padded piece writes K/V for its pad positions (harmless: the mask
    never reaches them before they are overwritten) but never a state or a
    tail; a parked row of a decode sweep leaves all three where they were."""
    cfg = TINY
    params = init_params(jax.random.PRNGKey(0), cfg)
    step = make_step()
    cache = jax.tree_util.tree_map(lambda a: a + 2.5, init_kv_cache(cfg, 3, max_seq=128))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(3, 500, (3, 64)), jnp.int32)
    valid = jnp.asarray([0, 40, 64], jnp.int32)
    _, new = step(params, cfg, toks, jnp.zeros((3,), jnp.int32), cache, valid, fresh=True)
    # row 0: nothing real, so state and tail are what a fresh prefill starts from: zero
    for leaf in ("ssm_state", "ssm_conv"):
        assert not np.asarray(new[leaf][:, 0]).any()
    # positions past the piece keep what the stripe held
    assert np.array_equal(np.asarray(new["k"][:, :, :, 64:]), np.asarray(cache["k"][:, :, :, 64:]))
    # row 1's state is what its 40 real tokens alone give
    _, alone = step(params, cfg, toks[1:2, :40], jnp.zeros((1,), jnp.int32),
                    init_kv_cache(cfg, 1, max_seq=128), jnp.asarray([40], jnp.int32), fresh=True)
    assert np.max(np.abs(new["ssm_state"][:, 1] - alone["ssm_state"][:, 0])) < 1e-5
    assert np.max(np.abs(new["ssm_conv"][:, 1] - alone["ssm_conv"][:, 0])) < 1e-5
    # a decode sweep in which only row 2 is live
    before = {k: np.asarray(v) for k, v in new.items()}
    _, after = step(params, cfg, toks[:, :1], jnp.asarray([0, 40, 64], jnp.int32), new,
                    jnp.asarray([0, 0, 1], jnp.int32))
    for leaf in ("ssm_state", "ssm_conv"):
        assert np.array_equal(np.asarray(after[leaf])[:, :2], before[leaf][:, :2])
        assert not np.array_equal(np.asarray(after[leaf])[:, 2], before[leaf][:, 2])
    assert np.array_equal(np.asarray(after["k"])[:, 2, :, 65:], before["k"][:, 2, :, 65:])


def test_one_cache_tree_with_three_leaves_for_every_layer():
    cfg = TINY
    cache = init_kv_cache(cfg, 5, max_seq=64)
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((3, 5, 2, 64, 16), "float32"), "v": ((3, 5, 2, 64, 16), "float32"),
        "ssm_state": ((3, 5, 8, 12, 32), "float32"),
        "ssm_conv": ((3, 5, 3, 8 * 12 + 2 * 2 * 32), "float32"),
    }
    bf16 = init_kv_cache(get_config("falcon-h1-tiny"), 5, max_seq=64, quantized=True)
    assert bf16["ssm_state"].dtype == jnp.float32 and bf16["ssm_conv"].dtype == jnp.bfloat16
    assert bf16["k"].dtype == jnp.int8 and bf16["k_s"].shape == (3, 5, 2, 64)
    marked = {k: v.at[:, 3].set(1) for k, v in cache.items()}
    sub = slice_cache_slots(marked, 3)
    assert all(v.shape[1] == 1 and bool((v == 1).all()) for v in sub.values())
    back = update_cache_slots(cache, sub, 1)
    assert all(bool((v[:, 1] == 1).all()) and bool((v[:, 0] == 0).all()) for v in back.values())
    with pytest.raises(ValueError, match="no positions to page"):
        init_paged_kv_cache(cfg, 8)
    # the published widths: K/V [12, slots, 4, 2048, 128], state [12, slots, 32, 128, 256]
    m = bench_config.load_cell(CELL)["config"]["model"]
    big = jax.eval_shape(lambda: init_kv_cache(system.model_config(m), 16, max_seq=2048))
    assert {k: v.shape for k, v in big.items()} == {
        "k": (12, 16, 4, 2048, 128), "v": (12, 16, 4, 2048, 128),
        "ssm_state": (12, 16, 32, 128, 256), "ssm_conv": (12, 16, 3, 5120)}
    assert big["ssm_state"].dtype == jnp.float32 and big["k"].dtype == jnp.bfloat16


# -- configuration and trees -----------------------------------------------------

def test_config_counts_the_published_model_and_the_cut():
    full = get_config("falcon-h1-34b")
    assert (full.n_layers, full.n_ssm_layers, full.n_full_layers, full.n_parallel_layers) == (72,) * 4
    assert abs(full.param_count - (72 * 430.1e6 + 2 * 1336.9e6)) < 0.005 * 33.64e9
    assert (full.mamba_d_inner, full.mamba_conv_channels, full.head_dim) == (4096, 5120, 128)
    assert full.block_mixers == 2 and get_config("nemotron-3-super-120b").block_mixers == 1
    m = bench_config.load_cell(CELL)["config"]["model"]
    cfg = system.model_config(m)
    assert hash(cfg) is not None and cfg.is_hybrid and cfg.is_pattern_stack
    assert m["num_hidden_layers"] == cfg.n_layers == 12 and cfg.hybrid_override_pattern == "P" * 12
    # the benchmark's file and the preset are one model, but for depth, context and name
    assert cfg == full.scaled(name=cfg.name, n_layers=12, hybrid_override_pattern="P" * 12,
                              max_seq_len=2048)
    assert isinstance(cfg.ssm_multipliers, tuple) and len(cfg.ssm_multipliers) == 5
    base = dict(name="x", vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=16,
                mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=8, n_groups=2)
    assert ModelConfig(**base, hybrid_override_pattern="PMP").n_layers == 3
    assert ModelConfig(**base, hybrid_override_pattern="PMP", ssm_multipliers=[1, 2, 3, 4, 5]
                       ).ssm_multipliers == (1.0, 2.0, 3.0, 4.0, 5.0)
    for bad in (dict(hybrid_override_pattern="P*"), dict(hybrid_override_pattern="PX"),
                dict(hybrid_override_pattern="PP", n_groups=3),
                dict(hybrid_override_pattern="PP", ssm_multipliers=(1.0, 2.0)),
                dict(hybrid_override_pattern="PP", mlp_multipliers=(1.0, 2.0, 3.0))):
        with pytest.raises(ValueError):
            ModelConfig(**{**base, **bad})


def test_rotary_dim_follows_the_block_kind():
    """A pattern's ``*`` blocks carry no position, its ``P`` blocks do: a stack
    with rotary attention no longer loses its positions by being a pattern."""
    assert get_config("nemotron-h-tiny").rotary_dim == 0
    assert get_config("nemotron-3-super-120b").rotary_dim == 0
    assert get_config("falcon-h1-tiny").rotary_dim == 16
    assert get_config("falcon-h1-34b").rotary_dim == 128
    assert get_config("falcon-h1-tiny", partial_rotary_factor=0.5).rotary_dim == 8
    assert get_config("llama-tiny").rotary_dim == 32 and get_config("phi-tiny").rotary_dim == 16


@pytest.mark.parametrize("name", BENCH_FILES)
def test_the_accepted_configurations_build_the_model_config_they_built(name):
    """The four benchmark files through ``model_config``: every field this PR
    adds is at the value that traces nothing, and every other field is what
    the parent's ``ModelConfig`` held (spelled out per file)."""
    cell = next(w["name"] for w in bench_config.benchmark()["workloads"] if w["config"] == name)
    cfg = system.model_config(bench_config.load_cell(cell)["config"]["model"])
    added = dict(embedding_multiplier=1.0, lm_head_multiplier=1.0, attention_in_multiplier=1.0,
                 attention_out_multiplier=1.0, key_multiplier=1.0, ssm_in_multiplier=1.0,
                 ssm_out_multiplier=1.0, ssm_multipliers=None, mlp_multipliers=None)
    assert {k: getattr(cfg, k) for k in added} == added and cfg.block_mixers == 1
    want = {
        "mistral-7b-int8": dict(d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8, d_ff=14336,
                                vocab_size=32000, sliding_window=4096, rotary_dim=128),
        "qwen2-7b-int8": dict(d_model=3584, n_layers=28, n_heads=28, n_kv_heads=4, d_ff=18944,
                              vocab_size=152064, attn_bias=True, rotary_dim=128),
        "olmo-hybrid-7b-int8": dict(d_model=3840, n_layers=32, n_full_layers=8, block="olmo2",
                                    n_linear_layers=24, rotary_dim=128),
        "nemotron-3-super-ep4-int8": dict(d_model=4096, n_layers=22, n_ssm_layers=10,
                                          n_full_layers=2, n_expert_layers=10, rotary_dim=0,
                                          hybrid_override_pattern="MEMEMEM*EMEMEMEM*EMEME"),
    }[name]
    assert {k: getattr(cfg, k) for k in want} == want


def test_int8_tree_quantizes_every_matmul_and_nothing_small():
    cfg = get_config("falcon-h1-tiny")
    key = jax.random.PRNGKey(3)
    q = init_params_quantized(key, cfg)
    assert set(q["layers"]) == {"parallel"}
    stack = q["layers"]["parallel"]
    assert {k for k, v in stack.items() if is_quantized(v)} == {
        "in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert {k for k, v in stack.items() if not is_quantized(v)} == {
        "norm_in", "mlp_norm", "norm", "conv", "conv_b", "A_log", "D", "dt_bias"}
    assert stack["in_proj"]["q"].shape == (3, 128, 2 * 96 + 2 * 2 * 32 + 8)
    assert stack["A_log"].dtype == jnp.float32 and stack["conv"].dtype == jnp.bfloat16
    assert q["embed"].dtype == jnp.bfloat16 and q["lm_head"].dtype == jnp.bfloat16
    after = quantize_params(init_params(key, cfg))
    same = jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype
        and float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) <= 1.0,
        q, after)
    assert all(jax.tree_util.tree_leaves(same))
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 500, (1, 40)), jnp.int32)
    fwd = jax.jit(lambda p: forward(p, cfg, toks, jnp.arange(40)[None])[0])
    a, b = fwd(q), fwd(init_params(key, cfg))
    assert float(jnp.mean(jnp.abs(a - b))) < 0.1 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("quantization", ["int8", "none"])
def test_the_reference_regenerates_the_served_weights_exactly(quantization):
    m = tiny_model(quantization)
    big = 2**31 + 5
    served = reference.weights.all_weights(big, m)
    root = reference.weights.root_key(big)
    gen = jax.jit(partial(reference.weights.layer_weights, m=m))
    for li in range(m["num_hidden_layers"]):
        one = gen(root, layer=jnp.int32(li))
        stacked = jax.tree_util.tree_map(lambda a: a[li], served["layers"]["parallel"])
        same = jax.tree_util.tree_map(
            lambda a, b: np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)),
            one, stacked)
        assert all(jax.tree_util.tree_leaves(same)), (li, same)
    top = jax.jit(partial(reference.weights.top_weights, m=m))(root)
    for name in ("embed", "lm_head", "final_norm"):
        assert np.array_equal(np.asarray(top[name], np.float32), np.asarray(served[name], np.float32))
    # the program's own tree has the same leaves in the same places
    mine = jax.eval_shape(lambda: (init_params_quantized if quantization == "int8" else init_params)(
        jax.random.PRNGKey(0), system.model_config(m)))
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(served)
    assert all(a.shape == b.shape and a.dtype == b.dtype for a, b in zip(
        jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(served)))
    # small leaves show when they are left out: no gain at 1, no tap or bias at 0
    stack = served["layers"]["parallel"]
    assert float(jnp.std(stack["norm"].astype(jnp.float32))) > 0.05
    assert float(jnp.mean(jnp.abs(stack["conv_b"].astype(jnp.float32)))) > 0.03
    decay = jnp.exp(-jnp.exp(stack["A_log"]) * jax.nn.softplus(stack["dt_bias"]))
    assert float(decay.max() - decay.min()) > 0.1 and float(decay.max()) > 0.95


def test_a_vocabulary_table_is_drawn_in_row_blocks_and_never_whole_in_float32(monkeypatch):
    """The same rows whatever else is drawn, each block from its own key; and
    no float32 value of the whole table's size is ever formed."""
    w = reference.weights
    m = {**tiny_model(), "vocab_size": 96}
    monkeypatch.setattr(w, "TABLE_BLOCK_ROWS", 32)
    assert w.table_block_rows(96) == 32 and w.table_block_rows(261120) <= 32
    monkeypatch.setattr(w, "TABLE_BLOCK_ROWS", 16384)
    assert w.table_block_rows(261120) == 16320 and w.table_block_rows(512) == 512
    monkeypatch.setattr(w, "TABLE_BLOCK_ROWS", 32)
    root = w.root_key(9)
    whole = w.table(root, "lm_head", m, 0.02)
    assert whole.shape == (96, 128) and whole.dtype == jnp.bfloat16
    base = w._key(root, "lm_head", 0)
    for b in range(3):
        rows = (jax.random.normal(jax.random.fold_in(base, b), (32, 128), jnp.float32) * 0.02)
        assert np.array_equal(np.asarray(whole[32 * b:32 * (b + 1)], np.float32),
                              np.asarray(rows.astype(jnp.bfloat16), np.float32))
    widest = [0]

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                if getattr(v.aval, "dtype", None) == jnp.float32:
                    widest[0] = max(widest[0], int(np.prod(v.aval.shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                visit(sub)

    visit(jax.make_jaxpr(lambda r: w.table(r, "lm_head", m, 0.02))(root).jaxpr)
    assert 0 < widest[0] <= 32 * 128
    logits = jax.make_jaxpr(lambda x, t: reference._head(x, t, m=m))(
        jnp.zeros((4, 128)), {"lm_head": whole, "final_norm": jnp.ones((128,), jnp.bfloat16)})
    widest[0] = 0
    visit(logits.jaxpr)
    assert widest[0] < 96 * 128     # the head never widens the whole table

"""A stack of operator + feed-forward pairs (gated short convolutions and
attention with per-head QK-norm in an irregular order; a dense MLP then routed
SwiGLU experts): the program against the benchmark's plain reference, logits
not tokens, at tiny widths on the CPU; what must hold of a cache that keeps
K/V for some layers and a two-position tail for the others; the shares of an
expert layer; configuration and trees."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import config as bench_config
from kserve_vllm_mini_tpu.models import moe
from kserve_vllm_mini_tpu.models.config import ModelConfig, get_config
from kserve_vllm_mini_tpu.models.llama import (
    init_kv_cache,
    init_paged_kv_cache,
    init_params,
    init_params_quantized,
    slice_cache_slots,
    update_cache_slots,
)
from kserve_vllm_mini_tpu.ops.quant import is_quantized, quantize_params
# one padded prefill (or pieces of it) then token-by-token decode through the cache
from tests.test_hybrid import make_step, prefill_then_decode

reference = bench_config.load_module("references", "conv_moe_decoder")
system = bench_config.load_module("systems", "engine")
CELL = "lfm2-8b-a1b.chat"
TINY = get_config("lfm2-moe-tiny", dtype="float32")


def tiny_model(quantization="int8", **keys):
    """The benchmark's rehearsal sizes of the configuration: conv, conv,
    attention, conv, attention, conv; hidden 128, 4 / 2 heads x 32; one dense
    layer (256) then five of 8 experts (96), 2 a token. ``keys`` override the
    model's keys (``model_extra`` follows where it repeats one)."""
    m = bench_config.load_cell(CELL, rehearse=True)["config"]["model"]
    extra = {k: v for k, v in keys.items() if k in m["model_extra"]}
    return {**m, "quantization": quantization, **keys,
            "model_extra": {**m["model_extra"], **extra}}


@functools.cache
def _served_weights(seed, model_json):
    params = reference.weights.all_weights(seed, json.loads(model_json))
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params)


def served_weights(seed, m):
    """The benchmark's weights as the program is handed them, the bfloat16
    leaves widened (the values are the same): the program runs in float32.
    Drawn once a (seed, model) in a worker; a caller gets a tree of its own
    (new containers over the same arrays), so a control may swap a leaf."""
    return jax.tree_util.tree_map(lambda a: a, _served_weights(seed, json.dumps(m, sort_keys=True)))


@functools.cache
def _wanted(seed, model_json, seqs):
    m = json.loads(model_json)
    return reference.logits_after(seed, m, [list(s) for s in seqs], [1] * len(seqs), 128, 64)


def wanted(seed, m, seqs):
    """The reference's logits for ``seqs`` (each from its second position on,
    padded to 128), one call for all of them and once a worker: every call
    of ``logits_after`` compiles its layers anew (~8 s here)."""
    return _wanted(seed, json.dumps(m, sort_keys=True), tuple(tuple(s) for s in seqs))


def f32_config(m, **kw):
    return system.model_config({**m, "torch_dtype": "float32"}).scaled(**kw)


def a_sequence(m, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, m["vocab_size"], n)]


def off_by(got, want):
    """The widest difference of two sets of logits, as a share of the largest."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# Float32 on both sides, the weights at the rehearsal's std 0.08 (0.02 x sqrt(2048 / 128):
# a layer adds to the residual stream what it adds at the published width; at 0.02 the
# tiny layers add next to nothing and every control below read 4-6 times less). The sound
# program reads 1.1e-6 to 2.8e-6 of the largest logit (~4.1) off the reference over the
# eight shapes of the first test (jax 0.9.0, CPU): two float32 programs that order their
# sums differently. The tolerance is 2e-5 of the largest logit, seven times the widest
# sound reading, and SEEN = 1e-3, fifty times the tolerance, is what every case of
# ``test_the_comparison_sees_what_it_must`` (tests/test_conv_moe_controls.py) has to
# pass; the least of them reads 0.21 (208 x SEEN). Readings: ``expert_bias_weighs_too``
# 0.209, ``q_and_k_gains_swapped`` 0.253, ``qk_norm_over_the_whole_projection`` 0.290,
# ``rope_theta_1e4`` 0.324, ``no_per_head_qk_norm`` 0.334, ``w8a8`` 0.367,
# ``no_normalisation_over_the_chosen`` 0.443, ``rms_eps_1e-3`` 0.485, ``softmax_router``
# 0.498, ``expert_bias_dropped_from_the_choice`` 0.552, ``one_expert_a_token_fewer`` 0.578,
# ``routed_scaling_factor_2`` 0.700, ``pad_in_the_tail`` 0.973,
# ``silu_inside_the_convolution`` 1.01, ``tail_not_reset`` 1.02, ``head_not_tied`` 1.23,
# ``no_dense_mlp_under_layer_0`` 1.26, ``taps_reversed`` 1.27, ``no_C_gate`` 1.30,
# ``no_B_gate`` 1.33, ``B_and_C_swapped`` 1.35. A routing choice is discrete: were a
# float32 score to round the other way in one of the two programs a whole expert would
# swap and the gap would read ~1e-2, so the tolerance also says that none did. What it
# cannot see: the family's ``+ 1e-6`` against ``+ 1e-20`` in the normalisation over the
# chosen (5e-7 of a weight); the constant is passed as the family writes it all the same
# (models/moe.py NORM_EPS_GLU).
TOLERANCE, SEEN = 2e-5, 1e-3


SHAPES = [(50, 64), (70, 128), (23, 32), (97, 128)]
RAGGED = [64, 23, 41]


def sequences(m):
    """Every sequence this module compares with the reference: the four
    prompts of ``SHAPES`` with twelve tokens to decode, the prompt that is
    prefilled in pieces, the three ragged rows."""
    return ([a_sequence(m, n + 12, n) for n, _ in SHAPES] + [a_sequence(m, 76, 1)]
            + [a_sequence(m, n + 2, 10 + i) for i, n in enumerate(RAGGED)])


def want_of(m, seq):
    """The reference's logits for ``seq`` at seed 7, out of ONE call for all
    of ``sequences`` a model (a call compiles the reference anew)."""
    seqs = sequences(m)
    return wanted(7, m, seqs)[seqs.index(seq)]


@pytest.mark.parametrize("quantization", ["int8", "none"])
@pytest.mark.parametrize("n_prompt,bucket", SHAPES)
def test_prefill_then_decode_against_the_references_full_forward(quantization, n_prompt, bucket):
    """One padded piece, then twelve tokens one at a time through the cache
    (K/V of two layers, the tail of four), against the reference's forward
    pass over the whole sequence. ``int8``: the program contracts the int8
    leaves with their scales as an epilogue, the reference the dequantized
    float32 weights."""
    m = tiny_model(quantization)
    seq = sequences(m)[SHAPES.index((n_prompt, bucket))]
    want = want_of(m, seq)
    got, _ = prefill_then_decode(served_weights(7, m), f32_config(m), seq, n_prompt, bucket)
    assert got.shape[0] == len(seq)
    assert off_by(got[:-1], want) < TOLERANCE


@pytest.mark.parametrize("pieces", [[32, 32, 6], [64, 6], [17, 53], [1, 64, 5], [1, 1, 68]])
def test_a_prompt_in_pieces_against_the_reference_and_the_prompt_at_once(pieces):
    """The tail is carried from piece to piece: pieces of one token (shorter
    than the tail itself) and pieces that end anywhere."""
    m = tiny_model()
    params, cfg = served_weights(7, m), f32_config(m)
    seq = a_sequence(m, 76, 1)
    want = want_of(m, seq)
    whole, c1 = prefill_then_decode(params, cfg, seq, 70, 128)
    split, c2 = prefill_then_decode(params, cfg, seq, 70, 128, pieces=pieces)
    assert off_by(split[:-1], want) < TOLERANCE and off_by(split, whole) < TOLERANCE
    assert np.max(np.abs(c1["conv_tail"] - c2["conv_tail"])) < 1e-5
    for leaf in ("k", "v"):       # the 76 positions written; past them a bucket's padding differs
        assert np.max(np.abs(c1[leaf][:, :, :, :76] - c2[leaf][:, :, :, :76])) < 1e-5


def test_a_batch_with_ragged_valid_lengths_is_each_row_alone():
    """Three rows of one padded prefill, each told its own length, then one
    decode sweep in which the middle row is parked: every real position's
    logits are the reference's for that row's sequence alone, and the parked
    row's tail does not advance."""
    m = tiny_model()
    params, cfg = served_weights(7, m), f32_config(m)
    lens = RAGGED
    seqs = sequences(m)[-3:]
    want = [want_of(m, s) for s in seqs]
    toks = jnp.asarray([s[:n] + [0] * (64 - n) for s, n in zip(seqs, lens)], jnp.int32)
    step = make_step()
    cache = init_kv_cache(cfg, 3, max_seq=256)
    lg, cache = step(params, cfg, toks, jnp.zeros((3,), jnp.int32), cache,
                     jnp.asarray(lens, jnp.int32), fresh=True)
    for row, n in enumerate(lens):
        assert off_by(np.asarray(lg[row, :n]), want[row][:n]) < TOLERANCE
    before = np.asarray(cache["conv_tail"])
    nxt = jnp.asarray([[s[n]] for s, n in zip(seqs, lens)], jnp.int32)
    lg, cache = step(params, cfg, nxt, jnp.asarray(lens, jnp.int32), cache,
                     jnp.asarray([1, 0, 1], jnp.int32))
    for row in (0, 2):
        assert off_by(np.asarray(lg[row, 0]), want[row][lens[row]]) < TOLERANCE
    after = np.asarray(cache["conv_tail"])
    assert np.array_equal(after[:, 1], before[:, 1])
    assert not np.array_equal(after[:, 0], before[:, 0])
    # the live rows' tails moved on by one: the older of the two is the newer of before
    assert np.array_equal(after[:, 0, 0], before[:, 0, 1])


def test_padded_rows_and_parked_slots_leave_the_tail():
    """A padded piece writes K/V for its pad positions (harmless) but its tail
    is the two inputs before ``valid``; a fresh prefill starts from an empty
    tail whatever the slot held; a parked row keeps its tail bit for bit."""
    cfg = TINY
    params = init_params(jax.random.PRNGKey(0), cfg)
    step = make_step()
    cache = jax.tree_util.tree_map(lambda a: a + 2.5, init_kv_cache(cfg, 3, max_seq=128))
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 500, (3, 64)), jnp.int32)
    valid = jnp.asarray([0, 40, 64], jnp.int32)
    _, new = step(params, cfg, toks, jnp.zeros((3,), jnp.int32), cache, valid, fresh=True)
    assert not np.asarray(new["conv_tail"][:, 0]).any()      # nothing real: the empty tail
    assert np.array_equal(np.asarray(new["k"][:, :, :, 64:]), np.asarray(cache["k"][:, :, :, 64:]))
    _, alone = step(params, cfg, toks[1:2, :40], jnp.zeros((1,), jnp.int32),
                    init_kv_cache(cfg, 1, max_seq=128), jnp.asarray([40], jnp.int32), fresh=True)
    assert np.max(np.abs(new["conv_tail"][:, 1] - alone["conv_tail"][:, 0])) < 1e-6
    before = {k: np.asarray(v) for k, v in new.items()}
    _, after = step(params, cfg, toks[:, :1], jnp.asarray([0, 40, 64], jnp.int32), new,
                    jnp.asarray([0, 0, 1], jnp.int32))
    assert np.array_equal(np.asarray(after["conv_tail"])[:, :2], before["conv_tail"][:, :2])
    assert not np.array_equal(np.asarray(after["conv_tail"])[:, 2], before["conv_tail"][:, 2])


def test_one_cache_tree_kv_for_the_attention_layers_a_tail_for_the_others():
    cache = init_kv_cache(TINY, 5, max_seq=64)
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((2, 5, 2, 64, 32), "float32"), "v": ((2, 5, 2, 64, 32), "float32"),
        "conv_tail": ((4, 5, 2, 128), "float32")}
    bf16 = init_kv_cache(get_config("lfm2-moe-tiny"), 5, max_seq=64, quantized=True)
    assert bf16["conv_tail"].dtype == jnp.bfloat16
    assert bf16["k"].dtype == jnp.int8 and bf16["k_s"].shape == (2, 5, 2, 64)
    marked = {k: v.at[:, 3].set(1) for k, v in cache.items()}
    sub = slice_cache_slots(marked, 3)
    assert all(v.shape[1] == 1 and bool((v == 1).all()) for v in sub.values())
    back = update_cache_slots(cache, sub, 1)
    assert all(bool((v[:, 1] == 1).all()) and bool((v[:, 0] == 0).all()) for v in back.values())
    with pytest.raises(ValueError, match="no positions to page"):
        init_paged_kv_cache(TINY, 8)
    # the published sizes: K/V [6, 16, 8, 2048, 64] beside a tail [18, 16, 2, 2048], no state
    m = bench_config.load_cell(CELL)["config"]["model"]
    big = jax.eval_shape(lambda: init_kv_cache(system.model_config(m), 16, max_seq=2048))
    assert {k: v.shape for k, v in big.items()} == {
        "k": (6, 16, 8, 2048, 64), "v": (6, 16, 8, 2048, 64), "conv_tail": (18, 16, 2, 2048)}
    assert big["conv_tail"].dtype == jnp.bfloat16 and big["k"].dtype == jnp.bfloat16


# -- the shares of an expert layer ------------------------------------------------

def _expert_layer(m, seed=11, layer=3):
    """The weights of one expert layer of the WHOLE model and a normed input."""
    whole = {**m, "share": None}
    w = jax.jit(lambda r: reference.weights.stack_weights(r, whole, layer, "experts"))(
        reference.weights.root_key(seed))
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(37, m["hidden_size"])), jnp.float32)
    return w, x


def test_four_shares_of_two_experts_add_up_to_the_uncut_layer():
    """The guide's share test, at 8 experts: every share routes over all 8,
    weighs as the whole layer does and computes its own two experts' part;
    the four parts add up to the uncut layer. The reference's shares, and the
    program's block told the same share (``n_experts_held`` 2, ``expert_offset``
    0, 2, 4, 6) against each of them."""
    m = tiny_model("none")
    w, x = _expert_layer(m)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(reference.routed_part(x, w, m=m))
        parts = []
        for offset in (0, 2, 4, 6):
            share = {**m, "share": {"expert_offset": offset, "experts_held": 2}}
            held = {k: (v[offset:offset + 2] if k.startswith("w_") else v) for k, v in w.items()}
            part = np.asarray(reference.routed_part(x, held, m=share))
            cfg = f32_config(m, n_experts_held=2, expert_offset=offset)
            got, stats = moe.moe_glu_block(held, cfg, x[None])
            assert np.max(np.abs(np.asarray(got[0]) - part)) < 1e-5 * np.max(np.abs(whole))
            assert int(stats[0]) == int((np.asarray(reference.route(x, w, m=m))
                                         [:, offset:offset + 2] > 0).sum())
            parts.append(part)
    assert np.max(np.abs(sum(parts) - whole)) < 1e-5 * np.max(np.abs(whole))
    assert min(np.max(np.abs(p)) for p in parts) > 0.05 * np.max(np.abs(whole))
    # the shares' weights are drawn from the expert's index in the whole layer
    share = {**m, "share": {"expert_offset": 4, "experts_held": 2}}
    drawn = jax.jit(lambda r: reference.weights.stack_weights(r, share, 3, "experts"))(
        reference.weights.root_key(11))
    assert np.array_equal(np.asarray(drawn["w_up"]), np.asarray(w["w_up"][4:6]))
    assert np.array_equal(np.asarray(drawn["router"]), np.asarray(w["router"]))


def test_the_routing_counts_are_over_the_real_rows():
    m = tiny_model("none")
    w, x = _expert_layer(m)
    cfg = f32_config(m)
    real = jnp.arange(37)[None] < 20
    _, stats = moe.moe_glu_block(w, cfg, x[None], real)
    took = np.asarray(reference.route(x, w, m=m))[:20] > 0
    # ... and, fourth, the experts the dense dispatch multiplied: every held one
    assert stats.tolist() == [int(took.sum()), int(took.any(axis=0).sum()),
                              int(took.sum(axis=0).max()), cfg.experts_held]
    assert int(stats[0]) == 20 * m["num_experts_per_tok"]


def test_token_blocks_are_the_whole_piece(monkeypatch):
    """Pieces longer than ``TOKEN_BLOCK`` run block by block, unrolled."""
    m = tiny_model("none")
    w, x = _expert_layer(m)
    cfg = f32_config(m)
    whole, _ = moe.moe_glu_block(w, cfg, x[None])
    monkeypatch.setattr(moe, "TOKEN_BLOCK", 16)
    blocks, _ = moe.moe_glu_block(w, cfg, x[None])
    assert np.max(np.abs(whole - blocks)) < 1e-6


# -- configuration and trees -------------------------------------------------------

def test_config_counts_the_published_model():
    full = get_config("lfm2-8b-a1b")
    assert full.param_count == 8_339_930_560               # the family's "8.3B"
    assert (full.n_layers, full.n_conv_layers, full.n_full_layers, full.n_expert_layers,
            full.num_dense_layers) == (24, 18, 6, 22, 2)
    assert [i for i, k in enumerate(full.layer_types) if k == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    assert (full.head_dim, full.rotary_dim, full.experts_held) == (64, 64, 32)
    assert full.is_hybrid and full.is_conv_stack and not full.is_pattern_stack
    assert full.n_linear_layers == 0 and full.n_ssm_layers == 0 and full.layer_period == 1
    # the four weight stacks, as the plan's layers name them
    stacks = [s for e in full.layer_plan for s in (e.stack, e.ffn_stack)]
    assert {s: stacks.count(s) for s in set(stacks)} == {
        "conv": 18, "attn": 6, "mlp": 2, "experts": 22}
    m = bench_config.load_cell(CELL)["config"]["model"]
    cfg = system.model_config(m)
    assert hash(cfg) is not None
    # the benchmark's file and the preset are one model, but for context and name
    assert cfg == full.scaled(name=cfg.name, max_seq_len=2048)
    tiny = get_config("lfm2-moe-tiny")
    assert tiny.layer_types == ("conv", "conv", "full_attention", "conv", "full_attention", "conv")
    assert sum(a.size for a in jax.tree.leaves(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), tiny)))) == tiny.param_count
    # the olmo hybrid's counts are what they were
    olmo = get_config("olmo-hybrid-tiny")
    assert (olmo.layer_period, olmo.n_full_layers, olmo.n_linear_layers, olmo.n_conv_layers,
            olmo.n_expert_layers, olmo.is_conv_stack) == (4, 2, 6, 0, 0, False)


def test_config_refuses_what_the_stack_is_not():
    base = dict(name="x", vocab_size=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2, d_ff=16,
                layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
                n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=8)
    ok = ModelConfig(**base)
    assert ok.layer_types == ("conv", "full_attention", "conv") and ok.n_expert_layers == 2
    assert ModelConfig(**{**base, "num_dense_layers": 3, "n_routed_experts": 0}).n_expert_layers == 0
    for bad in (dict(layer_types=["conv", "linear_attention", "conv"]),
                dict(layer_types=["conv", "conv"]), dict(block="olmo2"), dict(n_experts=4),
                dict(sliding_window=8), dict(conv_L_cache=1), dict(num_dense_layers=4),
                dict(n_routed_experts=0), dict(num_experts_per_tok=5),
                dict(n_experts_held=2, expert_offset=3)):
        with pytest.raises(ValueError):
            ModelConfig(**{**base, **bad})


def test_the_int8_tree_quantizes_the_four_stacks_matmuls_and_nothing_else():
    cfg = get_config("lfm2-moe-tiny")
    key = jax.random.PRNGKey(3)
    # one program a tree: drawn eagerly, every leaf is a compile of its own
    direct = jax.jit(lambda k: init_params_quantized(k, cfg))(key)
    after = jax.jit(lambda k: quantize_params(init_params(k, cfg)))(key)
    quantized = {"conv": {"in_proj", "out_proj"}, "attn": {"wq", "wk", "wv", "wo"},
                 "mlp": {"w_gate", "w_up", "w_down"}, "experts": {"w_gate", "w_up", "w_down"}}
    for tree in (direct, after):
        assert set(tree["layers"]) == set(quantized) and "lm_head" not in tree
        for kind, names in quantized.items():
            assert {k for k, v in tree["layers"][kind].items() if is_quantized(v)} == names
        assert tree["layers"]["experts"]["router"].dtype == jnp.float32
        assert tree["layers"]["experts"]["w_up"]["q"].shape == (5, 8, 128, 96)
        assert tree["layers"]["experts"]["w_up"]["s"].shape == (5, 8, 96)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype
        and float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) <= 1.0,
        direct, after))


def test_the_reference_regenerates_the_served_weights_layer_by_layer():
    """``all_weights`` (what the program is handed) and ``layer_weights`` (what
    the reference draws again, one layer at a time) are the same values, each
    of the four stacks in stack order. Jitted, as the reference draws them:
    an eager float32 draw of the router differs in the last bit."""
    from functools import partial

    m = tiny_model()
    served = reference.weights.all_weights(9, m)
    root = reference.weights.root_key(9)
    seen = dict.fromkeys(("conv", "attn", "mlp", "experts"), 0)
    draw = functools.cache(lambda op, ffn: jax.jit(partial(      # one program a kind of layer
        reference.weights.layer_weights, m=m, operator=op, ffn=ffn)))
    for li in range(m["num_hidden_layers"]):
        op, ffn = reference.weights.operator_of(m, li), reference.weights.ffn_of(m, li)
        w = draw(op, ffn)(root, layer=jnp.int32(li))
        for stack, part in ((op, w["operator"]), (ffn, w["ffn"])):
            i = seen[stack]
            seen[stack] += 1
            want = jax.tree.map(lambda a: a[i], served["layers"][stack])
            assert jax.tree.all(jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), part, want))
    assert seen == {"conv": 4, "attn": 2, "mlp": 1, "experts": 5}
    assert "lm_head" not in served
    assert reference.weights.layer_nbytes(m, "experts") == 8 * (3 * 128 * 96 + 4 * (2 * 96 + 128))

"""A stack of single-mixer blocks (models/mamba.py, models/moe.py beside
attention without rotary embedding): the program against the benchmark's plain
reference, logits not tokens, at tiny widths on the CPU; what must hold of a
state-space state in a serving cache; that the shares of an expert layer add
up to the uncut layer; and what the comparison must see."""

import functools
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import config as bench_config
from kserve_vllm_mini_tpu.models import mamba, moe
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
from tests.test_hybrid import STEP, make_step, prefill_then_decode

reference = bench_config.load_module("references", "ssm_moe_decoder")
system = bench_config.load_module("systems", "engine")
CELL = "nemotron-3-super-ep4.decode-sat"
TINY = get_config("nemotron-h-tiny", dtype="float32")


def tiny_model(quantization="int8", **share):
    """The benchmark's rehearsal sizes of the configuration (pattern MEM*EME,
    8 experts held of 32, 4 a token); ``share`` moves the experts held."""
    m = bench_config.load_cell(CELL, rehearse=True)["config"]["model"]
    m = {**m, "quantization": quantization}
    if share:
        m["share"] = {**m["share"], "expert_offset": share["offset"]}
        m["n_routed_experts"] = share["held"]
        m["model_extra"] = {**m["model_extra"], "expert_offset": share["offset"],
                            "n_experts_held": share["held"]}
    return m


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
    of ``logits_after`` compiles its blocks anew (~8 s here)."""
    return _wanted(seed, json.dumps(m, sort_keys=True), tuple(tuple(s) for s in seqs))


def f32_config(m, **kw):
    return system.model_config({**m, "torch_dtype": "float32"}).scaled(**kw)


# Float32 on both sides: the sound program reads 1e-6 to 2e-6 of the largest
# logit (~4) off the reference, so the comparison's tolerance is 2e-4 of it:
# a hundred times the rounding of two float32 programs that order their sums
# differently. What the next test breaks reads at least 1.2e-3 (a bfloat16
# state, the smallest), and has to read over 5e-4.
TOLERANCE, SEEN = 2e-4, 5e-4


def a_sequence(m, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, m["vocab_size"], n)]


SHAPES = [(50, 64), (70, 128), (23, 32), (97, 128)]


def sequences(m):
    """Every sequence this module compares with the reference: the four
    prompts of ``SHAPES`` with twelve tokens to decode, and the one the
    broken programs run."""
    return [a_sequence(m, n + 12, n) for n, _ in SHAPES] + [a_sequence(m, 70, 5)]


def want_of(m, seq):
    """The reference's logits for ``seq`` at seed 7, out of ONE call for all
    of ``sequences`` a model (a call compiles the reference anew)."""
    seqs = sequences(m)
    return wanted(7, m, seqs)[seqs.index(seq)]


@pytest.mark.parametrize("quantization", ["int8", "none"])
@pytest.mark.parametrize("n_prompt,bucket", SHAPES)
def test_prefill_then_decode_against_the_references_full_forward(quantization, n_prompt, bucket):
    """Prompt lengths that are no multiple of the chunk (32) or the bucket."""
    m = tiny_model(quantization)
    seq = sequences(m)[SHAPES.index((n_prompt, bucket))]
    want = want_of(m, seq)
    got, _ = prefill_then_decode(served_weights(7, m), f32_config(m), seq, n_prompt, bucket)
    assert got.shape[0] == len(seq)
    assert np.max(np.abs(got[:-1] - want)) < TOLERANCE * np.max(np.abs(want))


def _capacity_bounded(weights, chosen, offset, held, capacity=3):
    """``moe.held_weights`` as a capacity-factor dispatch would leave it: an
    expert takes its first ``capacity`` tokens and drops the rest."""
    take, landed = HELD(weights, chosen, offset, held)
    keep = jnp.cumsum(landed, axis=0) <= capacity
    return jnp.where(keep, take, 0.0), landed & keep


HELD = moe.held_weights
BROKEN = ["dropped_assignment", "bf16_state", "w8a8", "pad_in_state", "no_selection_bias",
          "no_conv_bias", "no_gated_norm", "unscaled_weights", "rotary"]
PATCHED = {"dropped_assignment", "bf16_state", "no_gated_norm", "rotary"}   # by monkeypatch


@pytest.mark.parametrize("broken", BROKEN)
def test_the_comparison_sees_what_it_must(broken, monkeypatch):
    """Each of these passes every shape check and serves fluent tokens; each
    moves the logits past the comparison's tolerance with room."""
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    want = want_of(m, seq)
    params, cfg, valid = served_weights(7, m), f32_config(m), 50
    if broken == "dropped_assignment":
        monkeypatch.setattr(moe, "held_weights", _capacity_bounded)
    elif broken == "bf16_state":
        one, many = mamba.recurrent_step, mamba.chunked_scan

        def rounded(fn):
            return lambda *a, **k: (lambda out: (
                out[0].astype(jnp.bfloat16).astype(jnp.float32), out[1]))(fn(*a, **k))

        monkeypatch.setattr(mamba, "recurrent_step", rounded(one))
        monkeypatch.setattr(mamba, "chunked_scan", rounded(many))
    elif broken == "w8a8":
        cfg = cfg.scaled(quant_mode="w8a8")
    elif broken == "pad_in_state":
        valid = 64                          # the 14 pad positions are taken for real
    elif broken == "no_selection_bias":
        bias = params["layers"]["experts"]["router_bias"]
        params["layers"]["experts"]["router_bias"] = jnp.zeros_like(bias)
    elif broken == "no_conv_bias":
        bias = params["layers"]["mamba"]["conv_b"]
        params["layers"]["mamba"]["conv_b"] = jnp.zeros_like(bias)
    elif broken == "no_gated_norm":
        monkeypatch.setattr(mamba, "grouped_rms_norm", lambda y, gain, groups, eps: y)
    elif broken == "unscaled_weights":
        cfg = cfg.scaled(routed_scaling_factor=1.0)
    elif broken == "rotary":
        monkeypatch.setattr(ModelConfig, "rotary_dim", property(lambda self: self.head_dim))
    # a program of its own where the program itself was patched; another leaf
    # or length runs the sound program's executables
    step = make_step() if broken in PATCHED else STEP
    got, _ = prefill_then_decode(params, cfg, seq, 50, 64, valid=valid, step=step)
    assert np.max(np.abs(got[:-1] - want)) > SEEN * np.max(np.abs(want))


@pytest.mark.parametrize("pieces", [[32, 32, 6], [64, 6], [17, 53], [1, 64, 5]])
def test_a_prompt_prefilled_in_pieces_is_the_prompt_prefilled_at_once(pieces):
    m = tiny_model()
    params, cfg = served_weights(3, m), f32_config(m)
    seq = a_sequence(m, 76, 1)
    whole, c1 = prefill_then_decode(params, cfg, seq, 70, 128)
    split, c2 = prefill_then_decode(params, cfg, seq, 70, 64, pieces=pieces)
    assert np.max(np.abs(whole - split)) < TOLERANCE * np.max(np.abs(whole))
    assert np.max(np.abs(c1["ssm_state"] - c2["ssm_state"])) < 1e-4
    assert np.max(np.abs(c1["ssm_conv"] - c2["ssm_conv"])) < 1e-4


def scan_inputs(t, seed=0, b=2, h=4, p=8, g=2, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.0, 0.3, (b, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    return f(b, h, p, n), f(b, t, h, p), f(b, t, g, n), f(b, t, g, n), dt, a


@pytest.mark.parametrize("t", [1, 7, 32, 33, 64, 100, 256])
def test_chunked_scan_is_the_recurrence_token_by_token(t):
    s0, x, b, c, dt, a = scan_inputs(t, seed=t)
    per = x.shape[2] // b.shape[2]
    s1, y1 = mamba.recurrent_scan(s0, x, jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2), dt, a)
    s2, y2 = jax.jit(partial(mamba.chunked_scan, chunk=32))(s0, x, b, c, dt, a)
    assert np.max(np.abs(s1 - s2)) < 5e-5 and np.max(np.abs(y1 - y2)) < 5e-5


def test_positions_past_the_valid_length_leave_state_and_tail_bit_for_bit():
    cfg = TINY
    p = jax.tree_util.tree_map(lambda a: a[0], init_params(jax.random.PRNGKey(0), cfg)["layers"]["mamba"])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 128, cfg.d_model)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(3, cfg.mamba_num_heads, cfg.mamba_head_dim,
                                         cfg.ssm_state_size)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(3, 3, cfg.mamba_conv_channels)), jnp.float32)
    valid = jnp.asarray([0, 70, 128], jnp.int32)
    mixer = jax.jit(partial(mamba.mamba_mixer, cfg=cfg))
    _, s_new, t_new = mixer(p, x=x, state=state, tail=tail, valid=valid)
    assert np.array_equal(np.asarray(s_new[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(t_new[0]), np.asarray(tail[0]))
    # row 1: what 70 real positions alone give, whatever the 58 pad positions hold
    _, s_cut, t_cut = mixer(p, x=x[1:2, :70], state=state[1:2], tail=tail[1:2],
                            valid=jnp.asarray([70], jnp.int32))
    assert np.max(np.abs(s_new[1] - s_cut[0])) < 1e-5
    assert np.array_equal(np.asarray(t_new[1]), np.asarray(t_cut[0]))
    assert not np.array_equal(np.asarray(s_new[2]), np.asarray(state[2]))
    # one token, not live (a parked slot, or one between two pieces of its prefill): held
    _, s1, t1 = mixer(p, x=x[:, :1], state=state, tail=tail,
                      valid=jnp.asarray([0, 1, 0], jnp.int32))
    for row in (0, 2):
        assert np.array_equal(np.asarray(s1[row]), np.asarray(state[row]))
        assert np.array_equal(np.asarray(t1[row]), np.asarray(tail[row]))
    assert not np.array_equal(np.asarray(s1[1]), np.asarray(state[1]))


def test_one_cache_tree_slot_axis_at_dim_one():
    cfg = TINY
    cache = init_kv_cache(cfg, 5, max_seq=64)
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((1, 5, 2, 64, 32), "float32"), "v": ((1, 5, 2, 64, 32), "float32"),
        "ssm_state": ((3, 5, 8, 16, 16), "float32"),
        "ssm_conv": ((3, 5, 3, 8 * 16 + 2 * 2 * 16), "float32"),
    }
    bf16 = init_kv_cache(get_config("nemotron-h-tiny"), 5, max_seq=64, quantized=True)
    assert bf16["ssm_state"].dtype == jnp.float32 and bf16["ssm_conv"].dtype == jnp.bfloat16
    assert bf16["k"].dtype == jnp.int8 and bf16["k_s"].shape == (1, 5, 2, 64)
    marked = {k: v.at[:, 3].set(1) for k, v in cache.items()}
    sub = slice_cache_slots(marked, 3)
    assert all(v.shape[1] == 1 and bool((v == 1).all()) for v in sub.values())
    back = update_cache_slots(cache, sub, 1)
    assert all(bool((v[:, 1] == 1).all()) and bool((v[:, 0] == 0).all()) for v in back.values())
    with pytest.raises(ValueError, match="no positions to page"):
        init_paged_kv_cache(cfg, 8)


# -- the shares of an expert layer ---------------------------------------------

def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The reference at each of the four quarters of one layer's experts, the
    router and the shared expert counted once, against the reference with
    every expert held; and the program's block at each share against the
    reference's same share."""
    root = reference.weights.root_key(11)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(40, 128)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut_m = tiny_model(offset=0, held=32)
        w_all = reference.weights.layer_weights(root, uncut_m, 4, "E")
        uncut = reference.expert_mixer(x, w_all, m=uncut_m)
        parts = []
        for offset in (0, 8, 16, 24):
            m = tiny_model(offset=offset, held=8)
            w = reference.weights.layer_weights(root, m, 4, "E")
            # the share holds the whole layer's experts offset .. offset + 8, no others
            assert all(np.array_equal(np.asarray(w[k]["q"]), np.asarray(w_all[k]["q"][offset:offset + 8]))
                       for k in ("w1", "w2"))
            assert np.array_equal(np.asarray(w["router"]), np.asarray(w_all["router"]))
            parts.append(reference.routed_part(x, w, m=m))
            cfg = f32_config(m)
            p = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, w)
            mine, stats = moe.moe_latent_block(p, cfg, x[None])
            want = parts[-1] + reference.shared_part(x, w, m=m)
            assert np.max(np.abs(mine[0] - want)) < 1e-4 * np.max(np.abs(want))
            assert 0 < int(stats[0]) < 40 * 4 and int(stats[1]) <= 8 and int(stats[2]) >= 1
        total = sum(parts) + reference.shared_part(x, w_all, m=uncut_m)
    assert np.max(np.abs(total - uncut)) < 1e-5 * np.max(np.abs(uncut))
    # no share is nothing, and none is the whole
    assert all(0.05 < float(jnp.linalg.norm(p)) / float(jnp.linalg.norm(sum(parts))) < 0.95
               for p in parts)


def test_routing_counts_are_of_the_real_rows_and_the_held_experts():
    cfg = TINY
    p = jax.tree_util.tree_map(lambda a: a[0], init_params(jax.random.PRNGKey(1), cfg)["layers"]["experts"])
    h = jnp.asarray(np.random.default_rng(3).normal(size=(4, 6, cfg.d_model)), jnp.float32)
    w, chosen = moe.route_sigmoid(h.reshape(24, -1), p["router"], p["router_bias"], cfg)
    assert np.allclose(np.asarray(w.sum(axis=-1)), cfg.routed_scaling_factor, rtol=1e-5)
    chosen = np.asarray(chosen).reshape(4, 6, cfg.num_experts_per_tok)
    real = jnp.asarray(np.arange(6)[None, :] < np.asarray([6, 0, 3, 1])[:, None])
    _, stats = moe.moe_latent_block(p, cfg, h, real)
    rows = chosen[np.asarray(real)]
    load = np.bincount(rows[rows < cfg.experts_held], minlength=cfg.experts_held)
    # ... and, fourth, the experts the dense dispatch multiplied: every held one
    assert [int(s) for s in stats] == [int(load.sum()), int((load > 0).sum()), int(load.max()),
                                       cfg.experts_held]
    out_all, _ = moe.moe_latent_block(p, cfg, h)
    out_real, _ = moe.moe_latent_block(p, cfg, h, real)
    assert np.array_equal(np.asarray(out_all), np.asarray(out_real))   # counts only, never the result


def test_long_pieces_run_the_experts_block_by_block_to_the_same_result(monkeypatch):
    cfg = TINY
    p = jax.tree_util.tree_map(lambda a: a[0], init_params(jax.random.PRNGKey(1), cfg)["layers"]["experts"])
    h = jnp.asarray(np.random.default_rng(4).normal(size=(1, 70, cfg.d_model)), jnp.float32)
    whole, s1 = moe.moe_latent_block(p, cfg, h)
    monkeypatch.setattr(moe, "TOKEN_BLOCK", 32)
    blocks, s2 = moe.moe_latent_block(p, cfg, h)
    assert np.max(np.abs(whole - blocks)) < 1e-6 and np.array_equal(np.asarray(s1), np.asarray(s2))


# -- configuration and trees -----------------------------------------------------

def test_config_counts_the_published_model_and_the_cut():
    full = get_config("nemotron-3-super-120b")
    assert (full.n_layers, full.n_ssm_layers, full.n_full_layers, full.n_expert_layers) == (88, 40, 8, 40)
    assert abs(full.param_count - 120.67e9) < 0.01e9 and full.rotary_dim == 0
    m = bench_config.load_cell(CELL)["config"]["model"]
    cfg = system.model_config(m)
    assert hash(cfg) is not None and cfg.is_hybrid and cfg.is_pattern_stack
    # the depth follows the pattern: 22 of the published 88
    assert m["num_hidden_layers"] == 88 and cfg.n_layers == 22
    assert cfg.hybrid_override_pattern == full.hybrid_override_pattern[:22] == "MEMEMEM*EMEMEMEM*EMEME"
    assert (cfg.n_ssm_layers, cfg.n_full_layers, cfg.n_expert_layers) == (10, 2, 10)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset) == (512, 128, 0)
    assert (cfg.mamba_d_inner, cfg.mamba_conv_channels) == (8192, 10240)
    # the issue's count: 8.76B in the blocks held here, 0.27B in embedding and head
    top = 2 * cfg.vocab_size * cfg.d_model + cfg.d_model
    assert abs(cfg.param_count - top - 8.76e9) < 0.03e9 and top == 2 * 32768 * 4096 + 4096
    base = dict(name="x", vocab_size=64, d_model=32, n_layers=3, n_heads=2, n_kv_heads=2, d_ff=16,
                mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=8, n_groups=2,
                n_routed_experts=8, num_experts_per_tok=2, moe_latent_size=16,
                moe_intermediate_size=16, moe_shared_expert_intermediate_size=32)
    assert ModelConfig(**base, hybrid_override_pattern="EM*MM").n_layers == 5
    assert ModelConfig(**base, hybrid_override_pattern="MM").n_expert_layers == 0
    for bad in (dict(hybrid_override_pattern="MXE"), dict(hybrid_override_pattern=""),
                dict(hybrid_override_pattern="ME", n_experts_held=4, expert_offset=6),
                dict(hybrid_override_pattern="ME", num_experts_per_tok=9),
                dict(hybrid_override_pattern="ME", n_groups=3),
                dict(hybrid_override_pattern="ME", n_experts=4),
                dict(hybrid_override_pattern="ME", block="olmo2"),
                dict(hybrid_override_pattern="M*", layer_types=["linear_attention", "full_attention"])):
        with pytest.raises(ValueError):
            ModelConfig(**{**base, **bad})


def test_int8_tree_quantizes_every_matmul_and_nothing_small():
    cfg = get_config("nemotron-h-tiny")
    key = jax.random.PRNGKey(3)
    # one program a tree: drawn eagerly, every leaf is a compile of its own
    q = jax.jit(lambda k: init_params_quantized(k, cfg))(key)
    plain = jax.jit(lambda k: init_params(k, cfg))(key)
    quantized = {kind: {k for k, v in sub.items() if is_quantized(v)} for kind, sub in q["layers"].items()}
    assert quantized == {"mamba": {"in_proj", "out_proj"}, "attn": {"wq", "wk", "wv", "wo"},
                         "experts": {"w_in", "w_out", "w1", "w2", "s1", "s2"}}
    ex, mb = q["layers"]["experts"], q["layers"]["mamba"]
    assert ex["router"].dtype == jnp.float32 and ex["router_bias"].dtype == jnp.float32
    assert ex["w1"]["q"].shape == (3, 8, 64, 96) and ex["w1"]["s"].shape == (3, 8, 96)
    assert mb["A_log"].dtype == jnp.float32 and mb["conv"].dtype == jnp.bfloat16
    after = jax.jit(quantize_params)(plain)
    same = jax.tree_util.tree_map(
        lambda a, b: a.shape == b.shape and a.dtype == b.dtype
        and float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) <= 1.0,
        q, after)
    assert all(jax.tree_util.tree_leaves(same))
    toks = jnp.asarray(np.random.default_rng(0).integers(3, 500, (1, 40)), jnp.int32)
    fwd = jax.jit(lambda p: forward(p, cfg, toks, jnp.arange(40)[None])[0])
    a, b = fwd(q), fwd(plain)
    assert float(jnp.mean(jnp.abs(a - b))) < 0.1 * float(jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("quantization", ["int8", "none"])
def test_the_reference_regenerates_the_served_weights_exactly(quantization):
    m = tiny_model(quantization)
    big = 2**31 + 5
    served = reference.weights.all_weights(big, m)
    root = reference.weights.root_key(big)
    for ch, stack in reference.weights.KINDS.items():
        gen = jax.jit(partial(reference.weights.layer_weights, m=m, ch=ch))
        for row, li in enumerate(reference.weights.layers_of(m, ch)):
            one = gen(root, layer=jnp.int32(li))
            stacked = jax.tree_util.tree_map(lambda a: a[row], served["layers"][stack])
            same = jax.tree_util.tree_map(
                lambda a, b: np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)),
                one, stacked)
            assert all(jax.tree_util.tree_leaves(same)), (ch, li, same)
    mb, ex = served["layers"]["mamba"], served["layers"]["experts"]
    # small leaves show when they are left out: no gain at 1, no tap or bias at 0
    assert float(jnp.std(mb["norm"].astype(jnp.float32))) > 0.05
    assert float(jnp.mean(jnp.abs(mb["conv_b"].astype(jnp.float32)))) > 0.03
    assert float(jnp.std(ex["router_bias"])) > 0.02 and ex["router"].shape == (3, 128, 32)
    decay = jnp.exp(-jnp.exp(mb["A_log"]) * jax.nn.softplus(mb["dt_bias"]))
    assert float(decay.max() - decay.min()) > 0.1 and float(decay.max()) > 0.95

"""A stack of window layers (RoPE, a ring of K/V) beside global layers (no
positions, full stripes) with routed ReLU-gated GLU experts under every layer,
routed from the layer's input: the program against the benchmark's plain
reference, logits not tokens, at tiny widths on the CPU, through a ring that
wraps; what the comparison must see; the windowed flash kernel; the two K/V
leaves of the one cache tree; configuration and trees."""

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
    forward,
    init_kv_cache,
    init_paged_kv_cache,
    init_params,
    init_params_quantized,
    window_decode_kernel_blocks,
)
from kserve_vllm_mini_tpu.ops import flash_attention as fa
from kserve_vllm_mini_tpu.ops.attention import attention
from kserve_vllm_mini_tpu.ops.quant import is_quantized, quantize_params
# one padded prefill then token-by-token decode through the cache
from tests.test_hybrid import make_step, prefill_then_decode

reference = bench_config.load_module("references", "window_moe_decoder")
system = bench_config.load_module("systems", "engine")
CELL = "smallthinker-21b-pp4.doc-chat"
TINY = get_config("smallthinker-tiny", dtype="float32")


def tiny_model(quantization="int8", **keys):
    """The benchmark's rehearsal sizes of the configuration: two periods of a
    global layer and three window-16 layers; hidden 128, 4 / 2 heads x 32;
    8 experts (96), 2 a token."""
    m = bench_config.load_cell(CELL, rehearse=True)["config"]["model"]
    return {**m, "quantization": quantization, **keys}


@functools.cache
def _served_weights(seed, model_json):
    params = reference.weights.all_weights(seed, json.loads(model_json))
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params)


def served_weights(seed, m):
    """The benchmark's weights as the program is handed them, the bfloat16
    leaves widened (the values are the same): the program runs in float32. A
    caller gets a tree of its own, so a control may swap a leaf."""
    return jax.tree_util.tree_map(lambda a: a, _served_weights(seed, json.dumps(m, sort_keys=True)))


@functools.cache
def _wanted(seed, model_json, seqs):
    m = json.loads(model_json)
    return reference.logits_after(seed, m, [list(s) for s in seqs], [1] * len(seqs), 128, 64)


def wanted(seed, m, seqs):
    """The reference's logits for ``seqs`` (each from its second position on,
    padded to 128), one call for all of them and once a worker."""
    return _wanted(seed, json.dumps(m, sort_keys=True), tuple(tuple(s) for s in seqs))


def f32_config(m, **kw):
    return system.model_config({**m, "torch_dtype": "float32"}).scaled(**kw)


def a_sequence(m, n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(3, m["vocab_size"], n)]


def off_by(got, want):
    """The widest difference of two sets of logits, as a share of the largest."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# Float32 on both sides, the weights at the rehearsal's std 0.08. The sound program reads
# 7.3e-7 to 1.1e-6 of the largest logit off the reference over the eight shapes of the first
# test (jax 0.9.0, CPU): two float32 programs that order their sums differently, the
# reference with no ring, no cache and plain masks on absolute positions. The tolerance is
# 2e-5 of the largest logit, eighteen times the widest sound reading, and SEEN = 1e-3, fifty
# times the tolerance, is what every case of ``test_the_comparison_sees_what_it_must`` has
# to pass; the least of them reads 0.218 (218 x SEEN). Readings: ``w8a8`` 0.218,
# ``window_one_shorter`` 0.235 (ONE key in 16 of six layers), ``rope_theta_1e4`` 0.344,
# ``no_rope_on_the_window_layers`` 0.403, ``silu_for_relu`` 0.488,
# ``pad_pushes_real_keys_out_of_the_ring`` 0.629, ``no_normalisation_over_the_chosen`` 0.671,
# ``rope_on_the_global_layers`` 0.682, ``window_ignored`` 0.875,
# ``one_expert_a_token_fewer`` 0.888, ``router_fed_the_post_attention_stream`` 1.06,
# ``ring_written_before_it_is_read`` 1.46. A routing choice is discrete: were a float32
# logit to round the other way in one of the two programs a whole expert would swap and the
# gap would read ~1e-2, so the tolerance also says that none did.
TOLERANCE, SEEN = 2e-5, 1e-3

# (prompt, bucket): the ring is 16 long, so a prompt of 50 has wrapped it three times and
# 97 six times before the first decode step; 23 once; every bucket but 32 > 2 x 16 pads past
# the prompt, and the twelve decode steps wrap on
SHAPES = [(50, 64), (70, 128), (23, 32), (97, 128)]


@pytest.mark.parametrize("quantization", ["int8", "none"])
@pytest.mark.parametrize("n_prompt,bucket", SHAPES)
def test_prefill_then_decode_against_the_references_full_forward(quantization, n_prompt, bucket):
    """One padded fresh piece (its window layers attend under the window's
    mask, then leave their last 16 real positions in the ring), then twelve
    tokens one at a time through both leaves, against the reference's forward
    pass over the whole sequence, which has no ring."""
    m = tiny_model(quantization)
    seqs = [a_sequence(m, n + 12, n) for n, _ in SHAPES]
    which = SHAPES.index((n_prompt, bucket))
    seq, want = seqs[which], wanted(7, m, seqs)[which]
    got, cache = prefill_then_decode(served_weights(7, m), f32_config(m), seq, n_prompt, bucket)
    assert got.shape[0] == len(seq)
    assert off_by(got[:-1], want) < TOLERANCE
    assert cache["k_win"].shape == (6, 1, 2, 16, 32) and cache["k"].shape == (2, 1, 2, 256, 32)


# name -> what the program's configuration is changed to
BY_CONFIG = {
    "window_ignored": lambda c: c.scaled(sliding_window_size=10 ** 6),
    "window_one_shorter": lambda c: c.scaled(sliding_window_size=15),
    "rope_on_the_global_layers": lambda c: c.scaled(rope_layout=(1,) * len(c.rope_layout)),
    "no_rope_on_the_window_layers": lambda c: c.scaled(rope_layout=(0,) * len(c.rope_layout)),
    "no_normalisation_over_the_chosen": lambda c: c.scaled(norm_topk_prob=False),
    "one_expert_a_token_fewer": lambda c: c.scaled(num_experts_per_tok=1),
    "rope_theta_1e4": lambda c: c.scaled(rope_theta=1e4),
    "w8a8": lambda c: c.scaled(quant_mode="w8a8"),
}
BY_PATCH = ["router_fed_the_post_attention_stream", "silu_for_relu",
            "ring_written_before_it_is_read", "pad_pushes_real_keys_out_of_the_ring"]


@pytest.mark.parametrize("broken", list(BY_CONFIG) + BY_PATCH)
def test_the_comparison_sees_what_it_must(broken, monkeypatch):
    """Each of these passes every shape check and serves fluent tokens; each
    moves the logits past the comparison's tolerance with room (``SEEN``)."""
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    want = wanted(7, m, [seq])[0]
    params, cfg, valid = served_weights(7, m), f32_config(m), 50
    if broken in BY_CONFIG:
        cfg = BY_CONFIG[broken](cfg)
    elif broken == "router_fed_the_post_attention_stream":
        block = moe.moe_glu_block
        monkeypatch.setattr(moe, "moe_glu_block", lambda p, c, h, real=None, route_from=None, **kw: block(
            p, c, h, real, route_from=h, **kw))
    elif broken == "silu_for_relu":
        monkeypatch.setattr(jax.nn, "relu", jax.nn.silu)
    elif broken == "ring_written_before_it_is_read":
        # a piece longer than the ring, written first: the keys of its positions
        # before the last 16 are gone when its first queries look for them
        attend = fa.prefill_attention

        def after_the_write(q, k, v, kernels="auto", window=None):
            t = q.shape[2]
            if window is not None and t > window:
                kept = (jnp.arange(t) >= t - window)[None, None, :, None]
                k, v = jnp.where(kept, k, 0.0), jnp.where(kept, v, 0.0)
            return attend(q, k, v, kernels=kernels, window=window)

        monkeypatch.setattr(fa, "prefill_attention", after_the_write)
    elif broken == "pad_pushes_real_keys_out_of_the_ring":
        valid = 64                          # the 14 pad positions are taken for real
    # its own jitted step: the patched program must be traced, not a cached one
    got, _ = prefill_then_decode(params, cfg, seq, 50, 64, valid=valid, step=make_step())
    assert off_by(got[:-1], want) > SEEN, broken


def test_the_sound_program_under_the_same_patches_machinery_agrees():
    """The control of the controls: the same calls, nothing broken, agree."""
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    got, _ = prefill_then_decode(served_weights(7, m), f32_config(m), seq, 50, 64,
                                 step=make_step())
    assert off_by(got[:-1], wanted(7, m, [seq])[0]) < TOLERANCE


def test_the_cache_free_forward_is_the_fresh_prefill_and_agrees_too():
    m = tiny_model()
    seq = a_sequence(m, 70, 5)
    toks = jnp.asarray([seq + [0] * 58], jnp.int32)
    lg, none = jax.jit(forward, static_argnames="cfg")(      # eager, every operation is a dispatch
        served_weights(7, m), f32_config(m), toks, jnp.arange(128)[None],
        valid_len=jnp.asarray([70]))
    assert none is None
    assert off_by(np.asarray(lg[0, :69]), wanted(7, m, [seq])[0]) < TOLERANCE


@pytest.mark.parametrize("t,window,block", [(256, 64, 32), (256, 100, 64), (512, 128, 128),
                                            (128, 200, 32), (1024, 300, 512)])
def test_the_flash_kernel_under_a_window_is_the_masked_oracle(t, window, block):
    """Key blocks wholly outside ``(i - W, i]`` skipped and not fetched, the
    edge blocks masked, windows that are no multiple of a block, a window
    wider than the piece (the causal kernel), a tile as wide as a long piece's."""
    ks = jax.random.split(jax.random.PRNGKey(t + window), 3)
    q = jax.random.normal(ks[0], (1, 4, t, 32))
    k, v = (jax.random.normal(kk, (1, 2, t, 32)) for kk in ks[1:])
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    want = attention(q, k, v, ((j <= i) & (i - j < window))[None, None])
    got = fa.flash_attention(q, k, v, block_q=block, block_k=block, interpret=True, window=window)
    assert float(jnp.abs(got - want).max()) < 2e-6
    if window < t:
        causal = fa.flash_attention(q, k, v, block_q=block, block_k=block, interpret=True)
        assert float(jnp.abs(causal - want).max()) > 1e-2
    assert float(jnp.abs(fa.prefill_attention(q, k, v, kernels="off", window=window)
                         - want).max()) < 2e-6


def test_a_long_piece_runs_wider_tiles_and_the_accepted_lengths_the_ones_they_had(monkeypatch):
    seen = []
    monkeypatch.setattr(fa, "flash_attention", lambda q, k, v, **kw: seen.append(
        (q.shape[2], kw["block_q"], kw["block_k"], kw["window"])) or q)
    for t in (64, 1024, 2048, 8192):
        x = jax.ShapeDtypeStruct((1, 4, t, 128), jnp.bfloat16)
        jax.eval_shape(lambda q: fa.prefill_attention(q, q, q, kernels="interpret",
                                                      window=4096 if t == 8192 else None), x)
    assert seen == [(64, 64, 64, None), (1024, 128, 128, None), (2048, 512, 512, None),
                    (8192, 512, 512, 4096)]


def test_the_softmax_router_is_the_softmax_over_all_its_top_renormalised():
    cfg = TINY
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 128))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (128, 8))
    w, chosen = moe.route_softmax(x, router, cfg)
    probs = jax.nn.softmax(jnp.dot(x, router, precision="highest"), axis=-1)
    top, ids = jax.lax.top_k(probs, 2)
    assert np.array_equal(np.asarray(chosen), np.asarray(ids))
    assert np.allclose(w, top / top.sum(-1, keepdims=True), atol=1e-6)
    assert np.allclose(w.sum(-1), 1.0, atol=1e-6)
    unnormed, _ = moe.route_softmax(x, router, cfg.scaled(norm_topk_prob=False))
    assert np.allclose(unnormed, top, atol=1e-6)


@pytest.mark.parametrize("n,blocks", [(200, 1), (256, 1), (512, 2), (1024, 4), (1025, 2),
                                      (4096, 4), (8192, 8)])
def test_only_pieces_longer_than_any_accepted_cell_sends_run_the_long_token_block(
        n, blocks, monkeypatch):
    """The dense dispatch (what a piece runs without the kernels: the CPU, a
    mesh, ``w8a8``) unrolls blocks of 256 up to 1024 tokens, as it always
    did, and blocks of 1024 past that."""
    calls = []

    def body(xb):
        calls.append(xb.shape[0])
        return jnp.zeros((3, xb.shape[0], 8), xb.dtype)

    monkeypatch.setattr(moe, "experts_of", lambda p, b, qm: body)
    stacks = {k: jax.ShapeDtypeStruct((3, 8, 8), jnp.float32) for k in ("w_gate", "w_up", "w_down")}
    out, landed, _ = jax.eval_shape(
        lambda x, w, c, p: moe._run_held(x, w, c, 0, p, moe.GLU_SILU, "dequant"),
        jax.ShapeDtypeStruct((n, 8), jnp.float32), jax.ShapeDtypeStruct((n, 2), jnp.float32),
        jax.ShapeDtypeStruct((n, 2), jnp.int32), stacks)
    assert out.shape == (n, 8) and landed.shape == (n, 3) and len(calls) == blocks
    assert set(calls) <= {n, moe.TOKEN_BLOCK if n <= moe.LONG_PIECE else moe.LONG_TOKEN_BLOCK}


def test_two_kv_leaves_of_different_lengths_in_one_tree():
    cache = init_kv_cache(TINY, 3, max_seq=200)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 3, 2, 200, 32), "v": (2, 3, 2, 200, 32),
        "k_win": (6, 3, 2, 16, 32), "v_win": (6, 3, 2, 16, 32)}
    # a cache shorter than the window never wraps: the ring is as long as the stripes
    assert init_kv_cache(TINY, 1, max_seq=8)["k_win"].shape == (6, 1, 2, 8, 32)
    assert TINY.ring_len(16384) == 16 and TINY.scaled(sliding_window_size=4096).ring_len(2048) == 2048
    with pytest.raises(ValueError, match="no int8 leaves"):
        init_kv_cache(TINY, 1, quantized=True)
    with pytest.raises(ValueError, match="dense cache"):
        init_paged_kv_cache(TINY, 8)
    big = get_config("smallthinker-21b-a3b", n_layers=12)
    shapes = jax.eval_shape(lambda: init_kv_cache(big, 16, max_seq=16384))
    assert shapes["k_win"].shape == (9, 16, 4, 4096, 128) and shapes["k"].shape == (3, 16, 4, 16384, 128)
    assert sum(a.size * 2 for a in (shapes["k_win"], shapes["v_win"])) == 9 * 16 * 4 * 4096 * 128 * 2 * 2


def test_a_continuation_piece_against_the_ring_is_refused_by_name():
    params = init_params(jax.random.PRNGKey(0), TINY)
    cache = init_kv_cache(TINY, 1, max_seq=128)
    with pytest.raises(ValueError, match="continuation piece.*ring"):
        forward(params, TINY, jnp.zeros((1, 8), jnp.int32), 32 + jnp.arange(8)[None], cache,
                jnp.asarray([32]))


def test_both_leaves_go_through_the_decode_kernel_and_agree_with_the_eager_reads():
    """128-wide heads, the kernel interpreted: four steps past a wrapped ring,
    a parked row beside the live one."""
    cfg = TINY.scaled(explicit_head_dim=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_kv_cache(cfg, 2, max_seq=64)
    assert window_decode_kernel_blocks(cfg, "interpret", cache) == (64, 16)
    assert window_decode_kernel_blocks(cfg, "off", cache) is None
    assert window_decode_kernel_blocks(TINY, "interpret", init_kv_cache(TINY, 2, max_seq=64)) is None
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 3, 500)
    # jitted, one program a mode: an eager forward dispatches every operation on its own
    run = jax.jit(forward, static_argnames=("cfg", "fresh_prefill", "kernels"))
    _, cache = run(params, cfg, toks[:, :32], jnp.broadcast_to(jnp.arange(32), (2, 32)),
                   cache, jnp.zeros((2,), jnp.int32), fresh_prefill=True, kernels="off")
    caches = {"off": cache, "interpret": cache}
    live = jnp.asarray([1, 0])
    for q in range(32, 36):
        lens = jnp.asarray([q, 7], jnp.int32)
        out = {}
        for kernels in caches:
            out[kernels], caches[kernels] = run(
                params, cfg, toks[:, q:q + 1], lens[:, None], caches[kernels], lens,
                kernels=kernels, valid_len=live)
        assert float(jnp.abs(out["off"][0] - out["interpret"][0]).max()) < 2e-5
    # the parked row's ring is as the prefill left it: a row that is not decoding writes nothing
    for name in ("k_win", "v_win"):
        assert np.array_equal(np.asarray(caches["off"][name][:, 1]), np.asarray(cache[name][:, 1]))
        assert not np.array_equal(np.asarray(caches["off"][name][:, 0]), np.asarray(cache[name][:, 0]))


LAYOUT = (0, 1, 1, 1) * 2


@pytest.mark.parametrize("keys,why", [
    (dict(sliding_window_layout=LAYOUT[:5]), "equally long"),
    (dict(sliding_window_layout=LAYOUT[:5], rope_layout=LAYOUT[:5]), "at least n_layers"),
    (dict(rope_layout=None), "come together"),
    (dict(sliding_window_size=None), "needs sliding_window_size"),
    (dict(sliding_window_layout=(0, 2, 1, 1) * 2), "0 or 1 a layer"),
    (dict(block="olmo2"), "block='llama'"),
    (dict(block="phi"), "block='llama'"),
    (dict(sliding_window=16), "sliding_window"),
    (dict(n_experts=4), "n_experts"),
    (dict(n_routed_experts=0), "routed experts"),
    (dict(num_experts_per_tok=9), "num_experts_per_tok exceeds"),
])
def test_the_layouts_are_validated(keys, why):
    with pytest.raises(ValueError, match=why):
        TINY.scaled(**keys)


def test_a_cut_stack_reads_the_first_entries_of_the_published_layouts():
    cut = TINY.scaled(n_layers=5, sliding_window_layout=(0, 1, 1, 1) * 13,
                      rope_layout=[0, 1, 1, 1] * 13)
    assert cut.window_layers == (False, True, True, True, False)
    assert (cut.n_window_layers, cut.n_global_layers, cut.n_full_layers) == (3, 2, 2)
    assert cut.is_window_stack and cut.is_hybrid and cut.n_expert_layers == 5
    assert isinstance(cut.rope_layout, tuple) and hash(cut)
    # every layer global: no window size needed, no ring layer kept
    flat = TINY.scaled(sliding_window_layout=(0,) * 8, sliding_window_size=None)
    assert flat.n_window_layers == 0 and init_kv_cache(flat, 1, 32)["k_win"].shape[0] == 0
    plain = get_config("mistral-tiny")
    assert not plain.is_window_stack and not plain.is_hybrid and plain.window_layers == ()


def test_param_count_is_the_equations():
    """21.51 B parameters, of which a token multiplies 2.93 B in the layers
    (attention, its 6 chosen experts, the gains; beside the head)."""
    cfg = get_config("smallthinker-21b-a3b")
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    expert = 3 * 2560 * 768
    layer = attn + 64 * expert + 2560 * 64 + 2 * 2560
    assert cfg.param_count == 52 * layer + 2 * 151_936 * 2560 + 2560 == 21_506_562_560
    assert round(cfg.param_count / 1e9, 2) == 21.51
    active = 52 * (attn + 6 * expert + 2 * 2560)
    assert round(active / 1e9, 2) == 2.93
    # nothing is multiplied by a dense feed-forward's width: d_ff sizes no weight
    assert cfg.scaled(d_ff=12_345).param_count == cfg.param_count
    tiny = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TINY.scaled(d_ff=77)))
    leaves = jax.tree_util.tree_leaves(tiny)
    assert sum(a.size for a in leaves) == TINY.param_count
    assert not any(77 in a.shape for a in leaves)


def test_the_benchmarks_tree_is_the_programs_and_holds_no_dense_width():
    m = tiny_model(intermediate_size=77)
    cfg = system.model_config(m)
    served = jax.eval_shape(lambda: reference.weights.all_weights(3, m))
    own = jax.eval_shape(lambda: init_params_quantized(jax.random.PRNGKey(0), cfg))
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), served)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), own))
    assert not any(77 in a.shape for a in jax.tree_util.tree_leaves(served))
    assert set(served["layers"]) == {"attn", "experts"} and "lm_head" in served
    q = quantize_params(init_params(jax.random.PRNGKey(0), TINY))
    assert all(is_quantized(q["layers"]["attn"][n]) for n in ("wq", "wk", "wv", "wo"))
    assert all(is_quantized(q["layers"]["experts"][n]) for n in ("w_gate", "w_up", "w_down"))
    assert not is_quantized(q["layers"]["experts"]["router"])
    assert q["layers"]["experts"]["router"].dtype == jnp.float32


def test_the_served_configuration_is_the_published_one_cut_in_depth_alone():
    cell = bench_config.load_cell(CELL)
    m, cfg = cell["config"]["model"], system.model_config(cell["config"]["model"])
    assert (cfg.n_layers, cfg.n_window_layers, cfg.n_global_layers) == (12, 9, 3)
    assert len(m["sliding_window_layout"]) == len(m["rope_layout"]) == 52
    assert cfg.sliding_window_layout == tuple(m["sliding_window_layout"])
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (2560, 28, 4, 128)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size) == (64, 6, 768)
    assert (cfg.vocab_size, cfg.max_seq_len, cfg.sliding_window_size) == (151_936, 16_384, 4096)
    assert cfg.rope_theta == 1.5e6 and cfg.rms_eps == 1e-6 and not cfg.tie_embeddings
    full = get_config("smallthinker-21b-a3b")
    assert cfg.scaled(n_layers=52, name=full.name, d_ff=0) == full
    # the file's arithmetic: bytes this chip holds
    matmuls = 12 * (2 * 2560 * 3584 + 2 * 2560 * 512 + 64 * 3 * 2560 * 768)
    assert matmuls == 12 * 398_458_880
    assert reference.weights.layer_nbytes(m, "attn") + reference.weights.layer_nbytes(
        m, "experts") == 398_458_880 + 1_077_248

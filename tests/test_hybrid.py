"""A hybrid stack (models/linear_attention.py beside full attention): the
program against the benchmark's plain reference, logits not tokens, at tiny
widths on the CPU; what must hold of a recurrent state in a serving cache;
what the engine refuses; and the homogeneous models left as they were."""

import hashlib
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import config as bench_config
from kserve_vllm_mini_tpu.models import linear_attention as la
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

reference = bench_config.load_module("references", "hybrid_linear_decoder")
system = bench_config.load_module("systems", "engine")
TINY = get_config("olmo-hybrid-tiny", dtype="float32")


def tiny_model(quantization="int8"):
    """The benchmark's rehearsal sizes of the hybrid configuration."""
    m = bench_config.load_cell("olmo-hybrid-7b.chat", rehearse=True)["config"]["model"]
    return {**m, "quantization": quantization}


def served_weights(seed, m):
    """The benchmark's weights as the program is handed them, with the
    bfloat16 leaves widened (the values are the same) so that the program
    can run in float32 and be held to the reference tightly."""
    params = reference.weights.all_weights(seed, m)
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params)


def f32_config(m):
    return system.model_config({**m, "torch_dtype": "float32"})


def make_step():
    """One cached forward step, jitted (an eager ``forward`` compiles its
    scan anew at every call). A new one where a test patches the program."""
    @partial(jax.jit, static_argnames=("cfg", "fresh"))
    def step(params, cfg, toks, off, cache, valid, fresh=False):
        pos = off[:, None] + jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
        return forward(params, cfg, toks, pos, cache, off, fresh_prefill=fresh, valid_len=valid)

    return step


STEP = make_step()


def prefill_then_decode(params, cfg, seq, n_prompt, bucket, pieces=None, valid=None, step=STEP):
    """Logits at every position: the prompt through one padded fresh
    prefill (or ``pieces`` of it, each padded to ``bucket``), the rest one
    token at a time through the cache. ``valid`` overrides the length the
    (single) prefill is told."""
    cache = init_kv_cache(cfg, 1, max_seq=256)
    rows, off = [], 0
    for n in pieces or [n_prompt]:
        toks = jnp.asarray([seq[off:off + n] + [0] * (bucket - n)], jnp.int32)
        lg, cache = step(params, cfg, toks, jnp.asarray([off], jnp.int32), cache,
                         jnp.asarray([valid or n], jnp.int32), fresh=off == 0)
        rows.append(np.asarray(lg[0, :n]))
        off += n
    for t in range(n_prompt, len(seq)):
        lg, cache = step(params, cfg, jnp.asarray([[seq[t]]], jnp.int32),
                         jnp.asarray([t], jnp.int32), cache, jnp.asarray([1], jnp.int32))
        rows.append(np.asarray(lg[0]))
    return np.concatenate(rows), cache


@pytest.mark.parametrize("quantization", ["int8", "none"])
@pytest.mark.parametrize("n_prompt,bucket", [(50, 64), (70, 128), (23, 32), (97, 128)])
def test_prefill_then_decode_against_the_references_full_forward(quantization, n_prompt, bucket):
    """Prompt lengths that are no multiple of the chunk (64) or the bucket.
    Float32 on both sides: 1e-3 of the largest logit would not hold with the
    state in bfloat16, a pad position in the state, or a gate, the
    convolution or a norm left out (each breaks it by 1e-2 or more)."""
    m = tiny_model(quantization)
    seq = [int(t) for t in np.random.default_rng(n_prompt).integers(3, m["vocab_size"], n_prompt + 12)]
    want = reference.logits_after(7, m, [seq], [1], 128, 64)[0]
    got, _ = prefill_then_decode(served_weights(7, m), f32_config(m), seq, n_prompt, bucket)
    assert got.shape[0] == len(seq)
    assert np.max(np.abs(got[:-1] - want)) < 1e-3 * np.max(np.abs(want))


@pytest.mark.parametrize("broken", ["bf16_state", "pad_in_state", "no_gate", "no_conv", "no_norm"])
def test_the_comparison_sees_what_it_must(broken, monkeypatch):
    m = tiny_model()
    seq = [int(t) for t in np.random.default_rng(5).integers(3, m["vocab_size"], 70)]
    want = reference.logits_after(7, m, [seq], [1], 128, 64)[0]
    params, cfg = served_weights(7, m), f32_config(m)
    valid = 50
    if broken == "bf16_state":
        step = la.recurrent_step
        monkeypatch.setattr(la, "recurrent_step", lambda s, *a: (
            lambda out: (out[0].astype(jnp.bfloat16).astype(jnp.float32), out[1]))(step(s, *a)))
    elif broken == "pad_in_state":
        valid = 64                          # the 14 pad positions are taken for real
    elif broken == "no_gate":
        params["layers"]["linear"]["wb"] = jnp.zeros_like(params["layers"]["linear"]["wb"])
    elif broken == "no_conv":
        taps = params["layers"]["linear"]["conv"]
        params["layers"]["linear"]["conv"] = jnp.zeros_like(taps).at[:, -1].set(1.0)
    elif broken == "no_norm":
        params["layers"]["linear"]["o_norm"] = jnp.ones_like(params["layers"]["linear"]["o_norm"])
    # its own jitted step: the patched program must be traced, not a cached one
    got, _ = prefill_then_decode(params, cfg, seq, 50, 64, valid=valid, step=make_step())
    assert np.max(np.abs(got[:-1] - want)) > 1e-2 * np.max(np.abs(want))


@pytest.mark.parametrize("pieces", [[32, 32, 6], [64, 6], [17, 53], [1, 64, 5]])
def test_a_prompt_prefilled_in_pieces_is_the_prompt_prefilled_at_once(pieces):
    m = tiny_model()
    params, cfg = served_weights(3, m), f32_config(m)
    seq = [int(t) for t in np.random.default_rng(1).integers(3, m["vocab_size"], 76)]
    whole, c1 = prefill_then_decode(params, cfg, seq, 70, 128)
    split, c2 = prefill_then_decode(params, cfg, seq, 70, 64, pieces=pieces)
    assert np.max(np.abs(whole - split)) < 1e-3 * np.max(np.abs(whole))
    assert np.max(np.abs(c1["lin_state"] - c2["lin_state"])) < 1e-4
    assert np.max(np.abs(c1["lin_conv"] - c2["lin_conv"])) < 1e-4


def scan_inputs(t, seed=0, b=2, h=3, dk=24, dv=48):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q, k = la._l2(f(b, t, h, dk)) * dk ** -0.5, la._l2(f(b, t, h, dk))
    g = -jnp.asarray(rng.uniform(0.001, 0.7, (b, t, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (b, t, h)), jnp.float32)
    return f(b, h, dk, dv), q, k, f(b, t, h, dv), g, beta


@pytest.mark.parametrize("t", [1, 7, 32, 64, 65, 150, 256])
def test_chunk_parallel_is_the_recurrence_token_by_token(t):
    s0, *xs = scan_inputs(t, seed=t)
    s1, o1 = la.recurrent_scan(s0, *xs)
    s2, o2 = jax.jit(la.chunked_scan)(s0, *xs)
    assert np.max(np.abs(s1 - s2)) < 2e-5 and np.max(np.abs(o1 - o2)) < 2e-5


def test_positions_past_the_valid_length_leave_state_and_tail_bit_for_bit():
    cfg = TINY
    p = jax.tree_util.tree_map(lambda a: a[0], init_params(jax.random.PRNGKey(0), cfg)["layers"]["linear"])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 128, cfg.d_model)), jnp.float32)
    h, dk, dv = cfg.linear_num_key_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    state = jnp.asarray(rng.normal(size=(3, h, dk, dv)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(3, 3, cfg.linear_conv_channels)), jnp.float32)
    valid = jnp.asarray([0, 70, 128], jnp.int32)
    mixer = jax.jit(partial(la.linear_mixer, cfg=cfg))
    _, s_new, t_new = mixer(p, x=x, state=state, tail=tail, valid=valid)
    assert np.array_equal(np.asarray(s_new[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(t_new[0]), np.asarray(tail[0]))
    # row 1: what 70 real positions alone give, whatever the 58 pad positions hold
    _, s_cut, t_cut = mixer(p, x=x[1:2, :70], state=state[1:2], tail=tail[1:2],
                            valid=jnp.asarray([70], jnp.int32))
    assert np.max(np.abs(s_new[1] - s_cut[0])) < 1e-5
    assert np.array_equal(np.asarray(t_new[1]), np.asarray(t_cut[0]))
    assert not np.array_equal(np.asarray(s_new[2]), np.asarray(state[2]))
    # one token, not live: held
    _, s1, t1 = mixer(p, x=x[:, :1], state=state, tail=tail,
                      valid=jnp.asarray([0, 1, 0], jnp.int32))
    for row in (0, 2):
        assert np.array_equal(np.asarray(s1[row]), np.asarray(state[row]))
        assert np.array_equal(np.asarray(t1[row]), np.asarray(tail[row]))
    assert not np.array_equal(np.asarray(s1[1]), np.asarray(state[1]))


def test_one_cache_tree_slot_axis_at_dim_one():
    cfg = TINY
    cache = init_kv_cache(cfg, 5, max_seq=64)
    h = cfg.linear_num_key_heads
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((2, 5, 4, 64, 32), "float32"), "v": ((2, 5, 4, 64, 32), "float32"),
        "lin_state": ((6, 5, h, 24, 48), "float32"),
        "lin_conv": ((6, 5, 3, h * (2 * 24 + 48)), "float32"),
    }
    bf16 = init_kv_cache(get_config("olmo-hybrid-tiny"), 5, max_seq=64, quantized=True)
    assert bf16["lin_state"].dtype == jnp.float32 and bf16["lin_conv"].dtype == jnp.bfloat16
    assert bf16["k"].dtype == jnp.int8 and bf16["k_s"].shape == (2, 5, 4, 64)
    marked = {k: v.at[:, 3].set(1) for k, v in cache.items()}
    sub = slice_cache_slots(marked, 3)
    assert all(v.shape[1] == 1 and bool((v == 1).all()) for v in sub.values())
    back = update_cache_slots(cache, sub, 1)
    assert all(bool((v[:, 1] == 1).all()) and bool((v[:, 0] == 0).all()) for v in back.values())
    with pytest.raises(ValueError, match="no positions to page"):
        init_paged_kv_cache(cfg, 8)


def test_config_counts_the_published_model_and_refuses_other_patterns():
    m = bench_config.load_cell("olmo-hybrid-7b.chat")["config"]["model"]
    cfg = system.model_config(m)
    assert isinstance(cfg.layer_types, tuple) and hash(cfg) is not None
    assert (cfg.n_full_layers, cfg.n_linear_layers, cfg.layer_period) == (8, 24, 4)
    assert cfg.linear_conv_channels == 11520
    # the issue's count: 6.65B in the decoder, 0.77B in embedding and head
    top = 2 * cfg.vocab_size * cfg.d_model + cfg.d_model
    assert abs(cfg.param_count - top - 6.65e9) < 0.02e9 and abs(top - 0.771e9) < 0.001e9
    base = dict(name="x", vocab_size=64, d_model=32, n_layers=4, n_heads=2, n_kv_heads=2,
                d_ff=64, block="olmo2", linear_num_key_heads=2, linear_num_value_heads=2,
                linear_key_head_dim=8, linear_value_head_dim=16)
    lin, full = "linear_attention", "full_attention"
    assert ModelConfig(**base, layer_types=[lin, full, lin, full]).layer_period == 2
    for bad in ([lin, lin, full], [full, lin, lin, full], [lin, lin, lin, lin],
                [full, full, full, full], [lin, full, lin, lin]):
        with pytest.raises(ValueError):
            ModelConfig(**base, layer_types=bad)
    with pytest.raises(ValueError, match="olmo2"):
        ModelConfig(**{**base, "block": "llama"}, layer_types=[lin, full, lin, full])
    with pytest.raises(ValueError, match="needs layer_types"):
        ModelConfig(**base)      # olmo2 is the hybrid's block, no axis of its own
    with pytest.raises(ValueError, match="grouped"):
        ModelConfig(**{**base, "linear_num_value_heads": 4}, layer_types=[lin, full, lin, full])


def test_int8_tree_quantizes_the_mixers_matmuls_and_nothing_small():
    cfg = get_config("olmo-hybrid-tiny")
    key = jax.random.PRNGKey(3)
    q = init_params_quantized(key, cfg)
    lin, full = q["layers"]["linear"], q["layers"]["full"]
    assert {k for k, v in lin.items() if is_quantized(v)} == {
        "wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down"}
    assert {k for k, v in full.items() if is_quantized(v)} == {
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert lin["A_log"].dtype == jnp.float32 and lin["wa"].dtype == jnp.bfloat16
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


# -- the homogeneous models are what they were ---------------------------------

# sha256 (16 hex) of every leaf's path, dtype, shape and bytes, and of the
# lowered StableHLO of a decode step and a fresh prefill. The trees' digests
# (params, cache, cache_int8, params_int8) were taken at the parent commit of
# the PR that added hybrid stacks (893a704) and have not moved since. The six
# decode_hlo / prefill_hlo digests were re-taken at PR 31's tree, on purpose:
# qkv_proj gained one optimization_barrier before the head split (the q, k
# and v projections read their weights in place), so both programs differ
# from the parent's by that one operation. The five hybrid tiny presets'
# tree digests were taken at d695df8, the parent of the PR that folded the
# stacked kinds' three initialisers into one (``_init_stacks``) and read the
# cache's leaves off ``cfg.layer_plan``: no drawn value and no leaf moved.
# They carry no program digest (a ring's write may move past its attention).
PARENT = json.loads('''{"llama-tiny": {"params": "f326e46ecc0d9315", "cache": "61efc8faa242a0a3", "cache_int8": "1ad0de8f2066f61a", "params_int8": "f25451afb7173884", "decode_hlo": "796b38f2403139d8", "prefill_hlo": "bbb66e9249c3eadc"}, "mistral-tiny": {"params": "f326e46ecc0d9315", "cache": "61efc8faa242a0a3", "cache_int8": "1ad0de8f2066f61a", "params_int8": "f25451afb7173884", "decode_hlo": "f88c95e63b1b9932", "prefill_hlo": "bbb66e9249c3eadc"}, "qwen-tiny": {"params": "27fbb1315783b56a", "cache": "61efc8faa242a0a3", "cache_int8": "1ad0de8f2066f61a", "params_int8": "290c0c9654e97c79", "decode_hlo": "40c6717d18125800", "prefill_hlo": "1f00dba2b2c4eab2"}, "phi-tiny": {"params": "841795a40581b66b", "cache": "2816b46ab1964ad1", "cache_int8": "256289a52c3fd8d4"}, "gemma-tiny": {"params": "d74fc28b45ddc2c0", "cache": "d8564aaa7d973d13", "cache_int8": "7227f7177bbf0635"}, "mixtral-tiny": {"params": "8ea6f1cdcca11876", "cache": "61efc8faa242a0a3", "cache_int8": "1ad0de8f2066f61a"}, "falcon-h1-tiny": {"params": "b627e6c501e13b27", "cache": "fc3d845b2f1785f9", "cache_int8": "bc14ccb5c35f0127", "params_int8": "baae9f2688022100"}, "lfm2-moe-tiny": {"params": "d4ab9d0209f1b303", "cache": "bec8c1b1524199cc", "cache_int8": "11bdfbc73a33acbd", "params_int8": "b460f4ba8c820b35"}, "nemotron-h-tiny": {"params": "95653b2cd3f336e2", "cache": "095dc6b229243597", "cache_int8": "8e915373f9f96469", "params_int8": "fd09f544bc4d20f1"}, "olmo-hybrid-tiny": {"params": "808e4496c3ba42c2", "cache": "955bf329b4167fe9", "cache_int8": "e61abfaa98317899", "params_int8": "5564db4c187e706f"}, "smallthinker-tiny": {"params": "b6036cdb62a12d2c", "cache": "a9ccd274133e0de4", "params_int8": "1d9ba9b09dd73b77"}}''')


def _digest(tree):
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_flatten_with_path(tree)[0], key=lambda kv: str(kv[0])):
        a = np.asarray(leaf)
        for part in (str(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_homogeneous_trees_and_programs_are_byte_for_byte_the_parents(name):
    cfg, want = get_config(name), PARENT[name]
    key = jax.random.PRNGKey(7)
    params = init_params(key, cfg)
    assert _digest(params) == want["params"]
    assert _digest(init_kv_cache(cfg, 3, max_seq=64)) == want["cache"]
    if "cache_int8" in want:         # a ring has no int8 leaves
        assert _digest(init_kv_cache(cfg, 3, max_seq=64, quantized=True)) == want["cache_int8"]
    if "params_int8" not in want:
        return
    assert _digest(init_params_quantized(key, cfg)) == want["params_int8"]
    if "decode_hlo" not in want:
        return
    cache = init_kv_cache(cfg, 3, max_seq=64)
    toks, lens = jnp.zeros((3, 1), jnp.int32), jnp.asarray([5, 9, 0], jnp.int32)
    dec = jax.jit(lambda p, c, t, l: forward(p, cfg, t, l[:, None], c, l)).lower(  # noqa: E741
        params, cache, toks, lens).as_text()
    pre = jax.jit(lambda p, c, t: forward(
        p, cfg, t, jnp.arange(16)[None], c, jnp.zeros((1,), jnp.int32), fresh_prefill=True,
        logit_index=jnp.asarray([6]))).lower(
            params, init_kv_cache(cfg, 1, max_seq=64), jnp.zeros((1, 16), jnp.int32)).as_text()
    assert hashlib.sha256(dec.encode()).hexdigest()[:16] == want["decode_hlo"]
    assert hashlib.sha256(pre.encode()).hexdigest()[:16] == want["prefill_hlo"]

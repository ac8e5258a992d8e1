"""runtime/steps.py: the builders of the engine's compiled steps, directly.

The engine modules that serve through the paged, continuation, drafter,
LoRA and masked variants of the steps are slow-marked, and no benchmark
cell runs them; this file checks each variant of the builders against
``models.llama.forward`` at a tiny size, and pins the two names everything
outside the engine finds a program by: the jit name (``jit_<name>`` in a
trace) and the compile-statistics label.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import (
    forward,
    init_kv_cache,
    init_paged_kv_cache,
    init_params,
    slice_cache_slots,
)
from kserve_vllm_mini_tpu.runtime import steps
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig

CFG = get_config("llama-tiny", max_seq_len=64, dtype="float32")
HYBRID = get_config("olmo-hybrid-tiny", max_seq_len=64, dtype="float32")
S, SLOT, BLK, PAD = 3, 1, 8, 0
# the slot's pool blocks: scattered, nothing may assume contiguity
ROW = jnp.asarray([[5, 2, 7, 0, 9, 3, 8, 1]], jnp.int32)
BUCKETS = EngineConfig(min_prefill_bucket=16, max_prefill_len=32)
PROMPT = [int(t) for t in np.random.default_rng(0).integers(3, 200, size=20)]


@functools.cache
def params_of(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


@functools.cache
def prefill_step(cfg, cache_by, fresh):
    return steps.build_prefill(cfg, forward, "off", cache_by, fresh)


@functools.cache
def decode_step(n_steps=1, masked=False):
    return steps.build_decode(CFG, forward, "off", n_steps, masked)


def fresh_cache(cfg, cache_by):
    if cache_by == "table":
        return init_paged_kv_cache(cfg, 12, BLK)
    return init_kv_cache(cfg, S if cache_by == "slot" else 1, max_seq=64)


def prefill(cfg, cache_by, pieces):
    """Run ``pieces`` of one prompt through the builder's programs, each
    padded to its bucket; (cache, last-position logits)."""
    cache = fresh_cache(cfg, cache_by)
    addr = {"slot": jnp.int32(SLOT), "table": ROW, "whole": None}[cache_by]
    off, logits = 0, None
    for piece in pieces:
        operands = steps.piece_operands(
            cache_by, piece, steps.prefill_bucket(len(piece), BUCKETS), PAD, off, addr)
        cache, logits = prefill_step(cfg, cache_by, off == 0)(
            params_of(cfg), cache, *operands)
        off += len(piece)
    return cache, logits


def whole_prompt(cfg, cache_by="whole"):
    """``forward`` over the unpadded prompt on a fresh cache of the layout."""
    n = len(PROMPT)
    cache = fresh_cache(cfg, "table" if cache_by == "table" else "whole")
    kw = {"block_table": ROW} if cache_by == "table" else {}
    logits, cache = forward(
        params_of(cfg), cfg, jnp.asarray([PROMPT], jnp.int32),
        jnp.arange(n, dtype=jnp.int32)[None], cache, jnp.zeros((1,), jnp.int32),
        fresh_prefill=True, **kw)
    return cache, logits[0, -1]


# -- (a) prefill -----------------------------------------------------------


@pytest.mark.parametrize("pieces", [[PROMPT], [PROMPT[:16], PROMPT[16:]]],
                         ids=["fresh", "fresh+continuation"])
@pytest.mark.parametrize("cache_by", ["slot", "table", "whole"])
def test_prefill_gives_forwards_last_position_logits(cache_by, pieces):
    cache, logits = prefill(CFG, cache_by, pieces)
    _, want = whole_prompt(CFG, cache_by)
    np.testing.assert_allclose(logits, want, rtol=2e-5, atol=2e-5)
    if cache_by == "slot":
        # the slot was sliced out and written back; its neighbours were not
        assert float(jnp.abs(cache["k"][:, SLOT]).sum()) > 0
        assert float(jnp.abs(cache["k"][:, [0, 2]]).sum()) == 0


@pytest.mark.parametrize("pieces", [[PROMPT], [PROMPT[:16], PROMPT[16:]]],
                         ids=["fresh", "fresh+continuation"])
def test_a_hybrids_padding_leaves_the_recurrent_state_alone(pieces):
    """A padded bucket through slot addressing: the state after the piece is
    that of the unpadded prompt (``valid_len``, from ``forward_kwargs``)."""
    cache, logits = prefill(HYBRID, "slot", pieces)
    ref, want = whole_prompt(HYBRID)
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)
    got = slice_cache_slots(cache, SLOT)
    for leaf in ("lin_state", "lin_conv"):
        np.testing.assert_allclose(got[leaf], ref[leaf], rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(ref["lin_state"]).sum()) > 0


def test_forward_kwargs_is_where_each_argument_is_decided():
    length, live = jnp.int32(5), jnp.asarray([True, False])
    assert steps.forward_kwargs(CFG, "off", length=length, live=live) == {"kernels": "off"}
    kw = steps.forward_kwargs(HYBRID, "auto", length=length)
    assert kw["valid_len"].shape == (1,) and int(kw["valid_len"][0]) == 5
    kw = steps.forward_kwargs(HYBRID, "auto", live=live)
    assert kw["valid_len"].dtype == jnp.int32 and kw["valid_len"].tolist() == [1, 0]
    kw = steps.forward_kwargs(CFG, "off", table=ROW, lora={"x": 1}, ids=live)
    assert kw["block_table"] is ROW and kw["lora"] == {"x": 1} and kw["lora_ids"] is live


# -- (b) decode ------------------------------------------------------------


def decode_operands(cache_by):
    """A cache whose SLOT holds the prompt, and the operands of a greedy
    decode sweep over all S slots from there."""
    cache, logits = prefill(CFG, cache_by, [PROMPT])
    first = int(jnp.argmax(logits))
    tokens = jnp.asarray([7, first, 9], jnp.int32)
    lengths = jnp.asarray([0, len(PROMPT), 0], jnp.int32)
    zf, zi = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    counts = jnp.zeros((S, CFG.vocab_size), jnp.int32)
    kw = {}
    if cache_by == "table":
        # SLOT's row is ROW; its neighbours decode into blocks of their own
        kw["table"] = jnp.concatenate([jnp.full((1, 8), 10), ROW, jnp.full((1, 8), 11)]).astype(jnp.int32)
    return (cache, tokens, lengths, zf, zi, jnp.ones((S,), jnp.float32),
            jax.random.PRNGKey(0), counts, zf, zf), kw


def run_decode(step, operands, kw, *mask):
    cache, counts, toks, ys = step(params_of(CFG), *operands, *mask, **kw)
    return cache, counts, toks, np.asarray(ys[0])  # ys[0]: tokens [n, S]


def no_mask(allowed=None):
    bits = np.ones((S, CFG.vocab_size), bool)
    use = np.zeros((S,), bool)
    if allowed is not None:
        bits[SLOT] = False
        bits[SLOT, allowed] = True
        use[SLOT] = True
    packed = np.packbits(bits, axis=-1, bitorder="little")
    return jnp.asarray(packed), jnp.asarray(use)


def test_two_dispatches_of_one_step_are_one_dispatch_of_two():
    # the cache and the counts are donated: every dispatch takes fresh ones
    _, _, _, two = run_decode(decode_step(2), *decode_operands("slot"))
    operands, kw = decode_operands("slot")
    cache, counts, toks, a = run_decode(decode_step(1), operands, kw)
    _, _, lengths, *rest = operands
    rest[4] = counts
    _, _, _, b = run_decode(decode_step(1), (cache, toks, lengths + 1, *rest), kw)
    assert two.shape == (2, S) and np.array_equal(two, np.concatenate([a, b]))


def test_masked_with_no_row_masked_is_the_plain_step():
    _, _, _, plain = run_decode(decode_step(1), *decode_operands("slot"))
    _, _, toks, masked = run_decode(
        decode_step(masked=True), *decode_operands("slot"), *no_mask())
    assert np.array_equal(masked, plain) and np.array_equal(toks, plain[0])


def test_masked_with_a_one_token_mask_emits_that_token():
    _, _, _, plain = run_decode(decode_step(1), *decode_operands("slot"))
    only = (int(plain[0, SLOT]) + 1) % CFG.vocab_size
    _, counts, _, masked = run_decode(
        decode_step(masked=True), *decode_operands("slot"), *no_mask(only))
    assert masked[0, SLOT] == only and masked[0, 0] == plain[0, 0]
    assert int(counts[SLOT, only]) == 1
    with pytest.raises(ValueError, match="one step per dispatch"):
        steps.build_decode(CFG, forward, "off", n_steps=2, masked=True)


def test_paged_decode_gives_the_dense_tokens():
    _, _, _, want = run_decode(decode_step(2), *decode_operands("slot"))
    _, _, _, got = run_decode(decode_step(2), *decode_operands("table"))
    assert np.array_equal(got[:, SLOT], want[:, SLOT])


# -- (c) the names a program is found by ------------------------------------


@functools.cache
def engine(kind):
    ecfg = dict(max_slots=2, max_seq_len=64, max_prefill_len=BUCKETS.max_prefill_len,
                min_prefill_bucket=BUCKETS.min_prefill_bucket)
    p = params_of(CFG)
    if kind == "paged":
        return Engine(p, CFG, EngineConfig(kv_layout="paged", kv_block_size=BLK, **ecfg))
    if kind == "drafter":
        return Engine(p, CFG, EngineConfig(spec_tokens=3, **ecfg), drafter=(p, CFG))
    if kind == "disagg":
        return Engine(p, CFG, EngineConfig(disagg=True, **ecfg))
    return Engine(p, CFG, EngineConfig(**ecfg))


def lane_step(fresh):
    """The lane builds a program when it first runs a piece at its bucket."""
    lane = engine("disagg")._disagg
    lane._staging = lane._make_staging()
    lane._prefill_piece(PROMPT[:3], 0 if fresh else 16)
    return lane._prefill_fns[16, fresh]


NAMES = [
    # engine, kind, key, draft -> jit name, compile-statistics label
    ("dense", "prefill", (16, True), False, "prefill", "prefill[16]"),
    ("dense", "prefill", (32, False), False, "chunk_prefill", "chunk_prefill[32]"),
    ("paged", "prefill", (16, True), False, "prefill", "paged_prefill[16]"),
    ("paged", "prefill", (16, False), False, "chunk_prefill", "paged_chunk_prefill[16]"),
    ("drafter", "prefill", (16, True), True, "prefill", "prefill[16].draft"),
    ("drafter", "prefill", (16, False), True, "chunk_prefill", "chunk_prefill[16].draft"),
    ("dense", "decode", (4,), False, "decode", "decode[chunk=4]"),
    ("paged", "decode", (1,), False, "decode", "decode[chunk=1].paged"),
    ("dense", "decode_masked", (), False, "decode_masked", "decode.masked"),
    ("paged", "decode_masked", (), False, "decode_masked", "decode.masked.paged"),
    ("drafter", "spec", (), False, "spec_step", "spec[k=3]"),
    ("dense", "first", (), False, "first", None),
    ("dense", "reset", (), False, "reset", None),
    ("dense", "reset", (True,), False, "reset", None),     # the variant that merges the token into the carry
    ("disagg", "inject", (), False, "inject", "disagg_inject"),
    ("paged", "kv_block_write", (), False, "kv_block_write", "kv_block_write"),
    ("disagg", "lane", (True,), False, "fresh", "disagg_prefill[16]"),
    ("disagg", "lane", (False,), False, "chunk", "disagg_chunk_prefill[16]"),
]


@pytest.mark.parametrize("eng,kind,key,draft,jit_name,label", NAMES,
                         ids=[f"{n[0]}-{n[5] or n[4]}" for n in NAMES])
def test_every_kind_of_step_keeps_its_jit_name_and_its_label(eng, kind, key, draft, jit_name, label):
    """bench/trace_reduce.py and bench/readers/ find ``jit_<name>`` in a
    trace; bench/run.py, chip_smoke.py and /metrics read compile statistics
    by label. A rename here is a change to the yardstick."""
    if kind == "lane":
        fn = lane_step(*key)
    else:
        fn = engine(eng)._step(kind, *key, draft=draft)
        assert engine(eng)._step(kind, *key, draft=draft) is fn  # built once
    assert getattr(fn, "_fn", fn).__name__ == jit_name
    assert getattr(fn, "_label", None) == label


@pytest.mark.parametrize("carry", [False, True], ids=["drained", "behind_a_sweep"])
def test_reset_zeroes_the_row_records_the_token_and_merges_it_into_the_carry(carry):
    """``build_reset``: the slot's count row holds the first token alone, every
    other row is left; the ``carry`` variant (an admission dispatched behind
    the sweep in flight) also returns the decode carry with the token in the
    slot's row, all on the device."""
    counts = jnp.arange(4 * 6, dtype=jnp.int32).reshape(4, 6)
    want = np.arange(4 * 6, dtype=np.int32).reshape(4, 6)
    want[2] = [0, 0, 0, 1, 0, 0]
    fn = steps.build_reset(carry)
    if not carry:
        out = fn(counts, jnp.int32(2), jnp.int32(3))
        assert np.array_equal(np.asarray(out), want)
        return
    out, tokens = fn(counts, jnp.int32(2), jnp.int32(3), jnp.asarray([7, 8, 9, 10], jnp.int32))
    assert np.array_equal(np.asarray(out), want)
    assert np.asarray(tokens).tolist() == [7, 8, 3, 10] and tokens.dtype == jnp.int32

"""Window layers (a ring of K/V) beside global layers (stripes) with routed
experts, in the engine: streams under continuous batching, fused decode steps
and slot reuse through rings that wrap; admissions behind the sweep in flight;
a parked slot's ring through its neighbours' sweeps; the two leaves' gauges,
block counters and span arguments; and every setting a ring refuses, by name."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_params
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest
# the settings a per-slot state that positions do not address refuses, and the stream collector
from tests.test_conv_moe_engine import assert_a_decode_step_visits_the_touched_experts_alone
from tests.test_hybrid_engine import REFUSED, run_requests

# two periods of a global layer and three window-16 layers, 8 experts under each; float32
TINY = get_config("smallthinker-tiny", dtype="float32")


@functools.cache
def tiny_params():
    return init_params(jax.random.PRNGKey(0), TINY)


def make_engine(**ecfg):
    params = tiny_params()
    kw = dict(max_slots=4, max_seq_len=256, max_prefill_len=128)
    return params, Engine(params, TINY, EngineConfig(**{**kw, **ecfg}))


@jax.jit
def _padded_forward(params, toks, n):
    return forward(params, TINY, toks, jnp.arange(toks.shape[1])[None], valid_len=n)[0]


def greedy(params, seq, n_prompt):
    """The cache-free forward's own continuation (no ring: one fresh piece
    over the whole sequence), every sequence padded to one length."""
    toks = jnp.asarray([seq + [0] * (128 - len(seq))], jnp.int32)
    lg = _padded_forward(params, toks, jnp.asarray([len(seq)], jnp.int32))
    return [int(jnp.argmax(lg[0, i])) for i in range(n_prompt - 1, len(seq) - 1)]


@pytest.mark.parametrize("ecfg", [{}, {"decode_chunk": 4}, {"decode_pipeline": False}],
                         ids=["plain", "fused4", "sync"])
def test_engine_streams_are_the_models_own_under_batching_and_slot_reuse(ecfg):
    """Six requests on four slots, prompts of 17-100 tokens against a ring of
    16: every prompt but none fits the ring, every stream decodes through a
    wrapped ring, and two slots are reused after a LONGER request. Every
    stream is the cache-free forward's greedy continuation, which it is not if
    a pad position, a neighbour's sweep or a previous occupant's keys reach a
    ring, or if a token's routing depends on what else is in the batch."""
    params, eng = make_engine(**ecfg)
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (70, 23, 100, 45, 17, 90)]
    try:
        outs = run_requests(eng, prompts)
    finally:
        eng.stop()
    assert all(o == greedy(params, p + o, len(p)) for p, o in zip(prompts, outs))
    s = eng.snapshot_stats()
    # the two K/V leaves by kind: 6 window layers x 4 slots x 2 heads x R 16 x 32 x 2 x 4 B
    assert s["cache_bytes"]["kv_window"] == 6 * 4 * 2 * 16 * 32 * 2 * 4
    assert s["cache_bytes"]["kv_global"] == 2 * 4 * 2 * 256 * 32 * 2 * 4
    assert s["cache_bytes"]["kv"] == 0 and s["conv_tail_resets"] == s["linear_state_resets"] == 0
    # every live row offers 2 choices to each of 8 expert layers, and every expert is held
    live_steps = s["linear_state_slot_steps"] - s["linear_state_held_slot_steps"]
    assert 0 < s["moe_assignments"] <= live_steps * 2 * 8 and s["moe_assignments"] % 16 == 0
    assert s["moe_local_assignments"] == s["moe_assignments"]
    assert 0 < s["moe_expert_touches"] <= min(s["decode_steps"] * 8 * 8, s["moe_assignments"])


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_admissions_behind_the_sweep_in_flight_leave_every_ring_its_own(sampled):
    """Callers admitted while a sweep is in flight, without a drain
    (docs/DECODE_PIPELINE.md): the sweep leaves a parked row's ring alone and
    moves the row of the caller it was dispatched for; the fresh piece, later
    on the device, rewrites the ring from index 0. Token for token the
    synchronous scheduler's streams, and no admission names a drain."""
    from tests.admit_behind import check_stack

    check_stack(make_engine, greedy, sampled, resets="prefill_chunks")


def test_a_parked_slot_keeps_its_ring_through_neighbours_sweeps_and_the_spans_say_what_ran():
    params, eng = make_engine()
    from kserve_vllm_mini_tpu.runtime.tracing import SchedLedger

    spans = []

    class Span:
        def __init__(self, name, **args):
            spans.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    eng._sched = SchedLedger(annotate=Span)
    rng = np.random.default_rng(1)
    long_p = [int(t) for t in rng.integers(3, 500, 40)]
    short_p = [int(t) for t in rng.integers(3, 500, 12)]
    a = eng.submit(GenRequest(prompt_tokens=long_p, max_new_tokens=40, temperature=0.0, eos_id=None))
    eng._schedule_once()
    slot_a = next(i for i, h in enumerate(eng._slot_req) if h is a)
    parked = next(i for i in range(4) if i != slot_a)
    marked = {n: eng._cache[n].at[:, parked].set(1.5) for n in ("k_win", "v_win")}
    eng._cache = {**eng._cache, **marked}
    before = {n: np.asarray(eng._cache[n]) for n in ("k_win", "v_win")}
    for _ in range(20):                        # past a whole turn of the ring
        eng._dispatch_plain(eng._decode_active())
        eng._retire_one()
    for n in ("k_win", "v_win"):
        after = np.asarray(eng._cache[n])
        assert np.array_equal(after[:, parked], before[n][:, parked])
        assert not np.array_equal(after[:, slot_a], before[n][:, slot_a])
    # a piece's span names the two kinds of layer it ran and whether it wrapped the ring
    pieces = [args for name, args in spans if name == "kvmini.prefill"]
    assert pieces[0]["window_layers"] == 6 and pieces[0]["global_layers"] == 2
    assert pieces[0]["ring_wrapped"] == 1 and pieces[0]["moe_assignments"] == 40 * 2 * 8
    eng._free = [parked]
    del spans[:]
    c = eng.submit(GenRequest(prompt_tokens=short_p, max_new_tokens=3, temperature=0.0, eos_id=None))
    while not c.finish_reason:
        eng._schedule_once()
    # into the marked slot: what the ring held is hidden by the rule, no wipe needed
    assert c.tokens == greedy(params, short_p + c.tokens, len(short_p))
    pieces = [args for name, args in spans if name == "kvmini.prefill"]
    assert pieces[0]["ring_wrapped"] == 0 and "linear_chunks" not in pieces[0]


RING_REFUSED = {
    **REFUSED,
    "prefill_chunk": (dict(prefill_chunk=32), {}, "prefill_chunk"),
    "int8_kv": (dict(kv_cache_dtype="int8"), {}, "kv_cache_dtype"),
}


@pytest.mark.parametrize("what", sorted(RING_REFUSED))
def test_engine_refuses_what_a_ring_cannot_do_each_by_name(what):
    ecfg, extra, names = RING_REFUSED[what]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TINY))
    with pytest.raises(ValueError, match="sliding_window_layout stack.*ring.*" + names):
        Engine(params, TINY, EngineConfig(max_slots=2, max_seq_len=64, **ecfg),
               **{k: v() for k, v in extra.items()})


def test_a_prompt_longer_than_one_piece_is_an_error_and_fused_steps_and_filters_are_let_through():
    _, eng = make_engine(decode_chunk=2, max_prefill_len=64)
    err = eng.load_adapter("a", {"wq": (jnp.zeros((4, 128, 2)), jnp.zeros((4, 2, 128)))})
    assert err and "hybrid" in err
    eng.start()
    try:
        long = eng.submit(GenRequest(prompt_tokens=list(range(3, 103)), max_new_tokens=4,
                                     temperature=0.0, eos_id=None))
        kind, info = long.events.get(timeout=60)
        assert kind == "done" and info["finish_reason"] == "error"
        assert "max_prefill_len=64" in info["error"] and "one fresh piece" in info["error"]
        h = eng.submit(GenRequest(prompt_tokens=list(range(3, 50)), max_new_tokens=6,
                                  temperature=0.7, top_k=8, eos_id=None))
        while h.events.get(timeout=300)[0] == "token":
            pass
    finally:
        eng.stop()
    assert len(h.tokens) == 6 and eng.stats["prefill_chunks"] == 1


def test_both_leaves_go_through_the_decode_kernel_and_their_blocks_are_booked_by_kind():
    """128-wide heads and the kernel interpreted: a decode step holds one
    Mosaic call a layer, the streams are the eager reads', and the block
    counters book a layer of each kind by the kernel's rule at its own length."""
    cfg = TINY.scaled(explicit_head_dim=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [list(range(3, 43)), list(range(50, 60))]
    outs = {}
    for kernels in ("off", "interpret"):
        eng = Engine(params, cfg, EngineConfig(max_slots=2, max_seq_len=64, max_prefill_len=32 + 16))
        eng._kernels = kernels
        eng.start()
        try:
            outs[kernels] = run_requests(eng, prompts, max_new=6)
        finally:
            eng.stop()
        s = eng.snapshot_stats()
        if kernels == "off":
            assert eng._window_decode_blks is None and s["decode_kv_blocks"] == 0
            continue
        assert eng._window_decode_blks == (64, 16) and eng._dense_decode_blk is None
        # booked at dispatch, both slots' rows a step: one 64-position block a global
        # stripe, one 16-position block a ring, all fetched
        rows = s["decode_kv_blocks_global"] // 2
        assert rows >= 2 * s["decode_steps"] and rows % 2 == 0
        assert s["decode_kv_blocks_global"] == s["decode_kv_blocks_fetched_global"] == 2 * rows
        assert s["decode_kv_blocks_window"] == s["decode_kv_blocks_fetched_window"] == 6 * rows
        assert s["decode_kv_blocks"] == s["decode_kv_blocks_fetched"] == 8 * rows
    assert outs["off"] == outs["interpret"]


def test_the_gauges_and_counters_are_exported_and_a_dense_model_has_neither_kind():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    def scrape_of(eng):
        async def scrape():
            async with TestClient(TestServer(make_app(eng, ByteTokenizer(), "m"))) as client:
                return await (await client.get("/metrics")).text()

        eng.start()
        try:
            run_requests(eng, [[5, 6, 7, 8]], max_new=4)
            return asyncio.run(scrape()).splitlines()
        finally:
            eng.stop()

    lines = scrape_of(make_engine()[1])
    assert f'kvmini_tpu_cache_bytes{{kind="kv_window"}} {6 * 4 * 2 * 16 * 32 * 2 * 4}' in lines
    assert f'kvmini_tpu_cache_bytes{{kind="kv_global"}} {2 * 4 * 2 * 256 * 32 * 2 * 4}' in lines
    assert "kvmini_tpu_moe_assignments_total 48" in lines          # 3 steps x 2 x 8 layers
    assert "kvmini_tpu_moe_local_assignments_total 48" in lines
    assert 'kvmini_tpu_decode_kv_blocks_by_kind_total{kind="window"} 0' in lines   # eager reads here
    assert all(f'kvmini_tpu_admissions_drained_total{{reason="{why}"}} 0' in lines
               for why in ("paged", "sweep", "prefill_chunk"))
    cfg = get_config("llama-tiny")
    dense = Engine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                   EngineConfig(max_slots=2, max_seq_len=64))
    lines = scrape_of(dense)
    assert not any("kv_window" in line or "kv_global" in line for line in lines)
    assert 'kvmini_tpu_decode_kv_blocks_fetched_by_kind_total{kind="global"} 0' in lines


def test_a_decode_step_visits_the_touched_experts_alone():
    """Experts 128 wide, so that the kernel has a block for them."""
    assert_a_decode_step_visits_the_touched_experts_alone(
        TINY.scaled(moe_intermediate_size=128), [list(range(3, 43)), list(range(50, 60))])

"""A stack of short convolutions, attention layers, a dense MLP and routed
SwiGLU experts in the engine: streams under continuous batching, prefill in
pieces and slot reuse; admissions behind the sweep in flight; a parked or
mid-prefill slot's tail through its neighbours' sweeps; the counters and the
span arguments; and every setting a tail without positions refuses."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_params
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest
# the settings a state without positions refuses, and the stream collector: the delta rule's
from tests.test_hybrid_engine import REFUSED, run_requests

# conv, conv, attention, conv, attention, conv; one dense layer then five of 8 experts; float32
TINY = get_config("lfm2-moe-tiny", dtype="float32")


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
    """The cache-free forward's own continuation, every sequence padded to
    one length so that one program serves them all."""
    toks = jnp.asarray([seq + [0] * (128 - len(seq))], jnp.int32)
    lg = _padded_forward(params, toks, jnp.asarray([len(seq)], jnp.int32))
    return [int(jnp.argmax(lg[0, i])) for i in range(n_prompt - 1, len(seq) - 1)]


@pytest.mark.parametrize("ecfg", [{}, {"prefill_chunk": 32}, {"decode_chunk": 4},
                                  {"decode_pipeline": False, "prefill_chunk": 16}],
                         ids=["plain", "pieces32", "fused4", "sync_pieces16"])
def test_engine_streams_are_the_models_own_under_batching_and_slot_reuse(ecfg):
    """Six requests on four slots: admissions between decode sweeps, prompts
    in pieces, slots reused after a LONGER request (its tail is reset by the
    next fresh prefill). Every stream is the cache-free forward's greedy
    continuation, which it is not if a neighbour's sweep, a pad position or a
    previous occupant reaches a tail, or if a token's routing depends on what
    else is in the batch."""
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
    assert s["conv_tail_resets"] == 6 and s["linear_state_resets"] == 0
    assert s["linear_scan_chunks"] == 0                   # a short convolution scans nothing
    assert 0 < s["linear_state_held_slot_steps"] < s["linear_state_slot_steps"]
    assert s["cache_bytes"] == {"kv": 2 * 2 * 4 * 2 * 256 * 32 * 4, "linear_state": 0,
                                "conv_state": 0, "ssm_state": 0, "ssm_conv_state": 0,
                                "conv_tail": 4 * 4 * 2 * 128 * 4}
    # the expert layers' counters, booked with the retired decode steps: every live
    # row offers 2 choices to each of 5 expert layers, and every expert is held
    live_steps = s["linear_state_slot_steps"] - s["linear_state_held_slot_steps"]
    assert 0 < s["moe_assignments"] <= live_steps * 2 * 5 and s["moe_assignments"] % 10 == 0
    assert s["moe_local_assignments"] == s["moe_assignments"]
    assert 0 < s["moe_expert_touches"] <= min(s["decode_steps"] * 5 * 8, s["moe_assignments"])
    assert s["moe_assignments"] / 8 <= s["moe_expert_load_max"] <= s["moe_assignments"]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_admissions_behind_the_sweep_in_flight_leave_every_tail_its_own(sampled):
    """Callers admitted while a sweep is in flight, without a drain
    (docs/DECODE_PIPELINE.md): the sweep holds a parked row's tail and moves
    the row of the caller it was dispatched for; the fresh piece, later on the
    device, starts the tails from zero and overwrites the K/V rows from 0.
    Token for token the synchronous scheduler's streams."""
    from tests.admit_behind import check_stack

    check_stack(make_engine, greedy, sampled, resets="conv_tail_resets")


def test_a_parked_or_mid_prefill_slot_keeps_its_tail_through_neighbours_sweeps():
    params, eng = make_engine(prefill_chunk=32)
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
    long_p = [int(t) for t in rng.integers(3, 500, 100)]
    short_p = [int(t) for t in rng.integers(3, 500, 20)]
    a = eng.submit(GenRequest(prompt_tokens=short_p, max_new_tokens=40, temperature=0.0, eos_id=None))
    eng._schedule_once()                       # admit the short one: it decodes from now on
    slot_a = next(i for i, h in enumerate(eng._slot_req) if h is a)
    eng.submit(GenRequest(prompt_tokens=long_p, max_new_tokens=4, temperature=0.0, eos_id=None))
    eng._schedule_once()                       # admit the long one: first piece only
    slot_b = next(i for i, st in enumerate(eng._slot_prefill) if st is not None)
    parked = next(i for i in range(4) if i not in (slot_a, slot_b))
    eng._cache = {**eng._cache, "conv_tail": eng._cache["conv_tail"].at[:, parked].set(1.5)}
    before = np.asarray(eng._cache["conv_tail"])
    assert np.abs(before[:, slot_b]).max() > 0
    offered, touched = eng.stats["moe_assignments"], eng.stats["moe_expert_touches"]
    del spans[:]
    for _ in range(3):                         # decode sweeps alone: no further piece
        eng._dispatch_plain(eng._decode_active())
        eng._retire_one()
    after = np.asarray(eng._cache["conv_tail"])
    assert np.array_equal(after[:, slot_b], before[:, slot_b])
    assert np.array_equal(after[:, parked], before[:, parked])
    assert not np.array_equal(after[:, slot_a], before[:, slot_a])
    # three sweeps of one live row: 2 choices x 5 expert layers x 3 steps, each its own expert
    assert eng.stats["moe_assignments"] - offered == 3 * 2 * 5
    assert eng.stats["moe_expert_touches"] - touched == 3 * 2 * 5
    # the span that follows a sweep's read-back carries that sweep's touched experts, and
    # those its dispatch multiplied: every held one of the five layers, dense on the CPU
    emits = [args for name, args in spans if name == "kvmini.emit"]
    assert emits == [{"moe_touched": 10, "moe_visited": 5 * TINY.experts_held,
                      "moe_steps": 1}] * 3
    # a piece's span says what it offers the expert layers; a fresh prefill into the
    # marked slot starts from an empty tail whatever it held
    eng._free = [parked]
    del spans[:]
    c = eng.submit(GenRequest(prompt_tokens=short_p, max_new_tokens=3, temperature=0.0, eos_id=None))
    while not c.finish_reason:
        eng._schedule_once()
    assert c.tokens == greedy(params, short_p + c.tokens, len(short_p))
    pieces = [args for name, args in spans if name == "kvmini.prefill"]
    assert pieces and pieces[0]["moe_assignments"] == 20 * 2 * 5
    assert "linear_chunks" not in pieces[0] and "ssm_chunks" not in pieces[0]


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_engine_refuses_what_a_tail_without_positions_cannot_do(what):
    ecfg, extra, names = REFUSED[what]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TINY))
    with pytest.raises(ValueError, match="hybrid stack.*convolution tail.*" + names):
        Engine(params, TINY, EngineConfig(max_slots=2, max_seq_len=64, **ecfg),
               **{k: v() for k, v in extra.items()})


def test_runtime_adapter_load_is_refused_and_what_a_tail_does_not_lose_is_let_through():
    """int8 K/V, fused decode steps, prefill in pieces and sampling filters
    touch nothing a tail would lose: the engine builds and serves with them."""
    _, eng = make_engine(kv_cache_dtype="int8", decode_chunk=2, prefill_chunk=32)
    err = eng.load_adapter("a", {"in_proj": (jnp.zeros((4, 128, 2)), jnp.zeros((4, 2, 384)))})
    assert err and "hybrid" in err
    eng.start()
    try:
        h = eng.submit(GenRequest(prompt_tokens=list(range(3, 50)), max_new_tokens=6,
                                  temperature=0.7, top_k=8, eos_id=None))
        while h.events.get(timeout=300)[0] == "token":
            pass
    finally:
        eng.stop()
    assert len(h.tokens) == 6 and eng.stats["conv_tail_resets"] == 1


def test_the_counters_are_exported_and_a_dense_model_counts_nothing():
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
    assert f'kvmini_tpu_cache_bytes{{kind="conv_tail"}} {4 * 4 * 2 * 128 * 4}' in lines
    assert "kvmini_tpu_conv_tail_resets_total 1" in lines
    assert "kvmini_tpu_moe_assignments_total 30" in lines          # 3 steps x 2 x 5 layers
    assert "kvmini_tpu_moe_local_assignments_total 30" in lines
    cfg = get_config("llama-tiny")
    dense = Engine(init_params(jax.random.PRNGKey(0), cfg), cfg,
                   EngineConfig(max_slots=2, max_seq_len=64))
    lines = scrape_of(dense)
    assert 'kvmini_tpu_cache_bytes{kind="conv_tail"} 0' in lines
    assert "kvmini_tpu_conv_tail_resets_total 0" in lines


def test_the_engine_serves_a_long_piece_grouped_and_counts_its_bucket(monkeypatch):
    """A 70-token prompt's piece takes the grouped dispatch, a 12-token one
    and every decode step sweep the experts their rows chose (the kernel's
    row tile cut to 16, the experts 128 wide so that it has a block;
    tests/test_grouped_moe.py holds the dispatches themselves): the streams
    are the dense engine's, and the counter holds the long piece's bucket
    alone."""
    from kserve_vllm_mini_tpu.ops import grouped_matmul
    from kserve_vllm_mini_tpu.runtime import steps

    monkeypatch.setattr(grouped_matmul, "ROW_TILE", 16)
    # a convolution, attention, a convolution; a dense MLP then two expert layers
    cfg = get_config("lfm2-moe-tiny", dtype="float32", moe_intermediate_size=128, n_layers=3,
                     layer_types=("conv", "full_attention", "conv"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (70, 12)]
    outs, stats = {}, {}
    for kernels in ("auto", "interpret"):
        eng = Engine(params, cfg, EngineConfig(max_slots=2, max_seq_len=256, max_prefill_len=128))
        eng._kernels = kernels
        eng.start()
        try:
            outs[kernels] = run_requests(eng, prompts, max_new=4)
        finally:
            eng.stop()
        stats[kernels] = eng.snapshot_stats()
    assert outs["interpret"] == outs["auto"]
    buckets = [steps.prefill_bucket(len(p), eng.ecfg) for p in prompts]
    assert stats["interpret"]["prefill_bucket_tokens"] == sum(buckets)
    assert stats["interpret"]["prefill_grouped_bucket_tokens"] == buckets[0]
    assert stats["auto"]["prefill_grouped_bucket_tokens"] == 0


def served_with_and_without_kernels(cfg, prompts, max_new=6):
    """The streams, the engine's counters and the lines of ``/metrics`` with
    the kernels off and interpreted, two requests on four slots."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    params = init_params(jax.random.PRNGKey(0), cfg)
    got = {}
    for kernels in ("off", "interpret"):
        eng = Engine(params, cfg, EngineConfig(max_slots=4, max_seq_len=128, max_prefill_len=64))
        eng._kernels = kernels

        async def scrape():
            async with TestClient(TestServer(make_app(eng, ByteTokenizer(), "m"))) as client:
                return await (await client.get("/metrics")).text()

        eng.start()
        try:
            outs = run_requests(eng, prompts, max_new=max_new)
            got[kernels] = (outs, eng.snapshot_stats(), asyncio.run(scrape()).splitlines())
        finally:
            eng.stop()
    return got


def assert_a_decode_step_visits_the_touched_experts_alone(cfg, prompts):
    """Greedy streams equal with the kernels interpreted and off; the dense
    steps visit every held expert of every expert layer, the kernel's the
    experts a live row chose (two rows live of four: fewer), and ``/metrics``
    carries the count."""
    got = served_with_and_without_kernels(cfg, prompts)
    (dense_outs, dense, dense_lines), (outs, swept, lines) = got["off"], got["interpret"]
    assert outs == dense_outs and all(len(o) == 6 for o in outs)
    assert dense["moe_expert_visits"] == (
        dense["decode_steps"] * cfg.n_expert_layers * cfg.experts_held)
    assert 0 < swept["moe_expert_visits"] == swept["moe_expert_touches"]
    assert swept["moe_expert_visits"] < (
        swept["decode_steps"] * cfg.n_expert_layers * cfg.experts_held)
    assert f"kvmini_tpu_moe_expert_visits_total {swept['moe_expert_visits']}" in lines
    assert f"kvmini_tpu_moe_expert_visits_total {dense['moe_expert_visits']}" in dense_lines


def test_a_decode_step_visits_the_touched_experts_alone():
    # a convolution, attention, a convolution; a dense MLP then two layers of 8 experts 128 wide
    cfg = get_config("lfm2-moe-tiny", dtype="float32", moe_intermediate_size=128, n_layers=3,
                     layer_types=("conv", "full_attention", "conv"))
    assert_a_decode_step_visits_the_touched_experts_alone(
        cfg, [list(range(3, 43)), list(range(50, 60))])

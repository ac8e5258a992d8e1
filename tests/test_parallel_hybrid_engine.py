"""A stack of two-mixer blocks in the engine: streams under continuous
batching, prefill in pieces and slot reuse; a parked or mid-prefill slot's
state through its neighbours' sweeps; what the tracing exports of it (the one
cache tree's bytes by kind, the scan-chunk span argument, the recurrent-state
counters, the ``kvmini_tpu_block_mixers`` gauge); and every setting a state
without positions refuses."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_params
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest
# the settings a state without positions refuses, and the stream collector: the delta rule's
from tests.test_hybrid_engine import REFUSED, run_requests

# two blocks of the tiny preset (every multiplier away from 1), float32
TINY = get_config("falcon-h1-tiny", dtype="float32").scaled(hybrid_override_pattern="PP")


def make_engine(cfg=TINY, **ecfg):
    params = init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(max_slots=4, max_seq_len=256, max_prefill_len=128)
    return params, Engine(params, cfg, EngineConfig(**{**kw, **ecfg}))


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
                                  {"decode_pipeline": False, "prefill_chunk": 16},
                                  {"kv_cache_dtype": "int8"}],
                         ids=["plain", "pieces32", "fused4", "sync_pieces16", "int8kv"])
def test_engine_streams_are_the_models_own_under_batching_and_slot_reuse(ecfg):
    """Six requests on four slots: admissions between decode sweeps, prompts
    in pieces, slots reused. Every stream is the cache-free forward's greedy
    continuation, which it is not if a neighbour's sweep, a pad position or a
    previous occupant reaches a state, a tail or a K/V stripe."""
    params, eng = make_engine(**ecfg)
    eng.start()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (70, 23, 100, 45, 17, 90)]
    try:
        outs = run_requests(eng, prompts)
    finally:
        eng.stop()
    if "kv_cache_dtype" not in ecfg:
        assert all(o == greedy(params, p + o, len(p)) for p, o in zip(prompts, outs))
    else:     # int8 K/V rounds: the streams are whole, and mostly the float32 program's
        same = sum(a == b for p, o in zip(prompts, outs)
                   for a, b in zip(o, greedy(params, p + o, len(p))))
        assert all(len(o) == 10 for o in outs) and same >= 50
    s = eng.snapshot_stats()
    assert s["linear_state_resets"] == 6 and s["linear_state_slot_steps"] > 0
    assert 0 < s["linear_state_held_slot_steps"] < s["linear_state_slot_steps"]
    assert s["linear_scan_chunks"] >= 6 and s["moe_assignments"] == 0
    # ONE tree, three kinds of leaf, every one 2 layers deep
    kv_elem = (16 + 4) if "kv_cache_dtype" in ecfg else 16 * 4
    assert s["cache_bytes"] == {"kv": 2 * 2 * 4 * 2 * 256 * kv_elem, "linear_state": 0,
                                "conv_state": 0, "ssm_state": 2 * 4 * 8 * 12 * 32 * 4,
                                "ssm_conv_state": 2 * 4 * 3 * 224 * 4, "conv_tail": 0}
    assert s["block_mixers"] == 2


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_admissions_behind_the_sweep_in_flight_leave_every_state_its_own(sampled):
    """Callers admitted while a sweep is in flight, without a drain
    (docs/DECODE_PIPELINE.md): a ``P`` block holds a layer of Mamba-2 state and tail AND a layer of K/V.
    The sweep holds a parked row and moves the row of the caller it was
    dispatched for; the fresh piece, later on the device, starts the state
    from zero and overwrites the K/V rows from 0. Token for token the
    synchronous scheduler's streams."""
    from tests.admit_behind import check_stack

    check_stack(make_engine, greedy, sampled)


def test_a_parked_or_mid_prefill_slot_keeps_its_state_through_neighbours_sweeps():
    params, eng = make_engine(prefill_chunk=32)
    from kserve_vllm_mini_tpu.runtime.tracing import SchedLedger

    eng._sched = SchedLedger()
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
    eng._cache = {**eng._cache, "ssm_state": eng._cache["ssm_state"].at[:, parked].set(3.25),
                  "ssm_conv": eng._cache["ssm_conv"].at[:, parked].set(1.5)}
    before = {k: np.asarray(v) for k, v in eng._cache.items()}
    assert np.abs(before["ssm_state"][:, slot_b]).max() > 0
    for _ in range(3):                         # decode sweeps alone: no further piece
        eng._dispatch_plain(eng._decode_active())
        eng._retire_one()
    for k in ("ssm_state", "ssm_conv"):
        after = np.asarray(eng._cache[k])
        assert np.array_equal(after[:, slot_b], before[k][:, slot_b])
        assert np.array_equal(after[:, parked], before[k][:, parked])
        assert not np.array_equal(after[:, slot_a], before[k][:, slot_a])
    # the mid-prefill slot's K/V, the 32 positions its first piece wrote: untouched
    for k in ("k", "v"):
        assert np.array_equal(np.asarray(eng._cache[k])[:, slot_b, :, :32], before[k][:, slot_b, :, :32])
    # a fresh prefill into the marked slot starts from zero whatever it held
    eng._free = [parked]
    c = eng.submit(GenRequest(prompt_tokens=short_p, max_new_tokens=3, temperature=0.0, eos_id=None))
    while c.finish_reason is None:
        eng._schedule_once()
    assert c.tokens == greedy(params, short_p + c.tokens, len(short_p))
    assert eng.stats["linear_state_held_slot_steps"] > 0


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_engine_refuses_what_a_state_without_positions_cannot_do(what):
    """Exactly what ``_refuse_for_hybrid`` refuses for the two hybrids."""
    ecfg, extra, names = REFUSED[what]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TINY))
    with pytest.raises(ValueError, match="hybrid stack.*" + names):
        Engine(params, TINY, EngineConfig(max_slots=2, max_seq_len=64, **ecfg),
               **{k: v() for k, v in extra.items()})


def _scrape(eng):
    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    async def get():
        async with TestClient(TestServer(make_app(eng, ByteTokenizer(), "m"))) as client:
            return (await (await client.get("/metrics")).text()).splitlines()

    return asyncio.run(get())


def test_metrics_and_spans_say_what_kind_of_stack_is_served():
    """/metrics: the three kinds of the one cache tree, the recurrent-state
    counters and ``kvmini_tpu_block_mixers`` 2; the ``kvmini.prefill`` span
    carries ``ssm_chunks`` by the configuration's chunk size (32)."""
    from kserve_vllm_mini_tpu.runtime import tracing

    _, eng = make_engine()
    spans = []
    sound = tracing.SchedLedger.phase

    def recording(self, name, **args):
        spans.append((name, args))
        return sound(self, name, **args)

    tracing.SchedLedger.phase = recording
    eng.start()
    try:
        run_requests(eng, [list(range(3, 73)), list(range(5, 25))], max_new=6)
        lines = _scrape(eng)
    finally:
        eng.stop()
        tracing.SchedLedger.phase = sound
    assert "kvmini_tpu_block_mixers 2" in lines
    assert f'kvmini_tpu_cache_bytes{{kind="kv"}} {2 * 2 * 4 * 2 * 256 * 16 * 4}' in lines
    assert f'kvmini_tpu_cache_bytes{{kind="ssm_state"}} {2 * 4 * 8 * 12 * 32 * 4}' in lines
    assert f'kvmini_tpu_cache_bytes{{kind="ssm_conv_state"}} {2 * 4 * 3 * 224 * 4}' in lines
    assert 'kvmini_tpu_cache_bytes{kind="linear_state"} 0' in lines
    value = {ln.split()[0]: float(ln.split()[1]) for ln in lines if ln and not ln.startswith("#")}
    assert value["kvmini_tpu_linear_state_resets_total"] == 2
    assert value["kvmini_tpu_linear_state_slot_steps_total"] > value[
        "kvmini_tpu_linear_state_held_slot_steps_total"] > 0
    assert value["kvmini_tpu_linear_scan_chunks_total"] == 4 + 1      # buckets 128 and 32 of 32
    prefills = [args for name, args in spans if name == "prefill"]
    assert sorted(a["ssm_chunks"] for a in prefills) == [1, 4]
    assert all("linear_chunks" not in a for a in prefills)


def test_every_other_stack_reports_one_mixer_a_block():
    for name in ("llama-tiny", "olmo-hybrid-tiny", "nemotron-h-tiny"):
        cfg = get_config(name, dtype="float32")
        params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        eng = Engine(params, cfg, EngineConfig(max_slots=2, max_seq_len=64, compile_stats=False))
        assert eng.snapshot_stats()["block_mixers"] == 1

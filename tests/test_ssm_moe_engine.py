"""A stack of Mamba-2, attention and expert blocks in the engine: streams
under continuous batching, prefill in pieces and slot reuse; a parked or
mid-prefill slot's state through its neighbours' sweeps; the expert blocks'
counters; and every setting a state without positions refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import forward, init_params
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest
# the settings a state without positions refuses, and the stream collector: the delta rule's
from tests.test_conv_moe_engine import assert_a_decode_step_visits_the_touched_experts_alone
from tests.test_hybrid_engine import REFUSED, run_requests

# all three kinds of block, 8 experts held of 32, float32
TINY = get_config("nemotron-h-tiny", dtype="float32").scaled(hybrid_override_pattern="M*EME")


def make_engine(**ecfg):
    params = init_params(jax.random.PRNGKey(0), TINY)
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
    in pieces, slots reused. Every stream is the cache-free forward's greedy
    continuation, which it is not if a neighbour's sweep, a pad position or a
    previous occupant reaches a state, or if a token's routing depends on
    what else is in the batch."""
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
    assert s["linear_state_resets"] == 6 and s["linear_state_slot_steps"] > 0
    assert 0 < s["linear_state_held_slot_steps"] < s["linear_state_slot_steps"]
    assert s["linear_scan_chunks"] >= 6
    assert s["cache_bytes"] == {"kv": 2 * 4 * 2 * 256 * 32 * 4, "linear_state": 0, "conv_state": 0,
                                "ssm_state": 2 * 4 * 8 * 16 * 16 * 4,
                                "ssm_conv_state": 2 * 4 * 3 * 192 * 4, "conv_tail": 0}
    # the expert blocks' counters, booked with the retired decode steps: every live
    # row offers 4 choices to each of 2 expert layers; a quarter of the experts is held
    live_steps = s["linear_state_slot_steps"] - s["linear_state_held_slot_steps"]
    assert 0 < s["moe_assignments"] <= live_steps * 4 * 2 and s["moe_assignments"] % 8 == 0
    assert 0.1 < s["moe_local_assignments"] / s["moe_assignments"] < 0.45
    assert 0 < s["moe_expert_touches"] <= s["decode_steps"] * 2 * 8
    assert s["moe_expert_touches"] <= s["moe_local_assignments"]
    assert s["moe_local_assignments"] / 8 <= s["moe_expert_load_max"] <= s["moe_local_assignments"]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_admissions_behind_the_sweep_in_flight_leave_every_state_its_own(sampled):
    """Callers admitted while a sweep is in flight, without a drain
    (docs/DECODE_PIPELINE.md): the Mamba-2 state and tail of the ``M`` blocks, the K/V of the ``*`` block.
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
    marked = {**eng._cache, "ssm_state": eng._cache["ssm_state"].at[:, parked].set(3.25),
              "ssm_conv": eng._cache["ssm_conv"].at[:, parked].set(1.5)}
    eng._cache = marked
    before = {k: np.asarray(eng._cache[k]) for k in ("ssm_state", "ssm_conv")}
    assert np.abs(before["ssm_state"][:, slot_b]).max() > 0
    offered = eng.stats["moe_assignments"]
    for _ in range(3):                         # decode sweeps alone: no further piece
        eng._dispatch_plain(eng._decode_active())
        eng._retire_one()
    for k in ("ssm_state", "ssm_conv"):
        after = np.asarray(eng._cache[k])
        assert np.array_equal(after[:, slot_b], before[k][:, slot_b])
        assert np.array_equal(after[:, parked], before[k][:, parked])
        assert not np.array_equal(after[:, slot_a], before[k][:, slot_a])
    # three sweeps of one live row: 4 choices x 2 expert layers x 3 steps offered
    assert eng.stats["moe_assignments"] - offered == 3 * 4 * 2
    assert eng.stats["moe_expert_touches"] == eng.stats["moe_local_assignments"]  # one row: no sharing
    # a fresh prefill into the marked slot starts from zero whatever it held
    eng._free = [parked]
    c = eng.submit(GenRequest(prompt_tokens=short_p, max_new_tokens=3, temperature=0.0, eos_id=None))
    while not c.finish_reason:
        eng._schedule_once()
    assert c.tokens == greedy(params, short_p + c.tokens, len(short_p))


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_engine_refuses_what_a_state_without_positions_cannot_do(what):
    ecfg, extra, names = REFUSED[what]
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TINY))
    with pytest.raises(ValueError, match="hybrid stack.*" + names):
        Engine(params, TINY, EngineConfig(max_slots=2, max_seq_len=64, **ecfg),
               **{k: v() for k, v in extra.items()})


def test_the_counters_are_exported_and_a_dense_model_counts_nothing():
    """/metrics carries the five expert counters and the state-space kinds of
    kvmini_tpu_cache_bytes for every model; a model without such blocks
    leaves them at 0 and its decode program returns no routing counts."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    cfg = get_config("llama-tiny")
    eng = Engine(init_params(jax.random.PRNGKey(0), cfg), cfg, EngineConfig(max_slots=2, max_seq_len=64))
    eng.start()

    async def scrape():
        async with TestClient(TestServer(make_app(eng, ByteTokenizer(), "m"))) as client:
            return await (await client.get("/metrics")).text()

    try:
        run_requests(eng, [[5, 6, 7, 8]], max_new=4)
        text = asyncio.run(scrape())
    finally:
        eng.stop()
    for name in ("assignments", "local_assignments", "expert_touches", "expert_load_max",
                 "expert_visits"):
        assert f"kvmini_tpu_moe_{name}_total 0" in text.splitlines()
    assert 'kvmini_tpu_cache_bytes{kind="ssm_state"} 0' in text
    assert 'kvmini_tpu_cache_bytes{kind="ssm_conv_state"} 0' in text
    assert eng.stats["decode_steps"] > 0


def test_a_decode_step_visits_the_touched_experts_alone():
    """A latent and experts of whole lane tiles, so that the kernel has a
    block for them: the ``E`` blocks hand it their stack and index."""
    assert_a_decode_step_visits_the_touched_experts_alone(
        TINY.scaled(moe_latent_size=128, moe_intermediate_size=128),
        [list(range(3, 43)), list(range(50, 60))])

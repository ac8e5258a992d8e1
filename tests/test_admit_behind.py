"""An admission no longer drains the decode pipeline (docs/DECODE_PIPELINE.md
"Admission behind the sweep in flight"): the prefill, the first token and the
next sweep are dispatched behind the sweep in flight and the first token is
read back after it. Streams must be those of the synchronous scheduler token
for token, greedy and seeded sampled; a first token that ends the request, a
cancel before the readback and two callers in one iteration must settle; and
every mix the path refuses must drain as before, counted by reason.

The dense stack, driven iteration by iteration (``tests/admit_behind.py``);
the hybrid stacks run the same script in their own engine tests, and the slow
job's ``tests/test_decode_pipeline.py`` keeps the started-engine mixes."""

import jax
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import init_params
from kserve_vllm_mini_tpu.runtime.engine import (
    ADMIT_DRAIN_REASONS,
    Engine,
    EngineConfig,
    GenRequest,
)
from tests.admit_behind import assert_same_streams, assert_went_behind, scripted_run
from tests.oracle import greedy_reference
from tests.test_sched_ledger import ForcedSequence as Forced    # one allowed token a step

CFG = get_config("llama-tiny")
PROMPTS = [[3, 1, 4], [10, 11, 12, 13], [1, 5, 9], [27, 18], list(range(40, 75))]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def make_engine(params, drafter=False, **ecfg) -> Engine:
    kw = dict(max_slots=4, max_seq_len=128, max_prefill_len=64, min_prefill_bucket=16)
    return Engine(params, CFG, EngineConfig(**{**kw, **ecfg}),
                  drafter=(params, CFG) if drafter else None)


_REFERENCE: dict[tuple, list[int]] = {}


def reference(params, prompt, n: int) -> list[int]:
    """``greedy_reference``, computed once a prompt for the whole module (it
    recomputes the sequence a token, eagerly, a new shape each: seconds a
    token); a longer stream extends the shorter one."""
    have = _REFERENCE.get(tuple(prompt), [])
    if len(have) < n:
        have = have + greedy_reference(params, CFG, list(prompt) + have, n - len(have))
        _REFERENCE[tuple(prompt)] = have
    return have[:n]


def until_done(eng, *handles, limit=200):
    for _ in range(limit):
        if all(h.finish_reason for h in handles):
            return
        eng._schedule_once()
    raise AssertionError("the engine did not finish")


@pytest.mark.parametrize("chunk", [1, 4], ids=["chunk1", "fused4"])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_streams_behind_a_sweep_are_the_synchronous_schedulers(params, sampled, chunk):
    behind_eng = make_engine(params, decode_chunk=chunk)
    behind = scripted_run(behind_eng, PROMPTS, sampled)
    sync = scripted_run(make_engine(params, decode_chunk=chunk, decode_pipeline=False),
                        PROMPTS, sampled)
    assert_same_streams(behind, sync)
    assert_went_behind(behind_eng)
    if not sampled:
        for h, p in zip(behind[2:], PROMPTS[2:]):   # those admitted behind a sweep
            assert h.tokens == reference(params, p, len(h.tokens))


def test_a_prompt_in_several_pieces_goes_behind_too(params):
    """``max_prefill_len`` 16 and a 35-token prompt: three pieces dispatched
    back to back behind the sweep, the last one's logits sampled."""
    eng = make_engine(params, max_prefill_len=16)
    a = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=30))
    eng._schedule_once()
    eng._schedule_once()
    b = eng.submit(GenRequest(prompt_tokens=PROMPTS[4], max_new_tokens=8))
    until_done(eng, a, b)
    assert eng.stats["admissions_inflight"] == 1 and eng.stats["prefill_chunks"] == 1 + 3
    assert b.tokens == reference(params, PROMPTS[4], 8)
    assert a.tokens == reference(params, [3, 1, 4], 30)


def test_two_callers_in_one_iteration_get_their_tokens_one_after_the_other(params):
    """Each first token has a readback of its own, in the order of the
    dispatches: the first caller's token is stamped and handed out before
    the second caller's prompt has been waited for."""
    eng = make_engine(params)
    a = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=30, eos_id=None))
    eng._schedule_once()
    eng._schedule_once()
    b = eng.submit(GenRequest(prompt_tokens=PROMPTS[4], max_new_tokens=5, eos_id=None))
    c = eng.submit(GenRequest(prompt_tokens=[9, 9, 2], max_new_tokens=5, eos_id=None))
    spans0 = eng._sched.snapshot()["spans"]["prefill"]
    eng._schedule_once()
    assert eng.stats["admissions_inflight"] == 2
    assert len(b.tokens) == len(c.tokens) == 1
    assert a.t_first_token < b.t_first_token < c.t_first_token
    # a piece's dispatch and a readback each
    assert eng._sched.snapshot()["spans"]["prefill"] - spans0 == 4
    s = eng.snapshot_stats()
    assert s["sched_gaps"]["admission"] == 1 and s["sched_admit_gap_admissions"] == 2
    until_done(eng, a, b, c)
    assert b.tokens == reference(params, PROMPTS[4], 5)
    assert c.tokens == reference(params, [9, 9, 2], 5)


@pytest.mark.parametrize("ends", ["budget_of_one", "eos"])
def test_a_first_token_that_ends_the_request_finishes_it_at_the_readback(params, ends):
    """The sweep dispatched with the slot in it computes a row nobody takes:
    the handle-identity check drops it, and the slot is free again."""
    first = reference(params, [8, 6, 7, 5], 1)[0]
    req = (GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=1) if ends == "budget_of_one"
           else GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=9, eos_id=first))
    eng = make_engine(params)
    a = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=24, eos_id=None))
    eng._schedule_once()
    eng._schedule_once()
    b = eng.submit(req)
    eng._schedule_once()
    assert eng.stats["admissions_inflight"] == 1
    assert b.tokens == [first]
    assert b.finish_reason == ("length" if ends == "budget_of_one" else "stop")
    assert len(eng._free) == 3 and eng._decode_active() == [eng._slot_req.index(a)]
    c = eng.submit(GenRequest(prompt_tokens=[9, 9, 2], max_new_tokens=6, eos_id=None))
    until_done(eng, a, c)
    assert a.tokens == reference(params, [3, 1, 4], 24)
    assert c.tokens == reference(params, [9, 9, 2], 6)
    assert eng.stats["admissions_inflight"] == 2
    kinds = []
    while not b.events.empty():
        kinds.append(b.events.get()[0])
    assert kinds == ["token", "done"]


def test_alone_with_a_caller_that_ends_at_its_first_token_the_split_is_rewound(params):
    """Every other slot finishes in the sweep the caller was admitted behind
    and its own first token ends it: the sweep dispatched behind the prefill
    is dropped and its rng split rewound, as the synchronous scheduler would
    never have dispatched it."""
    outs = {}
    for pipeline in (True, False):
        eng = make_engine(params, decode_pipeline=pipeline, max_slots=2)
        kw = dict(temperature=0.9, top_k=16, eos_id=None)
        a = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=4, **kw))
        eng._schedule_once()
        eng._schedule_once()
        eng._schedule_once()            # a holds 3 (pipelined) or 4 tokens
        b = eng.submit(GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=1, **kw))
        until_done(eng, a, b)
        eng._schedule_once()
        assert not eng._inflight and eng._pending_steps == 0
        # (a later caller's SLOT differs between the two schedulers, and a
        # sampled row's noise follows its slot: the key itself is compared)
        outs[pipeline] = (a.tokens, b.tokens, np.asarray(eng._rng).tolist(),
                          eng.stats["admissions_inflight"])
    assert outs[True][:3] == outs[False][:3]
    assert outs[True][3] == 1 and outs[False][3] == 0


def test_a_cancel_before_the_readback_drops_the_first_token(params):
    eng = make_engine(params)
    a = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=20, eos_id=None))
    eng._schedule_once()
    eng._schedule_once()
    b = eng.submit(GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=9, eos_id=None))
    sweep_phase = eng._sweep_phase

    def cancel_after_the_dispatches(*args):
        sweep_phase(*args)              # the prefill, the first token, the next sweep: all out
        assert [p["st"]["handle"] for p in eng._first_pending] == [b]
        eng.cancel(b, "client_disconnect")

    eng._sweep_phase = cancel_after_the_dispatches
    eng._schedule_once()
    eng._sweep_phase = sweep_phase
    assert b.finish_reason == "client_disconnect" and b.tokens == []
    assert b.events.get_nowait() == ("done", {
        "finish_reason": "client_disconnect", "tokens_out": 0,
        "truncated": False, "truncated_tokens": 0})
    assert b.events.empty() and len(eng._free) == 3 and not eng._first_pending
    # the engine serves on: the dropped row and the reset count row leak nowhere
    c = eng.submit(GenRequest(prompt_tokens=[9, 9, 2], max_new_tokens=8, eos_id=None))
    until_done(eng, a, c)
    assert a.tokens == reference(params, [3, 1, 4], 20)
    assert c.tokens == reference(params, [9, 9, 2], 8)


def test_logprobs_of_a_first_token_read_back_late(params):
    eng = make_engine(params)
    a = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=20, eos_id=None))
    eng._schedule_once()
    eng._schedule_once()
    b = eng.submit(GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=4, eos_id=None,
                              logprobs=True))
    until_done(eng, a, b)
    sync = make_engine(params, decode_pipeline=False)
    b2 = sync.submit(GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=4, eos_id=None,
                                logprobs=True))
    until_done(sync, b2)
    assert eng.stats["admissions_inflight"] == 1 and b.tokens == b2.tokens
    assert len(b.logprobs) == 4
    for (lp, top), (lp2, top2) in zip(b.logprobs, b2.logprobs):
        assert lp == pytest.approx(lp2, abs=1e-5)
        assert [t for t, _ in top] == [t for t, _ in top2]


# reason -> (engine settings, settings of the request that decodes, of the one admitted)
REFUSALS = {
    "lockstep": ({}, {}, {}),
    "paged": ({"kv_layout": "paged", "kv_block_size": 16}, {}, {}),
    "drafter": ({"spec_tokens": 2}, {"logprobs": True}, {"logprobs": True}),
    "prefix_cache": ({"prefix_cache": True}, {}, {}),
    "prefill_chunk": ({"prefill_chunk": 32}, {}, {}),
    "constrained": ({}, {}, {"constraint": "forced"}),
    "sweep": ({"decode_chunk": 8, "max_seq_len": 64}, {}, {"prompt_tokens": list(range(3, 61))}),
}


@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_what_the_path_refuses_drains_as_before_and_is_counted(params, why):
    ecfg, first_kw, second_kw = REFUSALS[why]
    second_kw = dict(second_kw)
    if second_kw.get("constraint"):
        second_kw["constraint"] = Forced([40, 41, 42, 43])
    eng = make_engine(params, drafter=why == "drafter", **ecfg)
    decisions = []
    on_decision = None
    if why == "lockstep":
        eng._lockstep, on_decision = True, decisions.append
    a = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=20, eos_id=None, **first_kw))
    eng._schedule_once(on_decision)
    eng._schedule_once(on_decision)
    assert len(eng._inflight) == 1          # a sweep is in flight at the admission
    prompt = second_kw.pop("prompt_tokens", [8, 6, 7, 5])
    b = eng.submit(GenRequest(prompt_tokens=prompt, max_new_tokens=4, eos_id=None, **second_kw))
    eng._schedule_once(on_decision)
    s = eng.snapshot_stats()
    assert s["admissions_inflight"] == 0 and s["pipeline_fallback_active_set"] == 1
    assert {r: s[f"admissions_drained_{r}"] for r in ADMIT_DRAIN_REASONS if s[f"admissions_drained_{r}"]} == {why: 1}
    assert not eng._first_pending and len(b.tokens) >= 1     # sampled against settled state
    for _ in range(200):
        if a.finish_reason and b.finish_reason:
            break
        eng._schedule_once(on_decision)
    want = [40, 41, 42, 43] if why == "constrained" else reference(params, prompt, 4)
    assert b.tokens == want
    assert a.tokens == reference(params, [3, 1, 4], 20)
    if why == "lockstep":
        # the drain is part of the published stream: retire, then admit
        i = decisions.index(("admit", b.request))
        assert decisions[i - 1] == ("retire",)


def test_every_reason_has_its_counter_from_construction(params):
    s = make_engine(params).snapshot_stats()
    assert s["admissions_inflight"] == 0
    assert [s[f"admissions_drained_{r}"] for r in ADMIT_DRAIN_REASONS] == [0] * len(ADMIT_DRAIN_REASONS)
    # "disagg" and "lora" refuse by construction, like "paged": their engines
    # are built in tests/test_disagg.py and tests/test_lora.py
    assert set(REFUSALS) | {"disagg", "lora"} == set(ADMIT_DRAIN_REASONS)


def test_synchronous_scheduler_never_goes_behind(params):
    eng = make_engine(params, decode_pipeline=False)
    scripted_run(eng, PROMPTS, sampled=False)
    s = eng.snapshot_stats()
    assert s["admissions_inflight"] == 0 and s["pipeline_fallback_active_set"] == 0
    assert s["dispatch_depth"] <= 1


def test_metrics_export_the_counters(params):
    """``kvmini_tpu_admissions_inflight_total`` beside the fallback it
    narrows, and the drained admissions by reason."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    eng = make_engine(params)
    scripted_run(eng, PROMPTS, sampled=False)

    async def get():
        async with TestClient(TestServer(make_app(eng, ByteTokenizer(), "m"))) as client:
            return await (await client.get("/metrics")).text()

    text = asyncio.run(get())
    assert "kvmini_tpu_admissions_inflight_total 3\n" in text
    assert 'kvmini_tpu_pipeline_fallback_total{reason="active_set"} 0\n' in text
    for why in ADMIT_DRAIN_REASONS:
        assert f'kvmini_tpu_admissions_drained_total{{reason="{why}"}} 0\n' in text

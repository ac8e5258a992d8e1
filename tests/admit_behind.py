"""One scripted run of admissions that land while a decode sweep is in
flight, driven iteration by iteration (the engine's thread is never started,
so every admission lands at the same iteration whatever the scheduler):
shared by the engine tests of the dense stack and the three hybrid stacks.

The script: two callers decode; two more arrive together (one iteration
admits both); the first caller is cancelled and a fifth arrives in the same
iteration, so it is admitted into a slot whose old occupant was live in the
sweep in flight. Under ``decode_pipeline`` every one of the three later
admissions is dispatched behind that sweep (docs/DECODE_PIPELINE.md); with
it off the scheduler is the synchronous one. Both make the same dispatches
in the same order, so greedy AND seeded sampled streams must agree token
for token."""

from kserve_vllm_mini_tpu.runtime.engine import GenRequest

SAMPLED = {"temperature": 0.8, "top_k": 20}


def scripted_run(eng, prompts, sampled: bool, budgets=(40, 26, 13, 11, 9)):
    """Drive ``eng`` (not started) through the script; returns the five
    handles a..e. ``prompts``: five token lists."""
    kw = dict(SAMPLED) if sampled else {"temperature": 0.0}

    def submit(i):
        return eng.submit(GenRequest(prompt_tokens=list(prompts[i]), eos_id=None,
                                     max_new_tokens=budgets[i], **kw))

    a, b = submit(0), submit(1)
    eng._schedule_once()                # both admitted into an idle engine
    eng._schedule_once()                # a sweep is in flight when pipelined
    c, d = submit(2), submit(3)
    eng._schedule_once()                # two callers admitted in one iteration
    for _ in range(3):
        eng._schedule_once()
    eng.cancel(a, "stop")
    e = submit(4)
    eng._schedule_once()                # a's slot freed and taken in one iteration
    handles = [a, b, c, d, e]
    for _ in range(200):
        if all(h.finish_reason for h in handles):
            break
        eng._schedule_once()
    assert all(h.finish_reason for h in handles)
    return handles


def assert_same_streams(behind, sync):
    """``behind``, ``sync``: the handles of a pipelined and a synchronous
    run of the script. The cancelled caller's stream is cut one sweep
    earlier when its last sweep was still in flight."""
    for x, y in zip(behind[1:], sync[1:]):
        assert x.tokens == y.tokens
        assert x.finish_reason == y.finish_reason == "length"
    cut, whole = behind[0].tokens, sync[0].tokens
    assert 0 < len(cut) <= len(whole) and whole[: len(cut)] == cut
    assert behind[0].finish_reason == sync[0].finish_reason == "stop"


def assert_went_behind(eng, admissions: int = 3):
    """Every later admission of the script was dispatched behind a sweep
    and none drained the pipeline; the ledger booked their gaps."""
    s = eng.snapshot_stats()
    assert s["admissions_inflight"] == admissions
    assert s["pipeline_fallback_active_set"] == 0
    assert all(v == 0 for k, v in s.items() if k.startswith("admissions_drained_"))
    assert s["sched_admit_gap_admissions"] == admissions
    assert s["sched_gaps"]["admission"] == admissions - 1     # c and d share one
    assert not eng._first_pending and not any(eng._slot_behind)


def check_stack(make_engine, greedy, sampled: bool, kernels: str = "",
                resets: str = "linear_state_resets"):
    """The script on one of the hybrid stacks' tiny engines
    (``make_engine(**ecfg) -> (params, engine)``, four slots): a caller
    admitted behind a sweep lands on a parked row whose recurrent state the
    sweep held, or on the row of a caller that sweep still moved; either way
    its prefill starts the state from zero later on the device. Streams are
    the synchronous scheduler's, and the greedy ones the cache-free
    forward's own (``greedy(params, sequence, n_prompt)``). ``resets``: the
    counter that books a fresh prefill's reset of the stack's per-slot state."""
    import numpy as np

    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(3, 500, n)] for n in (20, 33, 45, 17, 60)]
    runs = []
    for ecfg in ({}, {"decode_pipeline": False}):
        params, eng = make_engine(**ecfg)
        if kernels:
            eng._kernels = kernels
        runs.append((eng, scripted_run(eng, prompts, sampled)))
    (eng, behind), (_, sync) = runs
    assert_same_streams(behind, sync)
    assert_went_behind(eng)
    assert eng.stats[resets] == 5
    if not sampled:
        for h, p in zip(behind[1:], prompts[1:]):
            assert h.tokens == greedy(params, p + h.tokens, len(p))

"""Double-buffered decode pipeline (docs/DECODE_PIPELINE.md): the
pipelined scheduler must be an invisible optimization — token streams
byte-identical to the synchronous loop across plain, sampled, chunked,
constrained-fallback, and cancellation scenarios — while the counters
prove the overlap actually engaged (dispatch_depth >= 2, nonzero
host_overlap_s) and each fallback-to-synchronous condition fires."""

import time

import jax
import numpy as np
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import init_params
from kserve_vllm_mini_tpu.runtime.engine import (
    Engine,
    EngineConfig,
    GenRequest,
    RequestHandle,
)

# compile-heavy: runs in the dedicated slow CI job (lint-test.yml)
pytestmark = pytest.mark.slow

CFG = get_config("llama-tiny")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _drain(handle):
    out = []
    while True:
        kind, *rest = handle.events.get(timeout=120)
        if kind == "token":
            out.append(rest[0])
        else:
            return out, rest[0]


def _normalize(outs):
    """(tokens, done-info) -> the deterministic fields only (timing like
    server_ttft_ms is wall-clock and legitimately differs between runs)."""
    return [
        (tokens, info.get("finish_reason"), info.get("tokens_out"))
        for tokens, info in outs
    ]


def make_engine(params, pipeline: bool, slots=8, max_seq=128, chunk=1) -> Engine:
    return Engine(
        params, CFG,
        EngineConfig(max_slots=slots, max_seq_len=max_seq, max_prefill_len=64,
                     min_prefill_bucket=16, decode_chunk=chunk,
                     decode_pipeline=pipeline),
    )


class ForcedSequenceMachine:
    """Token-protocol machine that allows exactly one token per step —
    deterministic constrained output (the sequence itself), so constrained
    streams can be compared across engines byte-for-byte."""

    def __init__(self, seq):
        self.seq = list(seq)
        self.i = 0

    @property
    def done(self):
        return self.i >= len(self.seq)

    def min_close(self):
        return len(self.seq) - self.i

    def token_mask(self, budget):
        mask = np.zeros((CFG.vocab_size,), dtype=bool)
        mask[self.seq[self.i]] = True
        return mask

    def advance_token(self, tid):
        assert tid == self.seq[self.i]
        self.i += 1


def _run_mix(params, pipeline: bool):
    eng = make_engine(params, pipeline)
    reqs = [
        # plain greedy
        GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=24),
        GenRequest(prompt_tokens=[10, 11, 12, 13], max_new_tokens=24),
        # sampled (rng-split sequence must match between modes too)
        GenRequest(prompt_tokens=[1, 5, 9], max_new_tokens=24,
                   temperature=0.8, top_k=20),
        GenRequest(prompt_tokens=[27, 18], max_new_tokens=24,
                   temperature=0.7, top_p=0.9),
        # grammar-constrained: forces the synchronous masked path while live
        GenRequest(prompt_tokens=[7, 8], max_new_tokens=10,
                   constraint=ForcedSequenceMachine([40, 41, 42, 43, 44])),
    ]
    # submit everything BEFORE starting so both engines admit the identical
    # population in their first iteration (admission timing is scheduler
    # wall-clock, not part of the determinism contract)
    handles = [eng.submit(r) for r in reqs]
    eng.start()
    try:
        outs = [_drain(h) for h in handles]
    finally:
        eng.stop()
    return outs, eng.snapshot_stats()


def test_pipelined_matches_sync_mixed_workload(params):
    """Acceptance: pipelined token streams byte-identical to the
    synchronous loop for the same seeded mix (plain greedy, sampled,
    constrained-fallback), with the steady-state counters engaged."""
    sync_outs, sync_stats = _run_mix(params, pipeline=False)
    pipe_outs, pipe_stats = _run_mix(params, pipeline=True)
    assert _normalize(pipe_outs) == _normalize(sync_outs)
    # the constrained slot emitted exactly its forced sequence in both
    assert pipe_outs[4][0] == [40, 41, 42, 43, 44]
    assert pipe_outs[4][1]["finish_reason"] == "stop"
    # synchronous engine never pipelines...
    assert sync_stats["dispatch_depth"] <= 1
    assert sync_stats["pipelined_sweeps"] == 0
    # ...the pipelined engine reached depth 2 with real host/device overlap
    # once the constrained slot finished and plain steady state began
    assert pipe_stats["dispatch_depth"] >= 2
    assert pipe_stats["pipelined_sweeps"] > 0
    assert pipe_stats["host_overlap_s"] > 0.0
    # and the constrained phase was pinned as a fallback, not pipelined
    assert pipe_stats["pipeline_fallback_constrained"] > 0


def test_plain_steady_state_counters(params):
    """Acceptance: snapshot_stats() reports dispatch_depth >= 2 and
    nonzero host_overlap_s during a plain-decode steady state."""
    eng = make_engine(params, pipeline=True, slots=4)
    handles = [
        eng.submit(GenRequest(prompt_tokens=[i + 1, i + 2], max_new_tokens=32))
        for i in range(4)
    ]
    eng.start()
    try:
        for h in handles:
            _drain(h)
        s = eng.snapshot_stats()
    finally:
        eng.stop()
    assert s["dispatch_depth"] >= 2
    assert s["host_overlap_s"] > 0.0
    assert s["pipelined_sweeps"] > 0
    # decode accounting must still add up: every emitted decode token came
    # from a retired (never a dropped) sweep
    assert s["decode_tokens"] == sum(31 for _ in handles)


def test_chunked_pipelined_matches_sync_and_headroom_fallback(params):
    """decode_chunk > 1 composes with dispatch-ahead, and the cache-window
    headroom guard (which also keeps chunk sizes mode-identical) falls
    back to synchronous near the end of the KV window."""

    def run(pipeline):
        eng = make_engine(params, pipeline, slots=2, max_seq=64, chunk=4)
        reqs = [
            # runs to out_of_space: slot_len approaches the window end
            GenRequest(prompt_tokens=[5, 9, 4], max_new_tokens=200),
            GenRequest(prompt_tokens=[2, 7], max_new_tokens=40,
                       temperature=0.9, top_k=16),
        ]
        handles = [eng.submit(r) for r in reqs]
        eng.start()
        try:
            outs = [_drain(h) for h in handles]
        finally:
            eng.stop()
        return outs, eng.snapshot_stats()

    sync_outs, _ = run(False)
    pipe_outs, pipe_stats = run(True)
    assert _normalize(pipe_outs) == _normalize(sync_outs)
    assert pipe_outs[0][1]["finish_reason"] == "length"  # window filled
    assert pipe_stats["pipeline_fallback_headroom"] > 0
    assert pipe_stats["dispatch_depth"] >= 2


def test_cancel_during_inflight_sweep_emits_no_token(params):
    """Satellite: a cancellation landing while a sweep is dispatched-but-
    not-retired must not leak that sweep's token into the cancelled
    stream. Driven synchronously (engine not started) so the in-flight
    window is deterministic."""
    eng = make_engine(params, pipeline=True, slots=2)
    h = eng.submit(GenRequest(prompt_tokens=[3, 1, 4, 1, 5], max_new_tokens=50))
    eng._schedule_once()  # admit (first token) + dispatch-ahead sweep 1
    assert eng.snapshot_stats()["inflight_sweeps"] == 1
    n_before = len(h.tokens)
    eng.cancel(h, "client_disconnect")
    eng._schedule_once()  # cancel lands; in-flight results are dropped
    assert len(h.tokens) == n_before
    assert h.finish_reason == "client_disconnect"
    assert eng.snapshot_stats()["inflight_sweeps"] == 0
    tokens, info = _drain(h)
    # the stream holds exactly the pre-cancel prefix, nothing more
    assert tokens == h.tokens and len(tokens) == n_before
    assert info["finish_reason"] == "client_disconnect"

    # the engine stays fully serviceable: a fresh request decodes exactly
    # the sequential oracle (the dropped sweep's garbage KV/counts never
    # leak into a later admission)
    from tests.oracle import greedy_reference

    h2 = eng.submit(GenRequest(prompt_tokens=[9, 9, 2], max_new_tokens=8))
    for _ in range(32):
        eng._schedule_once()
        if h2.finish_reason:
            break
    tokens2, _ = _drain(h2)
    assert tokens2 == greedy_reference(params, CFG, [9, 9, 2], 8)


def test_admission_during_inflight_gets_no_stale_token(params):
    """Satellite: a newly admitted request must never receive a token from
    a sweep dispatched before its admission. Since PR 39 the scheduler no
    longer retires the sweep in flight for that (docs/DECODE_PIPELINE.md
    "Admission behind the sweep in flight"): B is dispatched behind the
    sweep that was dispatched with the cancelled A live in the very slot B
    takes, and the retire's handle-identity check drops A's row."""
    from tests.oracle import greedy_reference

    eng = make_engine(params, pipeline=True, slots=2)
    ha = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=60))
    hc = eng.submit(GenRequest(prompt_tokens=[2, 7, 1], max_new_tokens=30))
    eng._schedule_once()  # admit A and C + dispatch sweep 1
    eng._schedule_once()  # dispatch sweep 2 + retire sweep 1
    assert eng.snapshot_stats()["inflight_sweeps"] == 1
    slot_a = eng._slot_req.index(ha)
    # B arrives while the sweep with A in it is in flight; the slot frees via cancel
    hb = eng.submit(GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=6))
    eng.cancel(ha, "stop")
    n_a = len(ha.tokens)
    eng._schedule_once()
    assert eng._slot_req[slot_a] is hb and len(ha.tokens) == n_a
    assert len(hb.tokens) == 1          # its own first token, read back this iteration
    for _ in range(64):
        eng._schedule_once()
        if hb.finish_reason and hc.finish_reason:
            break
    assert eng.stats["admissions_inflight"] == 1
    assert eng.stats["pipeline_fallback_active_set"] == 0
    tokens_b, _ = _drain(hb)
    assert tokens_b == greedy_reference(params, CFG, [8, 6, 7, 5], 6)
    tokens_c, _ = _drain(hc)
    assert tokens_c == greedy_reference(params, CFG, [2, 7, 1], 30)


def test_admission_with_nobody_decoding_still_drains(params):
    """One slot: the cancel leaves no slot decoding, so there is no next
    sweep to dispatch behind the prefill and the admission retires the
    (garbage) sweep in flight first, as it always did: counted as an
    ``active_set`` fallback, refused as ``sweep``."""
    from tests.oracle import greedy_reference

    eng = make_engine(params, pipeline=True, slots=1)
    ha = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=60))
    eng._schedule_once()  # admit A + dispatch sweep 1
    eng._schedule_once()  # dispatch sweep 2 + retire sweep 1
    assert eng.snapshot_stats()["inflight_sweeps"] == 1
    hb = eng.submit(GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=6))
    eng.cancel(ha, "stop")
    for _ in range(32):
        eng._schedule_once()
        if hb.finish_reason:
            break
    assert eng.stats["pipeline_fallback_active_set"] >= 1
    assert eng.stats["admissions_drained_sweep"] >= 1 and eng.stats["admissions_inflight"] == 0
    tokens_b, _ = _drain(hb)
    assert tokens_b == greedy_reference(params, CFG, [8, 6, 7, 5], 6)


def test_spec_partition_forces_sync(params):
    """Fallback pin: an engine with a speculative drafter never
    dispatches ahead while spec-eligible slots exist — the fused spec
    round interleaves its own dispatches."""
    eng = Engine(
        params, CFG,
        EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                     min_prefill_bucket=16, spec_tokens=2,
                     decode_pipeline=True),
        drafter=(params, CFG),
    )
    h = eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=12))
    eng.start()
    try:
        tokens, info = _drain(h)
    finally:
        eng.stop()
    s = eng.snapshot_stats()
    assert info["finish_reason"] == "length"
    assert s["spec_rounds"] > 0
    assert s["pipelined_sweeps"] == 0
    assert s["dispatch_depth"] <= 1
    assert s["pipeline_fallback_spec"] > 0

    # greedy spec output still matches the plain engine's
    eng2 = make_engine(params, pipeline=True, slots=2)
    h2 = eng2.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=12))
    eng2.start()
    try:
        tokens2, _ = _drain(h2)
    finally:
        eng2.stop()
    assert tokens == tokens2


def test_spec_slot_rejoining_plain_path_gets_fresh_feed(params):
    """Regression: the on-device token carry holds a GARBAGE row for a
    spec slot (the plain sweep's discarded sample, chunk steps ahead of
    the slot's real state). When the spec headroom gate flips off near
    the cache-window end and the slot rejoins the plain partition, the
    next dispatch must feed it from _last_tokens, not the stale carry —
    with decode_chunk > 1 the stale row is wrong and corrupted the
    slot's final tokens."""
    from tests.oracle import greedy_reference

    pa = [5, 9, 42]
    ref = greedy_reference(params, CFG, pa, 45)
    eng = Engine(
        params, CFG,
        EngineConfig(max_slots=2, max_seq_len=48, max_prefill_len=32,
                     min_prefill_bucket=16, spec_tokens=2, decode_chunk=4),
        drafter=(params, CFG),
    )
    # A speculates (greedy); B's frequency penalty pins it to the plain
    # partition, so every sweep is a spec+plain mix with a live carry
    ha = eng.submit(GenRequest(prompt_tokens=pa, max_new_tokens=100))
    hb = eng.submit(GenRequest(prompt_tokens=[7, 7], max_new_tokens=100,
                               frequency_penalty=0.5))
    eng.start()
    try:
        tokens_a, info_a = _drain(ha)
        _drain(hb)
    finally:
        eng.stop()
    # A runs to the window end: its last few tokens decode AFTER the spec
    # gate flipped it onto the plain path
    assert info_a["finish_reason"] == "length"
    assert tokens_a == ref


def test_multihost_follower_replays_pipelined_stream(params):
    """Satellite of the tentpole's (4): the on_decision stream now carries
    ('dispatch',)/('retire',) and a follower replaying it reproduces the
    primary's token streams exactly — the lockstep contract extended to
    the pipelined schedule."""
    from kserve_vllm_mini_tpu.runtime.multihost import (
        req_from_payload,
        req_payload,
    )

    primary = make_engine(params, pipeline=True, slots=2)
    primary._lockstep = True
    decisions = []

    def record(d):
        if d[0] == "admit":
            decisions.append(("admit", req_payload(d[1])))
        else:
            decisions.append(d)

    reqs = [
        GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=10),
        GenRequest(prompt_tokens=[1, 5, 9, 2], max_new_tokens=14,
                   temperature=0.8, top_k=12),
    ]
    handles = [primary.submit(r) for r in reqs]
    deadline = time.time() + 120
    while not all(h.finish_reason for h in handles):
        assert time.time() < deadline, "primary drive stalled"
        primary._schedule_once(on_decision=record)
    ops = [d[0] for d in decisions]
    assert "dispatch" in ops and "retire" in ops  # the stream IS pipelined

    follower = make_engine(params, pipeline=True, slots=2)
    follower._lockstep = True
    replayed: dict[str, RequestHandle] = {}
    for cmd in decisions:
        op = cmd[0]
        if op == "admit":
            h = RequestHandle(req_from_payload(cmd[1]))
            replayed[h.request.request_id] = h
            follower._admit_one(h)
        elif op == "sweep":
            follower._decode_sweep()
        elif op == "dispatch":
            follower._replay_dispatch()
        elif op == "retire":
            follower._retire_one()
        else:
            raise AssertionError(f"unexpected decision {cmd!r}")
    for h in handles:
        fh = replayed[h.request.request_id]
        assert fh.tokens == h.tokens
        assert fh.finish_reason == h.finish_reason

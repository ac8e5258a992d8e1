"""Engine stats-surface regressions that need no scheduler (no jit
compiles): the paged-backpressure queue_depth undercount fix and the
decode-pipeline counter contract of snapshot_stats
(docs/DECODE_PIPELINE.md)."""

import jax
import pytest

from kserve_vllm_mini_tpu.models.config import get_config
from kserve_vllm_mini_tpu.models.llama import init_params
from kserve_vllm_mini_tpu.runtime.engine import (
    Engine,
    EngineConfig,
    GenRequest,
    RequestHandle,
)

CFG = get_config("llama-tiny")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _paged_engine(params) -> Engine:
    return Engine(
        params, CFG,
        EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                     min_prefill_bucket=16, kv_layout="paged",
                     kv_block_size=16, kv_pool_blocks=8),
    )


def test_queue_depth_counts_deferred_backpressure_handle(params):
    """The backpressure-held head-of-line handle (_deferred) sits in
    neither _pending nor a slot; reported depth was one low whenever paged
    backpressure was active (ISSUE 1 satellite)."""
    eng = _paged_engine(params)
    assert eng.snapshot_stats()["queue_depth"] == 0
    # a queued request counts once...
    eng.submit(GenRequest(prompt_tokens=[1, 2, 3], max_new_tokens=4))
    assert eng.snapshot_stats()["queue_depth"] == 1
    # ...and the deferred head-of-line handle counts too (simulate the
    # scheduler parking a non-fitting request, exactly what
    # _schedule_once does under pool pressure)
    eng._deferred = RequestHandle(
        GenRequest(prompt_tokens=[4, 5, 6], max_new_tokens=64)
    )
    assert eng.snapshot_stats()["queue_depth"] == 2
    # submit()'s own stats write includes the deferred handle as well
    eng.submit(GenRequest(prompt_tokens=[7], max_new_tokens=4))
    assert eng.stats["queue_depth"] == 3


def test_queue_depth_dense_engine_unchanged(params):
    """Dense engines have no _deferred; depth is exactly the pending
    queue."""
    eng = Engine(
        params, CFG,
        EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                     min_prefill_bucket=16),
    )
    eng.submit(GenRequest(prompt_tokens=[1], max_new_tokens=4))
    assert eng.snapshot_stats()["queue_depth"] == 1


def _fake_live_slot(eng, slot=0, length=5):
    eng._slot_req[slot] = RequestHandle(
        GenRequest(prompt_tokens=[1, 2], max_new_tokens=8)
    )
    eng._slot_len[slot] = length
    return slot


def test_pipeline_eligibility_reasons(params):
    """Unit pins for the fallback-to-synchronous conditions
    (docs/DECODE_PIPELINE.md), checked without booting the scheduler."""
    eng = Engine(
        params, CFG,
        EngineConfig(max_slots=2, max_seq_len=64, max_prefill_len=32,
                     min_prefill_bucket=16),
    )
    slot = _fake_live_slot(eng)
    assert eng._pipeline_eligible([slot]) == (True, None)
    # grammar-constrained slot: the next mask depends on the just-emitted
    # byte, so nothing can be dispatched ahead
    eng._slot_machine[slot] = object()
    assert eng._pipeline_eligible([slot]) == (False, "constrained")
    eng._slot_machine[slot] = None
    # cache-window headroom: in-flight positions shrink the usable window;
    # a slot one position from the end cannot host a dispatched-ahead sweep
    eng._pending_steps = 1
    eng._slot_len[slot] = eng.ecfg.max_seq_len - 2  # window == 1
    assert eng._pipeline_eligible([slot]) == (False, "headroom")
    eng._pending_steps = 0
    assert eng._pipeline_eligible([slot]) == (True, None)
    # the kill switch pins fully synchronous, with no counted reason
    eng.ecfg.decode_pipeline = False
    assert eng._pipeline_eligible([slot]) == (False, None)


def test_pipeline_eligibility_spec_partition(params):
    """A drafter-equipped engine with spec-eligible slots must not
    dispatch ahead — the fused spec round interleaves its own
    drafter/target dispatches."""
    eng = Engine(
        params, CFG,
        EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                     min_prefill_bucket=16, spec_tokens=2),
        drafter=(params, CFG),
    )
    slot = _fake_live_slot(eng)
    assert eng._pipeline_eligible([slot]) == (False, "spec")
    # a logprobs request is spec-INeligible, so the plain path may pipeline
    eng._slot_req[slot].request.logprobs = True
    assert eng._pipeline_eligible([slot]) == (True, None)


def test_snapshot_stats_exposes_pipeline_counters(params):
    """The decode-pipeline counter contract: the keys the server /metrics
    layer and the bench pipeline read must exist from engine construction
    (zero-valued until the steady state engages)."""
    eng = _paged_engine(params)
    s = eng.snapshot_stats()
    assert s["dispatch_depth"] == 0
    assert s["pipelined_sweeps"] == 0
    assert s["host_overlap_s"] == 0.0
    assert s["bubble_s"] == 0.0
    assert s["inflight_sweeps"] == 0
    for reason in ("constrained", "spec", "active_set", "headroom"):
        assert s[f"pipeline_fallback_{reason}"] == 0
    # the admissions that went behind the sweep in flight, and those that
    # drained it (the active_set fallback) by what refused them
    assert s["admissions_inflight"] == 0
    from kserve_vllm_mini_tpu.runtime.engine import ADMIT_DRAIN_REASONS

    assert "paged" in ADMIT_DRAIN_REASONS and eng._behind_refused_as_built() == "paged"
    for reason in ADMIT_DRAIN_REASONS:
        assert s[f"admissions_drained_{reason}"] == 0

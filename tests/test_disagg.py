"""Disaggregated prefill/decode serving (ISSUE 13): prompt prefills run
on a dedicated lane and hand finished KV blocks to the decode engine
through the versioned handoff protocol (runtime/disagg.py,
docs/DISAGGREGATION.md). The contracts pinned here:

- greedy streams are BYTE-IDENTICAL to the colocated engine (the lane
  runs the same forward/params/bucket schedule and the stripe injects
  verbatim);
- TTFT-p95 and ITL-p95 are STRICTLY better with disagg on under mixed
  long-prefill/short-decode traffic at a prefill-compute-dominant
  config — the acceptance criterion;
- every failure mode (dropped handoff, cancel/drain mid-handoff, dead
  lane) ends in a terminal event exactly once and a released slot,
  never a hung request (the KVM09x-shaped paths);
- the observability rail (telemetry block, handoff_stall monitor rule,
  per-lane meshes) and the chaos/fault surfaces.

Engine tests are compile-heavy and ride the slow tier like
tests/test_prefill_chunking.py; protocol/telemetry/event/harness tests
are fast.
"""

import queue
import threading
import time

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
from kserve_vllm_mini_tpu.runtime.tracing import now_s

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


def _drain_timed(handle):
    out, times = [], []
    while True:
        kind, *rest = handle.events.get(timeout=300)
        if kind == "token":
            out.append(rest[0])
            times.append(rest[1])
        else:
            return out, rest[0], times


def _prompt(n, seed=3):
    return [(seed * i + 1) % (CFG.vocab_size // 2) for i in range(n)]


def make_engine(params, disagg=False, max_seq=512, max_prefill=256,
                slots=4, **ecfg_kw) -> Engine:
    eng = Engine(
        params, CFG,
        EngineConfig(max_slots=slots, max_seq_len=max_seq,
                     max_prefill_len=max_prefill, min_prefill_bucket=16,
                     disagg=disagg, **ecfg_kw),
    )
    eng.start()
    return eng


# -- per-lane meshes (parallel/mesh.lane_meshes) ------------------------------


def test_lane_meshes_2_plus_6_split():
    """The ISSUE's example split of the virtual 8-device CPU mesh: 2
    prefill devices + 6 decode devices, disjoint, tp-only."""
    from kserve_vllm_mini_tpu.parallel.mesh import lane_meshes

    pre, dec = lane_meshes(2)
    assert pre.size == 2 and dec.size == 6
    assert dict(pre.shape)["tp"] == 2
    assert dict(dec.shape)["tp"] == 6
    assert set(pre.devices.flat).isdisjoint(set(dec.devices.flat))


def test_lane_meshes_validation():
    from kserve_vllm_mini_tpu.parallel.mesh import lane_meshes

    with pytest.raises(ValueError, match="both lanes"):
        lane_meshes(0)
    with pytest.raises(ValueError, match="both lanes"):
        lane_meshes(8)
    # a tp override that doesn't cover its lane would build a dp>1 mesh
    # the disagg engine refuses downstream — rejected HERE with the real
    # fix (resize the split)
    with pytest.raises(ValueError, match="resize the split"):
        lane_meshes(2, decode_tp=3)


# -- config validation --------------------------------------------------------


def test_disagg_composition_validation():
    """Paged disagg (HANDOFF_VERSION=2) composes — but not with per-lane
    meshes (one shared block pool); dense prefix_cache and a mesh-less
    prefill_mesh stay rejected BEFORE any params/cache work."""
    with pytest.raises(ValueError, match="kv_layout=dense only"):
        Engine(None, CFG, EngineConfig(disagg=True, kv_layout="paged"),
               prefill_mesh=object())
    with pytest.raises(ValueError, match="prefix_cache"):
        Engine(None, CFG, EngineConfig(disagg=True, prefix_cache=True))
    with pytest.raises(ValueError, match="disagg=True"):
        Engine(None, CFG, EngineConfig(), prefill_mesh=object())


def test_multihost_rejects_disagg():
    """The lockstep contract refuses a disaggregated engine loudly: the
    prefill lane is host-local state the decision stream doesn't carry."""
    from types import SimpleNamespace

    from kserve_vllm_mini_tpu.runtime.multihost import check_multihost_engine

    eng = Engine.__new__(Engine)
    eng.mesh = SimpleNamespace(shape={"tp": 2})
    eng._disagg = object()
    with pytest.raises(ValueError, match="disagg"):
        check_multihost_engine(eng)


# -- the handoff protocol (runtime/disagg.py) ---------------------------------


def test_handoff_protocol_fields_and_version():
    from kserve_vllm_mini_tpu.runtime.disagg import (
        DENSE_HANDOFF_VERSION,
        HANDOFF_VERSION,
        KVHandoff,
    )

    # two wire formats, one constant each: a paged consumer expects
    # exactly v2 (block-table, zero-copy), a dense consumer exactly v1
    # (staged stripe). A bump = layout change; consume refuses drift.
    assert HANDOFF_VERSION == 2
    assert DENSE_HANDOFF_VERSION == 1
    ho = KVHandoff(version=DENSE_HANDOFF_VERSION, request_id="r1",
                   handle=None, n_tokens=100, n_blocks=2,
                   reused_prefix_tokens=0)
    assert ho.version == 1
    assert not ho.dropped and ho.kv is None


def test_lane_tombstones_cancelled_and_flushes_on_stop():
    """The never-hang contract, lane side: a cancelled job tombstones
    without compute, and jobs still queued when the lane stops flush as
    tombstones instead of vanishing."""
    from kserve_vllm_mini_tpu.runtime.disagg import PrefillLane

    lane = PrefillLane({}, CFG, EngineConfig(max_slots=2))
    cancelled = RequestHandle(GenRequest(prompt_tokens=[1, 2, 3]))
    cancelled.cancelled = "stop"
    lane.start()
    lane.submit(cancelled)
    deadline = time.time() + 5
    ho = None
    while ho is None and time.time() < deadline:
        ho = lane.pop_ready()
        time.sleep(0.005)
    assert ho is not None and ho.dropped
    assert "cancelled" in ho.error
    # stop with a job still queued: it must flush as a tombstone
    lane._stop.set()
    lane._thread.join(timeout=5)
    queued = RequestHandle(GenRequest(prompt_tokens=[1, 2, 3]))
    lane.submit(queued)
    lane._run()  # re-enter: stop is set, so the loop just flushes
    ho2 = lane.pop_ready()
    assert ho2 is not None and ho2.dropped
    assert "stopped" in ho2.error
    assert not lane.accepts()  # a dead lane refuses new work


def test_lane_backpressure_bound():
    from kserve_vllm_mini_tpu.runtime.disagg import PrefillLane

    lane = PrefillLane({}, CFG, EngineConfig(max_slots=2), max_inflight=2)
    assert lane.accepts()
    lane.submit(RequestHandle(GenRequest(prompt_tokens=[1])))
    lane.submit(RequestHandle(GenRequest(prompt_tokens=[1])))
    assert not lane.accepts()  # at the bound: route colocated
    assert lane.queue_depth() == 2


# -- JAX-free engine harness: drain/cancel mid-handoff (KVM09x shapes) --------


def _harness(slots=2):
    from collections import deque

    from kserve_vllm_mini_tpu.runtime import tracing as rt_tracing
    from kserve_vllm_mini_tpu.runtime.faults import FaultRegistry

    eng = Engine.__new__(Engine)
    eng.ecfg = EngineConfig(max_slots=slots, max_seq_len=64)
    eng.paged = False
    eng.tracer = None
    eng._lockstep = False
    eng._res_lock = threading.Lock()
    eng._faults = FaultRegistry()
    eng._faulted_ids = set()
    eng._phase_hist = {p: rt_tracing.PhaseHistogram() for p in rt_tracing.PHASES}
    eng._sched = rt_tracing.SchedLedger()
    eng.stats = {"requests_completed": 0, "queue_depth": 0}
    eng._slot_req = [None] * slots
    eng._slot_machine = [None] * slots
    eng._slot_adapter = [0] * slots
    eng._slot_len = [0] * slots
    eng._slot_tokens = [[] for _ in range(slots)]
    eng._retained = [[] for _ in range(slots)]
    eng._slot_prefill = [None] * slots
    eng._prefill_fifo = []
    eng._slot_handoff = [None] * slots
    eng._disagg = None
    eng._disagg_degraded = False
    eng._disagg_drop_run = 0
    eng._hit_depths = deque(maxlen=16)
    eng._free = []
    eng._inflight = []
    eng._pending_steps = 0
    eng._slot_behind = [0] * slots
    eng._first_pending = []
    eng._tokens_dev = None
    eng._tokens_dev_slots = frozenset()
    eng._sampling_arrays = None
    eng._adapter_ids_dev = None
    eng._pending = queue.Queue()
    eng._admin = queue.Queue()
    eng._deferred = None
    eng._running = False
    eng._thread = None
    return eng


def _route(eng, slot, rid="r1"):
    h = RequestHandle(GenRequest(prompt_tokens=[1, 2, 3], request_id=rid))
    h.t_admit = now_s()
    eng._slot_req[slot] = h
    eng._slot_handoff[slot] = {"handle": h, "t_route": h.t_admit}
    return h


def _done_events(handle):
    out = []
    while True:
        try:
            evt = handle.events.get_nowait()
        except queue.Empty:
            return out
        if evt[0] == "done":
            out.append(evt[1])


def test_drain_mid_handoff_exactly_once_no_leak():
    """Shutdown drain through a mid-handoff slot: exactly one terminal
    event, zero tokens, slot released (no block/slot leak), handoff
    state cleared — the drain contract extended to the new occupancy."""
    eng = _harness()
    h = _route(eng, 0)
    eng._drain_requests()
    dones = _done_events(h)
    assert len(dones) == 1
    assert dones[0]["finish_reason"] == "cancelled"
    assert dones[0]["tokens_out"] == 0
    assert eng._slot_req[0] is None
    assert eng._slot_handoff[0] is None
    assert 0 in eng._free


def test_abort_handoff_cancel_mid_handoff():
    """Cancel while the prompt is on the lane: zero-token terminal event
    carrying the truncation fields (KVM041), slot serves again."""
    eng = _harness()
    h = _route(eng, 1)
    h.cancelled = "stop"
    eng._abort_handoff(1, h.cancelled)
    dones = _done_events(h)
    assert len(dones) == 1
    assert dones[0]["finish_reason"] == "stop"
    assert dones[0]["tokens_out"] == 0
    assert "truncated" in dones[0]
    assert eng._slot_handoff[1] is None and 1 in eng._free


def test_orphan_handoff_dropped_by_identity_check():
    """A handoff whose slot was already released (cancel landed first)
    is an orphan: consumed silently, lane busy still accounted, no
    activation, no crash."""
    from kserve_vllm_mini_tpu.runtime.disagg import (
        HANDOFF_VERSION,
        KVHandoff,
        PrefillLane,
    )

    eng = _harness()
    eng.stats.update({"kv_handoffs": 0, "kv_handoff_blocks": 0,
                      "kv_handoff_wait_s": 0.0, "kv_handoff_drops": 0,
                      "prefill_lane_busy_s": 0.0,
                      "disagg_colocated_fallbacks": 0})
    lane = PrefillLane({}, CFG, eng.ecfg)
    eng._disagg = lane
    stray = RequestHandle(GenRequest(prompt_tokens=[1, 2, 3]))
    ho = KVHandoff(version=HANDOFF_VERSION, request_id="x", handle=stray,
                   n_tokens=3, n_blocks=1, busy_s=0.5, kv={}, logits=None)
    ho.t_enqueued = now_s()
    with lane._lock:
        lane._inflight += 1
    lane._ready.put(ho)
    eng._consume_handoffs()
    assert eng.stats["kv_handoffs"] == 0
    assert eng.stats["prefill_lane_busy_s"] == 0.5
    assert lane.queue_depth() == 0


def test_disagg_snapshot_empty_on_colocated():
    eng = _harness()
    assert eng.disagg_snapshot() == {}


def test_arm_refusal_on_colocated_engine():
    from kserve_vllm_mini_tpu.runtime.faults import FaultRegistry

    eng = Engine.__new__(Engine)
    eng.paged = False
    eng._faults = FaultRegistry()
    eng._disagg = None
    with pytest.raises(ValueError, match="disagg"):
        eng.arm_fault("kv_handoff_drop")
    eng._disagg = object()
    assert eng.arm_fault("kv_handoff_drop")["name"] == "kv_handoff_drop"


# -- telemetry / schema / tracing contracts (fast) ----------------------------


def test_disagg_block_scrape_contract():
    """DISAGG_METRIC_KEYS parses the exact exposition runtime/server.py
    emits; colocated/external engines yield NO block, not zeros."""
    from kserve_vllm_mini_tpu.analysis import telemetry

    assert telemetry.disagg_block(None) == {}
    assert telemetry.disagg_block("http://127.0.0.1:9") == {}
    text = (
        "# TYPE kvmini_tpu_kv_handoffs_total counter\n"
        "kvmini_tpu_kv_handoffs_total 5\n"
        "# TYPE kvmini_tpu_kv_handoff_blocks_total counter\n"
        "kvmini_tpu_kv_handoff_blocks_total 12\n"
        "# TYPE kvmini_tpu_kv_handoff_wait_seconds_total counter\n"
        "kvmini_tpu_kv_handoff_wait_seconds_total 0.125\n"
        "# TYPE kvmini_tpu_kv_handoff_drops_total counter\n"
        "kvmini_tpu_kv_handoff_drops_total 1\n"
        "# TYPE kvmini_tpu_prefill_lane_busy_seconds_total counter\n"
        "kvmini_tpu_prefill_lane_busy_seconds_total 2.5\n"
        "# TYPE kvmini_tpu_disagg_colocated_fallbacks_total counter\n"
        "kvmini_tpu_disagg_colocated_fallbacks_total 1\n"
        "# TYPE kvmini_tpu_kv_handoff_queue_depth gauge\n"
        "kvmini_tpu_kv_handoff_queue_depth 2\n"
        "# TYPE kvmini_tpu_disagg_degraded gauge\n"
        "kvmini_tpu_disagg_degraded 0\n"
    )
    parsed = telemetry.parse_prometheus_text(text)
    out = telemetry.disagg_block("http://x", runtime_metrics=parsed)
    block = out["disagg"]
    assert block["handoffs"] == 5.0
    assert block["handoff_blocks"] == 12.0
    assert block["handoff_wait_s"] == 0.125
    assert block["handoff_drops"] == 1.0
    assert block["lane_busy_s"] == 2.5
    assert block["colocated_fallbacks"] == 1.0
    assert block["queue_depth"] == 2.0
    assert block["source"] == "metrics:scrape"
    # zero-activity absence rule
    dead = telemetry.parse_prometheus_text(
        "kvmini_tpu_kv_handoffs_total 0\n"
        "kvmini_tpu_kv_handoff_drops_total 0\n"
    )
    assert telemetry.disagg_block("http://x", runtime_metrics=dead) == {}


def test_handoff_phase_and_span_budget_registered():
    """The server.handoff phase is a first-class /metrics histogram
    phase, and the span budget covers the extra per-request span."""
    from kserve_vllm_mini_tpu.runtime.tracing import MAX_REQUEST_SPANS, PHASES

    assert "handoff" in PHASES
    assert MAX_REQUEST_SPANS == 5  # queue+handoff+prefill+decode+cancel


def test_report_disagg_section_renders_and_absent_when_colocated():
    from kserve_vllm_mini_tpu.report.html import _disagg_section

    assert _disagg_section({}) == ""
    html = _disagg_section({
        "disagg": {"handoffs": 4, "handoff_blocks": 9,
                   "handoff_wait_s": 0.02, "handoff_drops": 1,
                   "lane_busy_s": 1.5, "colocated_fallbacks": 1,
                   "degraded": True},
        "monitor": {"events": [{"type": "handoff_stall", "t": 12.0,
                                "detail": "queue grew"}]},
    })
    assert "4 prefill(s) handed off" in html
    assert "9 KV blocks" in html
    assert "DEGRADED" in html
    assert "handoff_stall" in html


# -- handoff_stall monitor rule (fast) ----------------------------------------


def _sample(t, runtime=None, loadgen=None):
    s = {"t": t}
    if runtime is not None:
        s["runtime"] = runtime
    if loadgen is not None:
        s["loadgen"] = loadgen
    return s


def test_handoff_stall_fires_on_growing_queue_with_live_decode():
    from kserve_vllm_mini_tpu.monitor.events import EventDetector

    det = EventDetector(handoff_stall_samples=3)
    fired = []
    for i in range(6):
        fired += det.observe(_sample(
            float(i),
            runtime={"decode_steps_total": 100.0 + i,   # decode LIVE
                     "kv_handoff_queue_depth": float(i)},  # backlog GROWS
        ))
    assert [e.type for e in fired] == ["handoff_stall"]
    assert "prefill lane is saturated" in fired[0].detail


def test_handoff_stall_negative_cases():
    from kserve_vllm_mini_tpu.monitor.events import EventDetector

    # decode frozen -> that's decode_stall's attribution, not this rule's
    det = EventDetector(handoff_stall_samples=2, stall_samples=99)
    fired = []
    for i in range(6):
        fired += det.observe(_sample(
            float(i),
            runtime={"decode_steps_total": 100.0,
                     "kv_handoff_queue_depth": float(i)},
        ))
    assert fired == []

    # queue draining/flat -> healthy lane
    det2 = EventDetector(handoff_stall_samples=2)
    fired2 = []
    for i in range(6):
        fired2 += det2.observe(_sample(
            float(i),
            runtime={"decode_steps_total": 100.0 + i,
                     "kv_handoff_queue_depth": 2.0},
        ))
    assert fired2 == []

    # colocated runtime: no depth gauge at all -> rule inert
    det3 = EventDetector(handoff_stall_samples=2)
    fired3 = []
    for i in range(6):
        fired3 += det3.observe(_sample(
            float(i), runtime={"decode_steps_total": 100.0 + i},
        ))
    assert fired3 == []


# -- chaos surface (fast) -----------------------------------------------------


def test_chaos_local_handoff_drop_scenario_registered():
    from kserve_vllm_mini_tpu.chaos.local import FAULT_ARMS, LOCAL_FAULTS

    assert "handoff-drop" in LOCAL_FAULTS
    assert FAULT_ARMS["handoff-drop"]["name"] == "kv_handoff_drop"
    assert FAULT_ARMS["handoff-drop"]["times"] == 0  # until cleared


# -- live engine: byte identity, faults, cancel/drain (slow) ------------------


@pytest.mark.slow
def test_disagg_streams_byte_identical_to_colocated(params):
    """Greedy streams with the prefill lane on are byte-identical to the
    colocated engine's, across an unaligned prompt, a short prompt, and
    a prompt spilling past max_prefill_len (the lane chunks it at the
    same budget the colocated monolithic loop uses)."""
    prompts = [_prompt(100), _prompt(20, seed=5), _prompt(300, seed=7)]

    def run(disagg):
        eng = make_engine(params, disagg=disagg)
        try:
            outs = []
            for p in prompts:
                h = eng.submit(GenRequest(prompt_tokens=list(p),
                                          max_new_tokens=10))
                toks, info = _drain(h)
                assert info["finish_reason"] == "length"
                outs.append(toks)
            return outs, eng.snapshot_stats()
        finally:
            eng.stop()

    colo, s_colo = run(False)
    dis, s_dis = run(True)
    assert colo == dis
    assert s_dis["kv_handoffs"] == len(prompts)
    assert s_dis["kv_handoff_blocks"] > 0
    assert s_dis["prefill_lane_busy_s"] > 0.0
    assert s_dis["kv_handoff_drops"] == 0
    assert "kv_handoffs" not in s_colo  # colocated engines carry no rail


@pytest.mark.slow
def test_handoff_drop_degrades_to_colocated_never_hangs(params):
    """The handoff-drop chaos contract: with every handoff dropped, each
    request still completes byte-identically (colocated re-prefill), and
    after DROPS_TO_DEGRADE consecutive drops the engine stops routing to
    the lane entirely (degrade ladder's last step)."""
    from kserve_vllm_mini_tpu.runtime.disagg import DROPS_TO_DEGRADE

    eng = make_engine(params, disagg=False, slots=2)
    h = eng.submit(GenRequest(prompt_tokens=_prompt(100), max_new_tokens=6))
    ref, _ = _drain(h)
    eng.stop()

    eng = make_engine(params, disagg=True, slots=2)
    eng.arm_fault("kv_handoff_drop", times=0)
    try:
        outs = []
        for _ in range(DROPS_TO_DEGRADE + 1):
            h = eng.submit(GenRequest(prompt_tokens=_prompt(100),
                                      max_new_tokens=6))
            toks, info = _drain(h)
            assert info["finish_reason"] == "length"
            outs.append(toks)
        s = eng.snapshot_stats()
    finally:
        eng.stop()
    assert all(o == ref for o in outs)
    assert s["kv_handoff_drops"] == DROPS_TO_DEGRADE
    assert s["disagg_colocated_fallbacks"] == DROPS_TO_DEGRADE
    assert s["disagg_degraded"] == 1
    assert s["kv_handoffs"] == 0  # nothing ever landed


@pytest.mark.slow
def test_saturated_lane_with_queued_requests_never_crashes(params):
    """Every slot awaiting a handoff + more requests queued behind them:
    the scheduler's idle path must WAIT for a handoff instead of popping
    work no slot can hold (the pre-review bug: _free.pop() on an empty
    list killed the scheduler and failed every request). All requests
    complete, in admission order, byte-identically."""
    eng = make_engine(params, disagg=True, slots=1)
    try:
        # warm so the measured window races real lane compute
        _drain(eng.submit(GenRequest(prompt_tokens=_prompt(200),
                                     max_new_tokens=2)))
        hs = [
            eng.submit(GenRequest(prompt_tokens=_prompt(200, seed=19 + i),
                                  max_new_tokens=4))
            for i in range(3)
        ]
        for h in hs:
            toks, info = _drain(h)
            assert info["finish_reason"] == "length"
            assert len(toks) == 4
        s = eng.snapshot_stats()
        assert s["kv_handoffs"] == 4  # warm + 3, none crashed out
    finally:
        eng.stop()


@pytest.mark.slow
def test_cancel_mid_handoff_live_releases_slot(params):
    """A request cancelled while its prompt is on the lane ends with
    zero tokens and exactly one terminal event, and the slot serves
    again — live twin of the harness test."""
    eng = make_engine(params, disagg=True, slots=1)
    try:
        # warm the lane executables so the measured cancel window isn't
        # pure compile wall
        w = eng.submit(GenRequest(prompt_tokens=_prompt(200), max_new_tokens=2))
        _drain(w)
        h = eng.submit(GenRequest(prompt_tokens=_prompt(200, seed=11),
                                  max_new_tokens=8))
        eng.cancel(h, "stop")
        toks, info = _drain(h)
        assert toks == [] or info["tokens_out"] == len(toks)
        if info["tokens_out"] == 0:
            assert info["finish_reason"] == "stop"
        # the slot is free again either way: a fresh request completes
        h2 = eng.submit(GenRequest(prompt_tokens=[5, 9, 2], max_new_tokens=4))
        toks2, info2 = _drain(h2)
        assert len(toks2) == 4 and info2["finish_reason"] == "length"
    finally:
        eng.stop()


@pytest.mark.slow
def test_drain_mid_handoff_live_exactly_once(params):
    """stop() while prompts are mid-lane: every handle gets exactly one
    terminal event (KVM09x drain contract through the new occupancy)."""
    eng = make_engine(params, disagg=True, slots=2)
    # warm so the drain races real lane compute, not first-compile wall
    w = eng.submit(GenRequest(prompt_tokens=_prompt(200), max_new_tokens=2))
    _drain(w)
    hs = [
        eng.submit(GenRequest(prompt_tokens=_prompt(200, seed=13 + i),
                              max_new_tokens=8))
        for i in range(3)
    ]
    eng.stop()
    for h in hs:
        events = []
        while True:
            try:
                events.append(h.events.get_nowait())
            except queue.Empty:
                break
        dones = [e for e in events if e[0] == "done"]
        assert len(dones) == 1, h.request.request_id
    # no slot leak
    assert sorted(eng._free) == [0, 1]
    assert all(st is None for st in eng._slot_handoff)


@pytest.mark.slow
def test_disagg_lane_submesh_stream_identity(params):
    """Per-lane meshes end-to-end on the virtual 8-device CPU mesh: a
    4+4 split (llama-tiny's heads divide tp=4), cross-mesh handoff via
    host memory, streams byte-identical to the single-device colocated
    engine."""
    from kserve_vllm_mini_tpu.parallel.mesh import lane_meshes
    from kserve_vllm_mini_tpu.parallel.sharding import shard_params

    eng = make_engine(params, disagg=False, slots=2)
    h = eng.submit(GenRequest(prompt_tokens=_prompt(100), max_new_tokens=6))
    ref, _ = _drain(h)
    eng.stop()

    pre, dec = lane_meshes(4)
    dparams = shard_params(params, CFG, dec)
    eng = Engine(
        dparams, CFG,
        EngineConfig(max_slots=2, max_seq_len=512, max_prefill_len=256,
                     min_prefill_bucket=16, disagg=True),
        mesh=dec, prefill_mesh=pre,
    )
    eng.start()
    try:
        h = eng.submit(GenRequest(prompt_tokens=_prompt(100), max_new_tokens=6))
        toks, info = _drain(h)
        s = eng.snapshot_stats()
    finally:
        eng.stop()
    assert info["finish_reason"] == "length"
    assert toks == ref
    assert s["kv_handoffs"] == 1


# -- the acceptance A/B: mixed long-prefill / short-decode traffic (slow) -----


@pytest.mark.slow
def test_mixed_workload_ttft_and_itl_better_with_disagg():
    """The ISSUE 13 acceptance criterion: under mixed long-prefill/
    short-decode traffic at a prefill-compute-dominant config, TTFT-p95
    (short probes admitted behind long prompts) and ITL-p95 (a live
    stream's token gaps) are STRICTLY better with disaggregation on —
    while every greedy stream stays byte-identical to the colocated
    engine.

    Same scaling rationale as tests/test_prefill_chunking.py's A/B:
    llama-tiny's prefill is dispatch-bound on CPU, so the config scales
    until a warm 2k-token monolithic prefill executes in whole seconds
    against ~0.2 s decode sweeps. Colocated, every long admission
    freezes the stream AND queues the probes behind the monolithic
    execute; disaggregated, the long prefills run on the lane thread
    and the decode lane only ever pays the handoff injection. Buckets
    are pre-warmed so the A/B measures execution stall, not XLA
    compile; all latencies use server-side timestamps."""
    import numpy as np

    cfg = get_config("llama-tiny", max_seq_len=2048).scaled(
        d_model=256, n_heads=8, n_kv_heads=4, n_layers=4, d_ff=1024,
    )
    big_params = init_params(jax.random.PRNGKey(0), cfg)
    long_prompt = [(17 * i + 1) % (cfg.vocab_size // 2) for i in range(2000)]
    stream_prompt = [9, 4, 7, 1]
    probe_prompt = [2, 8, 6]
    n_stream = 16

    def run(disagg):
        eng = Engine(
            big_params, cfg,
            EngineConfig(max_slots=8, max_seq_len=2048,
                         max_prefill_len=1024, min_prefill_bucket=16,
                         disagg=disagg, disagg_min_prompt=64),
        )
        eng.start()
        try:
            # warm every executable: long prefill (lane or colocated
            # shapes), short prefill, first-token fn, decode fn, inject
            w = eng.submit(GenRequest(prompt_tokens=list(long_prompt),
                                      max_new_tokens=2))
            _drain(w)
            w2 = eng.submit(GenRequest(prompt_tokens=list(stream_prompt),
                                       max_new_tokens=4))
            _drain(w2)
            # measurement: one streaming decode; a long prefill lands
            # after the 1st and 6th streamed tokens, a short TTFT probe
            # right behind each long (the mixed-traffic victim)
            hs = eng.submit(GenRequest(prompt_tokens=list(stream_prompt),
                                       max_new_tokens=n_stream))
            stream_toks, s_times = [], []
            longs, probes = [], []
            while True:
                kind, *rest = hs.events.get(timeout=600)
                if kind != "token":
                    break
                stream_toks.append(rest[0])
                s_times.append(rest[1])
                if len(stream_toks) % 5 == 1 and len(longs) < 3:
                    longs.append(eng.submit(GenRequest(
                        prompt_tokens=list(long_prompt), max_new_tokens=4,
                    )))
                    probes.append(eng.submit(GenRequest(
                        prompt_tokens=list(probe_prompt), max_new_tokens=2,
                    )))
            long_streams, probe_streams, ttfts = [], [], []
            for hl in longs:
                l_toks, l_info, _t = _drain_timed(hl)
                assert l_info["finish_reason"] == "length"
                long_streams.append(l_toks)
            for hp in probes:
                p_toks, p_info, _t = _drain_timed(hp)
                assert p_info["finish_reason"] == "length"
                probe_streams.append(p_toks)
                ttfts.append(hp.server_ttft_ms)
            stats = eng.snapshot_stats()
            gaps = np.diff(np.asarray(s_times)) * 1000.0
            itl_p95 = float(np.percentile(gaps, 95))
            ttft_p95 = float(np.percentile(np.asarray(ttfts), 95))
            return ((stream_toks, long_streams, probe_streams),
                    ttft_p95, itl_p95, stats)
        finally:
            eng.stop()

    streams_off, ttft_off, itl_off, s_off = run(False)
    streams_on, ttft_on, itl_on, s_on = run(True)
    assert streams_on == streams_off  # byte-identical either way
    assert s_on["kv_handoffs"] >= 3   # the long prompts really handed off
    assert s_on["kv_handoff_drops"] == 0
    # the point of the architecture: long prefills no longer execute on
    # the decode lane, so neither the stream's gaps nor a probe's queue
    # wait contain a monolithic prefill wall
    assert ttft_on < ttft_off, (
        f"TTFT p95 with disagg ({ttft_on:.1f} ms) not better than "
        f"colocated ({ttft_off:.1f} ms)"
    )
    assert itl_on < itl_off, (
        f"ITL p95 with disagg ({itl_on:.1f} ms) not better than "
        f"colocated ({itl_off:.1f} ms)"
    )


# -- v2 paged handoff: orphan quarantine + version negotiation (fast) ---------


def _paged_harness(slots=2, blk=16):
    """The dense _harness furnished with just enough paged-pool state
    for the route/abort/orphan bookkeeping paths (no device arrays)."""
    from collections import OrderedDict

    import numpy as np

    eng = _harness(slots)
    eng.ecfg = EngineConfig(max_slots=slots, max_seq_len=64,
                            kv_layout="paged", kv_block_size=blk)
    eng.paged = True
    eng._blk = blk
    eng._maxb = 4
    eng._scratch_block = 8
    eng._block_table = np.full((slots, 4), 8, np.int32)
    eng._table_dev = None
    eng._slot_blocks = [[] for _ in range(slots)]
    eng._free_blocks = [2, 3, 4, 5, 6, 7]
    eng._orphan_blocks = {}
    eng._block_rc = {}
    eng._block_hash = {}
    eng._block_depth = {}
    eng._retained_lru = OrderedDict()
    return eng


def test_paged_abort_quarantines_blocks_until_payload_lands():
    """A paged-v2 slot aborted while its prompt is on the lane must NOT
    free its blocks — the lane may still have writes in flight against
    them. They quarantine in _orphan_blocks and return to the pool only
    when the lane's payload (or tombstone) lands (_reap_orphans)."""
    eng = _paged_harness()
    h = _route(eng, 0)
    eng._slot_blocks[0] = [0, 1]
    eng._block_rc.update({0: 1, 1: 1})
    eng._abort_handoff(0, "stop")
    # quarantined, not freed: a reallocation here could race lane stores
    assert eng._orphan_blocks == {id(h): [0, 1]}
    assert 0 not in eng._free_blocks and 1 not in eng._free_blocks
    assert eng._slot_blocks[0] == [] and 0 in eng._free
    # the payload lands later (consume identity check) -> blocks free
    eng._reap_orphans(h)
    assert eng._orphan_blocks == {}
    assert 0 in eng._free_blocks and 1 in eng._free_blocks


def test_version_negotiation_paged_refuses_v1_stripe():
    """A paged consumer speaks exactly HANDOFF_VERSION=2: a v1 dense
    stripe walks the drop ladder (counted, degrade-run bumped) and the
    slot's quarantined blocks reap — never a mis-shaped injection."""
    from kserve_vllm_mini_tpu.runtime.disagg import (
        DENSE_HANDOFF_VERSION,
        KVHandoff,
        PrefillLane,
    )

    eng = _paged_harness()
    eng.stats.update({"kv_handoffs": 0, "kv_handoff_blocks": 0,
                      "kv_handoff_wait_s": 0.0, "kv_handoff_drops": 0,
                      "kv_handoff_bytes_copied": 0,
                      "prefill_lane_busy_s": 0.0,
                      "disagg_colocated_fallbacks": 0})
    lane = PrefillLane({}, CFG, eng.ecfg)
    eng._disagg = lane
    h = _route(eng, 0)
    eng._slot_blocks[0] = [0, 1]
    eng._block_rc.update({0: 1, 1: 1})
    # cancelled too, so the fallback takes the lightweight abort path
    # (the negotiation + reap bookkeeping is what's under test here)
    h.cancelled = "stop"
    ho = KVHandoff(version=DENSE_HANDOFF_VERSION, request_id="r1",
                   handle=h, n_tokens=3, n_blocks=1, busy_s=0.25,
                   kv={}, logits=None)
    ho.t_enqueued = now_s()
    with lane._lock:
        lane._inflight += 1
    lane._ready.put(ho)
    eng._consume_handoffs()
    assert eng.stats["kv_handoff_drops"] == 1
    assert eng.stats["kv_handoffs"] == 0
    assert eng.stats["kv_handoff_bytes_copied"] == 0  # never injected
    assert eng._disagg_drop_run == 1
    # abort quarantined the blocks; the very payload that proved the
    # lane finished also reaped them back to the pool
    assert eng._orphan_blocks == {}
    assert 0 in eng._free_blocks and 1 in eng._free_blocks
    assert eng._slot_handoff[0] is None and 0 in eng._free


# -- the v2 acceptance A/B: zero-copy paged handoff (slow) --------------------


@pytest.mark.slow
def test_paged_handoff_zero_copy_byte_identical(params):
    """The ISSUE 16 tentpole acceptance: at the PR13 mixed config, the
    paged v2 block-table handoff copies <= 10% of the v1 dense stripe's
    KV bytes (it copies ZERO — the lane prefills directly into the
    slot's pool blocks) while greedy streams stay byte-identical. The
    copy tax is measured, not asserted by construction:
    kv_handoff_bytes_copied counts the consume-side inject volume."""
    cfg = get_config("llama-tiny", max_seq_len=2048).scaled(
        d_model=256, n_heads=8, n_kv_heads=4, n_layers=4, d_ff=1024,
    )
    big_params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [
        [(17 * i + 1) % (cfg.vocab_size // 2) for i in range(2000)],
        [(11 * i + 3) % (cfg.vocab_size // 2) for i in range(700)],
        [9, 4, 7, 1],  # below disagg_min_prompt: colocated either way
    ]

    def run(layout):
        eng = Engine(
            big_params, cfg,
            EngineConfig(max_slots=8, max_seq_len=2048,
                         max_prefill_len=1024, min_prefill_bucket=16,
                         disagg=True, disagg_min_prompt=64,
                         kv_layout=layout),
        )
        eng.start()
        try:
            outs = []
            for p in prompts:
                h = eng.submit(GenRequest(prompt_tokens=list(p),
                                          max_new_tokens=8))
                toks, info = _drain(h)
                assert info["finish_reason"] == "length"
                outs.append(toks)
            return outs, eng.snapshot_stats()
        finally:
            eng.stop()

    v1_streams, s_v1 = run("dense")
    v2_streams, s_v2 = run("paged")
    assert v1_streams == v2_streams  # byte-identical greedy either way
    assert s_v1["kv_handoffs"] == 2 and s_v2["kv_handoffs"] == 2
    assert s_v1["kv_handoff_drops"] == 0 and s_v2["kv_handoff_drops"] == 0
    # the tentpole: v1 injects the full staged stripe per handoff; v2
    # moves block IDs only
    assert s_v1["kv_handoff_bytes_copied"] > 0
    assert (s_v2["kv_handoff_bytes_copied"]
            <= 0.10 * s_v1["kv_handoff_bytes_copied"])
    assert s_v2["kv_handoff_bytes_copied"] == 0

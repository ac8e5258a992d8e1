"""In-process fault injection & overload resilience (ISSUE 10,
docs/RESILIENCE.md): the deterministic fault registry, deadline-aware
shedding, the loadgen retry/shed accounting and split timeouts, the
wedged-sweep watchdog + degrade ladder, the graceful-drain contract, the
two new monitor events, and the resilience_table schema.

The engine-side machinery (watchdog trip, engine-fault recovery, drain)
is pure host-side bookkeeping, so the fast tests drive it on a bare
``Engine.__new__`` harness — no params, no device arrays (the same
pattern as tests/test_kv_observability.py). The live end-to-end paths
(overload A/B, watchdog recovery on a real engine, fault determinism)
are slow tests.
"""

import asyncio
import json
import queue
import threading
import time
import urllib.request

import pytest

from kserve_vllm_mini_tpu.analysis.metrics import compute_latency_stats
from kserve_vllm_mini_tpu.core.rundir import RequestRecord, RunDir
from kserve_vllm_mini_tpu.core.schema import validate_resilience
from kserve_vllm_mini_tpu.loadgen.runner import LiveStats, LoadConfig, run_load_async
from kserve_vllm_mini_tpu.monitor.events import EventDetector
from kserve_vllm_mini_tpu.runtime import tracing as rt_tracing
from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig, GenRequest, RequestHandle
from kserve_vllm_mini_tpu.runtime.faults import (
    FAULT_POINTS,
    FaultRegistry,
    parse_faults,
)
from tests.mock_server import MockServer


# -- fault registry ----------------------------------------------------------

def test_registry_after_and_times():
    reg = FaultRegistry()
    reg.arm("device_error", after=2, times=2)
    fired = [reg.check("device_error") is not None for _ in range(6)]
    # checks 1-2 pass through, 3-4 fire, 5-6 exhausted
    assert fired == [False, False, True, True, False, False]


def test_registry_unlimited_times_and_disarm():
    reg = FaultRegistry()
    reg.arm("sse_disconnect", times=0)
    assert all(reg.check("sse_disconnect") for _ in range(5))
    reg.disarm("sse_disconnect")
    assert reg.check("sse_disconnect") is None
    assert reg.armed_count() == 0


def test_registry_probabilistic_is_seed_deterministic():
    def seq(seed):
        reg = FaultRegistry(seed=seed)
        reg.arm("publish_drop", p=0.5, times=0)
        return [reg.check("publish_drop") is not None for _ in range(64)]

    a, b, c = seq(7), seq(7), seq(8)
    assert a == b          # same seed -> identical event sequence
    assert a != c          # the seed actually matters
    assert any(a) and not all(a)


def test_registry_stall_sleeps_duration():
    reg = FaultRegistry()
    reg.arm("sweep_stall", duration=1.5)
    slept = []
    assert reg.stall("sweep_stall", sleep=slept.append) is True
    assert slept == [1.5]
    assert reg.stall("sweep_stall", sleep=slept.append) is False  # times=1


def test_parse_faults_syntax_and_unknown_point():
    reg = parse_faults("sweep_stall:after=5,duration=2.5; device_error:times=3")
    active = reg.active()
    assert active["sweep_stall"]["after"] == 5
    assert active["sweep_stall"]["duration"] == 2.5
    assert active["device_error"]["times"] == 3
    assert parse_faults("") is None
    with pytest.raises(ValueError):
        FaultRegistry().arm("meteor_strike")
    assert set(FAULT_POINTS) == {
        "sweep_stall", "device_error", "kv_alloc_fail", "sse_disconnect",
        "publish_drop", "kv_handoff_drop",
    }


def test_publish_drop_drops_exactly_the_scripted_decision():
    """The multihost publish closure consults check('publish_drop') per
    decision: with after=2,times=1 exactly the 3rd published decision is
    lost — deterministically."""
    reg = FaultRegistry()
    reg.arm("publish_drop", after=2, times=1)
    sent = [d for d in range(6) if not reg.check("publish_drop")]
    assert sent == [0, 1, 3, 4, 5]


# -- engine harness ----------------------------------------------------------

def _handle(rid="r1", deadline_s=None):
    req = GenRequest(prompt_tokens=[1, 2, 3], max_new_tokens=4,
                     request_id=rid, deadline_s=deadline_s)
    return RequestHandle(req)


def _harness(slots=2, **ecfg_kw):
    eng = Engine.__new__(Engine)
    eng.ecfg = EngineConfig(max_slots=slots, max_seq_len=64, **ecfg_kw)
    eng.paged = False
    eng.tracer = None
    eng._lockstep = False
    eng._res_lock = threading.Lock()
    eng._faults = FaultRegistry()
    eng._watch_beat = rt_tracing.now_s()
    eng._sweep_ema_s = 0.0
    eng._service_ema_s = 0.0
    eng._watchdog_trips = 0
    eng._engine_faults = 0
    eng._degrade_level = 0
    eng._requests_shed = 0
    eng._fault_pending = None
    eng._faulted_ids = set()
    eng._live_handles = []
    eng._watch_stop = threading.Event()
    eng._watch_thread = None
    eng._kv_fault_until = 0.0
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


def _done_events(handle):
    out = []
    while True:
        try:
            evt = handle.events.get_nowait()
        except queue.Empty:
            return out
        if evt[0] == "done":
            out.append(evt[1])


def test_deadline_expired_in_queue_sheds_without_prefill():
    eng = _harness()
    h = _handle(deadline_s=0.01)
    h.t_submit = rt_tracing.now_s() - 1.0  # already past its deadline
    eng._admit_one(h)
    dones = _done_events(h)
    assert len(dones) == 1
    assert dones[0]["finish_reason"] == "shed"
    assert dones[0]["tokens_out"] == 0
    assert eng._requests_shed == 1
    assert eng._slot_req == [None, None]  # no slot was ever taken


def test_deadline_shed_disabled_under_lockstep():
    eng = _harness()
    eng._lockstep = True
    h = _handle(deadline_s=0.01)
    h.t_submit = rt_tracing.now_s() - 1.0
    # the deadline branch must NOT fire; the full admission path then
    # needs JAX machinery, so assert via the branch state instead
    deadline_expired = (
        h.request.deadline_s is not None
        and not eng._lockstep
        and rt_tracing.now_s() - h.t_submit > h.request.deadline_s
    )
    assert deadline_expired is False
    assert eng._requests_shed == 0


def test_estimate_wait_reflects_queue_burn_rate():
    eng = _harness(slots=2)
    assert eng.estimate_wait_s() == 0.0  # no history: admit
    eng._service_ema_s = 2.0
    # free slot, empty queue: immediate admission — an idle engine must
    # never shed on a stale (compile-inflated) service EMA
    assert eng.estimate_wait_s() == 0.0
    # slots full + 5 queued: (5//2 + 1 + 1) waves x 2s
    eng._live_handles = [_handle("a"), _handle("b")]
    for i in range(5):
        eng._pending.put(_handle(f"q{i}"))
    assert eng.estimate_wait_s() == pytest.approx((5 // 2 + 2) * 2.0)
    # slots full, queue empty: its own wave plus one
    while not eng._pending.empty():
        eng._pending.get_nowait()
    assert eng.estimate_wait_s() == pytest.approx(2 * 2.0)


def test_watchdog_not_armed_before_first_retire():
    """A cold engine's first decode dispatch blocks in XLA compile; with
    no sweep EMA the watchdog must stay quiet (same arming rule as the
    monitor's stall detector)."""
    eng = _harness()
    eng.ecfg.watchdog_min_s = 0.05
    eng._live_handles = [_handle("cold")]
    eng._sweep_ema_s = 0.0
    eng._watch_beat = rt_tracing.now_s() - 10.0
    t = threading.Thread(target=eng._watchdog_loop, daemon=True)
    t.start()
    time.sleep(0.2)
    eng._watch_stop.set()
    t.join(timeout=2.0)
    assert eng._watchdog_trips == 0 and eng._fault_pending is None


def test_watchdog_trips_once_and_unblocks_clients():
    eng = _harness()
    eng.ecfg.watchdog_min_s = 0.05
    eng.ecfg.watchdog_factor = 1.0
    h1, h2 = _handle("w1"), _handle("w2")
    h1.tokens.append(11)
    eng._live_handles = [h1, h2]
    eng._sweep_ema_s = 0.01  # armed: at least one sweep has retired
    eng._watch_beat = rt_tracing.now_s() - 10.0  # long-stuck scheduler
    t = threading.Thread(target=eng._watchdog_loop, daemon=True)
    t.start()
    done1 = h1.events.get(timeout=2.0)
    done2 = h2.events.get(timeout=2.0)
    time.sleep(0.15)  # a second trip would land within this window
    eng._watch_stop.set()
    t.join(timeout=2.0)
    for done, h in ((done1, h1), (done2, h2)):
        assert done[0] == "done"
        assert done[1]["finish_reason"] == "engine_fault"
        assert h.cancelled == "engine_fault"  # retire path drops its tokens
        assert not _done_events(h)  # exactly once: no second terminal event
    assert done1[1]["tokens_out"] == 1
    assert eng._watchdog_trips == 1  # same stuck beat never trips twice
    assert eng._fault_pending is not None
    assert eng._faulted_ids == {"w1", "w2"}


def test_recovery_finishes_batch_once_frees_slots_and_degrades():
    eng = _harness()
    eng.ecfg.decode_pipeline = True
    eng.ecfg.decode_chunk = 4
    eng.ecfg.spec_tokens = 3
    faulted, fresh = _handle("f1"), _handle("f2")
    faulted.t_first_token = fresh.t_first_token = rt_tracing.now_s()
    eng._slot_req = [faulted, fresh]
    eng._faulted_ids = {"f1"}          # watchdog already unblocked f1
    eng._fault_pending = "watchdog: test"
    eng._inflight = [{"poisoned": True}]
    eng._pending_steps = 3
    eng._recover_engine_fault("watchdog: test")
    assert _done_events(faulted) == []  # no SECOND terminal event
    dones = _done_events(fresh)
    assert len(dones) == 1 and dones[0]["finish_reason"] == "engine_fault"
    assert eng._slot_req == [None, None]
    assert sorted(eng._free) == [0, 1]
    assert eng._inflight == [] and eng._pending_steps == 0
    assert eng._fault_pending is None and eng._faulted_ids == set()
    assert eng.stats["requests_completed"] == 2
    # ladder: trip 1 -> sync pipeline; 2 -> chunk 1; 3 -> spec off
    assert eng._degrade_level == 1 and eng.ecfg.decode_pipeline is False
    eng._recover_engine_fault("again")
    assert eng._degrade_level == 2 and eng.ecfg.decode_chunk == 1
    eng._recover_engine_fault("again")
    assert eng._degrade_level == 3 and eng.ecfg.spec_tokens == 0
    # past the ladder: gives up loudly — queued clients error out
    eng._free = []
    q = _handle("q1")
    eng._pending.put(q)
    eng._recover_engine_fault("again")
    assert eng._degrade_level == 4
    assert eng._running is False
    dq = _done_events(q)
    assert len(dq) == 1 and dq[0]["finish_reason"] == "error"


def test_drain_contract_exactly_one_terminal_event_no_leak():
    eng = _harness()
    live, watched = _handle("d1"), _handle("d2")
    live.t_admit = live.t_first_token = rt_tracing.now_s()
    watched.t_first_token = rt_tracing.now_s()
    eng._slot_req = [live, watched]
    eng._faulted_ids = {"d2"}  # already got its terminal event (watchdog)
    queued = _handle("d3")
    eng._pending.put(queued)
    eng._drain_requests()
    d_live = _done_events(live)
    assert len(d_live) == 1 and d_live[0]["finish_reason"] == "cancelled"
    assert _done_events(watched) == []      # released, not re-notified
    d_q = _done_events(queued)
    assert len(d_q) == 1 and d_q[0]["finish_reason"] == "cancelled"
    assert eng._slot_req == [None, None]
    assert sorted(eng._free) == [0, 1]      # no slot leak
    assert eng._pending.empty()


def test_stop_never_started_unblocks_queued_clients():
    eng = _harness()
    h = _handle("n1")
    eng._pending.put(h)
    eng.stop()
    dones = _done_events(h)
    assert len(dones) == 1 and dones[0]["finish_reason"] == "cancelled"


def test_kv_alloc_fail_opens_backpressure_window():
    eng = _harness()
    eng.paged = True
    eng._faults.arm("kv_alloc_fail", duration=30.0)
    # the fit check consults the fault BEFORE any plan math, so the
    # paged bookkeeping attrs are never touched while the window is open
    req = GenRequest(prompt_tokens=[1, 2], max_new_tokens=2)
    assert eng._paged_fits(req) is False
    assert eng._kv_fault_until > time.time()


# -- loadgen: retries, sheds, split timeouts ---------------------------------

def _run(coro):
    return asyncio.run(coro)


async def _arm_mock(url, name, **params):
    import httpx

    async with httpx.AsyncClient() as c:
        r = await c.post(url + "/faults",
                         json={"action": "arm", "name": name, **params})
        assert r.status_code == 200


def test_loadgen_retries_429_then_succeeds(tmp_path):
    async def go():
        async with MockServer(token_delay_s=0.0) as srv:
            await _arm_mock(srv.url, "shed", times=2, retry_after=0)
            cfg = LoadConfig(
                url=srv.url, num_requests=1, concurrency=1, streaming=False,
                target_rps=100.0, max_retries=3, retry_backoff_s=0.01,
            )
            rd = RunDir.create(tmp_path, run_id="retry")
            live = LiveStats()
            return live, await run_load_async(cfg, rd, live=live)

    live, records = _run(go())
    assert len(records) == 1
    rec = records[0]
    assert rec.ok and not rec.shed
    assert rec.retries == 2           # both 429s absorbed into ONE record
    snap = live.snapshot()
    assert snap["retries"] == 2 and snap["shed"] == 0 and snap["errors"] == 0


def test_loadgen_shed_past_budget_is_not_an_error(tmp_path):
    async def go():
        async with MockServer(token_delay_s=0.0) as srv:
            await _arm_mock(srv.url, "shed", times=0, retry_after=0)
            cfg = LoadConfig(
                url=srv.url, num_requests=2, concurrency=2, streaming=False,
                target_rps=100.0, max_retries=1, retry_backoff_s=0.01,
            )
            rd = RunDir.create(tmp_path, run_id="shed")
            live = LiveStats()
            return rd, live, await run_load_async(cfg, rd, live=live)

    rd, live, records = _run(go())
    assert all(r.shed and not r.ok and r.error == "shed" for r in records)
    assert all(r.status_code == 429 and r.retries == 1 for r in records)
    snap = live.snapshot()
    assert snap["shed"] == 2 and snap["errors"] == 0
    # CSV round-trip carries the columns
    back = rd.read_requests()
    assert all(r.shed and r.retries == 1 for r in back)
    # analyzer: sheds are SEPARATE from errors, percentiles over admitted
    stats = compute_latency_stats(back)
    assert stats["error_rate"] == 0.0
    assert stats["shed_requests"] == 2 and stats["shed_rate"] == 1.0
    assert stats["retries_total"] == 2
    assert "p95_ms" not in stats  # no admitted rows -> no fabricated p95


def test_stalled_sse_stream_fails_fast_as_timeout_row(tmp_path):
    """Split-timeout satellite: the mock stalls the stream after the
    first chunk WITHOUT closing it; the read timeout turns that into a
    `timeout` row in well under the legacy whole-request budget."""
    async def go():
        async with MockServer(token_delay_s=0.0, n_tokens=8) as srv:
            await _arm_mock(srv.url, "sse_stall", after_tokens=1,
                            duration=30.0)
            cfg = LoadConfig(
                url=srv.url, num_requests=1, concurrency=1, streaming=True,
                target_rps=100.0, timeout_s=120.0, read_timeout_s=0.3,
                max_retries=0,
            )
            rd = RunDir.create(tmp_path, run_id="stall")
            t0 = time.time()
            records = await run_load_async(cfg, rd)
            return records, time.time() - t0

    records, elapsed = _run(go())
    assert len(records) == 1
    assert records[0].error == "timeout" and not records[0].ok
    assert not records[0].shed
    assert elapsed < 10.0  # a worker never hangs for the 120 s budget


# -- monitor events ----------------------------------------------------------

def _sample(t, runtime=None, loadgen=None):
    s = {"t": t}
    if runtime is not None:
        s["runtime"] = runtime
    if loadgen is not None:
        s["loadgen"] = loadgen
    return s


def test_overload_shedding_event_is_delta_based():
    det = EventDetector()
    # a large HISTORICAL total that never moves must not fire
    det.observe(_sample(0, loadgen={"inflight": 1, "shed": 50}))
    fired = det.observe(_sample(1, loadgen={"inflight": 1, "shed": 50}))
    assert fired == []
    fired = det.observe(_sample(2, loadgen={"inflight": 1, "shed": 53}))
    assert [e.type for e in fired] == ["overload_shedding"]
    assert fired[0].data["shed_delta"] == 3
    # one-shot per run
    assert det.observe(_sample(3, loadgen={"inflight": 1, "shed": 60})) == []


def test_overload_shedding_event_from_runtime_counter():
    det = EventDetector()
    det.observe(_sample(0, runtime={"requests_shed_total": 0}))
    fired = det.observe(_sample(1, runtime={"requests_shed_total": 2}))
    assert [e.type for e in fired] == ["overload_shedding"]


def test_engine_fault_event_fires_on_counter_move_with_degrade_level():
    det = EventDetector()
    det.observe(_sample(0, runtime={"engine_faults_total": 0}))
    fired = det.observe(_sample(
        1, runtime={"engine_faults_total": 1, "degrade_level": 1}
    ))
    assert [e.type for e in fired] == ["engine_fault"]
    assert fired[0].data["degrade_level"] == 1
    # a flat counter never fires
    det2 = EventDetector()
    det2.observe(_sample(0, runtime={"engine_faults_total": 3}))
    assert det2.observe(_sample(1, runtime={"engine_faults_total": 3})) == []


# -- resilience_table schema -------------------------------------------------

def _table(**over):
    doc = {
        "service": "local", "namespace": "-", "target": "local",
        "all_recovered": True, "worst_mttr_s": 1.5,
        "faults": [
            {"fault": "sweep-wedge", "injected": True, "recovered": True,
             "mttr_s": 1.5, "p95_ms": 120.0, "error_rate": 0.5,
             "shed_rate": 0.0, "gate_ok": None, "detail": "ok"},
            {"fault": "publish-drop", "injected": False, "recovered": False,
             "mttr_s": None, "p95_ms": None, "error_rate": None,
             "shed_rate": None, "gate_ok": None, "detail": "needs multihost"},
        ],
    }
    doc.update(over)
    return doc


def test_validate_resilience_accepts_good_table():
    assert validate_resilience(_table()) == []


def test_validate_resilience_rejects_false_green_and_bad_values():
    bad = _table()
    bad["faults"][1]["gate_ok"] = True  # injection failed but gate green
    assert any("gate_ok must be null" in e for e in validate_resilience(bad))
    bad2 = _table()
    bad2["faults"][0]["mttr_s"] = -1
    assert any("mttr_s" in e for e in validate_resilience(bad2))
    bad3 = _table()
    bad3["faults"][0]["error_rate"] = 1.5
    assert any("error_rate" in e for e in validate_resilience(bad3))
    bad4 = _table()
    bad4["faults"][0]["mttr_s"] = None  # recovered row must carry MTTR
    assert any("numeric mttr_s" in e for e in validate_resilience(bad4))
    assert validate_resilience({"all_recovered": True}) == [
        "faults missing or not an array"
    ]


def test_resilience_report_section_renders_and_absent_when_clean():
    from kserve_vllm_mini_tpu.report.html import _resilience_section

    assert _resilience_section({}) == ""
    html = _resilience_section({
        "shed_requests": 3, "shed_rate": 0.1, "retries_total": 5,
        "resilience": {"requests_shed": 3, "watchdog_trips": 1,
                       "engine_faults": 1, "degrade_level": 1,
                       "faults_armed": 2, "source": "engine:snapshot"},
        "monitor": {"events": [
            {"t": 10.0, "type": "engine_fault", "detail": "recovered"},
        ]},
    })
    assert "Resilience" in html
    assert "3 request(s) shed" in html
    assert "watchdog trip" in html
    assert "sync pipeline" in html       # degrade ladder label
    assert "engine_fault" in html


# -- slow end-to-end ---------------------------------------------------------

def _post_json(url, payload, timeout=30.0):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read().decode()


@pytest.mark.slow
def test_overload_ab_shedding_keeps_admitted_p95_bounded(tmp_path):
    """Acceptance: at arrival >= 2x capacity, deadline shedding keeps
    admitted-request p95 bounded while sheds are counted separately;
    WITHOUT shedding the same run demonstrably collapses."""
    from kserve_vllm_mini_tpu.runtime.local import local_server

    N = 24

    def overload(deadline_ms, run_id):
        profile = {"model": "llama-tiny", "max_slots": 2,
                   "max_model_len": 128}
        with local_server(profile) as srv:
            # warm the compile caches; the LAST warm request's latency is
            # the steady-state service time the deadline scales from
            warm_s = 0.0
            for _ in range(3):
                t0 = time.time()
                _post_json(srv.url + "/v1/chat/completions", {
                    "messages": [{"role": "user", "content": "warm"}],
                    "max_tokens": 16, "stream": False,
                }, timeout=300.0)
                warm_s = time.time() - t0
            cfg = LoadConfig(
                url=srv.url, num_requests=N, concurrency=N,
                target_rps=1000.0, max_tokens=16, streaming=False,
                max_retries=0,
                deadline_ms=(
                    deadline_ms(warm_s) if deadline_ms is not None else None
                ),
            )
            rd = RunDir.create(tmp_path, run_id=run_id)
            records = asyncio.run(run_load_async(cfg, rd))
        return compute_latency_stats(records), records, warm_s

    # deadline = 3x one warm request: at ~12 queue waves, most of the
    # burst provably cannot meet it — the shed path MUST engage
    shed_stats, shed_records, warm_s = overload(
        lambda w: max(w * 3.0, 0.2) * 1000.0, "ab-shed"
    )
    base_stats, _, _ = overload(None, "ab-base")

    assert base_stats.get("shed_requests") is None  # B never sheds
    assert "p95_ms" in base_stats
    assert shed_stats.get("shed_requests", 0) > 0   # A sheds under overload
    assert shed_stats["error_rate"] == 0.0          # sheds are NOT errors
    assert "p95_ms" in shed_stats                   # some requests admitted
    # the A/B: admitted p95 stays bounded where the unshed twin collapses
    assert shed_stats["p95_ms"] < base_stats["p95_ms"]
    # shed responses carried Retry-After-driven 429s, never fabricated rows
    assert all(r.status_code == 429 for r in shed_records if r.shed)
    assert (shed_stats.get("shed_requests", 0)
            + sum(1 for r in shed_records if r.ok)) == N


@pytest.mark.slow
def test_watchdog_recovers_live_engine_and_monitor_sees_it(tmp_path):
    """Acceptance: an injected wedged sweep is detected, in-flight
    requests finish with finish_reason='engine_fault', the engine serves
    new requests afterward, and the monitor timeline carries the
    engine_fault event."""
    from kserve_vllm_mini_tpu.monitor import MonitorConfig, RunMonitor
    from kserve_vllm_mini_tpu.runtime.local import local_server

    profile = {
        "model": "llama-tiny", "max_slots": 2, "max_model_len": 128,
        "watchdog": True, "watchdog_min_s": 0.5,
        "allow_fault_injection": True,
    }
    with local_server(profile) as srv:
        # warm enough sweeps that the compile-inflated sweep EMA decays
        # to warm levels (the watchdog arms after the first retire and
        # thresholds at factor x EMA)
        for _ in range(3):
            _post_json(srv.url + "/v1/chat/completions", {
                "messages": [{"role": "user", "content": "warm"}],
                "max_tokens": 24, "stream": False,
            }, timeout=300.0)
        monitor = RunMonitor(
            tmp_path / "timeline.jsonl", endpoint=srv.url,
            cfg=MonitorConfig(interval_s=0.2),
        ).start()
        status, _ = _post_json(srv.url + "/faults", {
            "action": "arm", "name": "sweep_stall", "times": 1,
            "duration": 4.0,
        })
        assert status == 200
        # long enough decode that the wedge lands mid-request
        body = {"messages": [{"role": "user", "content": "go"}],
                "max_tokens": 64, "stream": False}
        req = urllib.request.Request(
            srv.url + "/v1/chat/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60.0) as r:
            data = json.loads(r.read())
        assert data["choices"][0]["finish_reason"] == "engine_fault"
        # the engine serves new requests after recovery (degraded)
        deadline = time.time() + 30.0
        ok_after = False
        while time.time() < deadline:
            try:
                status, text = _post_json(srv.url + "/v1/chat/completions", {
                    "messages": [{"role": "user", "content": "after"}],
                    "max_tokens": 4, "stream": False,
                }, timeout=10.0)
                after = json.loads(text)
                if after["choices"][0]["finish_reason"] in ("stop", "length"):
                    ok_after = True
                    break
            except Exception:
                time.sleep(0.3)
        assert ok_after, "engine did not serve requests after the fault"
        time.sleep(0.6)  # one more monitor tick past the recovery
        summary = monitor.stop()
        # runtime rail moved end to end
        from kserve_vllm_mini_tpu.analysis.telemetry import (
            resilience_block,
            scrape_runtime_metrics,
        )

        m = scrape_runtime_metrics(srv.url)
        assert m["kvmini_tpu_watchdog_trips_total"] >= 1
        assert m["kvmini_tpu_engine_faults_total"] >= 1
        assert m["kvmini_tpu_degrade_level"] >= 1
        block = resilience_block(srv.url, runtime_metrics=m)["resilience"]
        assert block["watchdog_trips"] >= 1
    assert "engine_fault" in [e["type"] for e in summary["events"]]


@pytest.mark.slow
def test_live_stop_drains_inflight_and_queued_deterministically():
    """Satellite: stop() with in-flight AND queued requests cancels
    deterministically on a LIVE engine — every handle gets a terminal
    event exactly once, and no slot or block leaks."""
    from kserve_vllm_mini_tpu.runtime.server import build_engine

    engine, tok, _ = build_engine(model="llama-tiny", max_slots=2,
                                  max_seq_len=128)
    engine.start()
    # warm one request so the stop lands mid-decode, not mid-compile
    warm = engine.submit(GenRequest(prompt_tokens=[5, 6, 7], max_new_tokens=2))
    while warm.events.get(timeout=120.0)[0] != "done":
        pass
    handles = [
        engine.submit(GenRequest(
            prompt_tokens=list(range(3 + i, 13 + i)), max_new_tokens=512,
            request_id=f"drain-{i}",
        ))
        for i in range(6)  # 2 slots in flight + 4 queued
    ]
    time.sleep(0.3)  # let the first pair admit and start decoding
    engine.stop()
    for h in handles:
        # exactly one terminal event: wait for the first, then assert no
        # second one is queued behind it (stop() has fully drained)
        while True:
            evt = h.events.get(timeout=10.0)
            if evt[0] == "done":
                first = evt[1]
                break
        assert first["finish_reason"] in ("cancelled", "stop", "length")
        extra = _done_events(h)
        assert extra == [], f"{h.request.request_id}: second done {extra}"
    assert all(h is None for h in engine._slot_req)
    assert sorted(engine._free) == [0, 1]  # no slot leak
    assert engine._pending.empty()


@pytest.mark.slow
def test_fault_determinism_and_untouched_streams_byte_identical():
    """Acceptance: with a fixed fault seed, two runs of the same scripted
    scenario produce identical event sequences, and requests untouched
    by the fault produce byte-identical streams vs a no-fault run."""
    from kserve_vllm_mini_tpu.runtime.server import build_engine

    def run_engine(faults):
        engine, tok, _ = build_engine(
            model="llama-tiny", max_slots=2, max_seq_len=128,
            faults=faults, fault_seed=7,
        )
        # queue everything BEFORE starting: admission order and sweep
        # interleaving are then fully deterministic
        handles = [
            engine.submit(GenRequest(
                prompt_tokens=list(range(10 + i, 20 + i)), max_new_tokens=8,
                request_id=f"req-{i}",
            ))
            for i in range(6)
        ]
        engine.start()
        out = {}
        for h in handles:
            while True:
                evt = h.events.get(timeout=120.0)
                if evt[0] == "done":
                    out[h.request.request_id] = (
                        h.finish_reason or evt[1]["finish_reason"],
                        tuple(h.tokens),
                    )
                    break
        engine.stop()
        return out

    clean = run_engine(None)
    assert all(r[0] in ("stop", "length") for r in clean.values())
    fault_cfg = "device_error:after=8,times=1"
    a = run_engine(fault_cfg)
    b = run_engine(fault_cfg)
    assert a == b  # identical event sequence, fixed seed/script
    faulted = {rid for rid, r in a.items() if r[0] == "engine_fault"}
    assert faulted  # the scripted fault actually hit something
    for rid, (reason, toks) in a.items():
        if rid not in faulted:
            assert reason == clean[rid][0]
            assert toks == clean[rid][1]  # byte-identical untouched streams

"""Request-lifecycle tracing tests (ISSUE 2, docs/TRACING.md): the
ring-buffer recorder's bounds, W3C traceparent parsing, OTLP export
clamps, the phase histograms, traceparent propagation end-to-end against
the echoing mock server, the analyzer-side merge + phase_breakdown, and
the engine-side overhead-guard contract. Everything here runs without a
TPU; only the full-generation test at the bottom is slow-marked."""

import asyncio
import json

import pytest

from kserve_vllm_mini_tpu.analysis import traces as traces_mod
from kserve_vllm_mini_tpu.analysis.telemetry import parse_prometheus_text
from kserve_vllm_mini_tpu.core.rundir import RunDir
from kserve_vllm_mini_tpu.core.schema import validate_traces
from kserve_vllm_mini_tpu.loadgen.runner import LoadConfig, run_load_async
from kserve_vllm_mini_tpu.loadgen.tracing import TraceSpan
from kserve_vllm_mini_tpu.runtime.tracing import (
    MAX_REQUEST_SPANS,
    PHASE_BUCKETS,
    PhaseHistogram,
    SpanRecorder,
    new_span_id,
    new_trace_id,
    parse_traceparent,
    render_phase_histograms,
    span_to_otlp,
    spans_from_otlp,
)
from tests.mock_server import MockServer


# -- traceparent parsing -----------------------------------------------------

def test_parse_traceparent_roundtrips_loadgen_header():
    from kserve_vllm_mini_tpu.loadgen.tracing import traceparent

    tid, sid = new_trace_id(), new_span_id()
    assert parse_traceparent(traceparent(tid, sid)) == (tid, sid)


@pytest.mark.parametrize("bad", [
    None, "", "00-abc-def-01", "garbage",
    "00-" + "z" * 32 + "-" + "a" * 16 + "-01",   # non-hex trace id
    "00-" + "0" * 32 + "-" + "a" * 16 + "-01",   # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
    "00-" + "a" * 31 + "-" + "a" * 16 + "-01",   # short trace id
    "00-" + "A" * 32 + "-" + "a" * 16 + "-01",   # uppercase (W3C: lowercase)
    "00-0x" + "a" * 30 + "-" + "a" * 16 + "-01",  # int()-parseable junk
])
def test_parse_traceparent_rejects_malformed(bad):
    assert parse_traceparent(bad) is None


# -- ring buffer (the overhead guard) ----------------------------------------

def test_span_recorder_ring_eviction_bounded():
    """Recording must never grow the buffer past capacity — the bounded-
    memory half of the overhead guard (docs/TRACING.md)."""
    rec = SpanRecorder(capacity=16)
    tid = new_trace_id()
    for i in range(100):
        rec.record("server.queue", tid, i, i + 1)
    assert len(rec) == 16
    assert rec.dropped == 84
    # the survivors are the NEWEST 16 (ring semantics, oldest evict)
    starts = [r[4] for r in rec.snapshot()]
    assert starts == list(range(84, 100))
    doc = rec.to_otlp()
    assert doc["droppedSpans"] == 84
    assert len(doc["resourceSpans"][0]["scopeSpans"][0]["spans"]) == 16


def test_span_recorder_rejects_zero_capacity():
    with pytest.raises(ValueError):
        SpanRecorder(capacity=0)


def test_to_otlp_safe_under_concurrent_recording():
    """GET /traces renders while the scheduler thread records: to_otlp
    must snapshot (one C-level copy), never iterate the live deque — a
    concurrent append mid-iteration raises 'deque mutated during
    iteration' and 500s the endpoint."""
    import threading

    rec = SpanRecorder(capacity=64)
    tid = new_trace_id()
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            rec.record("server.queue", tid, i, i + 1)
            i += 1

    def reader():
        try:
            for _ in range(300):
                rec.to_otlp()
        except RuntimeError as e:  # pragma: no cover - the bug itself
            errors.append(e)

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    reader()
    stop.set()
    w.join(timeout=5)
    assert errors == []


def test_request_span_ceiling_is_pinned():
    """The engine stamps queue + handoff (disaggregated admissions only,
    docs/DISAGGREGATION.md) + prefill + decode + cancel per request and
    NOTHING per token; MAX_REQUEST_SPANS is the contract tests and docs
    key off — changing it means re-auditing the engine's stamping sites."""
    assert MAX_REQUEST_SPANS == 5


def test_recorder_otlp_shape_valid_against_schema():
    rec = SpanRecorder(capacity=8)
    tid = new_trace_id()
    parent = new_span_id()
    rec.record("server.queue", tid, 1000, 2000, parent_span_id=parent,
               attrs={"request_id": "r1", "slot": 3, "ratio": 0.5,
                      "pipelined": True})
    doc = rec.to_otlp()
    assert validate_traces(doc) == []
    span = doc["resourceSpans"][0]["scopeSpans"][0]["spans"][0]
    assert span["kind"] == 2  # SPAN_KIND_SERVER
    assert span["parentSpanId"] == parent
    attr_keys = {a["key"] for a in span["attributes"]}
    assert attr_keys == {"request_id", "slot", "ratio", "pipelined"}


def test_never_ended_server_span_clamps_at_export():
    """end < start (a span abandoned mid-error) must export a zero
    duration and an error status, never a negative duration."""
    rec = SpanRecorder(capacity=4)
    rec.record("server.decode", new_trace_id(), 5000, 0)
    span = span_to_otlp(rec.snapshot()[0])
    assert span["startTimeUnixNano"] == span["endTimeUnixNano"] == "5000"
    assert span["status"]["code"] == 2


def test_client_trace_span_clamps_never_ended_export():
    """Satellite: loadgen TraceSpan error paths can leave end_ns=0; the
    OTLP export must clamp to the start and flag status_ok=False."""
    s = TraceSpan(name="http.request", trace_id=new_trace_id()).start()
    # .end() never runs (error path)
    out = s.to_otlp()
    assert out["endTimeUnixNano"] == out["startTimeUnixNano"]
    assert out["status"]["code"] == 2
    # the span object itself is NOT mutated (export is read-only)
    assert s.end_ns == 0 and s.status_ok is True
    # a properly ended span is untouched
    s2 = TraceSpan(name="ok", trace_id=new_trace_id()).start()
    s2.end()
    assert s2.to_otlp()["status"]["code"] == 1
    assert int(s2.to_otlp()["endTimeUnixNano"]) >= int(
        s2.to_otlp()["startTimeUnixNano"]
    )


# -- phase histograms --------------------------------------------------------

def test_phase_histogram_cumulative_buckets():
    h = PhaseHistogram()
    h.observe(0.0005)   # <= 0.001
    h.observe(0.003)    # <= 0.005
    h.observe(0.003)
    h.observe(100.0)    # +Inf only
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(100.0065)
    # cumulative: every bucket >= the previous, last finite bucket == 3
    assert snap["buckets"][0] == 1
    assert snap["buckets"][PHASE_BUCKETS.index(0.005)] == 3
    assert snap["buckets"][-1] == 3  # 100 s is beyond the largest bound


def test_render_phase_histograms_prometheus_shape():
    h = PhaseHistogram()
    h.observe(0.01)
    lines = render_phase_histograms({"queue": h})
    text = "\n".join(lines)
    assert '# TYPE kvmini_tpu_phase_seconds histogram' in text
    assert 'kvmini_tpu_phase_seconds_bucket{phase="queue",le="+Inf"} 1' in text
    assert 'kvmini_tpu_phase_seconds_count{phase="queue"} 1' in text
    # the flat scrape parser reads it (buckets sum across le labels — the
    # flat dict is not a histogram decoder, it just must not choke)
    parsed = parse_prometheus_text(text)
    assert parsed["kvmini_tpu_phase_seconds_count"] == 1.0


def test_parse_prometheus_sums_duplicate_labeled_series():
    """Satellite: labeled series sharing a metric name must SUM, not
    last-wins — a multi-tenant counter export silently reported only the
    exporter's last series before."""
    text = (
        'kvmini_tpu_requests_total{tenant="a"} 3\n'
        'kvmini_tpu_requests_total{tenant="b"} 4\n'
        'kvmini_tpu_duty_cycle 0.5\n'
    )
    parsed = parse_prometheus_text(text)
    assert parsed["kvmini_tpu_requests_total"] == 7.0
    assert parsed["kvmini_tpu_duty_cycle"] == 0.5


# -- traceparent propagation end-to-end (mock server echoes) -----------------

def _load_against_mock(tmp_path, n_requests=6, streaming=True):
    """Run the loadgen against the echoing mock and return
    (run_dir, records, server /traces doc, /metrics text)."""
    import urllib.request

    async def go():
        async with MockServer(token_delay_s=0.001) as srv:
            cfg = LoadConfig(
                url=srv.url, num_requests=n_requests, concurrency=3,
                target_rps=300.0, max_tokens=4, streaming=streaming,
            )
            rd = RunDir.create(tmp_path, run_id="trace-e2e")
            records = await run_load_async(cfg, rd)
            server_doc = await asyncio.to_thread(
                traces_mod.fetch_server_traces, srv.url
            )
            metrics_text = await asyncio.to_thread(
                lambda: urllib.request.urlopen(srv.url + "/metrics").read().decode()
            )
            return rd, records, server_doc, metrics_text

    return asyncio.run(go())


def test_traceparent_propagates_and_server_spans_parent_correctly(tmp_path):
    rd, records, server_doc, metrics_text = _load_against_mock(tmp_path)
    assert all(r.ok for r in records)
    client_doc = rd.read_traces()

    # client http.request span id per trace — the traceparent the loadgen
    # sent names exactly this span
    http_span = {
        s["traceId"]: s for _svc, s in spans_from_otlp(client_doc)
        if s["name"] == "http.request"
    }
    server_spans = list(spans_from_otlp(server_doc))
    assert server_spans, "mock /traces served no spans"
    queue_spans = [s for _svc, s in server_spans if s["name"] == "server.queue"]
    assert len(queue_spans) == len(records)
    for s in queue_spans:
        assert s["traceId"] in http_span, "server span on an unknown trace"
        # THE parenting assertion: server spans hang under the client's
        # http.request span (the traceparent's span id), so the joined
        # trace reads http.request -> server.queue/prefill/decode
        assert s["parentSpanId"] == http_span[s["traceId"]]["spanId"]
        # the mock echoes the raw header too
        tp_attr = {a["key"]: a["value"] for a in s["attributes"]}
        assert tp_attr["traceparent"]["stringValue"].split("-")[1] == s["traceId"]
    names_by_trace = {}
    for _svc, s in server_spans:
        names_by_trace.setdefault(s["traceId"], set()).add(s["name"])
    for tid in http_span:
        assert names_by_trace[tid] == {
            "server.queue", "server.prefill", "server.decode"
        }

    # /metrics exposes the phase histograms alongside
    assert 'kvmini_tpu_phase_seconds_bucket{phase="queue"' in metrics_text
    assert 'kvmini_tpu_phase_seconds_count{phase="decode"} 6' in metrics_text


def test_merge_joins_by_trace_id_with_clock_offset(tmp_path):
    rd, records, server_doc, _ = _load_against_mock(tmp_path)
    client_doc = rd.read_traces()
    merged, matched = traces_mod.merge_server_traces(client_doc, server_doc)
    assert matched and len(matched) == 3 * len(records)
    assert validate_traces(merged) == []
    # same-process clocks: the offset estimate is the fastest one-way
    # delivery — tiny and non-negative (server.queue starts after the
    # client sent the request)
    offset = merged["clockOffsetNanosEstimate"]
    assert 0 <= offset < 5e9
    # every request's trace now carries BOTH legs in one doc
    by_trace = {}
    for _svc, s in spans_from_otlp(merged):
        by_trace.setdefault(s["traceId"], set()).add(s["name"])
    full = [
        t for t, names in by_trace.items()
        if {"http.request", "server.queue", "server.prefill",
            "server.decode"} <= names
    ]
    assert len(full) == len(records)

    pb = traces_mod.phase_breakdown(matched, offset)
    for phase in ("queue", "prefill", "decode"):
        assert pb[phase]["count"] == len(records)
        assert pb[phase]["p50_ms"] <= pb[phase]["p95_ms"] <= pb[phase]["max_ms"]
    assert pb["clock_offset_ms_est"] == pytest.approx(offset / 1e6)
    assert pb["source"] == "server:/traces"


def test_merge_is_idempotent_on_reanalyze(tmp_path):
    """`kvmini-tpu analyze` is re-runnable on an existing run dir: the
    second merge reads back the ALREADY-MERGED doc and must replace the
    server leg, not append a duplicate block per re-run."""
    rd, records, server_doc, _ = _load_against_mock(tmp_path, n_requests=3)
    client_doc = rd.read_traces()
    merged1, matched1 = traces_mod.merge_server_traces(client_doc, server_doc)
    merged2, matched2 = traces_mod.merge_server_traces(merged1, server_doc)
    assert len(matched2) == len(matched1)
    n1 = sum(1 for _ in spans_from_otlp(merged1))
    n2 = sum(1 for _ in spans_from_otlp(merged2))
    assert n1 == n2
    assert len(merged2["resourceSpans"]) == len(merged1["resourceSpans"])


def test_merge_degrades_without_server_doc(tmp_path):
    """External engines: no /traces -> client doc untouched, no
    phase_breakdown (absence, not zeros)."""
    assert traces_mod.fetch_server_traces("http://127.0.0.1:9") == {}
    client_doc = {"resourceSpans": []}
    merged, matched = traces_mod.merge_server_traces(client_doc, {})
    assert matched == [] and merged["resourceSpans"] == []
    assert traces_mod.phase_breakdown([]) == {}


def test_merge_drops_other_runs_spans(tmp_path):
    """Spans of OTHER runs still in the server ring must not leak into
    this run's traces.json."""
    rd, records, server_doc, _ = _load_against_mock(tmp_path, n_requests=3)
    client_doc = rd.read_traces()
    alien = {
        "resourceSpans": [{
            "resource": {"attributes": [
                {"key": "service.name",
                 "value": {"stringValue": "kvmini-tpu-runtime"}}]},
            "scopeSpans": [{"scope": {"name": "x"}, "spans": [
                {"traceId": "ab" * 16, "spanId": "cd" * 8,
                 "name": "server.queue",
                 "startTimeUnixNano": "1", "endTimeUnixNano": "2",
                 "attributes": [], "kind": 2, "status": {"code": 1}},
            ]}],
        }]
    }
    # alien-only server doc: nothing joins
    _merged, matched = traces_mod.merge_server_traces(client_doc, alien)
    assert matched == []


# -- traces.json schema (satellite: bench-smoke gate) ------------------------

def test_validate_traces_flags_violations():
    good = {"resourceSpans": [{"scopeSpans": [{"spans": [
        {"traceId": "ab" * 16, "spanId": "cd" * 8, "name": "x",
         "startTimeUnixNano": "5", "endTimeUnixNano": "7"},
    ]}]}]}
    assert validate_traces(good) == []
    assert validate_traces("nope") == ["document is not an object"]
    assert validate_traces({}) == ["resourceSpans missing or not an array"]
    bad_id = json.loads(json.dumps(good))
    bad_id["resourceSpans"][0]["scopeSpans"][0]["spans"][0]["traceId"] = "xyz"
    assert any("bad traceId" in e for e in validate_traces(bad_id))
    # uppercase hex violates the schema's ^[0-9a-f]{32}$ pattern — the
    # gate must agree with the published TRACES_JSON_SCHEMA, and int(v,16)
    # laxity would let it through
    upper = json.loads(json.dumps(good))
    upper["resourceSpans"][0]["scopeSpans"][0]["spans"][0]["traceId"] = "AB" * 16
    assert any("bad traceId" in e for e in validate_traces(upper))
    neg = json.loads(json.dumps(good))
    neg["resourceSpans"][0]["scopeSpans"][0]["spans"][0]["endTimeUnixNano"] = "1"
    assert any("negative duration" in e for e in validate_traces(neg))


# -- engine-side contract (needs jax; llama-tiny on CPU) ---------------------

def _tiny_engine(**ecfg_kwargs):
    jax = pytest.importorskip("jax")
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import init_params
    from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig

    cfg = get_config("llama-tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return Engine(
        params, cfg,
        EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                     min_prefill_bucket=16, **ecfg_kwargs),
    )


def test_engine_tracing_default_on_and_disable_knob():
    from kserve_vllm_mini_tpu.runtime.engine import GenRequest

    eng = _tiny_engine()
    assert eng.tracer is not None
    assert eng.tracer.capacity == 4096
    # submit mints a trace id when the client sent none
    h = eng.submit(GenRequest(prompt_tokens=[1, 2], max_new_tokens=2))
    assert h.request.trace_id and len(h.request.trace_id) == 32

    off = _tiny_engine(request_tracing=False)
    assert off.tracer is None
    h2 = off.submit(GenRequest(prompt_tokens=[1, 2], max_new_tokens=2))
    assert h2.request.trace_id is None  # zero tracing cost on the path
    # phase histograms stay on (plain counters) even with spans disabled
    assert set(off.snapshot_phase_hist()) == {"queue", "handoff", "prefill",
                                              "decode", "emit"}


def test_engine_trace_buffer_capacity_knob():
    eng = _tiny_engine(trace_buffer=32)
    assert eng.tracer.capacity == 32
    assert eng._engine_tracer.capacity == 32  # min(1024, trace_buffer)


def test_engine_lane_ring_is_separate_from_request_ring():
    """Per-sweep engine.decode.window spans accrue orders of magnitude
    faster than request spans; flooding their ring must NEVER evict the
    per-request phase spans the analyzer joins."""
    eng = _tiny_engine()
    tid = new_trace_id()
    eng.tracer.record("server.queue", tid, 1, 2)
    for i in range(5000):  # a long run's worth of sweep windows
        eng._trace_engine_span("engine.decode.window", i, i + 1)
    assert len(eng.tracer) == 1  # request span survived
    assert len(eng._engine_tracer) == 1024
    doc = eng.traces_otlp()
    scopes = doc["resourceSpans"][0]["scopeSpans"]
    assert [s["scope"]["name"] for s in scopes] == [
        "kserve_vllm_mini_tpu.runtime",
        "kserve_vllm_mini_tpu.runtime.engine",
    ]
    assert len(scopes[0]["spans"]) == 1
    assert len(scopes[1]["spans"]) == 1024
    assert doc["droppedSpans"] == 5000 - 1024
    assert validate_traces(doc) == []


def test_server_traces_and_metrics_endpoints():
    """GET /traces and the /metrics phase histograms over a real aiohttp
    app — no scheduler, no generation, no TPU (the recorder is fed
    directly, like a crashed-mid-run buffer would be)."""
    pytest.importorskip("jax")
    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import load_tokenizer

    eng = _tiny_engine()
    tid, parent = new_trace_id(), new_span_id()
    eng.tracer.record("server.queue", tid, 1000, 2000, parent_span_id=parent,
                      attrs={"request_id": "r1"})
    eng._observe_phase("queue", 0.002)
    tok = load_tokenizer(None)
    app = make_app(eng, tok, "llama-tiny")

    async def go():
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/traces")
            doc = await r.json()
            m = await client.get("/metrics")
            text = await m.text()
            return doc, text

    doc, metrics_text = asyncio.run(go())
    assert validate_traces(doc) == []
    spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert spans[0]["name"] == "server.queue"
    assert spans[0]["traceId"] == tid
    assert spans[0]["parentSpanId"] == parent
    assert 'kvmini_tpu_phase_seconds_bucket{phase="queue",le="0.0025"} 1' \
        in metrics_text
    assert 'kvmini_tpu_phase_seconds_count{phase="queue"} 1' in metrics_text


def test_server_traces_endpoint_disabled_engine():
    pytest.importorskip("jax")
    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import load_tokenizer

    eng = _tiny_engine(request_tracing=False)
    app = make_app(eng, load_tokenizer(None), "llama-tiny")

    async def go():
        async with TestClient(TestServer(app)) as client:
            r = await client.get("/traces")
            return await r.json()

    doc = asyncio.run(go())
    assert doc["resourceSpans"] == [] and doc["tracing"] == "disabled"


@pytest.mark.slow
def test_engine_generation_stamps_phase_spans():
    """Full generation on the CPU engine: every request lands exactly
    queue/prefill/decode spans (<= MAX_REQUEST_SPANS — the bounded-
    allocations-per-request guard), parented under the client's span,
    plus engine-lane dispatch->retire windows; phase histograms count
    every request once per phase."""
    from kserve_vllm_mini_tpu.runtime.engine import GenRequest

    eng = _tiny_engine()
    eng.start()
    try:
        handles = []
        ctx = []
        for _ in range(3):
            tid, sid = new_trace_id(), new_span_id()
            ctx.append((tid, sid))
            handles.append(eng.submit(GenRequest(
                prompt_tokens=[1, 2, 3], max_new_tokens=4,
                trace_id=tid, parent_span_id=sid,
            )))
        for h in handles:
            while True:
                kind, *rest = h.events.get(timeout=120)
                if kind == "done":
                    assert rest[0]["finish_reason"] in ("stop", "length")
                    break
    finally:
        eng.stop()

    spans = eng.tracer.snapshot()
    for tid, sid in ctx:
        mine = [r for r in spans if r[1] == tid]
        names = sorted(r[0] for r in mine)
        assert names == ["server.decode", "server.prefill", "server.queue"]
        assert len(mine) <= MAX_REQUEST_SPANS
        assert all(r[3] == sid for r in mine)       # parent span id
        assert all(r[5] >= r[4] for r in mine)      # end >= start
        decode = next(r for r in mine if r[0] == "server.decode")
        assert decode[7]["tokens_out"] == 4
    # dispatch->retire windows land in the engine-lane ring
    assert any(
        r[0] == "engine.decode.window" for r in eng._engine_tracer.snapshot()
    )
    hist = eng.snapshot_phase_hist()
    for phase in ("queue", "prefill", "decode"):
        assert hist[phase]["count"] == 3
    # the emit histogram has no stopwatch of its own: it is fed the
    # scheduler ledger's ``emit`` phase, entry for entry
    sched = eng.snapshot_stats()
    assert hist["emit"]["count"] == sched["sched_spans"]["emit"] > 0
    assert hist["emit"]["sum"] == pytest.approx(sched["sched_s"]["emit"])

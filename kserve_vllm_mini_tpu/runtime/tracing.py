"""Runtime-side request tracing: bounded ring-buffer span recorder.

The loadgen already traces the client leg (loadgen/tracing.py) and
propagates W3C ``traceparent`` headers; this module is the SERVER leg.
The engine stamps per-request phase spans (queue wait, prefill, decode,
cancellation) plus engine-lane dispatch->retire window spans, and
runtime/server.py exposes the buffer at ``GET /traces`` in the same
OTLP/JSON shape the loadgen exports — so the analyzer can join the two
legs by trace_id into one ``runs/<id>/traces/traces.json``
(analysis/traces.py, docs/TRACING.md).

Design constraints (the overhead guard, pinned by tests/test_tracing.py):

- **Bounded memory**: spans land in a ``deque(maxlen=capacity)`` — old
  spans evict, recording never grows the buffer past capacity.
- **Bounded allocations per request**: the engine stamps at most
  ``MAX_REQUEST_SPANS`` spans per request (one tuple + one small dict
  each); no per-token recording ever happens on the decode hot path.
- **JAX-free**: importable by the harness layers (mock server, analyzer
  tests) without touching the accelerator stack.

Phase histograms (``kvmini_tpu_phase_seconds``) live here too: plain
cumulative-bucket counters the engine observes once per phase transition
and /metrics renders in Prometheus histogram exposition.

So does the scheduler ledger (``SchedLedger``): the one place where the
engine thread's own time is recorded, phase by phase, as spans on the
profiler's clock and as ``kvmini_tpu_sched_*`` counters.
"""

from __future__ import annotations

import contextlib
import secrets
import time
from collections import deque
from typing import Any, Callable, Iterable, Optional

# The one clock every duration of the runtime is taken from (the ledger,
# the phase histograms, the request timestamps that feed them): monotonic,
# so a stepped wall clock can never produce a negative or inflated phase.
# /traces keeps Unix nanoseconds through ONE offset per engine
# (``unix_offset_ns``, captured at engine start).
now_s = time.perf_counter
now_ns = time.perf_counter_ns


def unix_offset_ns() -> int:
    """What to add to a ``now_ns`` reading to get Unix nanoseconds."""
    return time.time_ns() - now_ns()


# the engine's per-request span ceiling: server.queue + server.handoff
# (disaggregated admissions only, docs/DISAGGREGATION.md) +
# server.prefill + server.decode + server.cancel. A request can never
# allocate more spans than this — the recorder-overhead contract tests
# pin against it.
MAX_REQUEST_SPANS = 5

# request phases with /metrics histograms (kvmini_tpu_phase_seconds);
# "emit" is the per-sweep host emission window of the decode pipeline,
# "handoff" the prefill-lane route->consume window of disaggregated
# admissions (zero observations on colocated engines)
PHASES = ("queue", "handoff", "prefill", "decode", "emit")

# OTLP scope name every server-leg exporter uses (the real runtime AND the
# mock); the analyzer's merge keys off it to stay idempotent — re-analyzing
# a run replaces the previously merged server leg instead of duplicating it
SERVER_SCOPE = "kserve_vllm_mini_tpu.runtime"

# OTLP scope name the fleet router's span ring exports under
# (fleet/router.py GET /traces). A separate scope keeps the analyzer's
# idempotent strip-and-replace working per LANE: re-stitching a run
# replaces the router leg and the server leg independently.
ROUTER_SCOPE = "kserve_vllm_mini_tpu.fleet"

# histogram bucket upper bounds (seconds). Spans request-phase scales from
# sub-ms queue waits on an idle engine to multi-second long decodes.
PHASE_BUCKETS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)


def new_trace_id() -> str:
    return secrets.token_hex(16)


def new_span_id() -> str:
    return secrets.token_hex(8)


_HEX_CHARS = frozenset("0123456789abcdef")


def is_hex_id(v: Any, width: int) -> bool:
    """Strict lowercase-hex id of exactly ``width`` chars — the W3C
    trace-context charset and the TRACES_JSON_SCHEMA pattern. int(v, 16)
    is NOT equivalent: it accepts uppercase, '0x' prefixes and underscore
    separators, which would let ids through that the published schema
    rejects."""
    return (
        isinstance(v, str) and len(v) == width and _HEX_CHARS.issuperset(v)
    )


def parse_traceparent(header: Optional[str]) -> Optional[tuple[str, str]]:
    """W3C trace-context header -> (trace_id, parent_span_id), or None on
    anything malformed. Accepts the ``00-<32hex>-<16hex>-<2hex>`` shape
    the loadgen emits (loadgen/tracing.py traceparent()); hex is
    lowercase-only per the W3C spec."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    _ver, trace_id, span_id, _flags = parts
    if not is_hex_id(trace_id, 32) or not is_hex_id(span_id, 16):
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def _otlp_attr(k: str, v: Any) -> dict[str, Any]:
    if isinstance(v, bool):
        val: dict[str, Any] = {"boolValue": v}
    elif isinstance(v, int):
        val = {"intValue": str(v)}
    elif isinstance(v, float):
        val = {"doubleValue": v}
    else:
        val = {"stringValue": str(v)}
    return {"key": k, "value": val}


def span_to_otlp(rec: tuple) -> dict[str, Any]:
    """One recorded span tuple -> OTLP/JSON span. Tuples are 8-wide
    (legacy engine records, SPAN_KIND_SERVER implied) or 9-wide with an
    explicit OTLP kind as the last element (the router's fleet.proxy
    client-leg spans record kind 3)."""
    name, trace_id, span_id, parent_span_id, start_ns, end_ns, ok, attrs = (
        rec[:8]
    )
    kind = rec[8] if len(rec) > 8 else 2  # SPAN_KIND_SERVER default
    if end_ns < start_ns:
        # never-ended / clock-skewed record: clamp rather than export a
        # negative duration (same rule the client tracer applies at export)
        end_ns, ok = start_ns, False
    return {
        "traceId": trace_id,
        "spanId": span_id,
        **({"parentSpanId": parent_span_id} if parent_span_id else {}),
        "name": name,
        "kind": kind,
        "startTimeUnixNano": str(start_ns),
        "endTimeUnixNano": str(end_ns),
        "attributes": [_otlp_attr(k, v) for k, v in (attrs or {}).items()],
        "status": {"code": 1 if ok else 2},
    }


class SpanRecorder:
    """Bounded ring buffer of completed spans.

    Spans are recorded post-hoc (start AND end already known) as flat
    tuples — no open-span bookkeeping, no growth past ``capacity``. The
    scheduler thread appends; /traces snapshots from the aiohttp thread
    (deque append/iteration are atomic enough under the GIL for this
    monitoring surface — a torn read costs at most one span)."""

    def __init__(self, capacity: int = 4096) -> None:
        if capacity < 1:
            raise ValueError(f"trace buffer capacity {capacity} must be >= 1")
        self.capacity = capacity
        self._spans: "deque[tuple]" = deque(maxlen=capacity)
        self.dropped = 0  # evicted span count (buffer wrapped)

    def __len__(self) -> int:
        # Deliberately lock-free monitoring surface (class docstring):
        # scheduler-thread appends are atomic under the GIL and snapshot()
        # takes a C-level copy; a torn read costs at most one span.
        return len(self._spans)

    def record(
        self,
        name: str,
        trace_id: str,
        start_ns: int,
        end_ns: int,
        parent_span_id: Optional[str] = None,
        ok: bool = True,
        attrs: Optional[dict[str, Any]] = None,
        kind: int = 2,
        span_id: Optional[str] = None,
    ) -> str:
        """Append one completed span; returns its span id (generated when
        ``span_id`` is not supplied — the router pre-mints attempt span
        ids so it can rewrite the outgoing traceparent BEFORE the span's
        end time is known)."""
        sid = span_id or new_span_id()
        if len(self._spans) == self.capacity:
            self.dropped += 1  # kvmini: async-ok — single-writer counter
        # kvmini: async-ok — lock-free by contract (class docstring)
        self._spans.append(
            (name, trace_id, sid, parent_span_id, start_ns, end_ns, ok,
             attrs, kind)
        )
        return sid

    def snapshot(self) -> list[tuple]:
        return list(self._spans)

    def to_otlp(
        self,
        service_name: str = "kvmini-tpu-runtime",
        scope: str = SERVER_SCOPE,
    ) -> dict[str, Any]:
        """Same resourceSpans document shape as loadgen/tracing.py, so the
        analyzer merges both legs with one parser. Renders from snapshot():
        iterating the live deque directly would race the scheduler thread's
        appends (RuntimeError: deque mutated during iteration) — list(deque)
        is one C-level copy and safe under the GIL. The router exports
        under ``scope=ROUTER_SCOPE`` so the analyzer can strip/replace its
        lane independently of the server leg."""
        return {
            "resourceSpans": [
                {
                    "resource": {
                        "attributes": [
                            {
                                "key": "service.name",
                                "value": {"stringValue": service_name},
                            }
                        ]
                    },
                    "scopeSpans": [
                        {
                            "scope": {"name": scope},
                            "spans": [span_to_otlp(r) for r in self.snapshot()],
                        }
                    ],
                }
            ],
            # Monotonic int bumped only by the recording thread; a stale
            # read costs an off-by-one drop count in a monitoring doc.
            "droppedSpans": self.dropped,
        }


class PhaseHistogram:
    """Cumulative-bucket histogram (Prometheus semantics) for one phase.
    ``observe`` is two int increments and a float add — cheap enough to
    stay on even when span recording is disabled."""

    __slots__ = ("counts", "sum", "count")

    def __init__(self) -> None:
        self.counts = [0] * (len(PHASE_BUCKETS) + 1)  # +1 = +Inf bucket
        self.sum = 0.0
        self.count = 0

    def observe(self, seconds: float) -> None:
        if seconds < 0:
            seconds = 0.0
        i = 0
        for i, le in enumerate(PHASE_BUCKETS):  # noqa: B007 — small, fixed
            if seconds <= le:
                break
        else:
            i = len(PHASE_BUCKETS)
        self.counts[i] += 1
        self.sum += seconds
        self.count += 1

    def snapshot(self) -> dict[str, Any]:
        cum, total = [], 0
        for c in self.counts[: len(PHASE_BUCKETS)]:
            total += c
            cum.append(total)
        return {"buckets": cum, "sum": self.sum, "count": self.count}


# the phases of the engine thread (docs/TRACING.md "Scheduler ledger"):
# each is a ``kvmini.<phase>`` span on the profiler's clock and one label
# value of the kvmini_tpu_sched_* counters. They nest as the calls do.
SCHED_PHASES = (
    "loop", "idle", "admit", "prefill", "first_token", "decode_prepare",
    "decode_dispatch", "decode_retire", "emit", "spec_sweep",
    "masked_sweep", "handoff",
)

_SPAN_NAMES = {p: "kvmini." + p for p in SCHED_PHASES}

# what a decode gap is booked under when it closes (docs/TRACING.md "The
# decode gap"): by the phases entered inside it
SCHED_GAP_CAUSES = ("admission", "prefill_piece", "sweep")

# an entry is a stall, not work, once its own time passes twice the wall of
# a decode sweep (``sweep_s``: the engine's rolling dispatch -> retire time,
# which scales with the model, ``decode_chunk`` and the pipeline's depth; the
# longest ordinary wait of any phase is for one sweep), and never under 50 ms
SCHED_SLOW_FLOOR_NS = 50_000_000
SCHED_SLOW_SWEEPS = 2.0

_NO_SPAN = contextlib.nullcontext()


def _no_annotation(_name: str, **_args: Any):
    return _NO_SPAN


class _SchedPhase:
    """One entry of one phase: ``with ledger.phase(name) as ph``. After the
    block, ``ph.seconds`` is the entry's whole duration and ``ph.t_start`` /
    ``ph.t_end`` the ``now_s`` instants it began and ended — what the
    engine's older counters (``busy_s``, the ``emit`` histogram) take
    instead of timing the same interval again."""

    __slots__ = ("_ledger", "name", "_span", "_own_ns", "_t0_ns", "_t1_ns")

    def __init__(self, ledger: "SchedLedger", name: str, span: Any) -> None:
        self._ledger, self.name, self._span = ledger, name, span
        self._own_ns = self._t0_ns = self._t1_ns = 0

    def __enter__(self) -> "_SchedPhase":
        self._span.__enter__()
        self._t0_ns = self._ledger._switch(self, entering=True)
        return self

    def __exit__(self, *exc: Any) -> None:
        self._t1_ns = self._ledger._switch(self, entering=False)
        self._span.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return (self._t1_ns - self._t0_ns) * 1e-9

    @property
    def t_start(self) -> float:
        return self._t0_ns * 1e-9

    @property
    def t_end(self) -> float:
        return self._t1_ns * 1e-9


class _Gap:
    """One open decode gap: the slots that wait through it (0 until the
    engine has emitted the sweep whose readback opened it; a gap nobody
    waits through is not booked), the self time and the entries of each
    phase inside it, the requests admitted, and where it ended if the engine
    said so and no dispatch did."""

    __slots__ = ("t0_ns", "end_ns", "waiting", "ns", "entered", "request_ids")

    def __init__(self, t0_ns: int) -> None:
        self.t0_ns = t0_ns
        self.end_ns: Optional[int] = None
        self.waiting = 0
        self.ns: dict[str, int] = {}
        self.entered: dict[str, int] = {}
        self.request_ids: list[Any] = []

    @property
    def cause(self) -> str:
        # an admission began (``admit``) or was completed (``first_token``:
        # the last piece of a chunked prompt, a handoff) inside the gap
        if "admit" in self.entered or "first_token" in self.entered:
            return "admission"
        return "prefill_piece" if "prefill" in self.entered else "sweep"

    @property
    def admissions(self) -> int:
        return self.entered.get("first_token", 0)


class _GapBook:
    """The decode gaps booked so far: their number and wall time by cause,
    the slot-time they cost, and of the ``admission`` gaps the self time by
    phase and the first tokens sampled inside."""

    __slots__ = ("gaps", "ns", "slot_ns", "admit_ns", "admissions")

    def __init__(self) -> None:
        self.gaps = dict.fromkeys(SCHED_GAP_CAUSES, 0)
        self.ns = dict.fromkeys(SCHED_GAP_CAUSES, 0)
        self.slot_ns = 0
        self.admit_ns = dict.fromkeys(SCHED_PHASES, 0)
        self.admissions = 0

    def add(self, gap: _Gap) -> None:
        wall, cause = sum(gap.ns.values()), gap.cause
        self.gaps[cause] += 1
        self.ns[cause] += wall
        self.slot_ns += wall * gap.waiting
        if cause == "admission":
            for name, took in gap.ns.items():
                self.admit_ns[name] += took
            self.admissions += gap.admissions

    def snapshot(self) -> dict[str, Any]:
        return {
            "gaps": dict(self.gaps),
            "gap_seconds": {c: v * 1e-9 for c, v in self.ns.items()},
            "gap_slot_seconds": self.slot_ns * 1e-9,
            "admit_gap_seconds": {p: v * 1e-9 for p, v in self.admit_ns.items()},
            "admit_gap_admissions": self.admissions,
        }


class SchedLedger:
    """Where the scheduler thread's time goes, kept in one place.

    The engine enters a phase as a context manager. Entering opens
    ``annotate("kvmini.<phase>", **args)`` — the engine hands in
    ``jax.profiler.TraceAnnotation``, so the span sits on the device
    trace's clock; the default is a no-op and this module stays JAX-free —
    and every entry or exit charges the time since the previous one to the
    innermost open phase. A phase is therefore charged its SELF time (its
    duration less its children's), and the self times of all phases
    partition the thread's wall time: their sum over an interval is that
    interval. Time with no phase open is charged to nothing (the engine
    keeps ``loop`` open around every iteration).

    Per phase: ``seconds`` (self time), ``exposed`` (the part of it spent
    while ``exposed()`` held when the stretch began: work live and no
    decode sweep in flight, so the device had nothing queued), ``spans``
    (entries) and ``slow`` (self time of the entries whose own time passed
    ``SCHED_SLOW_SWEEPS`` x ``sweep_s()``, at least ``SCHED_SLOW_FLOOR_NS``).

    **The decode gap**: what the slots that were decoding lose between the
    readback of the last sweep in flight and the next dispatch, or, when an
    admission was dispatched behind the sweep in flight, between that
    sweep's readback and the readback of the admission's first token (the
    device runs the prefill in between, then the sweep that was dispatched
    behind it). The engine says ``gap_open()`` where such a readback ends,
    ``gap_waiting(n)`` once that sweep's tokens are emitted (0: nobody
    waits, no gap) and ``gap_close()`` where the waiting slots are gone or
    the first tokens are read back; the next ``decode_dispatch`` entered
    closes the gap by itself. While one is open
    every stretch charged to a phase is charged to the gap too, so a gap's
    wall time is the sum of its phases'. It is booked once, under the cause
    its phases give it: ``admission`` if an admission began (``admit``) or
    was completed (``first_token``) inside it, else ``prefill_piece`` if a
    ``prefill`` was entered, else ``sweep``. An ``admission`` gap also books
    its phases' self times and its ``first_token`` entries. The dispatch
    that closes a gap carries ``gap_ms``, ``cause``, ``waiting`` and
    ``admissions`` as its span's arguments; ``on_gap`` is handed each booked
    gap (cause, start and end in ``now_ns``, waiting, admissions, the
    ``request_id`` of every ``admit`` entered).

    Written by the one thread that enters phases; a snapshot from another
    thread is at worst one stretch stale."""

    def __init__(
        self,
        annotate: Optional[Callable[..., Any]] = None,
        exposed: Optional[Callable[[], bool]] = None,
        sweep_s: Optional[Callable[[], float]] = None,
        on_gap: Optional[Callable[..., None]] = None,
    ) -> None:
        self._annotate = annotate or _no_annotation
        self._exposed = exposed or (lambda: False)
        self._sweep_s = sweep_s or (lambda: 0.0)
        self._on_gap = on_gap
        self._stack: list[_SchedPhase] = []
        self._mark_ns = 0
        self._mark_exposed = False
        self._self_ns = dict.fromkeys(SCHED_PHASES, 0)
        self._exposed_ns = dict.fromkeys(SCHED_PHASES, 0)
        self._slow_ns = dict.fromkeys(SCHED_PHASES, 0)
        self._spans = dict.fromkeys(SCHED_PHASES, 0)
        self._gap: Optional[_Gap] = None
        self._gap_book = _GapBook()

    def phase(self, name: str, **args: Any) -> _SchedPhase:
        """``args`` become the span's arguments: only for the rare phases
        (a few a second) — the annotation formats them whether or not a
        profiler is listening. The ``decode_dispatch`` that closes a gap is
        such a one, and is given the gap's."""
        gap = self._gap
        if gap is not None and gap.end_ns is None:
            if name == "decode_dispatch" and gap.waiting:
                args = {"gap_ms": round((now_ns() - gap.t0_ns) * 1e-6, 3),
                        "cause": gap.cause, "waiting": gap.waiting,
                        "admissions": gap.admissions}
            elif name == "admit" or (name == "first_token" and "request_id" in args):
                # (a first token names its caller when the admission began
                # before the gap: one dispatched behind a sweep)
                gap.request_ids.append(args.get("request_id"))
        return _SchedPhase(self, name, self._annotate(_SPAN_NAMES[name], **args))

    def gap_open(self) -> None:
        """A sweep's tokens are on the host and no other sweep is in flight:
        a gap begins where that readback ended (the stretch in progress
        began there), if ``gap_waiting`` then says somebody waits. None is
        open at such a moment: the dispatch of the sweep just read back
        closed the one before."""
        # kvmini: thread-ok — the engine asks from inside a phase, on the one thread that enters them
        self._gap = _Gap(self._mark_ns)

    def gap_waiting(self, slots: int) -> None:
        """The sweep's tokens are emitted and ``slots`` slots go on decoding:
        they wait through the gap. With none there is no gap."""
        if self._gap is not None:
            self._gap.waiting = slots

    def gap_close(self, admitted: Iterable[Any] = ()) -> None:
        """The gap ends where the last stretch did, with no dispatch, and
        the next phase boundary books it: the slots that waited are gone
        (finished, cancelled), or they waited for callers admitted BEHIND
        the sweep whose readback opened the gap and the stretch that just
        ended was the readback of the last of their first tokens (the
        device has begun the sweep dispatched behind them). ``admitted``:
        the ``request_id``s of the callers whose ``admit`` was entered
        before the gap and whose ``first_token`` follows it, so they are
        counted here (one whose token was read back earlier entered its
        ``first_token`` inside the gap, with its ``request_id``)."""
        gap = self._gap
        if gap is not None and gap.end_ns is None:
            gap.end_ns = self._mark_ns
            for request_id in admitted:
                gap.request_ids.append(request_id)
                gap.entered["first_token"] = gap.entered.get("first_token", 0) + 1

    def _book_gap(self, gap: _Gap, end_ns: int) -> None:
        self._gap = None
        if not gap.waiting:
            return      # nobody waited through it: no gap
        self._gap_book.add(gap)
        if self._on_gap is not None:
            self._on_gap(gap.cause, gap.t0_ns, end_ns, gap.waiting,
                         gap.admissions, gap.request_ids)

    def _switch(self, ph: _SchedPhase, entering: bool) -> int:
        now = now_ns()
        gap = self._gap
        if gap is not None and gap.end_ns is not None:
            self._book_gap(gap, gap.end_ns)
            gap = None
        if self._stack:
            top = self._stack[-1]
            took = now - self._mark_ns
            top._own_ns += took
            self._self_ns[top.name] += took
            if self._mark_exposed:
                self._exposed_ns[top.name] += took
            if gap is not None:
                gap.ns[top.name] = gap.ns.get(top.name, 0) + took
        if entering:
            self._stack.append(ph)
            self._spans[ph.name] += 1
            if gap is not None:
                if ph.name == "decode_dispatch":
                    self._book_gap(gap, now)
                else:
                    gap.entered[ph.name] = gap.entered.get(ph.name, 0) + 1
        else:
            self._stack.pop()
            # the sweep wall is asked only about the few entries over the floor
            if (ph._own_ns > SCHED_SLOW_FLOOR_NS
                    and ph._own_ns > SCHED_SLOW_SWEEPS * 1e9 * self._sweep_s()):
                self._slow_ns[ph.name] += ph._own_ns
        self._mark_ns = now
        self._mark_exposed = self._exposed()
        return now

    def snapshot(self) -> dict[str, dict[str, float]]:
        """``seconds`` / ``exposed`` / ``slow`` in seconds and ``spans`` as
        counts, each by phase, and the decode gaps booked so far (``gaps``
        and ``gap_seconds`` by cause, ``gap_slot_seconds``, an admission
        gap's ``admit_gap_seconds`` by phase and ``admit_gap_admissions``),
        as /metrics exports them."""
        return {
            "seconds": {p: v * 1e-9 for p, v in self._self_ns.items()},
            "exposed": {p: v * 1e-9 for p, v in self._exposed_ns.items()},
            "slow": {p: v * 1e-9 for p, v in self._slow_ns.items()},
            "spans": dict(self._spans),
            **self._gap_book.snapshot(),
        }


def render_phase_histograms(
    hists: dict[str, "PhaseHistogram"],
    metric: str = "kvmini_tpu_phase_seconds",
) -> list[str]:
    """Prometheus text-exposition lines for the phase histograms — shared
    by runtime/server.py /metrics and tests/mock_server.py so the scrape
    path is exercised end-to-end without the JAX engine."""
    lines = [f"# TYPE {metric} histogram"]
    for phase, h in hists.items():
        snap = h.snapshot()
        for le, cum in zip(PHASE_BUCKETS, snap["buckets"]):
            lines.append(
                f'{metric}_bucket{{phase="{phase}",le="{le}"}} {cum}'
            )
        lines.append(
            f'{metric}_bucket{{phase="{phase}",le="+Inf"}} {snap["count"]}'
        )
        lines.append(f'{metric}_sum{{phase="{phase}"}} {snap["sum"]:.6f}')
        lines.append(f'{metric}_count{{phase="{phase}"}} {snap["count"]}')
    return lines


def spans_from_otlp(doc: dict[str, Any]) -> Iterable[tuple[str, dict[str, Any]]]:
    """Yield (service_name, span) pairs from an OTLP/JSON document —
    the one parser both report/html.py and analysis/traces.py use."""
    for rs in doc.get("resourceSpans", []) or []:
        service = "unknown"
        for a in (rs.get("resource") or {}).get("attributes", []) or []:
            if a.get("key") == "service.name":
                service = (a.get("value") or {}).get("stringValue", service)
        for ss in rs.get("scopeSpans", []) or []:
            for s in ss.get("spans", []) or []:
                yield service, s

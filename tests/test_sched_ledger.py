"""The scheduler ledger (runtime/tracing.py ``SchedLedger``, docs/TRACING.md):
the engine thread's time by phase, as spans on the profiler's clock and as
``kvmini_tpu_sched_*`` counters.

The arithmetic runs on a hand-driven clock; the books are then balanced on
tiny engines serving a few requests on the CPU. The last tests pin, by name,
what the benchmark reads from the program (bench/trace_reduce.py,
bench/readers/): span names, program names, counter names.
"""

import asyncio
import subprocess
import sys
import time

import pytest

from kserve_vllm_mini_tpu.runtime import tracing as rt_tracing
from kserve_vllm_mini_tpu.runtime.tracing import SCHED_PHASES, SchedLedger

MS = 1_000_000


class Clock:
    """``now_ns`` under the test's hand."""

    def __init__(self) -> None:
        self.ns = 5_000 * MS

    def __call__(self) -> int:
        return self.ns

    def tick(self, ms: float) -> None:
        self.ns += int(ms * MS)


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(rt_tracing, "now_ns", c)
    return c


# -- the arithmetic -----------------------------------------------------------


def test_tracing_imports_without_jax():
    code = ("import sys; sys.modules['jax'] = None; "
            "import kserve_vllm_mini_tpu.runtime.tracing as t; "
            "led = t.SchedLedger(); "
            "exec('with led.phase(\"loop\"): pass'); "
            "print(led.snapshot()['spans']['loop'])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "1"


def test_child_time_is_taken_from_its_parent(clock):
    led = SchedLedger()
    with led.phase("loop") as loop:
        clock.tick(2)
        with led.phase("admit") as admit:
            clock.tick(3)
            with led.phase("prefill") as prefill:
                clock.tick(40)
            clock.tick(1)
        clock.tick(4)
    s = led.snapshot()
    assert s["seconds"]["loop"] == pytest.approx(0.006)
    assert s["seconds"]["admit"] == pytest.approx(0.004)
    assert s["seconds"]["prefill"] == pytest.approx(0.040)
    assert s["spans"]["loop"] == s["spans"]["admit"] == s["spans"]["prefill"] == 1
    # an entry's own numbers are its WHOLE duration and its two instants
    assert loop.seconds == pytest.approx(0.050)
    assert admit.seconds == pytest.approx(0.044)
    assert prefill.seconds == pytest.approx(0.040)
    assert admit.t_start == pytest.approx(5.002) and admit.t_end == pytest.approx(5.046)


def test_self_times_partition_the_interval(clock):
    """Whatever the nesting, the self times add up to the time between the
    first entry and the last exit: nothing is counted twice, nothing lost."""
    led = SchedLedger()
    t0 = clock.ns
    for i in range(7):
        with led.phase("loop"):
            clock.tick(0.3)
            if i % 2:
                with led.phase("admit"):
                    clock.tick(0.7)
                    with led.phase("prefill"):
                        clock.tick(11 + i)
                    with led.phase("first_token"):
                        clock.tick(1.1)
            with led.phase("decode_prepare"):
                clock.tick(0.2)
                with led.phase("decode_dispatch"):
                    clock.tick(0.5)
            with led.phase("decode_retire"):
                clock.tick(19)
            with led.phase("emit"):
                clock.tick(0.4)
    s = led.snapshot()
    assert sum(s["seconds"].values()) == pytest.approx((clock.ns - t0) * 1e-9, rel=1e-9)
    assert s["spans"]["loop"] == 7 and s["spans"]["prefill"] == 3
    assert set(s["seconds"]) == set(SCHED_PHASES)


def test_exposed_splits_on_inflight(clock):
    """``exposed`` is asked where each stretch of self time begins: the part
    of a phase after a dispatch is no longer exposed."""
    inflight = []
    led = SchedLedger(exposed=lambda: not inflight)
    with led.phase("loop"):
        clock.tick(1)                      # exposed: nothing in flight
        with led.phase("decode_prepare"):
            clock.tick(2)                  # exposed
            with led.phase("decode_dispatch"):
                clock.tick(3)              # exposed: entered with nothing in flight
                inflight.append("sweep")
            clock.tick(4)                  # not exposed
        clock.tick(5)                      # not exposed
        inflight.clear()
        with led.phase("emit"):
            clock.tick(6)                  # exposed again
    s = led.snapshot()
    assert s["exposed"]["loop"] == pytest.approx(0.001)
    assert s["seconds"]["loop"] == pytest.approx(0.006)
    assert s["exposed"]["decode_prepare"] == pytest.approx(0.002)
    assert s["seconds"]["decode_prepare"] == pytest.approx(0.006)
    assert s["exposed"]["decode_dispatch"] == pytest.approx(0.003)
    assert s["exposed"]["emit"] == pytest.approx(0.006)


@pytest.mark.parametrize("own_ms,slow", [(49.0, False), (51.0, True)])
def test_slow_threshold_is_50ms_of_own_time(clock, own_ms, slow):
    led = SchedLedger()
    with led.phase("emit"):
        clock.tick(own_ms)
    assert led.snapshot()["slow"]["emit"] == pytest.approx(own_ms / 1e3 if slow else 0.0)


@pytest.mark.parametrize("sweep_ms,own_ms,slow", [
    (10.0, 51.0, True),       # short sweeps: the 50 ms floor holds
    (41.0, 70.0, False),      # the benchmark's models, two sweeps in flight: 82 ms
    (41.0, 101.0, True),      # a 0.1 s stall still counts there
    (300.0, 400.0, False),    # a large model or decode_chunk: a sweep's wait is work
    (300.0, 601.0, True),
])
def test_slow_threshold_scales_with_the_sweep_wall(clock, sweep_ms, own_ms, slow):
    """``sweep_s`` is the engine's rolling dispatch -> retire wall: an entry is a
    stall once its own time passes two of them, whatever the model's step."""
    led = SchedLedger(sweep_s=lambda: sweep_ms / 1e3)
    with led.phase("decode_retire"):
        clock.tick(own_ms)
    got = led.snapshot()["slow"]["decode_retire"]
    assert got == pytest.approx(own_ms / 1e3 if slow else 0.0)
    assert rt_tracing.SCHED_SLOW_SWEEPS == 2.0 and rt_tracing.SCHED_SLOW_FLOOR_NS == 50 * MS


def test_sweep_wall_is_asked_only_over_the_floor(clock):
    asked = []
    led = SchedLedger(sweep_s=lambda: asked.append(1) or 0.0)
    for own_ms in (0.2, 19.0, 49.0):
        with led.phase("decode_retire"):
            clock.tick(own_ms)
    assert not asked
    with led.phase("decode_retire"):
        clock.tick(60)
    assert asked == [1]


def test_a_long_child_does_not_make_its_parent_slow(clock):
    led = SchedLedger()
    with led.phase("admit"):
        clock.tick(2)
        with led.phase("prefill"):
            clock.tick(120)
        clock.tick(2)
    s = led.snapshot()
    assert s["slow"]["admit"] == 0.0
    assert s["slow"]["prefill"] == pytest.approx(0.120)


def test_annotation_factory_gets_names_and_args():
    seen = []

    class Span:
        def __init__(self, name, **args):
            seen.append([name, args, "made"])
            self._rec = seen[-1]

        def __enter__(self):
            self._rec[2] = "open"

        def __exit__(self, *exc):
            self._rec[2] = "closed"

    led = SchedLedger(annotate=Span)
    with led.phase("prefill", request_id="r1", prompt_tokens=37, bucket=64, offset=0):
        assert seen[0][2] == "open"
        with led.phase("decode_retire"):
            pass
    assert seen == [
        ["kvmini.prefill",
         {"request_id": "r1", "prompt_tokens": 37, "bucket": 64, "offset": 0}, "closed"],
        ["kvmini.decode_retire", {}, "closed"],
    ]


def test_an_exception_closes_the_phase(clock):
    led = SchedLedger()
    with pytest.raises(RuntimeError):
        with led.phase("loop"):
            with led.phase("decode_prepare"):
                clock.tick(1)
                raise RuntimeError("device fault")
    with led.phase("loop"):
        clock.tick(2)
    s = led.snapshot()
    assert s["seconds"]["loop"] == pytest.approx(0.002)
    assert s["seconds"]["decode_prepare"] == pytest.approx(0.001)


# -- the decode gap: the arithmetic ------------------------------------------


class Recorded:
    """An annotation factory that keeps what it was handed."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **args):
        self.seen.append((name, args))
        return rt_tracing._NO_SPAN


def sweep(led, clock, retire_ms=10.0, waiting=None, emit_ms=0.25):
    """One synchronous sweep as the engine enters it: dispatch, readback,
    then (``waiting`` given) the gap's opening around the emission."""
    with led.phase("decode_prepare"):
        clock.tick(0.5)
        with led.phase("decode_dispatch"):
            clock.tick(0.7)
    with led.phase("decode_retire"):
        clock.tick(retire_ms)
    if waiting is not None:
        led.gap_open()
    with led.phase("emit"):
        clock.tick(emit_ms)
    if waiting is not None:
        led.gap_waiting(waiting)


def test_an_admission_gap_is_partitioned_by_phase(clock):
    """From the readback's end to the next dispatch's entry: every stretch
    inside is charged to the gap and to its phase, so the phases of an
    admission gap add up to its wall time, and nothing outside it is counted."""
    note, spans = [], Recorded()
    led = SchedLedger(annotate=spans, on_gap=lambda *a: note.append(a))
    with led.phase("loop"):
        clock.tick(1)
        sweep(led, clock, waiting=3)            # the gap opens: emit 0.25
        t_open = clock.ns - int(0.25 * MS)
        clock.tick(0.1)                         # loop
    with led.phase("loop"):
        clock.tick(0.4)
        with led.phase("admit", request_id="r7", prompt_tokens=40):
            clock.tick(0.2)
            with led.phase("prefill", request_id="r7", bucket=64):
                clock.tick(21)
            with led.phase("first_token"):
                clock.tick(6)
        with led.phase("decode_prepare"):
            clock.tick(2.5)
            t_close = clock.ns
            with led.phase("decode_dispatch"):  # the gap closes at its entry
                clock.tick(0.7)
        clock.tick(3)
    s = led.snapshot()
    assert s["gaps"] == {"admission": 1, "prefill_piece": 0, "sweep": 0}
    assert s["gap_seconds"]["admission"] == pytest.approx(0.03045)
    assert s["gap_seconds"]["admission"] == pytest.approx((t_close - t_open) * 1e-9)
    assert s["gap_slot_seconds"] == pytest.approx(3 * 0.03045)
    assert s["admit_gap_admissions"] == 1
    want = {"emit": 0.25, "loop": 0.5, "admit": 0.2, "prefill": 21.0,
            "first_token": 6.0, "decode_prepare": 2.5}
    for p in SCHED_PHASES:
        assert s["admit_gap_seconds"][p] == pytest.approx(want.get(p, 0.0) / 1e3)
    assert sum(s["admit_gap_seconds"].values()) == pytest.approx(
        s["gap_seconds"]["admission"], rel=1e-12)
    assert note == [("admission", t_open, t_close, 3, 1, ["r7"])]
    # the dispatch that closed the gap says so; the steady one is bare
    dispatches = [a for n, a in spans.seen if n == "kvmini.decode_dispatch"]
    assert dispatches == [{}, {"gap_ms": 30.45, "cause": "admission",
                               "waiting": 3, "admissions": 1}]
    assert all(not a for n, a in spans.seen
               if n in ("kvmini.decode_retire", "kvmini.emit", "kvmini.loop"))


def test_an_admission_behind_the_sweep_books_readback_to_readback(clock):
    """An admission dispatched behind the sweep in flight drains nothing, and
    the slots still wait for its prefill: ONE gap of cause ``admission`` from
    the end of that sweep's readback to the end of the first token's. The
    wait is the ``prefill`` phase's, the rest (the sweep's emission, the loop)
    the host's, the two add up to the gap's wall; what the host did for the
    admission before (``admit``, the pieces' dispatch, the next sweep's) and
    does with the token after (``first_token``) is in no gap; and the
    admission is counted, by name, where the gap closes."""
    note, spans = [], Recorded()
    led = SchedLedger(annotate=spans, on_gap=lambda *a: note.append(a))
    with led.phase("loop"):
        sweep(led, clock)                       # the steady state: nothing opens
    with led.phase("loop"):
        clock.tick(0.4)
        with led.phase("admit", request_id="r9", prompt_tokens=40, inflight=True):
            clock.tick(1.0)
            with led.phase("prefill", request_id="r9", bucket=64):
                clock.tick(2.2)                 # the dispatch alone
            clock.tick(4.5)                     # first, reset: dispatched, not waited for
        with led.phase("decode_prepare"):
            clock.tick(3.5)
            with led.phase("decode_dispatch"):  # sweep N+1, behind the prefill
                clock.tick(0.7)
        with led.phase("decode_retire"):        # sweep N
            clock.tick(5)
        led.gap_open()
        t_open = clock.ns
        with led.phase("emit"):
            clock.tick(0.4)
        led.gap_waiting(15)
        clock.tick(0.1)
        with led.phase("prefill", first_tokens=1):
            clock.tick(16)                      # the device runs the prompt's pieces
        t_close = clock.ns
        led.gap_close(admitted=["r9"])
        with led.phase("first_token"):
            clock.tick(1.2)
        clock.tick(0.3)
    with led.phase("loop"):
        sweep(led, clock)                       # sweep N+2: the gap is long closed
    s = led.snapshot()
    assert s["gaps"] == {"admission": 1, "prefill_piece": 0, "sweep": 0}
    assert s["gap_seconds"]["admission"] == pytest.approx(0.0165)
    assert s["gap_seconds"]["admission"] == pytest.approx((t_close - t_open) * 1e-9)
    assert s["gap_slot_seconds"] == pytest.approx(15 * 0.0165)
    assert s["admit_gap_admissions"] == 1 == s["spans"]["first_token"]
    want = {"emit": 0.4, "loop": 0.1, "prefill": 16.0}
    for p in SCHED_PHASES:
        assert s["admit_gap_seconds"][p] == pytest.approx(want.get(p, 0.0) / 1e3)
    # the two parts the benchmark reads: prefill + the rest = the gap's wall
    rest = sum(v for p, v in s["admit_gap_seconds"].items() if p != "prefill")
    assert s["admit_gap_seconds"]["prefill"] + rest == pytest.approx(
        s["gap_seconds"]["admission"], rel=1e-12)
    assert note == [("admission", t_open, t_close, 15, 1, ["r9"])]
    # no dispatch closed it: none says so
    assert all(not a for n, a in spans.seen if n == "kvmini.decode_dispatch")
    # the phases' own books are whole: both prefill entries, one first token
    assert s["spans"]["prefill"] == 2 and s["seconds"]["prefill"] == pytest.approx(0.0182)
    assert s["seconds"]["first_token"] == pytest.approx(0.0012)


def test_two_callers_behind_one_sweep_share_a_gap_and_a_drain_after_it_keeps_it_open(clock):
    """Two callers behind one sweep: a readback each, in the order of their
    dispatch, and ONE gap with two admissions that ends with the second
    readback. The first caller's ``first_token`` is entered inside the gap
    (the device is at the second caller's prompt) and names it; the second's
    follows the gap's end and is counted where the gap closes.
    If the pipeline was drained meanwhile (a refused caller in the same
    iteration) the engine closes nothing: the first tokens are counted by
    their ``first_token`` entries and the next dispatch ends the gap."""
    note = []
    led = SchedLedger(on_gap=lambda *a: note.append(a))
    with led.phase("loop"):
        with led.phase("decode_retire"):
            clock.tick(5)
        led.gap_open()
        with led.phase("emit"):
            clock.tick(0.5)
        led.gap_waiting(14)
        with led.phase("prefill", first_tokens=2):
            clock.tick(16)
        with led.phase("first_token", request_id="r1"):
            clock.tick(1)
        with led.phase("prefill", first_tokens=1):
            clock.tick(13)
        led.gap_close(admitted=["r2"])
        with led.phase("first_token", request_id="r2"):
            clock.tick(1)
    s = led.snapshot()
    assert s["gaps"]["admission"] == 1 and s["admit_gap_admissions"] == 2
    assert s["gap_seconds"]["admission"] == pytest.approx(0.0305)
    assert s["admit_gap_seconds"]["prefill"] == pytest.approx(0.029)
    assert s["admit_gap_seconds"]["first_token"] == pytest.approx(0.001)
    assert [(g[0], g[3], g[4], g[5]) for g in note] == [("admission", 14, 2, ["r1", "r2"])]

    led = SchedLedger()
    with led.phase("loop"):
        sweep(led, clock, waiting=3)            # drained for the refused caller
        with led.phase("prefill", first_tokens=1):
            clock.tick(12)
        with led.phase("first_token"):          # no gap_close: nothing in flight
            clock.tick(1)
        with led.phase("admit", request_id="r3"):
            clock.tick(20)
            with led.phase("first_token"):
                clock.tick(6)
        sweep(led, clock)
    s = led.snapshot()
    assert s["gaps"]["admission"] == 1 and s["admit_gap_admissions"] == 2
    assert s["gap_seconds"]["admission"] == pytest.approx((0.25 + 12 + 1 + 20 + 6 + 0.5) / 1e3)


@pytest.mark.parametrize("inside,cause,admissions", [
    ((), "sweep", 0),
    (("prefill",), "prefill_piece", 0),
    (("admit", "prefill", "first_token"), "admission", 1),
    (("admit", "prefill"), "admission", 0),         # a chunked prompt's first piece
    (("prefill", "first_token"), "admission", 1),   # and its last: the admission completes
    (("first_token",), "admission", 1),
    (("admit", "first_token", "admit", "first_token"), "admission", 2),
])
def test_a_gap_is_booked_under_the_cause_its_phases_give(clock, inside, cause, admissions):
    led = SchedLedger()
    with led.phase("loop"):
        sweep(led, clock, waiting=2)
        for name in inside:
            with led.phase(name):
                clock.tick(1)
        sweep(led, clock)
    s = led.snapshot()
    assert s["gaps"] == {c: int(c == cause) for c in rt_tracing.SCHED_GAP_CAUSES}
    assert s["gap_seconds"][cause] == pytest.approx((0.25 + len(inside) + 0.5) / 1e3)
    assert s["admit_gap_admissions"] == admissions
    # only an admission gap is split by phase
    in_phases = sum(s["admit_gap_seconds"].values())
    assert in_phases == pytest.approx(s["gap_seconds"]["admission"])


def test_no_gap_where_nobody_waits_and_none_while_a_sweep_is_in_flight(clock):
    spans = Recorded()
    led = SchedLedger(annotate=spans)
    with led.phase("loop"):
        sweep(led, clock, waiting=0)        # the last slot finished in this emission
        clock.tick(5)
        sweep(led, clock)                   # pipelined: the engine opens nothing
        sweep(led, clock)
        led.gap_open()                      # the engine never says who waited
        sweep(led, clock)
    s = led.snapshot()
    assert sum(s["gaps"].values()) == 0 and s["gap_slot_seconds"] == 0.0
    assert all(not a for _, a in spans.seen)


def test_a_gap_whose_slots_are_gone_closes_without_a_dispatch(clock):
    note, spans = [], Recorded()
    led = SchedLedger(annotate=spans, on_gap=lambda *a: note.append(a))
    with led.phase("loop"):
        sweep(led, clock, waiting=1)
        clock.tick(0.3)
    with led.phase("loop"):
        clock.tick(0.2)                     # the cancel pass finishes the slot
        led.gap_close()
        led.gap_close()                     # nothing open: nothing booked
        clock.tick(4)
        with led.phase("idle"):
            clock.tick(20)
    s = led.snapshot()
    assert s["gaps"]["sweep"] == 1
    # it ends where the last stretch did: the emission and the first loop's tail
    assert s["gap_seconds"]["sweep"] == pytest.approx(0.00055)
    assert len(note) == 1 and note[0][0] == "sweep" and note[0][3:] == (1, 0, [])
    assert all(not a for _, a in spans.seen)


# -- the books of a serving engine --------------------------------------------

jax = pytest.importorskip("jax")

from kserve_vllm_mini_tpu.models.config import get_config  # noqa: E402
from kserve_vllm_mini_tpu.models.llama import init_params  # noqa: E402
from kserve_vllm_mini_tpu.runtime.engine import (  # noqa: E402
    Engine,
    EngineConfig,
    GenRequest,
)

CFG = get_config("llama-tiny")
CORE = ("loop", "idle", "admit", "prefill", "first_token", "decode_prepare",
        "decode_dispatch", "decode_retire", "emit")


class ForcedSequence:
    """A grammar that allows exactly one token a step: the masked sweep."""

    def __init__(self, seq):
        self.seq, self.i = list(seq), 0

    @property
    def done(self):
        return self.i >= len(self.seq)

    def min_close(self):
        return len(self.seq) - self.i

    def token_mask(self, budget):
        import numpy as np

        mask = np.zeros((CFG.vocab_size,), dtype=bool)
        mask[self.seq[self.i]] = True
        return mask

    def advance_token(self, tid):
        self.i += 1


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def serve(params, requests, drafter=False, drive=None, **ecfg):
    """A tiny engine, its ledger handed a recording annotation factory,
    serving ``requests`` to the end. Returns the engine (stopped), the spans
    the factory saw as (name, args), and the engine thread's wall time as
    the spans' own clock reads it: first ``loop`` entered -> last one left.
    ``drive(engine, handles)`` runs while it serves (more callers, a cancel)
    and waits for the requests itself."""
    eng = Engine(
        params, CFG,
        EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                     min_prefill_bucket=16, **ecfg),
        drafter=(params, CFG) if drafter else None,
    )
    spans, loops = [], []

    class Span:
        def __init__(self, name, **args):
            spans.append((name, args))
            self.is_loop = name == "kvmini.loop"

        def __enter__(self):
            if self.is_loop:
                loops.append(time.perf_counter())

        def __exit__(self, *exc):
            if self.is_loop:
                loops.append(time.perf_counter())

    eng._sched = SchedLedger(annotate=Span, exposed=eng._host_exposed,
                             sweep_s=lambda: eng._sweep_ema_s, on_gap=eng._trace_gap)
    # queued before the thread starts: every admission finds work live (a
    # request waiting or a slot occupied), whatever the threads' timing
    handles = [eng.submit(r) for r in requests]
    eng.start()
    try:
        if drive is not None:
            drive(eng, handles)     # it waits for what it drives
        else:
            for h in handles:
                wait_done(h)
        time.sleep(0.05)            # a few idle waits
    finally:
        eng.stop()
    eng.handles = handles
    return eng, spans, loops[-1] - loops[0]


def wait_done(handle):
    while handle.events.get(timeout=120)[0] != "done":
        pass


@pytest.fixture(scope="module")
def plain(params):
    unix_before = time.time_ns()
    eng, spans, wall = serve(params, [
        GenRequest(prompt_tokens=[3, 1, 4, 1, 5], max_new_tokens=12, request_id="a"),
        GenRequest(prompt_tokens=list(range(1, 38)), max_new_tokens=12, request_id="b"),
        GenRequest(prompt_tokens=[2, 7], max_new_tokens=8, request_id="c"),
        GenRequest(prompt_tokens=[7, 8], max_new_tokens=8, request_id="d",
                   constraint=ForcedSequence([40, 41, 42])),
    ])
    return {"eng": eng, "spans": spans, "wall": wall, "stats": eng.snapshot_stats(),
            "unix": (unix_before, time.time_ns())}


def test_self_times_sum_to_the_engine_threads_wall_time(plain):
    """The acceptance criterion: the phases' self times add up to the wall
    time of the thread that entered them, within 2%."""
    total = sum(plain["stats"]["sched_s"].values())
    assert total == pytest.approx(plain["wall"], rel=0.02)
    assert total <= plain["wall"]      # the spans enclose what is counted


def test_engine_phases_and_counts(plain):
    s = plain["stats"]
    names = {name for name, _ in plain["spans"]}
    assert names == {f"kvmini.{p}" for p in CORE + ("masked_sweep",)}
    assert s["sched_spans"]["admit"] == 4 == s["prefills"]
    assert s["sched_spans"]["prefill"] == s["prefill_chunks"] == 4
    assert s["sched_spans"]["first_token"] == 4
    assert s["sched_spans"]["decode_retire"] == s["sched_spans"]["emit"] > 0
    assert s["sched_spans"]["masked_sweep"] >= 2
    for p in SCHED_PHASES:
        assert 0.0 <= s["sched_exposed_s"][p] <= s["sched_s"][p] + 1e-9
        assert s["sched_slow_s"][p] <= s["sched_s"][p] + 1e-9
    # a wait for work and a device wait are never exposed HOST time
    assert s["sched_exposed_s"]["idle"] == 0.0
    # admissions run with the pipeline settled: all of it is exposed
    assert s["sched_exposed_s"]["admit"] == pytest.approx(s["sched_s"]["admit"])


def test_span_args_only_on_the_rare_phases(plain):
    by_name = {}
    for name, args in plain["spans"]:
        by_name.setdefault(name, []).append(args)
    assert all(not a for n in ("loop", "idle", "decode_prepare",
                               "decode_retire", "emit", "first_token")
               for a in by_name[f"kvmini.{n}"])
    # a dispatch is bare unless it closes a decode gap (here: after the
    # admissions beside a decoding slot and between the masked sweeps)
    closing = [a for a in by_name["kvmini.decode_dispatch"] if a]
    assert 0 < len(closing) < len(by_name["kvmini.decode_dispatch"])
    assert all(set(a) == {"gap_ms", "cause", "waiting", "admissions"} for a in closing)
    prefill = {a["request_id"]: a for a in by_name["kvmini.prefill"]}
    assert prefill["b"] == {"request_id": "b", "prompt_tokens": 37, "bucket": 64, "offset": 0}
    assert prefill["a"]["bucket"] == 16 and prefill["a"]["prompt_tokens"] == 5
    admit = {a["request_id"]: a for a in by_name["kvmini.admit"]}
    # (admitted into an idle engine: no sweep in flight to go behind)
    assert admit["b"] == {"request_id": "b", "prompt_tokens": 37, "inflight": False}


def test_bucket_tokens_count_the_padding(plain):
    s = plain["stats"]
    assert s["prefill_tokens"] == 5 + 37 + 2 + 2
    assert s["prefill_bucket_tokens"] == 16 + 64 + 16 + 16


def test_older_counters_take_the_ledgers_durations(plain):
    """One stopwatch an interval: the ``emit`` histogram IS the ``emit``
    phase, on the masked path too (``emit`` has no child phase, so its self
    time is its whole duration), and ``busy_s`` holds every prefill and
    first-token phase whole."""
    eng, s = plain["eng"], plain["stats"]
    hist = eng.snapshot_phase_hist()
    assert hist["emit"]["count"] == s["sched_spans"]["emit"]
    assert hist["emit"]["sum"] == pytest.approx(s["sched_s"]["emit"], abs=1e-6)
    assert s["busy_s"] >= s["sched_s"]["prefill"] + s["sched_s"]["first_token"] - 1e-9
    assert s["busy_s"] <= plain["wall"]


def test_synchronous_sweeps_enter_the_plain_paths_child_phases(params, plain):
    """``masked_sweep`` and ``spec_sweep`` keep no stopwatch of their own:
    each sweep enters ``decode_dispatch``, ``decode_retire`` and ``emit`` once,
    like a plain step, and ``busy_s`` and the ``emit`` histogram read those."""
    s = plain["stats"]
    masked = s["sched_spans"]["masked_sweep"]
    # every decode step of the plain engine, masked or not, was read back once
    assert s["sched_spans"]["decode_retire"] == s["decode_steps"] >= masked >= 2
    assert s["sched_spans"]["decode_dispatch"] >= s["sched_spans"]["decode_retire"]
    spec, _, _ = serve(params, [GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=8)],
                       drafter=True, spec_tokens=2)
    t = spec.snapshot_stats()
    rounds = t["sched_spans"]["spec_sweep"]
    assert rounds == t["spec_rounds"] > 0
    for child in ("decode_dispatch", "decode_retire", "emit"):
        assert t["sched_spans"][child] == rounds
    hist = spec.snapshot_phase_hist()["emit"]
    assert hist["count"] == rounds
    assert hist["sum"] == pytest.approx(t["sched_s"]["emit"], abs=1e-6)
    # the rounds feed the sweep wall the stall threshold scales from
    assert spec._sweep_ema_s > 0.0
    assert t["busy_s"] >= t["sched_s"]["decode_retire"] - 1e-9


def test_traces_keep_unix_time_through_one_offset(plain):
    """Every duration is taken on the monotonic clock; /traces still speaks
    Unix nanoseconds."""
    eng = plain["eng"]
    lo, hi = plain["unix"]
    spans = eng.traces_otlp()["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert {s["name"] for s in spans} >= {"server.queue", "server.prefill", "server.decode"}
    for s in spans:
        assert lo <= int(s["startTimeUnixNano"]) <= int(s["endTimeUnixNano"]) <= hi
    # the histograms saw the same monotonic durations: no negative, no epoch
    for phase in ("queue", "prefill", "decode"):
        h = eng.snapshot_phase_hist()[phase]
        assert h["count"] == 4 and 0.0 <= h["sum"] < 4 * plain["wall"]


def test_spec_and_handoff_phases_complete_the_table(params, plain):
    """The phases no benchmark cell runs are there so that the partition is
    whole: a drafter's engine shows ``spec_sweep``, a disaggregated one
    ``handoff``; with the plain engine's, that is every name of the table
    and no other."""
    req = [GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=8)]
    spec, spec_spans, spec_wall = serve(params, req, drafter=True, spec_tokens=2)
    assert spec.snapshot_stats()["sched_spans"]["spec_sweep"] == spec.stats["spec_rounds"] > 0
    lane, lane_spans, lane_wall = serve(
        params, [GenRequest(prompt_tokens=list(range(1, 41)), max_new_tokens=4)],
        disagg=True, disagg_min_prompt=8)
    assert lane.stats["kv_handoffs"] == 1
    assert lane.snapshot_stats()["sched_spans"]["handoff"] > 0
    seen = {n for n, _ in plain["spans"] + spec_spans + lane_spans}
    assert seen == {f"kvmini.{p}" for p in SCHED_PHASES}
    for eng, wall in ((spec, spec_wall), (lane, lane_wall)):
        assert sum(eng.snapshot_stats()["sched_s"].values()) == pytest.approx(wall, rel=0.02)


def test_multihost_drivers_keep_the_partition(params, monkeypatch):
    """The lockstep drivers run the scheduler outside ``Engine._loop``: the
    primary opens ``loop`` around every iteration, the follower books its wait
    for the next command as ``idle`` and the replay as ``loop``, so the self
    times add up to the driver's wall time there too."""
    import threading

    from kserve_vllm_mini_tpu.runtime import multihost
    from kserve_vllm_mini_tpu.runtime.multihost import run_follower, run_primary

    # one process, no process-spanning mesh: the drivers' loops are the same
    monkeypatch.setattr(multihost, "check_multihost_engine", lambda eng: None)

    def engine():
        eng = Engine(params, CFG, EngineConfig(max_slots=2, max_seq_len=128,
                                               max_prefill_len=64, min_prefill_bucket=16))
        edges = []

        class Span:
            def __init__(self, name, **args):
                pass

            def __enter__(self):
                edges.append(time.perf_counter())

            def __exit__(self, *exc):
                edges.append(time.perf_counter())

        eng._sched = SchedLedger(annotate=Span, exposed=eng._host_exposed)
        return eng, edges

    class Wire:
        def __init__(self):
            self.sent = []

        def publish(self, decision):
            self.sent.append(decision)

        def commands(self):
            return iter(self.sent)

    wire, stop = Wire(), threading.Event()
    primary, p_edges = engine()
    driver = threading.Thread(target=run_primary, args=(primary, wire, stop))
    driver.start()
    try:
        handles = [primary.submit(GenRequest(prompt_tokens=[3, 1, 4, 1], max_new_tokens=10)),
                   primary.submit(GenRequest(prompt_tokens=[2, 7, 1], max_new_tokens=6))]
        for h in handles:
            while h.events.get(timeout=120)[0] != "done":
                pass
    finally:
        stop.set()
        driver.join(timeout=60)
    assert wire.sent[-1] == ("stop",)
    follower, f_edges = engine()
    run_follower(follower, wire)
    for eng, edges in ((primary, p_edges), (follower, f_edges)):
        s = eng.snapshot_stats()
        assert sum(s["sched_s"].values()) == pytest.approx(edges[-1] - edges[0], rel=0.02)
        assert s["sched_spans"]["loop"] > 0 and s["sched_spans"]["first_token"] == 2
    assert follower.snapshot_stats()["sched_spans"]["idle"] == len(wire.sent)
    assert follower.stats["decode_tokens"] == primary.stats["decode_tokens"] > 0


# -- the decode gap on a serving engine ---------------------------------------


def gap_spans(eng):
    """The ``engine.decode.gap`` spans of /traces as (t0, t1, attributes), in
    seconds on the engine's monotonic clock."""
    out = []
    for scope in eng.traces_otlp()["resourceSpans"][0]["scopeSpans"]:
        for sp in scope["spans"]:
            if sp["name"] == "engine.decode.gap":
                attrs = {a["key"]: next(iter(a["value"].values())) for a in sp["attributes"]}
                out.append(((int(sp["startTimeUnixNano"]) - eng._unix_offset_ns) * 1e-9,
                            (int(sp["endTimeUnixNano"]) - eng._unix_offset_ns) * 1e-9, attrs))
    return out


def closing_dispatches(spans):
    return [a for n, a in spans if n == "kvmini.decode_dispatch" and a]


@pytest.fixture(scope="module", params=[True, False], ids=["pipelined", "synchronous"])
def closed_loop(params, request):
    """2 x slots callers, each sending its next request when the last one is
    answered: a freed slot always has a request waiting, as in the
    benchmark's saturated cells."""
    import threading

    lengths = [(6, 9, 5), (20, 7, 11), (9, 12, 6), (12, 5, 8)]
    later = []

    def caller(eng, first, mine):
        wait_done(first)
        for k, n in enumerate(mine[1:]):
            h = eng.submit(GenRequest(prompt_tokens=[5, 3, 2 + k], max_new_tokens=n,
                                      request_id=f"{first.request.request_id}.{k + 1}"))
            later.append(h)
            wait_done(h)

    def drive(eng, handles):
        threads = [threading.Thread(target=caller, args=(eng, h, mine))
                   for h, mine in zip(handles, lengths)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)

    eng, spans, wall = serve(
        params, [GenRequest(prompt_tokens=[3, 1, 4, 1 + i], max_new_tokens=mine[0],
                            request_id=f"c{i}") for i, mine in enumerate(lengths)],
        drive=drive, decode_pipeline=request.param)
    return {"eng": eng, "spans": spans, "stats": eng.snapshot_stats(),
            "handles": eng.handles + later}


def test_admission_gaps_are_partitioned_and_the_bubble_is_their_sum(closed_loop):
    s = closed_loop["stats"]
    assert s["sched_gaps"]["admission"] >= 4
    in_phases = sum(s["sched_admit_gap_s"].values())
    assert in_phases == pytest.approx(s["sched_gap_s"]["admission"], rel=0.02)
    assert s["sched_admit_gap_s"]["prefill"] > 0
    if s["admissions_inflight"] == s["sched_admit_gap_admissions"]:
        # every gap was an admission's behind the sweep in flight: it holds
        # that sweep's emission and the wait for the first token, and ends
        # where the token is read back, before the host's work on it
        assert {p for p, v in s["sched_admit_gap_s"].items() if v} <= {"emit", "loop", "prefill"}
    elif not s["admissions_inflight"]:
        assert s["sched_admit_gap_s"]["first_token"] > 0
    # the wait for a sweep's tokens is never part of a gap, an idle wait neither
    assert s["sched_admit_gap_s"]["decode_retire"] == s["sched_admit_gap_s"]["idle"] == 0.0
    assert s["bubble_s"] == pytest.approx(sum(s["sched_gap_s"].values()), rel=1e-12)
    assert 0 < s["bubble_s"] < sum(s["sched_s"].values())
    # 2 slots: one waits while the other is admitted into, both between two
    # synchronous sweeps
    assert s["bubble_s"] * (1 - 1e-9) <= s["sched_gap_slot_s"] <= 2 * s["bubble_s"]
    if closed_loop["eng"].ecfg.decode_pipeline:
        assert s["sched_gap_slot_s"] == pytest.approx(s["bubble_s"], rel=1e-9)
    for p in SCHED_PHASES:
        assert s["sched_admit_gap_s"][p] <= s["sched_s"][p] + 1e-9


def test_one_admission_gap_an_admission_or_one_for_two_admitted_together(closed_loop):
    s, eng = closed_loop["stats"], closed_loop["eng"]
    from kserve_vllm_mini_tpu.core.schema import validate_traces

    assert validate_traces(eng.traces_otlp()) == []
    gaps = gap_spans(eng)
    assert len(gaps) == sum(s["sched_gaps"].values())
    admission = [g for g in gaps if g[2]["cause"] == "admission"]
    assert len(admission) == s["sched_gaps"]["admission"] <= s["sched_admit_gap_admissions"]
    assert s["sched_admit_gap_admissions"] < s["sched_spans"]["first_token"] == 12
    # the first tokens sampled inside a gap, counted from the requests' own
    # stamps, are the admissions the ledger booked, and the spans name them
    inside = {h.request.request_id for h in closed_loop["handles"]
              if any(t0 <= h.t_first_token <= t1 for t0, t1, _ in admission)}
    assert len(inside) == s["sched_admit_gap_admissions"]
    named = [r for g in admission for r in g[2]["request_ids"].split(",")]
    assert sorted(named) == sorted(inside)
    for _, _, attrs in admission:
        assert int(attrs["admissions"]) == len(attrs["request_ids"].split(",")) >= 1
        assert int(attrs["waiting_slots"]) == 1
    # every gap but one closed without a dispatch was said on the dispatch
    # that closed it; the gap of an admission behind the sweep in flight is
    # closed by its first token's readback, and no dispatch says it
    said = closing_dispatches(closed_loop["spans"])
    assert all(set(a) == {"gap_ms", "cause", "waiting", "admissions"} for a in said)
    if s["admissions_inflight"]:
        assert eng.ecfg.decode_pipeline
        assert sum(a["admissions"] for a in said) < s["sched_admit_gap_admissions"]
        assert sum(a["gap_ms"] for a in said) < 1e3 * s["bubble_s"]
    else:
        assert sum(a["admissions"] for a in said) == s["sched_admit_gap_admissions"]
        assert [a["cause"] for a in said].count("admission") == s["sched_gaps"]["admission"]
        assert sum(a["gap_ms"] for a in said) == pytest.approx(1e3 * s["bubble_s"], rel=0.02)


def test_an_engines_admission_behind_a_sweep_books_one_gap_and_a_drained_one_as_before(params):
    """Driven iteration by iteration, two slots, one caller decoding: the
    second caller is admitted behind the sweep in flight and the ledger books
    ONE admission gap from that sweep's readback to the first token's, the
    token stamped where the gap ends; with ``decode_pipeline`` off the same
    admission books the drained gap that the next dispatch closes."""
    for pipeline in (True, False):
        eng = Engine(params, CFG, EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64,
                                               min_prefill_bucket=16, decode_pipeline=pipeline))
        spans = []
        eng._sched = SchedLedger(annotate=lambda n, **a: spans.append((n, a)) or rt_tracing._NO_SPAN,
                                 exposed=eng._host_exposed, on_gap=eng._trace_gap)

        def iterate():
            with eng._sched.phase("loop"):      # as Engine._loop does around every iteration
                eng._schedule_once()

        eng.submit(GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=20, request_id="a"))
        iterate()
        iterate()
        before = eng.snapshot_stats()
        b = eng.submit(GenRequest(prompt_tokens=[8, 6, 7, 5], max_new_tokens=5, request_id="b"))
        iterate()
        iterate()
        s = eng.snapshot_stats()
        assert len(b.tokens) >= 1
        gaps = {c: s["sched_gaps"][c] - before["sched_gaps"][c] for c in s["sched_gaps"]}
        assert gaps["admission"] == 1 and gaps["prefill_piece"] == 0
        assert s["sched_admit_gap_admissions"] == 1 == s["admissions_inflight"] + (not pipeline)
        in_phases = s["sched_admit_gap_s"]
        assert sum(in_phases.values()) == pytest.approx(s["sched_gap_s"]["admission"], rel=1e-9)
        (t0, t1, attrs), = [g for g in gap_spans(eng) if g[2]["cause"] == "admission"]
        assert attrs["request_ids"] == "b" and int(attrs["admissions"]) == 1
        assert int(attrs["waiting_slots"]) == 1
        # (the drained gap spans two iterations, and this test's time between
        # them is nobody's; behind a sweep the gap lies inside one iteration)
        assert t1 - t0 >= s["sched_gap_s"]["admission"] * (1 - 1e-6)
        if pipeline:
            assert t1 - t0 == pytest.approx(s["sched_gap_s"]["admission"], rel=1e-6)
        closing = [x for n, x in spans if n == "kvmini.decode_dispatch" and x.get("cause") == "admission"]
        admit = [x for n, x in spans if n == "kvmini.admit" and x["request_id"] == "b"]
        assert admit == [{"request_id": "b", "prompt_tokens": 4, "inflight": pipeline}]
        if pipeline:
            # the first token is stamped where its readback, and the gap, ends
            assert b.t_first_token == pytest.approx(t1, abs=1e-9)
            assert {p for p, v in in_phases.items() if v} <= {"emit", "loop", "prefill"}
            assert in_phases["prefill"] > 0 and not closing
            assert s["busy_s"] > before["busy_s"] and s["prefill_chunk_stall_s"] > 0
        else:
            assert t0 < b.t_first_token < t1
            assert in_phases["first_token"] > 0 and in_phases["admit"] > 0
            assert [x["admissions"] for x in closing] == [1]
        assert s["bubble_s"] == pytest.approx(sum(s["sched_gap_s"].values()), rel=1e-12)


def test_pipelined_sweeps_book_no_gap_and_synchronous_ones_each_their_own(closed_loop):
    s = closed_loop["stats"]
    if closed_loop["eng"].ecfg.decode_pipeline:
        assert s["sched_gaps"]["sweep"] == 0
        assert len(closing_dispatches(closed_loop["spans"])) < s["sched_spans"]["decode_dispatch"] / 3
    else:
        assert s["sched_gaps"]["sweep"] > s["sched_gaps"]["admission"]
    assert s["sched_gaps"]["prefill_piece"] == 0


def test_the_wait_for_a_sweeps_tokens_is_never_exposed(closed_loop, plain):
    """A sweep is in flight until its tokens are on the host: the readback of
    a drain's last sweep (before every admission) and of a synchronous sweep
    is a wait for the device, while the host phases around it read as before."""
    for s in (closed_loop["stats"], plain["stats"]):
        assert s["sched_s"]["decode_retire"] > 0
        assert s["sched_exposed_s"]["decode_retire"] == 0.0
        if s["admissions_inflight"]:
            # admissions behind the sweep in flight: the device has that
            # sweep and then their prefill queued while the host works
            assert s["sched_exposed_s"]["admit"] < s["sched_s"]["admit"]
            assert s["sched_exposed_s"]["first_token"] < s["sched_s"]["first_token"]
            continue
        # the emission after a drained readback, the admission, the next
        # dispatch: exposed, as they were
        for p in ("emit", "admit", "first_token", "decode_prepare", "decode_dispatch", "loop"):
            assert s["sched_exposed_s"][p] > 0
        assert s["sched_exposed_s"]["admit"] == pytest.approx(s["sched_s"]["admit"])
        assert s["sched_exposed_s"]["first_token"] == pytest.approx(s["sched_s"]["first_token"])
    # the masked sweeps of the plain fixture are synchronous: none exposed either
    assert plain["stats"]["sched_spans"]["masked_sweep"] >= 2


def test_every_synchronous_sweep_books_a_sweep_gap(params):
    """``decode_pipeline`` off: each readback leaves nothing in flight, so each
    sweep but the one that finishes the request opens a gap the next dispatch closes."""
    eng, spans, _ = serve(params, [GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=9)],
                          decode_pipeline=False)
    s = eng.snapshot_stats()
    assert s["sched_spans"]["decode_retire"] == 8
    assert s["sched_gaps"] == {"admission": 0, "prefill_piece": 0, "sweep": 7}
    said = closing_dispatches(spans)
    assert [a["cause"] for a in said] == ["sweep"] * 7
    assert all(a["waiting"] == 1 and a["admissions"] == 0 for a in said)
    # gap + sweep + gap + ...: the thread's time between the first readback and the last
    assert s["bubble_s"] < s["sched_s"]["decode_prepare"] + s["sched_s"]["emit"] + s["sched_s"]["loop"]


def test_a_speculative_round_follows_the_same_rule(params):
    eng, spans, _ = serve(params, [GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=12)],
                          drafter=True, spec_tokens=2)
    s = eng.snapshot_stats()
    assert s["sched_gaps"]["sweep"] == s["spec_rounds"] - 1 > 0
    assert s["sched_exposed_s"]["decode_retire"] == 0.0
    assert [a["cause"] for a in closing_dispatches(spans)] == ["sweep"] * s["sched_gaps"]["sweep"]


def test_a_chunked_prompts_later_pieces_book_prefill_piece(params):
    """Synchronous sweeps, a 59-token prompt in pieces of 16 beside a slot that
    decodes: the first piece runs before any sweep, the second and third each
    in a gap of their own, the last one's gap completes the admission."""
    eng, spans, _ = serve(params, [
        GenRequest(prompt_tokens=[3, 1, 4, 1, 5], max_new_tokens=30, request_id="a"),
        GenRequest(prompt_tokens=list(range(1, 60)), max_new_tokens=5, request_id="b"),
    ], decode_pipeline=False, prefill_chunk=16)
    s = eng.snapshot_stats()
    assert s["sched_spans"]["prefill"] == 5
    assert s["sched_gaps"]["prefill_piece"] == 2 and s["sched_gaps"]["admission"] == 1
    assert s["sched_admit_gap_admissions"] == 1
    assert s["sched_gap_s"]["prefill_piece"] > 0
    pieces = [a for a in closing_dispatches(spans) if a["cause"] != "sweep"]
    assert [(a["cause"], a["admissions"]) for a in pieces] == [
        ("prefill_piece", 0), ("prefill_piece", 0), ("admission", 1)]


def test_a_gap_whose_slots_all_finish_closes_without_a_dispatch(params):
    """A cancelled request is finished by the next iteration's cancel pass,
    inside the gap its last readback opened: the gap is booked though no
    dispatch follows it."""
    def cancel_after_a_few_tokens(eng, handles):
        seen = 0
        while seen < 4:
            seen += handles[0].events.get(timeout=120)[0] == "token"
        eng.cancel(handles[0])
        wait_done(handles[0])

    eng, spans, _ = serve(params, [GenRequest(prompt_tokens=[3, 1, 4], max_new_tokens=100)],
                          drive=cancel_after_a_few_tokens, decode_pipeline=False)
    s = eng.snapshot_stats()
    assert eng.handles[0].finish_reason == "stop" and len(eng.handles[0].tokens) < 100
    sweeps = s["sched_spans"]["decode_retire"]
    assert s["sched_spans"]["decode_dispatch"] == sweeps
    assert s["sched_gaps"]["sweep"] == sweeps == len(gap_spans(eng))
    assert len(closing_dispatches(spans)) == sweeps - 1


# -- what the benchmark reads by name -----------------------------------------


def test_names_the_benchmark_reads(plain):
    """bench/trace_reduce.py names idle gaps by these two spans and finds the
    programs by these jit names; bench/readers/ read these series from
    /metrics. A rename here is a change to the yardstick."""
    from aiohttp.test_utils import TestClient, TestServer

    from kserve_vllm_mini_tpu.runtime.server import make_app
    from kserve_vllm_mini_tpu.runtime.tokenizer import load_tokenizer

    eng = plain["eng"]
    assert {"kvmini.decode_dispatch", "kvmini.decode_retire"} <= {n for n, _ in plain["spans"]}

    def jit_name(fn):
        """The name XLA gives the program (``jit_<name>``): that of the
        jitted function under the compile-statistics wrapper."""
        return getattr(fn, "_fn", fn).__name__

    assert jit_name(eng._step("decode", 1)) == "decode"
    assert jit_name(eng._step("prefill", 16, True)) == "prefill"
    assert jit_name(eng._step("first")) == "first"

    async def scrape():
        async with TestClient(TestServer(make_app(eng, load_tokenizer(None), "llama-tiny"))) as c:
            return await (await c.get("/metrics")).text()

    values = {}
    for line in asyncio.run(scrape()).splitlines():
        if line and not line.startswith("#") and len(line.split()) == 2:
            values[line.split()[0]] = float(line.split()[1])
    s = plain["stats"]
    assert values["kvmini_tpu_decode_steps_total"] == s["decode_steps"] > 0
    assert values["kvmini_tpu_decode_tokens_total"] == s["decode_tokens"] > 0
    assert values["kvmini_tpu_prefills_total"] == 4
    assert values["kvmini_tpu_prefill_tokens_total"] == 46
    assert values["kvmini_tpu_prefill_bucket_tokens_total"] == 112
    for p in SCHED_PHASES:
        assert values[f'kvmini_tpu_sched_seconds_total{{phase="{p}"}}'] == pytest.approx(
            s["sched_s"][p], abs=1e-6)
        assert values[f'kvmini_tpu_sched_exposed_seconds_total{{phase="{p}"}}'] == pytest.approx(
            s["sched_exposed_s"][p], abs=1e-6)
        assert values[f'kvmini_tpu_sched_slow_seconds_total{{phase="{p}"}}'] == pytest.approx(
            s["sched_slow_s"][p], abs=1e-6)
        assert values[f'kvmini_tpu_sched_spans_total{{phase="{p}"}}'] == s["sched_spans"][p]
    for phase in ("queue", "prefill"):
        assert values[f'kvmini_tpu_phase_seconds_count{{phase="{phase}"}}'] == 4
        assert f'kvmini_tpu_phase_seconds_sum{{phase="{phase}"}}' in values
    # the decode gap's series (bench/readers/decode_gap_share.py, admit_gap_*.py),
    # and the older name of their sum
    for c in rt_tracing.SCHED_GAP_CAUSES:
        assert values[f'kvmini_tpu_sched_gaps_total{{cause="{c}"}}'] == s["sched_gaps"][c]
        assert values[f'kvmini_tpu_sched_gap_seconds_total{{cause="{c}"}}'] == pytest.approx(
            s["sched_gap_s"][c], abs=1e-6)
    for p in SCHED_PHASES:
        assert values[f'kvmini_tpu_sched_admit_gap_seconds_total{{phase="{p}"}}'] == pytest.approx(
            s["sched_admit_gap_s"][p], abs=1e-6)
    assert values["kvmini_tpu_sched_gap_slot_seconds_total"] == pytest.approx(
        s["sched_gap_slot_s"], abs=1e-6)
    assert values["kvmini_tpu_sched_admit_gap_admissions_total"] == s["sched_admit_gap_admissions"]
    assert sum(s["sched_gaps"].values()) > 0
    assert values["kvmini_tpu_bubble_seconds_total"] == pytest.approx(
        sum(values[f'kvmini_tpu_sched_gap_seconds_total{{cause="{c}"}}']
            for c in rt_tracing.SCHED_GAP_CAUSES), abs=2e-6)

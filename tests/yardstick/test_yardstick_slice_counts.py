"""A reader that divides a count by a device time takes both from the same
seconds (PR 48): ``trace_reduce.events_of`` keeps the arguments of the
program's ``kvmini.*`` spans, ``reduce`` sums them over the spans that begin
inside the device's window (``spans``), and the routed experts' four readers
count from there. Since PR 46 the expert call's time follows the touched
experts, so the 45 s window's count over a quiet 3 s slice's time read up to
98% and, one traced run in three, over 100 (the run fails)."""

import hashlib
import json
from pathlib import Path

import pytest

from bench import (config, conv_moe_roofline, metrics, run, ssm_moe_roofline, trace_reduce,
                   window_moe_roofline)
from tests.yardstick import test_yardstick_conv_moe as lfm2
from tests.yardstick import test_yardstick_ssm_moe as nemotron
from tests.yardstick import test_yardstick_window_moe as smallthinker

DATA = Path(__file__).resolve().parents[2] / "bench" / "testdata"
DEV, HOST = "/device:TPU:0", "/host:CPU"
HBM_READERS = {"glu_moe_expert_hbm_share": (lfm2, conv_moe_roofline),
               "reglu_moe_expert_hbm_share": (smallthinker, window_moe_roofline),
               "moe_expert_hbm_share": (nemotron, ssm_moe_roofline)}
# what PR 46's ``reduce`` returned on each recorded head, every key but ``spans``
# (sha256 of ``json.dumps(..., sort_keys=True)``, its first 16 digits)
REDUCED_AT_PR46 = {"trace_head": "87d4702b8e63ac51", "trace_head_pr26": "a20c923f36e0ac8b",
                   "trace_head_pr28": "74db827f4264537c", "trace_head_pr32": "072d5bd7a194161c",
                   "trace_head_pr35": "5669d30e57f3a43d", "trace_head_pr40": "b3a40e6bbdff02c0",
                   "trace_head_pr44": "7a21f1e511c5938d"}
HEADS = sorted(REDUCED_AT_PR46) + ["trace_head_pr48"]


def ev(plane, line, name, start_us, dur_us, **args):
    e = {"plane": plane, "line": line, "name": name,
         "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}
    return {**e, "args": args} if args else e


def read(name, ctx):
    return config.load_module("readers", name).read(ctx)


# -- events_of ------------------------------------------------------------------

def test_events_of_keeps_the_arguments_of_the_programs_spans_and_of_nothing_else(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("kvmini.emit", moe_touched=227, moe_steps=1):
        jnp.ones(8).block_until_ready()
    with jax.profiler.TraceAnnotation("kvmini.prefill", request_id="r1", prompt_tokens=567):
        pass
    with jax.profiler.TraceAnnotation("kvmini.loop"):
        pass
    with jax.profiler.TraceAnnotation("someone.else", moe_touched=5):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    events = trace_reduce.events_of(tmp_path)
    named = {e["name"]: e for e in events}
    assert named["kvmini.emit"]["args"] == {"moe_touched": 227, "moe_steps": 1}
    assert named["kvmini.prefill"]["args"] == {"request_id": "r1", "prompt_tokens": 567}
    assert "args" not in named["kvmini.loop"] and "args" not in named["someone.else"]
    assert len(events) > 4 and all(e["name"].startswith("kvmini.") for e in events if "args" in e)
    assert all(set(e) - {"args"} == {"plane", "line", "name", "start_ns", "dur_ns"} for e in events)


def test_events_of_drops_a_device_operations_arguments(monkeypatch):
    """A device plane's events keep none, whatever they are called: tens of
    thousands a slice, and no reader wants them."""
    import jax

    class Ev:
        def __init__(self, name):
            self.name, self.start_ns, self.duration_ns = name, 5.0, 2.0
            self.stats = [("flops", 7), ("moe_touched", 3)]

    class Line:
        name, events = "XLA Ops", [Ev("kvmini.emm"), Ev("%fusion.1")]

    class Plane:
        def __init__(self, name):
            self.name, self.lines = name, [Line()]

    class Data:
        planes = [Plane(DEV), Plane(HOST)]

    monkeypatch.setattr(trace_reduce.glob, "glob", lambda pattern: ["a.xplane.pb"])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", lambda path: Data())
    events = trace_reduce.events_of("anywhere")
    assert [(e["plane"], e["name"], e.get("args")) for e in events] == [
        (DEV, "kvmini.emm", None), (DEV, "%fusion.1", None),
        (HOST, "kvmini.emm", {"flops": 7, "moe_touched": 3}), (HOST, "%fusion.1", None)]


# -- reduce: what the slice's spans counted ----------------------------------------

def test_reduce_sums_only_the_spans_that_begin_inside_the_device_window():
    ops, mods = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
    events = [
        ev(DEV, mods, "jit_decode(1)", 100, 50), ev(DEV, ops, "%fusion.1 = bf16[16] fusion()", 100, 50),
        ev(DEV, mods, "jit_decode(1)", 900, 100), ev(DEV, ops, "%fusion.1 = bf16[16] fusion()", 900, 100),
        ev(HOST, "python3", "kvmini.emit", 40, 5, moe_touched=1000, moe_steps=1),     # before
        ev(HOST, "python3", "kvmini.emit", 100, 5, moe_touched=200, moe_steps=1),     # at the edge
        ev(HOST, "python3", "kvmini.emit", 500, 5, moe_touched=30, moe_steps=2, moe_visited=30.5),
        ev(HOST, "python3", "kvmini.emit", 990, 50, moe_touched=4, moe_steps=1),      # ends after
        ev(HOST, "python3", "kvmini.emit", 1001, 5, moe_touched=5000, moe_steps=1),   # after
        ev(HOST, "python3", "kvmini.prefill", 300, 9, request_id="r", prompt_tokens=567, wide=True),
        ev(HOST, "python3", "kvmini.prefill", 400, 90, first_tokens=1),
        ev(HOST, "python3", "kvmini.loop", 200, 700),
        ev(HOST, "python3", "someone.else", 300, 5, prompt_tokens=9),
        ev(DEV, "Steps", "kvmini.step", 300, 5, prompt_tokens=9),
    ]
    spans = trace_reduce.reduce(events)["spans"]
    assert spans == {
        "kvmini.emit": {"count": 3, "sums": {"moe_touched": 234, "moe_steps": 4, "moe_visited": 30.5}},
        "kvmini.prefill": {"count": 2, "sums": {"prompt_tokens": 567, "first_tokens": 1}},
        "kvmini.loop": {"count": 1, "sums": {}}}
    assert trace_reduce.span_counts(events) == spans
    trace = {"spans": spans}
    assert metrics.span_sums(trace, "kvmini.emit")["moe_steps"] == 4
    assert metrics.span_sums(trace, "kvmini.loop") == {} == metrics.span_sums(trace, "kvmini.idle")
    assert metrics.span_sums(None, "kvmini.emit") == {} == metrics.span_sums({}, "kvmini.emit")
    # no device plane: no window to be inside of
    assert trace_reduce.reduce([e for e in events if e["plane"] == HOST])["spans"] == {}
    assert json.loads(json.dumps(spans)) == spans       # trace_reduced.json holds it


@pytest.mark.parametrize("head", HEADS)
def test_reduce_returns_on_a_recorded_head_what_it_returned(head):
    """The eight other cells read nothing that changed but ``spans``: every
    key ``reduce`` had, value for value, with and without the arguments."""
    events = trace_reduce.load_events(DATA / f"{head}.json.gz")
    got = trace_reduce.reduce(events)
    assert set(got) == {"busy_s", "window_s", "programs", "ops", "breakdown", "spans"}
    spans = got.pop("spans")
    if head in REDUCED_AT_PR46:        # PR 48's own head has no earlier reading to be held to
        assert hashlib.sha256(json.dumps(got, sort_keys=True).encode()).hexdigest()[:16] == (
            REDUCED_AT_PR46[head])
    bare = trace_reduce.reduce([{k: v for k, v in e.items() if k != "args"} for e in events])
    assert {k: v for k, v in bare.items() if k != "spans"} == got
    assert all(not s["sums"] for s in bare["spans"].values())
    assert {n: s["count"] for n, s in bare["spans"].items()} == {
        n: s["count"] for n, s in spans.items()}


# -- a time that follows the count ------------------------------------------------

def slice_touching(per_layer: int, steps: int = 40):
    """A traced slice of lfm2's decode whose steps each touch ``per_layer``
    experts in each of the 22 expert layers: a layer's call takes 15 us a
    touched expert, and the sweep's ``emit`` span says what it touched."""
    ops, mods = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
    call = ("%emm.{} = bf16[16,2048]{{1,0}} custom-call(s8[22,32,2048,1792]{{3,2,1,0}} %p.1, "
            "s8[22,32,1792,2048]{{3,2,1,0}} %p.2)")
    events, t, took = [], 0.0, 15.0 * per_layer
    for _ in range(steps):
        events.append(ev(DEV, mods, "jit_decode(7)", t, 22 * took + 100.0))
        events += [ev(DEV, ops, call.format(k), t + k * took, took) for k in range(22)]
        t += 22 * took + 100.0
        events.append(ev(HOST, "python3", "kvmini.emit", t - 50.0, 20.0, moe_steps=1,
                         moe_touched=22 * per_layer, moe_visited=22 * per_layer))
    return trace_reduce.reduce(events)


def test_a_slice_with_half_the_windows_live_rows_reads_the_same_share_as_a_full_one(monkeypatch):
    """The window's counters say 12 experts a layer a step (3 rows live); one
    slice holds that, another half of it. The old arithmetic (the window's
    count over the slice's time) is kept as a foil: on the quiet slice it
    reads twice the truth, and ``collect`` fails the run."""
    m = lfm2.full_model()
    counters = {**lfm2.COUNTERS, "kvmini_tpu_moe_expert_touches_total": 3000.0 * 12 * 22}

    def foil(trace):
        took, steps = conv_moe_roofline.seconds_and_runs(
            trace, m, "jit_decode", conv_moe_roofline.reads_expert_stack)
        need = 12 * 22 * conv_moe_roofline.expert_bytes(m) + 5.0 * 4 * 22 * 8192
        return 100.0 * need / 819e9 / (took / steps)

    full, quiet = slice_touching(12), slice_touching(6)
    assert quiet["spans"]["kvmini.emit"] == {"count": 40, "sums": {
        "moe_steps": 40, "moe_touched": 40 * 6 * 22, "moe_visited": 40 * 6 * 22}}
    full_share = read("glu_moe_expert_hbm_share", lfm2.ctx(counters, full))
    quiet_share = read("glu_moe_expert_hbm_share", lfm2.ctx(counters, quiet))
    assert 85.0 < full_share < 92.0             # 11,032,576 B at 819 GB/s is 13.47 us of the 15
    assert quiet_share == pytest.approx(full_share, rel=2e-3)   # the window's activations' term
    assert foil(full) == pytest.approx(full_share)
    assert foil(quiet) > 170.0
    spec = [{"name": "glu_moe_expert_hbm_share", "unit": "%"}]
    with monkeypatch.context() as patched:
        patched.setattr(run, "read_metric", lambda name, ctx: foil(ctx.trace))
        assert run.collect(spec, lfm2.ctx(counters, full))
        with pytest.raises(RuntimeError, match="> 100%"):
            run.collect(spec, lfm2.ctx(counters, quiet))
    assert run.collect(spec, lfm2.ctx(counters, quiet))["glu_moe_expert_hbm_share"]["value"] == (
        quiet_share)


def test_the_prefill_share_counts_the_tokens_of_the_slices_own_pieces():
    """Two slices of one program, a 200-token and an 800-token piece, each a
    microsecond a token in the grouped calls: the share is the same, whatever
    the window's mean length (260 by the counters) says."""
    m, (ops, mods) = lfm2.full_model(), (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)
    call = ("%gmm.3 = bf16[1024,1792]{1,0} custom-call(s8[22,32,2048,1792]{3,2,1,0} %p.1, "
            "bf16[1024,2048]{1,0} %p.2)")

    def share(tokens):
        events = [ev(DEV, mods, "jit_prefill(3)", 0, 30.0 * tokens),
                  ev(DEV, ops, call, 10, 22.0 * tokens),
                  ev(HOST, "python3", "kvmini.prefill", 5, 40, request_id="r",
                     prompt_tokens=tokens, bucket=1024, offset=0),
                  ev(HOST, "python3", "kvmini.prefill", 50, 900, first_tokens=1)]
        return read("glu_moe_prefill_flop_share", lfm2.ctx(lfm2.COUNTERS, trace_reduce.reduce(events)))

    assert share(200) == pytest.approx(share(800))
    assert share(200) == pytest.approx(100.0 * 22 * 4 * 3 * 2 * 2048 * 1792 / 197e12 / 22e-6)


# -- nothing to divide: nothing read ------------------------------------------------

@pytest.mark.parametrize("name", [*HBM_READERS, "glu_moe_prefill_flop_share"])
def test_a_trace_without_the_arguments_reads_nothing(name):
    """An older program's trace, or a recorded head cut before the spans
    carried counts: the metric is left out, nothing raises."""
    cell, _ = HBM_READERS.get(name, (lfm2, None))
    events = [{k: v for k, v in e.items() if k != "args"} for e in trace_reduce.load_events(cell.HEAD)]
    trace = trace_reduce.reduce(events)
    assert trace["spans"]["kvmini.emit"]["sums"] == {}
    ctx = cell.ctx(cell.COUNTERS, trace)
    assert read(name, ctx) is None
    assert run.collect([{"name": name, "unit": "%"}], ctx) == {}
    assert read(name, cell.ctx(cell.COUNTERS, {**trace, "spans": {}})) is None
    older = {k: v for k, v in trace.items() if k != "spans"}       # a trace reduced before PR 48
    assert read(name, cell.ctx(cell.COUNTERS, older)) is None


@pytest.mark.parametrize("name", sorted(HBM_READERS))
def test_the_three_readers_are_the_shared_function_over_their_module(name):
    cell, stack = HBM_READERS[name]
    trace = trace_reduce.reduce(trace_reduce.load_events(cell.HEAD))
    x = stack.sizes(cell.full_model())
    n = len(trace["programs"]["jit_decode"])
    for spans in (trace["spans"], {"kvmini.emit": {"count": n, "sums": {
            "moe_steps": n, "moe_touched": n * x["expert_layers"] * x["held"] // 2}}}):
        ctx = cell.ctx(cell.COUNTERS, {**trace, "spans": spans})
        assert read(name, ctx) == conv_moe_roofline.expert_hbm_share(ctx, stack)
    assert 0.0 < read(name, ctx) < 100.0
    # another kind of expert's module finds no such layers in this model: nothing
    other = next(s for s in (conv_moe_roofline, window_moe_roofline) if s is not stack)
    assert conv_moe_roofline.expert_hbm_share(ctx, other) is None


# -- the recorded head whose expert time follows the count ------------------------------

def test_the_readers_on_a_recorded_head_whose_expert_time_follows_the_count():
    """0.115 s of lfm2-8b-a1b.chat on the v5e with PR 46's ``emm`` calls (PR 48,
    seed 4600001002 at 6.4 req/s, the run whose window arithmetic read 98.1):
    thirteen decode steps around one admission, two rows live, then three."""
    m = lfm2.full_model()
    events = trace_reduce.load_events(DATA / "trace_head_pr48.json.gz")
    trace = trace_reduce.reduce(events)
    emits = [e["args"] for e in events if e["name"] == "kvmini.emit"]
    touched = [a["moe_touched"] for a in emits]
    assert len(trace["programs"]["jit_decode"]) == 13 == len(emits)
    assert all(a["moe_steps"] == 1 and a["moe_visited"] == a["moe_touched"] for a in emits)
    assert trace["spans"]["kvmini.emit"] == {"count": 13, "sums": {
        "moe_touched": 2357, "moe_visited": 2357, "moe_steps": 13}}
    assert trace["spans"]["kvmini.prefill"]["sums"]["prompt_tokens"] == 376
    # the 22 calls a step are found under their cut names, and nothing else of the step is
    calls = [n for n in trace["ops"] if n.startswith("jit_decode/")
             and conv_moe_roofline.reads_expert_stack(n, m)]
    assert len(calls) == 22 and all("/%emm." in n and trace["ops"][n]["calls"] == 13 for n in calls)
    # a step's calls take what its rows touched: 160-166 experts 2.6 ms, 219-231 experts 3.5 ms
    # (an emit retires the step before the one in flight, so the counts lag the steps by one)
    steps = sorted((e for e in events if e["line"] == trace_reduce.MODULES_LINE
                    and e["name"].startswith("jit_decode(")), key=lambda e: e["start_ns"])
    emm = [e for e in events if e["line"] == trace_reduce.OPS_LINE and e["name"].startswith("%emm.")]
    took = [sum(c["dur_ns"] for c in emm if s["start_ns"] <= c["start_ns"] < s["start_ns"] + s["dur_ns"])
            for s in steps]
    quiet = [t for t, n in zip(took, touched[1:]) if n <= 166]
    busy = [t for t, n in zip(took, touched[1:]) if n >= 219]
    assert len(quiet) == 7 and len(busy) == 5 and min(busy) > 1.25 * max(quiet)
    # the reader, by hand: the slice's 181.3 touched experts a step x one expert's bytes (and the
    # window's 440 assignments' activations) at 819 GB/s, over the calls' 3.06 ms a step
    ctx = lfm2.ctx(lfm2.COUNTERS, trace)
    share = read("glu_moe_expert_hbm_share", ctx)
    need_s = (2357 / 13 * 11_032_576 + 5.0 * 4 * 22 * 8192) / 819e9
    assert share == pytest.approx(100.0 * need_s / (sum(took) / 1e9 / 13))
    assert 78.0 < share < 82.0
    # the old arithmetic with the window's 13.5 experts a layer (297 a step) over this slice's time
    assert 100.0 * (297 * 11_032_576 / 819e9) / (sum(took) / 1e9 / 13) > 125.0
    # the one piece dispatched inside the head, over the grouped calls of the two prefills it holds
    assert 5.0 < read("glu_moe_prefill_flop_share", ctx) < 25.0
    for name in ("glu_moe_decode_ms", "glu_moe_prefill_share", "short_conv_decode_ms"):
        assert read(name, ctx) > 0.0

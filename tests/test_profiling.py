"""Profiling subsystem (docs/PROFILING.md): compile-stats extraction on
the CPU mesh (pinned keys, monotonic FLOPs with batch), the
InstrumentedJit compile-once / raise-once contract, the headroom downshift
decision under mocked HBM capacities, and the engine's end-to-end
compile-stats surface."""

import jax
import jax.numpy as jnp
import pytest

from kserve_vllm_mini_tpu.profiling.compile_stats import (
    CompileRecorder,
    InstrumentedJit,
    capture_compile_stats,
    extract_compile_stats,
    hlo_op_histogram,
    state_copy_bytes,
    weight_copy_bytes,
)
from kserve_vllm_mini_tpu.profiling.devices import device_facts
from kserve_vllm_mini_tpu.profiling.headroom import (
    estimate_serving_bytes,
    plan_admission,
    serving_headroom_plan,
)


def _matmul_fn():
    return jax.jit(lambda a, b: (a @ b).sum())


# -- compile-stats extraction -------------------------------------------------

def test_capture_pins_stat_keys():
    """The CompileStats record must carry every key downstream consumers
    (artifact, schema, report) read — pinned here so a jax upgrade that
    drops an analysis surfaces as a test failure, not silent zeros."""
    fn = _matmul_fn()
    x = jnp.ones((32, 32))
    compiled, cs = capture_compile_stats(fn, x, x, label="t")
    d = cs.to_dict()
    for key in ("label", "compile_wall_s", "flops", "bytes_accessed",
                "peak_bytes", "argument_bytes", "output_bytes",
                "temp_bytes", "generated_code_bytes", "hlo_ops",
                "mosaic_calls", "weight_copy_bytes", "state_copy_bytes"):
        assert key in d, key
    assert d["label"] == "t"
    assert d["compile_wall_s"] > 0
    assert d["flops"] > 0
    assert d["bytes_accessed"] > 0
    # two f32[32,32] args = 8192 bytes, and they dominate the peak
    assert d["argument_bytes"] == 2 * 32 * 32 * 4
    assert d["peak_bytes"] >= d["argument_bytes"]
    assert d["hlo_ops"].get("dot", 0) >= 1 or d["hlo_ops"].get("fusion", 0) >= 1
    # the compiled executable actually runs and agrees with the jit path
    assert float(compiled(x, x)) == float(fn(x, x))


def test_cost_model_flops_monotonic_with_batch():
    """Doubling the batch must not shrink cost-model FLOPs — the analytic
    invariant the proxy trajectory leans on."""
    fn = jax.jit(lambda a, w: (a @ w).sum())
    w = jnp.ones((64, 64))
    flops = []
    for batch in (2, 8, 32):
        _, cs = capture_compile_stats(fn, jnp.ones((batch, 64)), w)
        flops.append(cs.flops)
    assert flops[0] < flops[1] < flops[2], flops


def test_abstract_lowering_needs_no_weights():
    """ShapeDtypeStruct args compile the same program as concrete arrays
    (identical cost-model FLOPs) — the proxy tier's no-materialize path."""
    fn = _matmul_fn()
    x = jnp.ones((16, 16))
    _, concrete = capture_compile_stats(fn, x, x)
    sds = jax.ShapeDtypeStruct(x.shape, x.dtype)
    _, abstract = capture_compile_stats(fn, sds, sds)
    assert abstract.flops == concrete.flops
    assert abstract.argument_bytes == concrete.argument_bytes


def test_hlo_op_histogram_parses_and_caps():
    text = "\n".join([
        "HloModule m, is_scheduled=true",
        "%main.9 (Arg_0.1: f32[4,4]) -> f32[] {",
        "  %Arg_0.1 = f32[4,4]{1,0} parameter(0)",
        '  %dot.3 = f32[4,4]{1,0} dot(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(x)/dot_general"}',
        "  %t = (f32[2]{0}, f32[3]{0}) tuple(%dot.3, %dot.3)",
        "  ROOT %reduce.8 = f32[] reduce(%dot.3, %c), dimensions={0,1}",
        "}",
    ])
    hist = hlo_op_histogram(text)
    assert hist == {"parameter": 1, "dot": 1, "tuple": 1, "reduce": 1}
    # cap: >top opcodes fold into "other", counts preserved
    many = "\n".join(f"  %x{i} = f32[] op{i}(%a)" for i in range(20))
    capped = hlo_op_histogram(many, top=4)
    assert len(capped) == 5 and capped["other"] == 16


# optimized-HLO lines as a v5e compile prints them (layout, tiling, memory
# space after the shape); the first is PR 30's %copy.69, mistral's wq
_WQ = ("  %copy.69 = s8[1,4096,4096]{1,2,0:T(8,128)(4,1)S(1)} "
       "copy(%constant_dynamic-slice_fusion.6), metadata={}")
_WK = "  %copy.72 = s8[1,4096,1024]{1,2,0:T(8,128)(4,1)S(1)} copy(%constant_dynamic-slice_fusion.7)"
_WV = "  %copy.73 = s8[1,4096,1024]{1,2,0:T(8,128)(4,1)S(1)} copy(%constant_dynamic-slice_fusion.8)"


@pytest.mark.parametrize("lines,want", [
    ([_WQ], 16_777_216),
    # an asynchronous prefetch of a scale is no copy instruction, and no weight
    (["  %copy-start.2 = (f32[32,14336]{1,0:T(8,128)S(1)}, f32[32,14336]{1,0:T(8,128)}, u32[]{:S(2)}) "
      "copy-start(%get-tuple-element.598)",
      "  %copy-done.2 = f32[32,14336]{1,0:T(8,128)S(1)} copy-done(%copy-start.2)"], 0),
    (["  %copy.63 = bf16[16,8,1,128]{3,1,0,2:T(2,128)(2,1)S(1)} copy(%bitcast_multiply_fusion.9)"], 0),
    (["  %copy.5 = s8[1,64,64]{1,2,0:T(8,128)(4,1)} copy(%fusion.2)"], 0),              # under 64 KiB
    (["  %copy.7 = s8[1,8,512,128]{3,2,1,0:T(8,128)(4,1)} copy(%dynamic-slice.3)"], 0),  # int8 K/V: rank 4
    ([_WQ, _WK, _WV], 25_165_824),
    (["  %copy.1 = s4[1,4096,4096]{1,2,0} copy(%f.1)"], 8_388_608),   # two to a byte
    (["  %copy.1 = u8[1,2048,4096]{1,2,0} copy(%f.1)"], 8_388_608),
], ids=["wq", "copy-start-done", "activation", "small", "int8-kv", "wq+wk+wv",
        "packed-int4", "uint8"])
def test_weight_copy_bytes_counts_integer_weight_slices(lines, want):
    text = "\n".join(["HloModule jit_decode, is_scheduled=true", *lines, "}"])
    assert weight_copy_bytes(text) == want


_STATE = "f32[3,16,30,96,192]{4,3,2,1,0:T(8,128)}"
_LAYER = "f32[1,16,30,96,192]{4,3,2,1,0:T(8,128)S(1)}"
_ENTRY = ["ENTRY %main.1 (cache__lin_state__.1: f32[3,16,30,96,192]) -> f32[3,16,30,96,192] {",
          f"  %cache__lin_state__.1 = {_STATE} parameter(47)"]
_ONE = 16 * 30 * 96 * 192 * 4


@pytest.mark.parametrize("body,entry,want", [
    ([f"  ROOT %dynamic_update_slice.26 = {_STATE} dynamic-update-slice(%p, %s, %c, %c)"], [], _ONE),
    ([f"  %dynamic-slice.3 = {_LAYER} dynamic-slice(%p, %i, %c, %c, %c, %c)",
      f"  %slice.2 = {_LAYER} slice(%p), slice={{[1:2], [0:16]}}"], [], 2 * _ONE),
    ([], [f"  %slice-done = {_LAYER} slice-done(%slice-start)",
          f"  %copy.9 = {_STATE} copy(%cache__lin_state__.1)"], 4 * _ONE),
    ([], [f"  %k.9 = ({_STATE}, f32[16,30,192]{{2,1,0}}) custom-call(%c, %v, %cache__lin_state__.1), "
          'custom_call_target="tpu_custom_call"',
          f"  %g.1 = {_STATE} get-tuple-element(%k.9), index=0"], 0),
    ([f"  %param_0.1 = f32[1,30,16,64,96]{{4,3,2,1,0}} parameter(0)",
      f"  %copy.4 = f32[1,30,16,64,96]{{4,3,2,1,0}} copy(%param_0.1)",
      f"  %dus.1 = bf16[3,16,30,2048,128]{{4,3,2,1,0}} dynamic-update-slice(%kv, %u, %c)"], [], 0),
], ids=["write-back", "slices-out", "async-slice+whole-copy", "kernel-in-place", "not-a-state"])
def test_state_copy_bytes_counts_layers_of_a_float32_rank5_parameter(body, entry, want):
    """The stack is known by the entry computation's parameter; a fusion's
    own rank-5 float32 parameter (a prefill's chunk blocks) and a K/V stack
    are not states."""
    text = "\n".join(["HloModule jit_decode, is_scheduled=true",
                      "%fused_computation.38 (param_0.123: f32[3,16,30,96,192]) -> f32[3,16,30,96,192] {",
                      f"  %param_0.123 = {_STATE} parameter(0)", *body, "}", "",
                      *_ENTRY, *entry, "}"])
    assert state_copy_bytes(text) == want
    assert state_copy_bytes(text.replace("ENTRY ", "")) == 0       # no entry, no stack


# -- InstrumentedJit ----------------------------------------------------------

def test_instrumented_jit_compiles_once_per_signature():
    rec = CompileRecorder()
    fn = InstrumentedJit(_matmul_fn(), rec, label="mm")
    x = jnp.ones((8, 8))
    y = jnp.ones((4, 4))
    for _ in range(3):
        out = fn(x, x)
    assert rec.snapshot()["compiles"] == 1
    assert float(out) == float(x.sum() * 8)
    fn(y, y)  # new shape -> one more compile
    snap = rec.snapshot()
    assert snap["compiles"] == 2
    assert snap["compile_s"] > 0
    assert snap["compiled_flops"] > 0
    assert snap["compile_peak_bytes"] > 0
    assert [e.label for e in rec.entries()] == ["mm", "mm"]


def test_instrumented_jit_raises_compile_error_once():
    """A step that cannot compile raises to its caller — no retry through
    plain jit (which would compile the program a second time and turn the
    stats off), nothing recorded, nothing cached for the signature."""
    traces = []

    @jax.jit
    def broken(a):
        traces.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED: mock compile failure")

    rec = CompileRecorder()
    fn = InstrumentedJit(broken, rec, label="broken")
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        fn(jnp.ones((4,)))
    assert len(traces) == 1          # lowered once, not re-tried via jit
    assert rec.snapshot()["compiles"] == 0
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        fn(jnp.ones((4,)))           # still an error, never a silent path


def test_instrumented_jit_preserves_donation():
    """donate_argnums through the AOT path: the donated input buffer is
    consumed exactly like under plain jit."""
    import functools

    rec = CompileRecorder()
    base = functools.partial(jax.jit, donate_argnums=(0,))(lambda c, d: c + d)
    fn = InstrumentedJit(base, rec, label="don")
    c = jnp.ones((128,))
    out = fn(c, jnp.ones((128,)))
    assert float(out[0]) == 2.0
    assert rec.snapshot()["compiles"] == 1
    assert c.is_deleted()  # the donation actually happened


# -- headroom guard -----------------------------------------------------------

def _linear_estimate(per_slot: int, per_ctx: int):
    return lambda slots, ctx: slots * per_slot + ctx * per_ctx


def test_plan_admission_fits_untouched():
    plan = plan_admission(_linear_estimate(10, 1), capacity_bytes=10_000,
                          slots=80, max_seq=512)
    assert plan.fits and plan.downshifted is None
    assert (plan.slots, plan.max_seq) == (80, 512)


def test_plan_admission_downshifts_slots_first():
    # 80*100 + 512 = 8512 > 0.9*6000; 40 slots -> 4512 > 5400? no: fits
    plan = plan_admission(_linear_estimate(100, 1), capacity_bytes=6_000,
                          slots=80, max_seq=512)
    assert plan.fits
    assert plan.slots == 40 and plan.max_seq == 512
    assert "slots 80->40" in plan.downshifted
    assert "ctx" not in plan.downshifted


def test_plan_admission_downshifts_ctx_after_slot_floor():
    # even 8 slots * 100 = 800 plus ctx*10: needs ctx cuts too
    plan = plan_admission(_linear_estimate(100, 10), capacity_bytes=5_000,
                          slots=64, max_seq=2048)
    assert plan.fits
    assert plan.slots == 8
    assert plan.max_seq == 256
    assert "slots 64->8" in plan.downshifted and "ctx 2048->256" in plan.downshifted


def test_plan_admission_reaches_min_slots_floor_from_default():
    """80 -> 40 -> 20 -> 10 -> 8: the last halving clamps TO the floor
    instead of stopping at 10 — a config that fits at 8 slots must be
    admitted there, not escalated to ctx cuts or 'unfittable'."""
    # est(8) = 800 fits the 900 budget; est(10) = 1000 does not
    plan = plan_admission(_linear_estimate(100, 0), capacity_bytes=1_000,
                          slots=80, max_seq=512)
    assert plan.fits
    assert plan.slots == 8 and plan.max_seq == 512
    assert "slots 80->8" in plan.downshifted


def test_plan_admission_ctx_clamps_to_min_seq():
    """Same clamp rule on the context loop: a custom min_seq floor that
    is not a power-of-two divisor is still reachable."""
    plan = plan_admission(_linear_estimate(0, 10), capacity_bytes=3_300,
                          slots=8, max_seq=2048, min_seq=297)
    assert plan.fits
    assert plan.max_seq == 297   # 2048 -> 1024 -> 512 -> max(256, 297)
    assert "ctx 2048->297" in plan.downshifted


def test_plan_admission_reports_unfittable():
    plan = plan_admission(_linear_estimate(10_000, 10_000), capacity_bytes=1_000,
                          slots=8, max_seq=256)
    assert not plan.fits
    assert plan.estimate_bytes > plan.budget_bytes


def test_serving_headroom_plan_mocked_capacities():
    """The real analytic estimator over llama-tiny: a generous mocked HBM
    admits the config as-is; a tight one forces a labeled downshift whose
    admitted shape fits its budget."""
    from kserve_vllm_mini_tpu.models.config import get_config

    v5e_hbm = device_facts("TPU v5 lite").hbm_bytes
    fits = serving_headroom_plan("llama-tiny", 80, 512, "int8", False,
                                 capacity_bytes=v5e_hbm)
    assert fits.fits and fits.downshifted is None
    base = estimate_serving_bytes(
        get_config("llama-tiny", max_seq_len=512), 80, 512, quant="int8",
    )["total_bytes"]
    tight = serving_headroom_plan("llama-tiny", 80, 512, "int8", False,
                                  capacity_bytes=base // 2)
    assert tight.downshifted and tight.slots < 80
    assert tight.estimate_bytes <= tight.budget_bytes
    d = tight.to_dict()
    assert d["downshifted"].startswith("downshifted: ")


def test_estimate_monotonic_in_slots_and_ctx():
    from kserve_vllm_mini_tpu.models.config import get_config

    cfg = get_config("llama-tiny", max_seq_len=1024)
    e = lambda s, c: estimate_serving_bytes(cfg, s, c)["total_bytes"]  # noqa: E731
    assert e(8, 256) < e(16, 256) < e(16, 512) < e(32, 1024)


def test_estimate_prices_w8a8_activation_workspace():
    """quant_mode=w8a8 must cost MORE workspace than dequant at the same
    shape (the int8 activation copy + per-row f32 scales), and the delta
    must scale with slots — the term exists so the guard can never admit
    a shape whose activation-quant transient is the OOM allocation."""
    from kserve_vllm_mini_tpu.models.config import get_config

    cfg = get_config("llama-tiny", max_seq_len=1024)
    deq = estimate_serving_bytes(cfg, 16, 512, quant="int8")
    w8 = estimate_serving_bytes(cfg, 16, 512, quant="int8", quant_mode="w8a8")
    assert w8["weight_bytes"] == deq["weight_bytes"]
    assert w8["kv_bytes"] == deq["kv_bytes"]
    extra = w8["workspace_bytes"] - deq["workspace_bytes"]
    widest = max(cfg.d_ff, cfg.d_model)
    assert extra == 16 * 512 * (widest + 4)
    w8_32 = estimate_serving_bytes(cfg, 32, 512, quant="int8", quant_mode="w8a8")
    deq_32 = estimate_serving_bytes(cfg, 32, 512, quant="int8")
    assert (w8_32["workspace_bytes"] - deq_32["workspace_bytes"]) == 2 * extra


# -- engine surface -----------------------------------------------------------

def test_engine_accumulates_compile_stats():
    """End-to-end: a tiny engine run records its prefill/decode compiles
    with labels, snapshot_stats carries the /metrics keys, and the
    compile_stats_snapshot block is results.json-shaped."""
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import init_params
    from kserve_vllm_mini_tpu.runtime.engine import (
        Engine,
        EngineConfig,
        GenRequest,
    )

    cfg = get_config("llama-tiny", max_seq_len=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg,
                 EngineConfig(max_slots=2, max_seq_len=128, max_prefill_len=64))
    eng.start()
    try:
        h = eng.submit(GenRequest(prompt_tokens=[1, 2, 3], max_new_tokens=4))
        while True:
            ev = h.events.get(timeout=60)
            if ev[0] == "done":
                break
        s = eng.snapshot_stats()
        for key in ("compiles", "compile_s", "compiled_flops",
                    "compiled_bytes", "compile_peak_bytes"):
            assert key in s, key
        assert s["compiles"] >= 2  # one prefill bucket + one decode chunk
        assert s["compile_s"] > 0 and s["compiled_flops"] > 0
        block = eng.compile_stats_snapshot()
        assert block["compiles"] == s["compiles"]
        labels = [e["label"] for e in block["executables"]]
        assert any(lab.startswith("prefill[") for lab in labels)
        assert any(lab.startswith("decode[") for lab in labels)
    finally:
        eng.stop()


def test_engine_compile_stats_can_be_disabled():
    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import init_params
    from kserve_vllm_mini_tpu.runtime.engine import Engine, EngineConfig

    cfg = get_config("llama-tiny", max_seq_len=128)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg,
                 EngineConfig(max_slots=2, max_seq_len=128,
                              max_prefill_len=64, compile_stats=False))
    fn = eng._step("prefill", 16, True)
    assert not isinstance(fn, InstrumentedJit)
    assert eng.snapshot_stats()["compiles"] == 0

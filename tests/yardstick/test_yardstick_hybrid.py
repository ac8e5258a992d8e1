"""The hybrid configuration in the yardstick (PR 28): its weights and plain
reference, the served path against it at the tiny sizes, the lower-precision
control, the new cells' rehearsal, and the four readers of the linear mixer
on a recorded trace and against programs that have no such counters."""

import json
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from bench import check, config, hybrid_roofline, run, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
HEAD = ROOT / "bench" / "testdata" / "trace_head_pr28.json.gz"
CELL = "olmo-hybrid-7b.chat"
NEW = ("linear_decode_ms", "linear_state_hbm_share", "linear_prefill_share",
       "linear_state_held_share")
reference = config.load_module("references", "hybrid_linear_decoder")
system = config.load_module("systems", "engine")
weights = reference.weights


def tiny():
    return config.load_cell(CELL, rehearse=True)["config"]


def full_model():
    return config.load_cell(CELL)["config"]["model"]


def test_configuration_file_holds_the_sources_keys_and_the_byte_table():
    raw = json.loads((ROOT / "bench" / "configs" / "olmo-hybrid-7b-int8.json").read_text())
    source = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
              "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
              "num_key_value_heads": 30, "hidden_act": "silu", "attention_bias": False,
              "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "linear_num_key_heads": 30,
              "linear_num_value_heads": 30, "linear_key_head_dim": 96,
              "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
              "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
    assert {k: raw[k] for k in source} == source
    assert raw["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    assert list(raw["reduced"]) == ["max_position_embeddings"] and raw["max_position_embeddings"] == 2048
    # what only the program names repeats the source's keys, never another value
    extra = raw["model_extra"]
    assert all(raw[k] == v for k, v in extra.items() if k != "block") and extra["block"] == "olmo2"
    assert raw["engine"] == {"quantization": "int8", "kv_cache_dtype": None, "kv_layout": "dense",
                             "max_slots": 16, "max_seq_len": 2048}
    m = full_model()
    # the deployment's byte table: int8 decoder, bf16 embedding and head, one cache tree
    nbytes = weights.kind(m).nbytes
    decoder = sum(sum(nbytes(s) for s in weights.matmul_shapes(m, t).values())
                  for t in m["layer_types"])
    assert abs(decoder - 6.65e9) < 0.03e9
    assert 2 * 2 * m["vocab_size"] * m["hidden_size"] == 1_541_406_720
    kv_token = 8 * 2 * 30 * 128 * 2
    assert kv_token == 122_880 and 16 * 2048 * kv_token == 4_026_531_840
    assert hybrid_roofline.state_bytes_per_slot(m) == 53_084_160
    assert hybrid_roofline.mixer(m) == {"heads": 30, "dk": 96, "dv": 192, "channels": 11520,
                                        "layers": 24}


@pytest.mark.parametrize("quantization", ["int8", "none"])
def test_the_reference_regenerates_the_served_weights_exactly(quantization):
    import jax
    import jax.numpy as jnp
    from functools import partial

    m = {**tiny()["model"], "quantization": quantization}
    big = 2**31 + 5
    served = weights.all_weights(big, m)
    root = weights.root_key(big)
    for kind, stack in (("full_attention", "full"), ("linear_attention", "linear")):
        gen = jax.jit(partial(weights.layer_weights, m=m, layer_type=kind))
        for row, li in enumerate(weights.layers_of(m, kind)):
            one = gen(root, layer=jnp.int32(li))
            stacked = jax.tree_util.tree_map(lambda a: a[row], served["layers"][stack])
            same = jax.tree_util.tree_map(
                lambda a, b: np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32)),
                one, stacked)
            assert all(jax.tree_util.tree_leaves(same)), (kind, li, same)
    lin = served["layers"]["linear"]
    assert lin["conv"].shape == (6, 4, 4 * (2 * 24 + 48)) and lin["A_log"].dtype == jnp.float32
    # small leaves show when they are left out: no gain at 1, no tap at 0, decays spread
    assert float(jnp.mean(jnp.abs(lin["o_norm"].astype(jnp.float32) - 1.0))) > 0.03
    assert float(jnp.std(lin["o_norm"].astype(jnp.float32))) > 0.05
    assert float(jnp.mean(jnp.abs(lin["conv"].astype(jnp.float32)))) > 0.2
    decay = jnp.exp(-jnp.exp(lin["A_log"]) * jax.nn.softplus(lin["dt_bias"]))
    assert 0.4 < float(decay.min()) < 0.8 and float(decay.max()) > 0.95


def serve(cfg, seed, asks):
    s = system.boot(cfg, seed, 1)
    try:
        seqs, first = [], []
        for content, n in asks:
            body = json.dumps({"model": "x", "max_tokens": n, "temperature": 0.0,
                               "messages": [{"role": "user", "content": content}]}).encode()
            req = urllib.request.Request(s.url + "/v1/chat/completions", data=body,
                                         headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                doc = json.loads(resp.read())
            ids = system.served_token_ids(doc["choices"][0]["message"]["content"])
            prompt = system.chat_prompt_ids(s.tokenizer, content)
            assert doc["usage"]["prompt_tokens"] == len(prompt) and len(ids) == n
            seqs.append(prompt + ids)
            first.append(len(prompt))
        return seqs, first
    finally:
        s.stop()


# 650 served positions at the tiny widths, one whole period x 2. Readings of gap_mean
# (CPU, seeds 5-8, 15, 16): sound 0.0035-0.0061, w8a8 0.0327-0.0416; gap_max 0.12-0.26
# against 0.52-0.69. The limit sits 2.4 times over the sound runs' largest and 2.2 times
# under the control's smallest; gap_max is held against a wrong token.
CONTROL_ASKS = [("a" * 20, 180), ("hello yardstick " * 6, 120), ("zulu9 " * 3, 200),
                ("kilo lima 7 " * 5, 150)]
CONTROL_LIMITS = {"gap_mean": 0.015, "gap_max": 1.0}
PADS = {"pad_sequence_to": 256, "pad_rows_to": 64}


def test_served_path_agrees_the_control_does_not_and_altered_tokens_do_not():
    """Prefill and decoding through the cache, served over HTTP, against the
    reference's full forward pass; the program's own w8a8 path in its place;
    then served tokens that are not the model's."""
    numbers = {}
    for mode in ("dequant", "w8a8"):
        cfg = tiny()
        cfg["engine"]["quant_mode"] = mode
        seqs, first = serve(cfg, 15, CONTROL_ASKS)
        numbers[mode], positions = check.served_numbers(15, cfg["model"], seqs, first, PADS)
        assert positions == sum(n for _, n in CONTROL_ASKS)
        if mode == "dequant":
            sound = (seqs, first)
    assert check.within(numbers["dequant"], CONTROL_LIMITS)[0], numbers
    ok, lines = check.within(numbers["w8a8"], CONTROL_LIMITS)
    assert not ok and any("gap_mean" in line and "OVER" in line for line in lines), numbers
    seqs, first = sound
    vocab = tiny()["model"]["vocab_size"]
    wrong = [s[:f] + [(t + 1) % vocab for t in s[f:]] for s, f in zip(seqs, first)]
    broken, _ = check.served_numbers(15, tiny()["model"], wrong, first, PADS)
    assert not check.within(broken, check.limits_for("olmo-hybrid-7b-int8"))[0]


def rehearse_args(cell, seed, trace):
    return run.parse_args(["--workload", cell, "--seed", str(seed), "--seconds", "4",
                           "--trace", str(trace), "--rehearse"])


@pytest.mark.parametrize("cell,trace,metrics", [
    (CELL, 0, {"ttft_p50_ms", "itl_p50_ms", "setup_s"}),
    (CELL, 1, None),
    ("qwen2-7b.decode-sat", 0, {"out_tokens_per_s", "setup_s"}),
])
def test_the_new_cells_rehearse(cell, trace, metrics):
    got = run.run_cell(rehearse_args(cell, 2**31 + 281 + trace, trace))
    assert got["correct"] is True and got["failed"] == 0 and got["attempted"] >= 8
    if metrics is not None:
        assert metrics <= set(got["metrics"])
    else:
        # no device trace on the CPU: the counter's reader reads, the trace's three do not
        assert 0.0 < got["metrics"]["linear_state_held_share"]["value"] < 100.0
        assert not set(NEW[:3]) & set(got["metrics"])
        assert "prefill_stall_share" in got["metrics"] and "gen_late_p95_ms" in got["metrics"]


def cells_of(name):
    return {m["name"]: m for m in BM["end_to_end"] + BM["per_layer"]}[name].get("workloads", [])


def test_benchmark_entries_of_this_pr():
    """What this PR brought is there; nothing here holds a list to its length,
    its order or its last entries, so a later PR appends what it likes."""
    cells = {w["name"]: w for w in BM["workloads"]}
    assert cells[CELL]["config"] == "olmo-hybrid-7b-int8" and cells[CELL]["traffic"] == "chat"
    assert cells["qwen2-7b.decode-sat"]["config"] == "qwen2-7b-int8"
    assert cells["qwen2-7b.decode-sat"]["traffic"] == "decode-sat"
    assert all(c["chips"] == 1 for c in (cells[CELL], cells["qwen2-7b.decode-sat"]))
    for name in NEW:
        assert CELL in cells_of(name)
    # the issue's list for the chat cell, and the saturated cell's
    for name in ("ttft_p50_ms", "itl_p50_ms", "decode_step_ms", "device_idle_share",
                 "flash_prefill_roofline", "prefill_stall_share", "gen_late_p95_ms",
                 "http_overhead_p50_ms", "ttft_p95_ms.rec", "itl_p95_ms.rec"):
        assert CELL in cells_of(name), name
    for name in ("out_tokens_per_s", "decode_step_ms.sat", "decode_hbm_share", "batch_occupancy",
                 "kv_in_use_share", "hbm_peak_share", "device_idle_share.sat", "ttft_p95_ms.sat"):
        assert "qwen2-7b.decode-sat" in cells_of(name), name
    params = json.loads((ROOT / "bench" / "workloads" / f"{CELL}.json").read_text())
    assert params["rate_rps"] == pytest.approx(0.8 * params["knee_rps"], abs=0.051)
    assert len(params["sweep"]["rows"]) >= 4


# -- the readers ---------------------------------------------------------------

def ctx(counters, trace=None, model=None):
    return run.Context(cell=None, records=[], window_s=45.0, run_end_s=47.0, setup_s=30.0,
                       counters=counters, memory={"model": model or full_model()}, trace=trace,
                       device_kind="TPU v5 lite")


COUNTERS = {   # a chat window: ~1,800 decode steps of 16 slots, ~6 live
    "kvmini_tpu_decode_steps_total": 1800.0,
    "kvmini_tpu_linear_state_slot_steps_total": 28800.0,
    "kvmini_tpu_linear_state_held_slot_steps_total": 18000.0,
}


def test_shape_rules_name_the_mixers_operations_and_nothing_else():
    m = full_model()
    state = ("jit_decode/%select_dynamic-update-slice_fusion.8 = f32[24,16,30,96,192] "
             "fusion(f32[24,16,30,96,192] %select")
    reads = ("jit_decode/%multiply_reduce_fusion.52 = (f32[16,30,192], f32[16,30,192]) "
             "fusion(f32[24,16,30,96,192] %selec")
    loop = ("jit_decode/%while.13 = (s32[], bf16[16,1,3840], bf16[8,16,30,2048,128], "
            "bf16[24,16,3,11520], f32[24,16,30,9")
    mlp = "jit_decode/%fusion.788 = bf16[16,11008] fusion(s8[24,3840,11008] %get-tuple-element.2146, s32[] %select_n.4"
    flash = "jit_prefill/%kvmini_flash_prefill.8 = bf16[1,30,1024,128] custom-call(bf16[1,30,1024,128] %pad_maximum_fusio"
    proj = "jit_prefill/%fusion.12 = bf16[1,1024,2880] fusion(s8[24,3840,2880] %get-tuple-element.1, bf16[1,1024,3840] %x"
    conv = "jit_prefill/%fusion.13 = f32[1,1024,11520] fusion(bf16[1,1027,11520] %concatenate.3, bf16[4,11520] %y"
    chunk = "jit_prefill/%fusion.14 = f32[1,30,16,64,64] fusion(f32[1,30,16,64,96] %a, f32[1,30,16,64,96] %b)"
    assert hybrid_roofline.shapes_in(reads) == [(16, 30, 192), (16, 30, 192), (24, 16, 30, 96, 192)]
    assert [hybrid_roofline.touches_state(n, m) for n in (state, reads, loop, mlp, chunk)] == [
        True, True, False, False, False]
    assert [hybrid_roofline.in_linear_scan(n, m) for n in (conv, chunk, state, proj, flash, mlp, loop)] == [
        True, True, True, False, False, False, False]


def test_held_share_from_counters_and_nothing_without_them():
    reader = config.load_module("readers", "linear_state_held_share")
    assert reader.read(ctx(COUNTERS)) == pytest.approx(62.5)
    assert reader.read(ctx({})) is None                                  # the parent's program
    assert reader.read(ctx({**COUNTERS, "kvmini_tpu_linear_state_slot_steps_total": 0.0})) is None


@pytest.mark.parametrize("name", NEW[:3])
def test_trace_readers_read_nothing_without_a_trace_or_without_linear_layers(name):
    reader = config.load_module("readers", name)
    assert reader.read(ctx(COUNTERS)) is None
    dense = config.load_cell("mistral-7b.chat")["config"]["model"]
    trace = trace_reduce.reduce(trace_reduce.load_events(HEAD))
    assert reader.read(ctx(COUNTERS, trace, model=dense)) is None
    old = trace_reduce.reduce(trace_reduce.load_events(HEAD.with_name("trace_head.json.gz")))
    assert reader.read(ctx(COUNTERS, old)) is None        # a dense model's trace: no such shapes


def test_the_four_readers_on_the_recorded_trace():
    """0.2 s of olmo-hybrid-7b.chat on the v5e around one long prefill (PR 28)."""
    trace = trace_reduce.reduce(trace_reduce.load_events(HEAD))
    assert len(trace["programs"]["jit_decode"]) >= 3 and trace["programs"]["jit_prefill"]
    c = ctx(COUNTERS, trace)
    got = {name: config.load_module("readers", name).read(c) for name in NEW}
    step = 1000.0 * sorted(trace["programs"]["jit_decode"])[len(trace["programs"]["jit_decode"]) // 2]
    assert 2.0 < got["linear_decode_ms"] < 0.4 * step            # the state: 3-6 ms of a ~23 ms step
    # 6 live slots' state, read and written once, over what the state's operations took
    need_ms = 2 * 6.0 * 53_084_160 / 819e9 * 1000.0
    assert got["linear_state_hbm_share"] == pytest.approx(100.0 * need_ms / got["linear_decode_ms"])
    assert 5.0 < got["linear_state_hbm_share"] < 100.0
    assert 5.0 < got["linear_prefill_share"] < 60.0
    assert got["linear_state_held_share"] == pytest.approx(62.5)
    # all 16 slots live: still under 100, because the operations move every slot's state
    full = {**COUNTERS, "kvmini_tpu_linear_state_held_slot_steps_total": 0.0}
    share = config.load_module("readers", "linear_state_hbm_share").read(ctx(full, trace))
    assert share < 100.0 and run.collect(
        [{"name": "linear_state_hbm_share", "unit": "%"}], ctx(full, trace))
    # the state's operations are exactly the ones the rule names, none of them a loop
    named = [n for n in trace["ops"] if n.startswith("jit_decode/")
             and hybrid_roofline.touches_state(n, full_model())]
    assert named and all("96,192]" in n and "%while" not in n for n in named)

"""The two-mixer configuration in the yardstick (PR 35): its entries and files,
the plain reference against a float64 computation by hand of a block, the cell
rehearsed end to end, the served path against the reference with the program's
lower precision and altered tokens as controls, and the three new trace readers
against a recorded slice of the cell on the chip."""

import json
import math
from functools import partial
from pathlib import Path

import numpy as np
import pytest

from bench import check, config, parallel_hybrid_roofline as rl, run, trace_reduce
from tests.yardstick.test_yardstick_correct import serve

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
HEAD = ROOT / "bench" / "testdata" / "trace_head_pr35.json.gz"
CONFIG = "falcon-h1-34b-int8"
CELL = "falcon-h1-34b.decode-sat"
READERS = ("pmix_ssm_decode_ms", "pmix_ssm_state_hbm_share", "pmix_mixer_step_share")
SAT_LISTS = ("batch_occupancy", "ttft_p95_ms.sat", "kv_in_use_share", "hbm_peak_share",
             "decode_step_ms.sat", "device_idle_share.sat", "admit_overhead_ms.sat",
             "host_exposed_share.sat", "sched_stall_ms.sat", "decode_kv_read_share.sat",
             "sampler_filter_share.sat")
reference = config.load_module("references", "parallel_hybrid_decoder")
weights = reference.weights
system = config.load_module("systems", "engine")

# the catalog row's `config`, every key (model-configs guide, architectures.jsonl)
CATALOG = {
    "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None, "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "model_type": "falcon_h1",
    "num_attention_heads": 20, "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120,
}


def tiny():
    return config.load_cell(CELL, rehearse=True)["config"]


def full_model():
    return config.load_cell(CELL)["config"]["model"]


def cells_of(name):
    return {m["name"]: m for m in BM["end_to_end"] + BM["per_layer"]}[name].get("workloads", [])


def test_benchmark_entries_of_this_pr():
    cells = {w["name"]: w for w in BM["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"], cells[CELL]["chips"]) == (
        CONFIG, "decode-sat", 1)
    entry = {c["name"]: c for c in BM["configs"]}[CONFIG]
    assert set(entry["reduced"]) == {"num_hidden_layers", "max_position_embeddings"}
    assert entry["source"] == ("https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/"
                               "blob/main/config.json")
    for name in ("out_tokens_per_s",) + SAT_LISTS + READERS:
        assert CELL in cells_of(name), name
    assert CELL not in cells_of("decode_hbm_share")      # its bytes count no state
    for name in READERS:
        spec = {m["name"]: m for m in BM["per_layer"]}[name]
        assert (spec["workloads"], spec["moves"], spec["source"]) == (
            [CELL], "out_tokens_per_s", "device_trace")
    params = json.loads((ROOT / "bench" / "workloads" / f"{CELL}.json").read_text())
    assert params["clients"] == 32 == 2 * config.load_cell(CELL)["config"]["engine"]["max_slots"]


def test_configuration_file_holds_the_sources_keys_the_cut_and_the_byte_table():
    raw = json.loads((ROOT / "bench" / "configs" / f"{CONFIG}.json").read_text())
    changed = {"num_hidden_layers": 12, "max_position_embeddings": 2048}
    assert {k: raw[k] for k in CATALOG} == {**CATALOG, **changed}      # every width as published
    assert list(raw["reduced"]) == list(changed)
    assert raw["published"]["num_hidden_layers"] == CATALOG["num_hidden_layers"] == 72
    assert raw["published"]["max_position_embeddings"] == CATALOG["max_position_embeddings"]
    assert raw["engine"] == {"quantization": "int8", "kv_cache_dtype": None, "kv_layout": "dense",
                             "max_slots": 16, "max_seq_len": 2048}
    # what only the program names repeats the file's keys under the program's names
    extra = raw["model_extra"]
    assert extra["hybrid_override_pattern"] == "P" * raw["num_hidden_layers"]
    assert (extra["mamba_num_heads"], extra["mamba_head_dim"], extra["ssm_state_size"],
            extra["n_groups"], extra["conv_kernel"], extra["chunk_size"]) == (
        raw["mamba_n_heads"], raw["mamba_d_head"], raw["mamba_d_state"], raw["mamba_n_groups"],
        raw["mamba_d_conv"], raw["mamba_chunk_size"])
    multipliers = [k for k in CATALOG if k.endswith(("_multiplier", "_multipliers"))]
    assert len(multipliers) == 9 and all(extra[k] == raw[k] for k in multipliers)
    assert sum(len(v) if isinstance(v, list) else 1 for v in (raw[k] for k in multipliers)) == 14
    tiny_extra = raw["rehearse"]["model_extra"]
    assert tiny_extra["hybrid_override_pattern"] == "P" * raw["rehearse"]["num_hidden_layers"]
    assert any("std 0.02 divided" in a for a in raw["assumed"])
    assert any("torch_dtype" in a for a in raw["assumed"]) and "six" in raw["deployment"].lower()
    # the byte table of the file's `deployment`
    m = full_model()
    assert weights.matmul_shapes(m) == {
        "in_proj": (5120, 9248), "out_proj": (4096, 5120), "wq": (5120, 2560), "wk": (5120, 512),
        "wv": (5120, 512), "wo": (2560, 5120), "w_gate": (5120, 21504), "w_up": (5120, 21504),
        "w_down": (21504, 5120)}
    assert sum(i * o for i, o in weights.matmul_shapes(m).values()) == 430_080_000
    assert weights.block_nbytes(m) == 430_364_800
    assert rl.state_bytes_per_slot(m) == 12 * 32 * 128 * 256 * 4 == 12 * 4_194_304
    assert rl.mixer_weight_bytes_per_layer(m) == sum(
        i * o + 4 * o for i, o in rl.mixer_weight_shapes(m).values()) == 99_870_848
    kv_slot_layer = 2048 * 4 * 128 * 2 * 2
    total = 12 * (weights.block_nbytes(m) + 16 * (kv_slot_layer + 4_194_304 + 3 * 5120 * 2)) \
        + 2 * 261120 * 5120 * 2
    assert 0.70 < total / 16_909_336_064 < 0.73
    cfg = system.model_config(m)
    assert (cfg.n_layers, cfg.head_dim, cfg.rotary_dim, cfg.block_mixers) == (12, 128, 128, 2)
    assert cfg.explicit_head_dim == 128 and cfg.rope_theta == 1e11


def by_hand(seed, m, seq):
    """One pass of the whole (tiny) model in float64 numpy, position by position,
    head by head and state column by state column: nothing shared with
    bench/references/parallel_hybrid_decoder.py but the weights."""
    import jax
    import jax.numpy as jnp

    root = weights.root_key(seed)
    gen_layer = jax.jit(partial(weights.layer_weights, m=m))
    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa: E731
    top = {k: f64(v) for k, v in jax.jit(partial(weights.top_weights, m=m))(root).items()}
    z = weights.sizes(m)
    nh, p_, n, g, di = z["heads"], z["head_dim"], z["state"], z["groups"], z["d_inner"]
    hd, qh, kvh = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    s_mul, (g_mul, d_mul) = m["ssm_multipliers"], m["mlp_multipliers"]

    def norm(x, gain):
        return x / math.sqrt(float(np.mean(x * x)) + eps) * gain

    def silu(x):
        return x / (1.0 + np.exp(-x))

    def rope(v, pos):
        out = v.copy()
        for i in range(hd // 2):
            ang = pos / (theta ** (2 * i / hd))
            a, b = v[i], v[i + hd // 2]
            out[i] = a * math.cos(ang) - b * math.sin(ang)
            out[i + hd // 2] = b * math.cos(ang) + a * math.sin(ang)
        return out

    xs = [top["embed"][t] * m["embedding_multiplier"] for t in seq]
    for li in range(m["num_hidden_layers"]):
        w = jax.tree_util.tree_map(f64, gen_layer(root, layer=jnp.int32(li)))
        mat = {k: w[k]["q"] * w[k]["s"][None, :] for k in weights.matmul_shapes(m)}
        hs = [norm(x, w["norm_in"]) for x in xs]
        # state-space mixer
        pre, state, mixed = [], np.zeros((nh, p_, n)), []
        for t, h in enumerate(hs):
            u = (h * m["ssm_in_multiplier"]) @ mat["in_proj"]
            u[:di] *= s_mul[0]
            u[di:2 * di] *= s_mul[1]
            u[2 * di:2 * di + g * n] *= s_mul[2]
            u[2 * di + g * n:2 * di + 2 * g * n] *= s_mul[3]
            u[2 * di + 2 * g * n:] *= s_mul[4]
            pre.append(u[di:di + z["channels"]])
            taps = w["conv"]
            conv = sum(taps[-1 - j] * pre[t - j] for j in range(taps.shape[0]) if t - j >= 0)
            xbc = silu(conv + w["conv_b"])
            dt = np.log1p(np.exp(u[di + z["channels"]:] + w["dt_bias"]))
            y = np.zeros((nh, p_))
            for head in range(nh):
                grp = head // (nh // g)
                x_h = xbc[head * p_:(head + 1) * p_]
                b_h = xbc[di + grp * n:di + (grp + 1) * n]
                c_h = xbc[di + g * n + grp * n:di + g * n + (grp + 1) * n]
                state[head] = (math.exp(dt[head] * -math.exp(w["A_log"][head])) * state[head]
                               + dt[head] * np.outer(x_h, b_h))
                y[head] = state[head] @ c_h + w["D"][head] * x_h
            gated = y.reshape(di) * silu(u[:di])
            parts = [norm(gated[k * di // g:(k + 1) * di // g], 1.0) for k in range(g)]
            mixed.append((np.concatenate(parts) * w["norm"]) @ mat["out_proj"]
                         * m["ssm_out_multiplier"])
        # attention on the same normed input
        qs, ks, vs = [], [], []
        for t, h in enumerate(hs):
            a = h * m["attention_in_multiplier"]
            q, k, v = a @ mat["wq"], (a @ mat["wk"]) * m["key_multiplier"], a @ mat["wv"]
            qs.append([rope(q[i * hd:(i + 1) * hd], t) for i in range(qh)])
            ks.append([rope(k[i * hd:(i + 1) * hd], t) for i in range(kvh)])
            vs.append([v[i * hd:(i + 1) * hd] for i in range(kvh)])
        new = []
        for t, x in enumerate(xs):
            heads = []
            for i in range(qh):
                kv = i // (qh // kvh)
                sc = np.array([qs[t][i] @ ks[j][kv] / math.sqrt(hd) for j in range(t + 1)])
                pr = np.exp(sc - sc.max())
                pr /= pr.sum()
                heads.append(sum(pr[j] * vs[j][kv] for j in range(t + 1)))
            attended = np.concatenate(heads) @ mat["wo"] * m["attention_out_multiplier"]
            x = x + mixed[t] + attended
            gn = norm(x, w["mlp_norm"])
            up = (gn @ mat["w_up"]) * silu((gn @ mat["w_gate"]) * g_mul)
            new.append(x + (up @ mat["w_down"]) * d_mul)
        xs = new
    return np.stack([norm(x, top["final_norm"]) @ top["lm_head"].T * m["lm_head_multiplier"]
                     for x in xs])


def test_reference_against_float64_by_hand(monkeypatch):
    m = {**tiny()["model"], "num_hidden_layers": 2, "attention_in_multiplier": 0.8}
    seq = [int(t) for t in np.random.default_rng(3).integers(3, m["vocab_size"], 24)]
    want = by_hand(11, m, seq)
    got = reference.logits_after(11, m, [seq], [1], 32, 32)[0]
    assert got.shape == (23, m["vocab_size"])
    assert np.max(np.abs(got - want[:-1])) < 2e-5 * np.max(np.abs(want))
    # a multiplier left out of the reference is a different function: the check sees it
    # (the weights kept as drawn: their scales follow the configuration's multipliers)
    monkeypatch.setattr(weights, "std_of", lambda _, drawn=weights.std_of(m): drawn)
    for key in ("key_multiplier", "ssm_out_multiplier", "embedding_multiplier"):
        wrong = reference.logits_after(11, {**m, key: 1.0}, [seq], [1], 32, 32)[0]
        assert np.max(np.abs(wrong - want[:-1])) > 1e-2 * np.max(np.abs(want)), key


def rehearse_args(seed, trace):
    return run.parse_args(["--workload", CELL, "--seed", str(seed), "--seconds", "4",
                           "--trace", str(trace), "--rehearse"])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses(trace):
    """The harness end to end at the tiny sizes, on a seed past 32 signed bits."""
    got = run.run_cell(rehearse_args(2**31 + 351 + trace, trace))
    assert got["failed"] == 0 and got["attempted"] >= 8 and got["correct"] is True
    if not trace:
        assert {"out_tokens_per_s", "setup_s"} <= set(got["metrics"])
    else:
        # no device trace on the CPU: the counters' readers read, the trace's three do not
        assert {"batch_occupancy", "kv_in_use_share", "ttft_p95_ms.sat", "admit_overhead_ms.sat",
                "host_exposed_share.sat", "sampler_filter_share.sat"} <= set(got["metrics"])
        assert not set(READERS) & set(got["metrics"])


# 650 served positions at the tiny widths (three blocks, hidden 128; the logits' std is
# 0.23, so the gaps are small numbers). Readings (CPU, jax 0.9.0, seeds 5, 6, 7, 15, 16):
# gap_mean sound 2.7e-5 to 4.8e-5, w8a8 1.85e-4 to 2.58e-4; the limit is the geometric mean
# of the sound runs' largest and the control's smallest, 1.95 times over the one and 1.97
# under the other. gap_max: sound 0.0038-0.0092, w8a8 0.0158-0.0211; held, at five times
# the sound runs' largest, against tokens that are not the model's (0.4 and more).
CONTROL_ASKS = [("a" * 20, 180), ("hello yardstick " * 6, 120), ("zulu9 " * 3, 200),
                ("kilo lima 7 " * 5, 150)]
PADS = {"pad_sequence_to": 256, "pad_rows_to": 64}
CONTROL_LIMITS = {"gap_mean": 9.4e-5, "gap_max": 0.05}


def _numbers(seed, mode):
    cfg = tiny()
    cfg["engine"]["quant_mode"] = mode
    seqs, first = serve(cfg, seed, CONTROL_ASKS)
    nums, positions = check.served_numbers(seed, cfg["model"], seqs, first, PADS)
    assert positions == sum(n for _, n in CONTROL_ASKS)
    return nums, seqs, first


def test_served_path_agrees_the_control_does_not_and_altered_tokens_do_not():
    """Prefill and decoding through the cache, served over HTTP, against the
    reference's full forward pass; the program's own w8a8 path in its place;
    then served tokens that are not the model's."""
    sound, seqs, first = _numbers(6, "dequant")
    assert check.within(sound, CONTROL_LIMITS)[0], sound
    lower, _, _ = _numbers(6, "w8a8")
    ok, lines = check.within(lower, CONTROL_LIMITS)
    assert not ok and any("gap_mean" in line and "OVER" in line for line in lines), lower
    vocab = tiny()["model"]["vocab_size"]
    wrong = [s[:f] + [(t + 1) % vocab for t in s[f:]] for s, f in zip(seqs, first)]
    broken, _ = check.served_numbers(6, tiny()["model"], wrong, first, PADS)
    assert not check.within(broken, CONTROL_LIMITS)[0]
    assert not check.within(broken, check.limits_for(CONFIG))[0]


# -- the readers ---------------------------------------------------------------

def record(first, done):
    """A request that held a slot from ``first`` to ``done`` (seconds of the window)."""
    return {"token_s": [first, (first + done) / 2, done], "done_s": done}


def ctx(trace=None, model=None, records=None):
    return run.Context(cell=None, records=records if records is not None else FULL_SLOTS,
                       window_s=45.0, run_end_s=47.0, setup_s=30.0, counters={},
                       memory={"model": model or full_model()}, trace=trace,
                       device_kind="TPU v5 lite")


# 16 requests that hold their slots over the whole traced slice (21.0-24.0 s of 45)
FULL_SLOTS = [record(5.0 + i, 40.0) for i in range(16)]


def test_the_traced_slice_is_the_one_bench_run_traces():
    assert rl.TRACE_SECONDS == run.TRACE_SECONDS == 3.0
    assert rl.live_slots_in_traced_slice(FULL_SLOTS, 45.0) == pytest.approx(16.0)
    # 8 of the 16 end at the slice's middle, none replaces them: 12 on average
    half = [record(5.0, 22.5 if i < 8 else 40.0) for i in range(16)]
    assert rl.live_slots_in_traced_slice(half, 45.0) == pytest.approx(12.0, abs=0.1)
    # what happens outside the slice does not count, however full the window was
    assert rl.live_slots_in_traced_slice([record(1.0, 20.9), record(24.1, 44.0)], 45.0) == 0.0
    assert rl.live_slots_in_traced_slice([], 45.0) is None


def test_shape_rules_name_the_mixers_operations_and_nothing_else():
    m = full_model()
    state = ("jit_decode/%select_dynamic-update-slice_fusion.3 = f32[12,16,32,128,256] "
             "fusion(f32[12,16,32,128,256] %cache")
    read = "jit_decode/%fusion.77 = f32[16,32,128] fusion(f32[12,16,32,128,256] %get-tuple-element.9, f32[16,"
    in_proj = "jit_decode/%fusion.12 = bf16[16,9248] fusion(s8[12,5120,9248] %get-tuple-element.1, bf16[16,5120] %x"
    out_proj = "jit_decode/%fusion.13 = bf16[16,5120] fusion(s8[12,4096,5120] %get-tuple-element.2, bf16[16,4096] %y"
    wq = "jit_decode/%fusion.14 = bf16[16,1,2560] fusion(s8[12,5120,2560] %get-tuple-element.3, s32[] %c"
    wk = "jit_decode/%fusion.15 = bf16[16,1,512] fusion(bf16[16,1,5120] %h, s8[12,5120,512] %custom-call.99"
    wo = "jit_decode/%fusion.16 = bf16[16,1,5120] fusion(s8[12,2560,5120] %get-tuple-element.5, bf16[16,2560]"
    attn = "jit_decode/%kvmini_dense_decode.24 = bf16[16,4,5,128] custom-call(s32[16] %c, bf16[16,4,5,128] %q, bf16[12,16,4,2048,128]"
    gate = "jit_decode/%fusion.20 = bf16[16,1,21504] fusion(s8[12,5120,21504] %get-tuple-element.7, bf16[16,1,5120]"
    down = "jit_decode/%fusion.21 = bf16[16,1,5120] fusion(s8[12,21504,5120] %get-tuple-element.8, bf16[16,21504]"
    head = "jit_decode/%convert_subtract_fusion = f32[16,261120] fusion(bf16[261120,5120] %lm_head, bf16[16,5120] %x"
    loop = ("jit_decode/%while.13 = (s32[], bf16[16,1,5120], f32[12,16,32,128,256], s8[12,5120,9248], "
            "bf16[12,16,3,5120]")
    ops = (state, read, in_proj, out_proj, wq, wk, wo, attn, gate, down, head, loop)
    assert [rl.touches_state(n, m) for n in ops] == [True, True] + [False] * 10
    assert [rl.reads_mixer_weight(n, m) for n in ops] == [False] * 2 + [True] * 5 + [False] * 5
    assert [rl.in_mixers(n, m) for n in ops] == [True] * 8 + [False] * 4
    for cell in ("mistral-7b.chat", "olmo-hybrid-7b.chat", "nemotron-3-super-ep4.decode-sat"):
        assert rl.sizes(config.load_cell(cell)["config"]["model"]) is None


@pytest.mark.parametrize("name", READERS)
def test_trace_readers_read_nothing_without_a_trace_or_without_such_blocks(name):
    """Against the parent's program, another model's trace or no trace at all a
    reader returns nothing and raises nothing: the line leaves the metric out."""
    reader = config.load_module("readers", name)
    assert reader.read(ctx()) is None
    assert reader.read(ctx({"programs": {}, "ops": {}, "busy_s": 0.0, "window_s": 0.0})) is None
    for head, cell in (("trace_head_pr32.json.gz", "nemotron-3-super-ep4.decode-sat"),
                       ("trace_head_pr28.json.gz", "olmo-hybrid-7b.chat"),
                       ("trace_head.json.gz", "mistral-7b.chat")):
        trace = trace_reduce.reduce(trace_reduce.load_events(HEAD.with_name(head)))
        other = config.load_cell(cell)["config"]["model"]
        assert reader.read(ctx(trace, model=other)) is None       # a model without such blocks
        assert reader.read(ctx(trace)) is None                    # a trace without such shapes


def test_the_readers_on_the_recorded_trace():
    """0.12 s of falcon-h1-34b.decode-sat on the v5e, 16 of 16 slots live (PR 35,
    seed 3500000102): seven decode steps of 15.3 ms."""
    m = full_model()
    trace = trace_reduce.reduce(trace_reduce.load_events(HEAD))
    steps = trace["programs"]["jit_decode"]
    assert len(steps) == 7
    step_ms = 1000.0 * sorted(steps)[len(steps) // 2]
    assert 14.0 < step_ms < 17.0
    c = ctx(trace)
    got = {name: config.load_module("readers", name).read(c) for name in READERS}
    # a layer's update (read and write in place, 0.20 ms) and its read for S C (0.09 ms), twelve times
    assert 3.3 < got["pmix_ssm_decode_ms"] < 3.8
    # 16 live slots' state read once and written once, over what the state's operations took
    need_ms = 2 * 16.0 * 12 * 4_194_304 / 819e9 * 1000.0
    assert got["pmix_ssm_state_hbm_share"] == pytest.approx(100.0 * need_ms / got["pmix_ssm_decode_ms"])
    assert 50.0 < got["pmix_ssm_state_hbm_share"] < 60.0
    # fewer live slots in the slice read lower, never over: the operations move every slot's state
    half = ctx(trace, records=[record(5.0, 22.5 if i < 8 else 40.0) for i in range(16)])
    assert config.load_module("readers", "pmix_ssm_state_hbm_share").read(half) == pytest.approx(
        0.75 * got["pmix_ssm_state_hbm_share"], rel=0.01)
    assert run.collect([{"name": "pmix_ssm_state_hbm_share", "unit": "%"}], c)
    assert 33.0 < got["pmix_mixer_step_share"] < 40.0
    mixers_ms = 1000.0 * rl.decode_seconds_and_steps(trace, m, rl.in_mixers)[0] / len(steps)
    assert got["pmix_ssm_decode_ms"] < mixers_ms < 0.45 * step_ms
    # the rules name leaf operations only: the state's 24 a step, in_proj's, out_proj's and
    # wq's 12 each, the kernel's 12; wk and wv read through their prefetched stack
    ops = [n for n in trace["ops"] if n.startswith("jit_decode/")]
    state = [n for n in ops if rl.touches_state(n, m)]
    assert len(state) == 24 and all("32,128,256]" in n and "%while" not in n for n in state)
    by_stack = {name: [n for n in ops if f"s8[12,{i},{o}]" in n and rl.reads_mixer_weight(n, m)
                       and "fusion." in n.split(" = ")[0]]
                for name, (i, o) in rl.mixer_weight_shapes(m).items()}
    # wk and wv share a shape (12 fusions each); wo's weight is past the cut of its name
    assert {k: len(v) for k, v in by_stack.items()} == {
        "in_proj": 12, "out_proj": 12, "wq": 12, "wk": 24, "wv": 24, "wo": 0}
    assert sum(1 for n in ops if n.split("/")[1].startswith("%" + rl.ATTENTION_KERNEL)) == 12
    # nothing of the MLP or of lm_head is taken for a mixer's
    assert not any(rl.in_mixers(n, m) for n in ops if "21504" in n or "261120" in n)

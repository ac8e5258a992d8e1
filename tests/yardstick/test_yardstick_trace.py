"""The trace reduction on the small recorded trace, and the rule that a share
over 100% fails the run."""

from pathlib import Path

import pytest

from bench import roofline, run, trace_reduce

HEAD = Path(__file__).resolve().parents[2] / "bench" / "testdata" / "trace_head.json.gz"


def ev(plane, line, name, start_ms, dur_ms):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_ms * 1e6, "dur_ns": dur_ms * 1e6}


def hand_trace():
    dev, ops, mods = "/device:TPU:0", trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE
    return [
        ev(dev, mods, "jit_decode(1)", 0, 20), ev(dev, mods, "jit_decode(1)", 30, 22),
        ev(dev, mods, "jit_prefill(2)", 60, 40),
        ev(dev, ops, "%while.1 = bf16[16]{0} while()", 0, 20),
        ev(dev, ops, "%fusion.1 = bf16[16]{0:T(8)} fusion()", 0, 12),
        ev(dev, ops, "%fusion.2 = bf16[16]{0} fusion()", 12, 6),
        ev(dev, ops, "%while.1 = bf16[16]{0} while()", 30, 22),
        ev(dev, ops, "%fusion.1 = bf16[16]{0:T(8)} fusion()", 30, 22),
        ev(dev, ops, "%closed_call.10 = bf16[1,32,1024,128]{3,2,1,0} custom-call(x)", 60, 40),
        ev("/host:CPU", "python3", "kvmini.decode_retire", 19, 10),
        ev("/host:CPU", "python3", "kvmini.decode_dispatch", 52, 3),
        ev("/host:CPU", "python3", "other", 0, 100),
    ]


def test_reduce_hand_trace():
    r = trace_reduce.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.020 + 0.022 + 0.040)
    assert r["programs"]["jit_decode"] == pytest.approx([0.020, 0.022])
    ops = dict(r["breakdown"]["device_ops"])
    # self time: the while holds 20 ms, its two fusions 18 of them
    assert ops["jit_decode/%while.1 = bf16[16] while()"] == pytest.approx(0.002)
    assert ops["jit_decode/%fusion.1 = bf16[16] fusion()"] == pytest.approx(0.034)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["kvmini.decode_retire", pytest.approx(0.010)]
    assert gaps[1] == ["other-host", pytest.approx(0.008)]
    key = "jit_prefill/%closed_call.10 = bf16[1,32,1024,128] custom-call(x)"
    assert r["ops"][key] == {"seconds": pytest.approx(0.040), "calls": 1}
    assert roofline.output_shape(key) == (1, 32, 1024, 128)


def test_no_device_plane_reads_nothing():
    r = trace_reduce.reduce([e for e in hand_trace() if e["plane"].startswith("/host")])
    assert r["busy_s"] == 0.0 and r["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_recorded_trace():
    """A quarter second of mistral-7b.chat on the v5e (PR 25)."""
    r = trace_reduce.reduce(trace_reduce.load_events(HEAD))
    assert 0.0 < r["busy_s"] <= r["window_s"] <= 0.5
    steps = r["programs"]["jit_decode"]
    assert len(steps) >= 5 and 0.015 < steps[len(steps) // 2] < 0.030
    assert len(r["breakdown"]["device_ops"]) == 10
    assert all(name.startswith("jit_") for name, _ in r["breakdown"]["device_ops"])
    assert {name for name, _ in r["breakdown"]["idle_gaps"]} <= {
        "kvmini.decode_dispatch", "kvmini.decode_retire", "other-host"}
    assert sum(s for _, s in r["breakdown"]["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9


def test_a_share_over_100_fails_the_run(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "read_metric", lambda name, ctx: 104.0)
    with pytest.raises(RuntimeError, match="> 100%"):
        run.collect([{"name": "x_roofline", "unit": "%"}], None)
    assert run.collect([{"name": "x_ms", "unit": "ms"}], None) == {
        "x_ms": {"value": 104.0, "unit": "ms"}}
    monkeypatch.setattr(run, "read_metric", lambda name, ctx: None)
    assert run.collect([{"name": "x_ms", "unit": "ms"}], None) == {}


def test_roofline_arithmetic():
    m = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 32,
         "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
         "vocab_size": 32000, "quantization": "int8", "reference": "dense_decoder"}
    assert roofline.kv_bytes_per_token(m) == 131072
    # 32 layers x 218.1 M int8 weights + scales, and the bf16 lm_head
    assert roofline.decode_step_bytes(m, 0) == pytest.approx(7.25e9, rel=0.01)
    assert roofline.decode_step_bytes(m, 1000) - roofline.decode_step_bytes(m, 0) == 131072000
    # unquantized: 2 bytes a weight, no scales
    assert roofline.decode_step_bytes({**m, "quantization": "none"}, 0) == pytest.approx(
        32 * 218.1e6 * 2 + 32000 * 4096 * 2, rel=0.001)
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.flash_prefill_min_s(m, 1024, pk) == pytest.approx(
        2 * 32 * 1024 * 1024 * 128 / 197e12)          # compute-bound at 1024
    assert roofline.flash_prefill_min_s(m, 32, pk) == pytest.approx(
        2 * (64 + 16) * 32 * 128 / 819e9)              # memory-bound at 32

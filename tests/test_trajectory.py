"""Perf trajectory (analysis/trajectory.py): round classification over
synthetic BENCH jsons (driver wrapper AND bare-line shapes), the
real/proxy series split, regression deltas vs the same-series anchor,
and the report's "Perf trajectory" rendering. JAX-free."""

import json

from kserve_vllm_mini_tpu.analysis.trajectory import (
    build_trajectory,
    load_round,
    load_rounds,
    render_table,
)


def _wrapper(n, parsed, tail=""):
    return {"n": n, "cmd": "python bench.py", "rc": 0 if parsed else 1,
            "tail": tail, "parsed": parsed}


def _real_parsed(value, status="ok", detail=None):
    return {
        "metric": "decode_tokens_per_sec_per_chip (llama-3.1-8b, int8, slots=80)",
        "value": value, "unit": "tokens/s/chip",
        "vs_baseline": round(value / 2000.0, 3), "status": status,
        "detail": detail or {},
    }


def _proxy_parsed(compile_s, ratio=1.2, flops=1e12):
    return {
        "metric": "decode_tokens_per_sec_per_chip (llama-3.1-8b, int8, "
                  "slots=80) [NOT MEASURED: tpu_unavailable]",
        "value": 0.0, "unit": "tokens/s/chip", "vs_baseline": 0.0,
        "status": "tpu_unavailable",
        "detail": {"proxy": {
            "status": "ok", "series": "proxy", "flops": flops,
            "bytes_accessed": 2e12, "compile_wall_s": compile_s,
            "peak_bytes": 2.1e10, "step_count_ratio": ratio,
        }},
    }


def _write_rounds(tmp_path, specs):
    paths = []
    for name, doc in specs:
        p = tmp_path / f"BENCH_{name}.json"
        p.write_text(json.dumps(doc))
        paths.append(p)
    return paths


def test_round_classification(tmp_path):
    paths = _write_rounds(tmp_path, [
        ("r01", _wrapper(1, _real_parsed(4645.0))),
        ("r02", _wrapper(2, None, tail="RESOURCE_EXHAUSTED: hbm")),
        ("r03", _wrapper(3, None,
                         tail="Unable to initialize backend 'tpu'")),
        ("r04", _wrapper(4, _proxy_parsed(60.0))),
    ])
    rounds = load_rounds(paths)
    assert [r.name for r in rounds] == ["r01", "r02", "r03", "r04"]
    assert [r.series for r in rounds] == ["real", "dark", "dark", "proxy"]
    assert rounds[1].status == "oom"
    assert rounds[2].status == "tpu_unavailable"
    assert rounds[0].tokens_per_sec_per_chip == 4645.0
    assert rounds[0].label == "llama-3.1-8b, int8, slots=80"
    assert rounds[3].proxy["compile_wall_s"] == 60.0
    # the failure-status wrapper fields never leak throughput
    assert rounds[3].tokens_per_sec_per_chip is None


def test_bare_artifact_line_accepted(tmp_path):
    """A raw bench.py line (no driver wrapper) parses identically."""
    p = tmp_path / "BENCH_r07.json"
    p.write_text(json.dumps(_real_parsed(3000.0)))
    r = load_round(p)
    assert r.series == "real" and r.tokens_per_sec_per_chip == 3000.0
    assert r.index == 7


def test_corrupt_artifact_becomes_dark_round(tmp_path):
    p = tmp_path / "BENCH_r09.json"
    p.write_text("{not json")
    r = load_round(p)
    assert r.series == "dark" and r.status == "error"


def test_regression_delta_vs_last_real(tmp_path):
    paths = _write_rounds(tmp_path, [
        ("r01", _wrapper(1, _real_parsed(4000.0))),
        ("r02", _wrapper(2, _proxy_parsed(50.0))),
        ("r03", _wrapper(3, _real_parsed(3000.0))),   # -25% vs r01
        ("r04", _wrapper(4, _real_parsed(3300.0))),   # +10% vs r03
    ])
    traj = build_trajectory(load_rounds(paths))
    rows = {r["name"]: r for r in traj["rounds"]}
    assert "delta_vs_last_real_pct" not in rows["r01"]  # no anchor yet
    assert rows["r03"]["delta_vs_last_real_pct"] == -25.0
    assert rows["r04"]["delta_vs_last_real_pct"] == 10.0
    # only the real drop is a regression; the proxy round is not compared
    # against device numbers at all
    regs = traj["regressions"]
    assert len(regs) == 1
    assert regs[0]["round"] == "r03"
    assert regs[0]["anchor_round"] == "r01"
    assert regs[0]["delta_pct"] == -25.0
    assert traj["last_real"]["name"] == "r04"


def test_proxy_series_tracked_separately(tmp_path):
    paths = _write_rounds(tmp_path, [
        ("r01", _wrapper(1, _proxy_parsed(40.0, ratio=1.1))),
        ("r02", _wrapper(2, _proxy_parsed(60.0, ratio=1.1))),  # +50% compile
        ("r03", _wrapper(3, _proxy_parsed(60.0, ratio=1.05))),  # better ratio
    ])
    traj = build_trajectory(load_rounds(paths))
    rows = {r["name"]: r for r in traj["rounds"]}
    assert rows["r02"]["proxy_delta_pct"]["compile_wall_s"] == 50.0
    # >10% in the worse direction flags a proxy regression
    assert any(
        reg["metric"] == "proxy:compile_wall_s" and reg["round"] == "r02"
        for reg in traj["regressions"]
    )
    # improvements are deltas, not regressions
    assert not any(reg["round"] == "r03" for reg in traj["regressions"])
    assert traj["coverage"] == {"total": 3, "real": 0, "proxy": 3, "dark": 0}


def test_coverage_accounting(tmp_path):
    paths = _write_rounds(tmp_path, [
        ("r01", _wrapper(1, _real_parsed(4645.0))),
        ("r02", _wrapper(2, None, tail="RESOURCE_EXHAUSTED")),
        ("r03", _wrapper(3, _proxy_parsed(55.0))),
    ])
    traj = build_trajectory(load_rounds(paths))
    assert traj["coverage"] == {"total": 3, "real": 1, "proxy": 1, "dark": 1}


def test_render_table_and_html_section(tmp_path):
    paths = _write_rounds(tmp_path, [
        ("r01", _wrapper(1, _real_parsed(4000.0))),
        ("r02", _wrapper(2, _proxy_parsed(50.0))),
        ("r03", _wrapper(3, _real_parsed(3000.0))),
    ])
    traj = build_trajectory(load_rounds(paths))
    table = render_table(traj)
    assert "r01" in table and "proxy" in table and "-25.0%" in table
    from kserve_vllm_mini_tpu.report.html import generate_trajectory_html

    html = generate_trajectory_html(traj)
    assert "Perf trajectory" in html
    assert "r02" in html and "regression r03" in html


def test_downshift_label_surfaces(tmp_path):
    parsed = _real_parsed(
        2500.0,
        detail={"downshifted": "downshifted: slots 80->40 (est 21.4 GB > "
                               "90% of 16.0 GB HBM)"},
    )
    p = tmp_path / "BENCH_r05.json"
    p.write_text(json.dumps(_wrapper(5, parsed)))
    r = load_round(p)
    assert r.downshifted.startswith("downshifted: slots 80->40")
    traj = build_trajectory([r])
    assert "slots 80->40" in render_table(traj)

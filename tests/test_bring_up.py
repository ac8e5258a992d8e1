"""What the program assumes about the machine it starts on (ISSUE 21):
where the compile cache goes, which device facts apply, and that
chip_smoke.py refuses to run without a TPU."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from kserve_vllm_mini_tpu.profiling.devices import TPU_DEVICES, device_facts

REPO = Path(__file__).resolve().parents[1]


# -- compile cache placement ---------------------------------------------------

@pytest.fixture
def cache_config():
    """The cache-dir config as the test found it, restored afterwards."""
    before = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    yield before
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)


def test_cache_env_set_means_code_sets_nothing(monkeypatch, cache_config):
    from kserve_vllm_mini_tpu.runtime.server import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == cache_config


def test_cache_default_is_checkout_jax_cache_on_an_accelerator(
        monkeypatch, cache_config):
    from kserve_vllm_mini_tpu.runtime.server import (
        DEFAULT_COMPILE_CACHE_DIR,
        configure_compile_cache,
    )

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert DEFAULT_COMPILE_CACHE_DIR == REPO / ".jax_cache"
    assert configure_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_cache_stays_off_on_the_cpu_backend(monkeypatch, cache_config):
    """CPU runs (this suite included) never write the default directory:
    it travels with the checkout, and XLA:CPU cache entries from another
    host can SIGILL (tests/conftest.py)."""
    from kserve_vllm_mini_tpu.runtime.server import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.default_backend() == "cpu"
    assert configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == cache_config


# -- device facts --------------------------------------------------------------

def test_unknown_tpu_device_kind_raises():
    with pytest.raises(ValueError, match="unknown TPU device_kind 'TPU v9'"):
        device_facts("TPU v9")
    # exact match only: no substring, no case folding
    with pytest.raises(ValueError):
        device_facts("tpu v5 lite")


def test_every_device_row_names_a_real_price_and_tdp_row():
    """``DeviceFacts.name`` is the key into tpu-cost.yaml and the TDP table;
    a row that fell through to either ``default`` would be a rate assumed
    for a chip the sheet does not know."""
    from kserve_vllm_mini_tpu.analysis.telemetry import TPU_TDP_WATTS
    from kserve_vllm_mini_tpu.costs.pricing import load_pricing

    pricing = load_pricing()
    for kind, facts in TPU_DEVICES.items():
        assert pricing.chip_price(facts.name)[1] != "default", kind
        assert facts.name in TPU_TDP_WATTS, kind
        assert facts.source, kind
    v5e = device_facts("TPU v5 lite")
    assert (v5e.name, v5e.hbm_bytes) == ("v5e", 16_000_000_000)
    assert (v5e.hbm_bytes_per_s, v5e.bf16_flops_per_s) == (819e9, 197e12)


# -- chip_smoke.py refuses the CPU --------------------------------------------

def _run_smoke(cwd, *args):
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=cwd,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )


def test_chip_smoke_exits_nonzero_without_a_tpu():
    proc = _run_smoke(REPO)
    assert proc.returncode != 0
    assert proc.stdout == ""            # no result, no timing
    assert "no TPU" in proc.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds the script and nothing else of the repo it
    must fail even past the device check (here: the dry run's)."""
    (tmp_path / "chip_smoke.py").write_text((REPO / "chip_smoke.py").read_text())
    proc = _run_smoke(tmp_path, "--cpu-dry-run")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "kserve_vllm_mini_tpu" in proc.stderr


def test_chip_smoke_last_line_is_the_verdict_only():
    """The driver reads the last stdout line: exactly ``ok`` and ``device``
    (platform, kind, count). The detail goes on the ``summary`` line above."""
    proc = _run_smoke(REPO, "--cpu-dry-run", "--legs", "kernels")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.splitlines()
    assert json.loads(lines[-1]) == {
        "ok": False,     # a dry or partial run never says true
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert lines[-2].startswith("summary {") and lines[-2].endswith('"claim": null}')

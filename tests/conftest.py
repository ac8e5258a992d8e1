"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

The reference fakes its cluster with a mock kubectl binary (SURVEY.md §4.3);
we additionally fake the accelerator: 8 virtual CPU devices let every sharding
test exercise a real Mesh without TPU hardware.
"""

import os

# Overwrite, not setdefault: on a machine with a chip the environment may
# name the TPU, and tests must never grab it (a chip belongs to one process).
# jax.config is updated too in case something imported jax before this file
# (backends initialize lazily — this works until a device is touched).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

try:
    import jax  # noqa: E402

    jax.config.update("jax_platforms", "cpu")
    # Persistent compilation cache: measured to halve warm-suite wall time,
    # but STRICTLY OPT-IN (set JAX_COMPILATION_CACHE_DIR): jaxlib 0.9.0's
    # XLA:CPU AOT cache loads entries whose recorded machine features don't
    # match the host ("prefer-no-scatter ... could lead to SIGILL") and a
    # full-suite run with a warm shared cache segfaulted at ~94% — a
    # default-on cache that can SIGSEGV the lane is worse than slow.
    _cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if _cache_dir:
        jax.config.update("jax_compilation_cache_dir", _cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
except ImportError:
    # JAX is the optional 'runtime' extra; harness-layer tests run without it.
    collect_ignore_glob = [
        "test_model*", "test_parallel*", "test_flash*", "test_loader*",
        "test_runtime*", "test_graft*", "test_pipeline*", "test_quant*",
    ]

import pytest  # noqa: E402

from tests.synthetic import make_synthetic_run  # noqa: E402


@pytest.fixture
def synthetic_run(tmp_path):
    """Deterministic synthetic run dir (seed=42, 5% errors, first 10 cold) —
    the repro-smoke fixture pattern from the reference CI."""
    return make_synthetic_run(tmp_path / "runs", seed=42)

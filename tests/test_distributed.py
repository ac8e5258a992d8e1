"""Multi-host initialization over localhost: the CI stand-in for a real
multi-host TPU slice.

Two jax.distributed CPU processes (8 virtual devices each) join a
coordinator, build the v5p-16 topology mesh through parallel/distributed.py,
and run a cross-process sharded reduction. The cluster-as-subprocess pattern
follows the reference's mock-kubectl strategy (SURVEY.md §4.3): fake the
infrastructure, run the real code.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

from tests import env_guards

WORKER = Path(__file__).parent / "distributed_worker.py"
REPO = Path(__file__).parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(extra: dict | None = None) -> dict:
    env = {k: v for k, v in os.environ.items() if not k.startswith(("KVMINI_", "JAX_"))}
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(REPO)
    if extra:
        env.update(extra)
    return env


def _run_pair(argv_style: bool) -> list[subprocess.CompletedProcess]:
    coord = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in (0, 1):
        if argv_style:
            cmd = [sys.executable, str(WORKER), coord, "2", str(pid)]
            env = _worker_env()
        else:
            cmd = [sys.executable, str(WORKER)]
            env = _worker_env({
                "KVMINI_COORDINATOR": coord,
                "KVMINI_NUM_PROCESSES": "2",
                "KVMINI_PROCESS_ID": str(pid),
            })
        procs.append(subprocess.Popen(
            cmd, env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        ))
    done = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            done.append(subprocess.CompletedProcess(p.args, p.returncode, out, err))
    finally:
        # a hung worker must not outlive the test
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    return done


@pytest.mark.slow
def test_two_process_mesh_and_psum():
    env_guards.require_child_jax()
    results = _run_pair(argv_style=True)
    env_guards.skip_if_multiprocess_unsupported([r.stderr for r in results])
    for i, r in enumerate(results):
        assert r.returncode == 0, f"worker {i} failed:\n{r.stderr[-2000:]}"
    outs = "\n".join(r.stdout for r in results)
    assert "WORKER_OK pid=0 primary=True total=120.0" in outs
    assert "WORKER_OK pid=1 primary=False total=120.0" in outs


@pytest.mark.slow
def test_env_var_resolution():
    env_guards.require_child_jax()
    results = _run_pair(argv_style=False)
    env_guards.skip_if_multiprocess_unsupported([r.stderr for r in results])
    for i, r in enumerate(results):
        assert r.returncode == 0, f"worker {i} failed:\n{r.stderr[-2000:]}"
    assert "WORKER_OK pid=0 primary=True" in "".join(r.stdout for r in results)


def test_single_process_mode_no_coordinator(monkeypatch):
    """No coordinator anywhere -> initialize() returns False (local mode)."""
    from kserve_vllm_mini_tpu.parallel import distributed as dist

    for var in ("KVMINI_COORDINATOR", "TPU_WORKER_HOSTNAMES",
                "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(var, raising=False)
    assert dist.initialize() is False


def test_global_mesh_wrong_size_raises():
    import jax

    from kserve_vllm_mini_tpu.parallel import distributed as dist
    from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec

    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        dist.global_mesh(MeshSpec(tp=n * 2))


def test_global_mesh_local_topology():
    """Single-process global mesh: cpu-8 preset over the 8 virtual devices."""
    import jax

    from kserve_vllm_mini_tpu.parallel import distributed as dist

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = dist.mesh_for_topology("cpu-8")
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("dp", "sp", "pp", "tp", "ep")

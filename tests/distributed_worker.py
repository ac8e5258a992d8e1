"""Worker process for tests/test_distributed.py.

Joins a 2-process jax.distributed cluster over localhost (CPU backend, 8
virtual devices per process => 16 global), builds the v5p-16 topology mesh
through parallel.distributed, runs a sharded computation whose result
requires cross-process collectives, and prints a checkable line.

Run: python tests/distributed_worker.py <coordinator> <num_procs> <proc_id>
(or with KVMINI_* env vars instead of argv to exercise env resolution).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()


def main() -> int:
    import jax

    # belt and braces with the env var main() set above: a worker must
    # never reach for a chip its parent may hold
    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kserve_vllm_mini_tpu.parallel import distributed as dist

    if len(sys.argv) > 1:
        joined = dist.initialize(
            coordinator_address=sys.argv[1],
            num_processes=int(sys.argv[2]),
            process_id=int(sys.argv[3]),
        )
    else:
        joined = dist.initialize()  # KVMINI_* env resolution path
    assert joined, "worker must join the distributed runtime"
    assert dist.process_count() == 2
    assert len(jax.devices()) == 16, f"global devices {len(jax.devices())}"

    mesh = dist.mesh_for_topology("v5p-16")  # 16 chips / 4 hosts preset
    assert mesh.devices.size == 16

    # tp-sharded computation: every process must participate in the psum
    sharding = NamedSharding(mesh, P(("dp", "sp", "pp", "tp")))
    ones = jax.jit(
        lambda: jnp.arange(16, dtype=jnp.float32), out_shardings=sharding
    )()
    total = jax.jit(jnp.sum)(ones)  # replicated scalar on every process
    np.testing.assert_allclose(np.asarray(total), 120.0)

    primary = dist.is_primary()
    assert primary == (dist.process_index() == 0)
    print(f"WORKER_OK pid={dist.process_index()} primary={primary} total={float(total)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Worker for test_yardstick_head_width.py: the decode step and a fresh
prefill piece compiled for a TPU v5e with no chip, at an attention narrower
than the hidden size.

The configuration is a dict, ``mistral-7b-int8``'s keys with ``hidden_size``
5120, 20 query / 4 KV heads of 128 (q and o are 5120 x 2560) and two layers,
built by ``bench/systems/engine.py model_config`` (so ``explicit_head_dim`` is
128) and compiled by ``tests/tpu_preflight_worker.py compile_case`` as the
four accepted configurations are. Runs in its own process: libtpu is loaded
here alone. Prints one JSON line, ``{"skip": reason}`` where libtpu or the
topology cannot be had, else ``{"head_dim", "explicit_head_dim", "q_width",
"cases": {step: CompileStats.to_dict()}}``.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from tests import tpu_preflight_worker as base  # noqa: E402  (sets the described chip's environment)

NARROW = {"hidden_size": 5120, "num_attention_heads": 20, "num_key_value_heads": 4,
          "head_dim": 128, "num_hidden_layers": 2}


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation, or no such topology
        print(json.dumps({"skip": f"no chipless v5e topology: {type(e).__name__}: {e}"[:400]}))
        return 0
    jax.default_backend = lambda: "tpu"   # what the kernel dispatch asks

    from bench import config

    m = {**config.load_cell("mistral-7b.chat")["config"]["model"], **NARROW}
    cfg = config.load_module("systems", "engine").model_config(m)
    out = {"head_dim": cfg.head_dim, "explicit_head_dim": cfg.explicit_head_dim,
           "q_width": cfg.n_heads * cfg.head_dim, "cases": {}}
    for step in sys.argv[1:] or ["decode", "prefill"]:
        _, stats = base.compile_case(cfg, step, topo.devices[0])
        out["cases"][step] = stats.to_dict()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

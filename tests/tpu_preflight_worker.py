"""Worker for test_weight_copy_preflight.py: compile engine steps for a TPU
v5e with no chip, by the README's pre-flight recipe.

Runs in its OWN process: libtpu is loaded here and nowhere in the test
workers, ``jax.default_backend`` is patched (the kernel dispatch asks it), and
the caller can hold the whole thing to a timeout.

Each case is ``<model>:<step>``: ``decode`` is ``steps.build_decode(cfg,
forward, "auto", 1)``, ``prefill`` is ``steps.build_prefill(cfg, forward,
"auto", "slot", True)`` at bucket 128 (512 for the two stacks of routed GLU
experts: a piece that takes the grouped dispatch, models/moe.py), over
abstract arguments (no weights are made). Models: the benchmark's configurations by name (``BENCHMARK.json``), at
their published widths and two layers (the hybrid: one period of four; the
pattern stack: one block of each kind, ``M*E``, with 8 of its experts held;
the two-mixer stack: two blocks, ``PP``; the short-convolution stack: a
convolution, an attention layer and a convolution, the dense MLP under the
first and all 32 experts under the other two; the window stack: its global
layer and a window layer, all 64 experts under each).

Prints one JSON line: ``{"skip": reason}`` where libtpu or the topology cannot
be had, else ``{"cases": {case: CompileStats.to_dict()}}`` (``mosaic_calls``,
``weight_copy_bytes``, ``state_copy_bytes`` among its keys; a prefill case
of routed GLU experts adds ``grouped`` and a decode case of routed experts
``swept``: what the dispatch left in the program, see ``kernel_dispatch``). ``--dump DIR`` also writes each case's optimized HLO
there.
"""

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.update(
    JAX_PLATFORMS="cpu", TPU_SKIP_MDS_QUERY="1",
    TPU_ACCELERATOR_TYPE="v5litepod-4", TPU_WORKER_HOSTNAMES="localhost",
)

SLOTS, CONTEXT, BUCKET = 16, 2048, 128
LONG_BUCKET = 512   # longer than the kernel's row tile: the grouped dispatch's


def bench_model(name: str) -> dict:
    """The configuration's model as a cell of the benchmark serves it
    (``bench.config.load_cell``)."""
    from bench import config as bench_config

    cell = next(w["name"] for w in bench_config.benchmark()["workloads"]
                if w["config"] == name)
    return dict(bench_config.load_cell(cell)["config"]["model"])


def model_config(name: str):
    """``bench_model`` cut to two layers or one period."""
    from bench import config as bench_config

    m = bench_model(name)
    m["num_hidden_layers"] = 2
    kinds = m.get("model_extra", {}).get("layer_types")
    if kinds and "conv" in kinds:
        # operator + feed-forward pairs: each operator, each feed-forward, and
        # a second layer in the convolution's and the experts' stacks
        kinds = ["conv", "full_attention", "conv"]
        m["num_hidden_layers"] = len(kinds)
        m["model_extra"] = {**m["model_extra"], "layer_types": kinds, "num_dense_layers": 1}
    elif kinds:   # a hybrid stack: one period, its linear layers then the full one
        period = kinds.index("full_attention") + 1
        m["num_hidden_layers"] = period
        m["model_extra"] = {**m["model_extra"], "layer_types": kinds[:period]}
    pattern = m.get("model_extra", {}).get("hybrid_override_pattern", "")
    if "P" in pattern:
        # a stack of two-mixer blocks: two of them
        m["model_extra"] = {**m["model_extra"], "hybrid_override_pattern": "PP"}
    elif pattern:
        # a stack of single-mixer blocks: one of each kind, a few experts held
        m["model_extra"] = {**m["model_extra"], "hybrid_override_pattern": "M*E",
                            "n_experts_held": 8}
    return bench_config.load_module("systems", "engine").model_config(m)


def build_case(cfg, step: str, device):
    """(the jitted step, its abstract arguments, its keywords) of a case."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from kserve_vllm_mini_tpu.models.llama import (
        forward,
        init_kv_cache,
        init_params_quantized,
    )
    from kserve_vllm_mini_tpu.runtime import steps

    sh = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), tree)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    key = jax.random.PRNGKey(0)
    params = on_chip(jax.eval_shape(lambda: init_params_quantized(key, cfg)))
    cache = on_chip(jax.eval_shape(
        lambda: init_kv_cache(cfg, SLOTS, max_seq=CONTEXT)))
    i32, f32 = jnp.int32, jnp.float32
    if step == "decode":
        fn = steps.build_decode(cfg, forward, "auto", 1)
        row = (SLOTS,)
        args = (params, cache, arg(row, i32), arg(row, i32), arg(row, f32),
                arg(row, i32), arg(row, f32), on_chip(key),
                arg((SLOTS, cfg.vocab_size), i32), arg(row, f32), arg(row, f32))
        kw = {"live": arg(row, jnp.bool_)} if cfg.is_hybrid else {}
    else:
        fn = steps.build_prefill(cfg, forward, "auto", "slot", True)
        bucket = LONG_BUCKET if cfg.is_conv_stack or cfg.is_window_stack else BUCKET
        args = (params, cache, arg((1, bucket), i32), arg((), i32), arg((), i32))
        kw = {}
    return fn, args, kw


def compile_case(cfg, step: str, device):
    from kserve_vllm_mini_tpu.profiling.compile_stats import capture_compile_stats

    fn, args, kw = build_case(cfg, step, device)
    return capture_compile_stats(fn, *args, label=step, **kw)


def kernel_dispatch(model: dict, lowered: str, optimized: str, body: str, kernel: str) -> dict:
    """What an expert dispatch left in a program: ``bodies``, the functions
    of ``body`` (``_run_grouped``, ``expert_sweep``) in the lowered module
    (one a shape, however many layers call it); ``calls``, the custom calls
    of ``kernel`` (``gmm``, ``emm``) in the optimized program; ``found``,
    those of them the benchmark's reader of this stack would count as an
    expert stack's operation, by the name a trace would show (result and
    operands with their shapes, cut to 96 characters), every operand's number
    written with four digits as a program of the whole depth has them;
    ``names``, those cut names."""
    import re

    from bench import conv_moe_roofline, ssm_moe_roofline, trace_reduce, window_moe_roofline

    roofline = next(r for r in (conv_moe_roofline, window_moe_roofline, ssm_moe_roofline)
                    if r.sizes(model))
    types = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+)", optimized, re.M))
    calls = re.findall(rf"^\s*(?:ROOT )?((%{kernel}[\w.\-]*) = (\S+) custom-call\(([^)]*)\))",
                       optimized, re.M)
    names = []
    for _, name, result, operands in calls:
        shown = ", ".join(f"{types.get(o, '?')} {re.sub(r'[.]\d+$', '.1234', o)}" for o in
                          re.findall(r"%[\w.\-]+", operands))
        names.append(trace_reduce.short_name(f"{name}.123 = {result} custom-call({shown})"))
    return {"bodies": lowered.count(f"func.func private @{body}"), "calls": len(calls),
            "found": sum(roofline.reads_expert_stack(n, model) for n in names), "names": names}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cases", nargs="+", help="<model>:<decode|prefill>")
    ap.add_argument("--dump", default=None)
    ns = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    # an executable for a described chip can be written to the persistent
    # cache but not read back: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation, or no such topology
        print(json.dumps({"skip": f"no chipless v5e topology: {type(e).__name__}: {e}"[:400]}))
        return 0
    jax.default_backend = lambda: "tpu"   # what the kernel dispatch asks
    from kserve_vllm_mini_tpu.profiling.compile_stats import capture_compile_stats

    out = {}
    for case in ns.cases:
        model, step = case.split(":")
        cfg = model_config(model)
        fn, args, kw = build_case(cfg, step, topo.devices[0])
        exe, stats = capture_compile_stats(fn, *args, label=step, **kw)
        out[case] = stats.to_dict()
        if step == "prefill" and (cfg.is_conv_stack or cfg.is_window_stack):
            out[case]["grouped"] = kernel_dispatch(
                bench_model(model), fn.lower(*args, **kw).as_text(), exe.as_text(),
                "_run_grouped", "gmm")
        if step == "decode" and cfg.n_expert_layers:
            # the readers look for the experts this case holds
            held = {"n_routed_experts": cfg.experts_held} if cfg.is_pattern_stack else {}
            out[case]["swept"] = kernel_dispatch(
                {**bench_model(model), **held}, fn.lower(*args, **kw).as_text(), exe.as_text(),
                "expert_sweep", "emm")
        if ns.dump:
            Path(ns.dump).mkdir(parents=True, exist_ok=True)
            (Path(ns.dump) / f"{case.replace(':', '.')}.hlo.txt").write_text(exe.as_text())
    print(json.dumps({"cases": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""AOT compile-time vs serving-performance tradeoff sweep.

The reference's analog is scripts/trtllm_build_vs_perf.py: time the TRT-LLM
engine *build*, then benchmark the built engine, and emit a CSV of
build-time vs p95/RPS tradeoffs (:124-308). On TPU the "engine build" is
XLA compilation — the cost moves from an offline builder container to
`jax.jit` tracing + compilation, paid per (shape-bucket, config). This sweep
makes that cost visible: for each config it AOT-compiles the runtime's
prefill and decode steps (`.lower().compile()`), records wall-clock compile
time, then measures steady-state decode throughput of the compiled step —
so operators can weigh e.g. more prefill buckets (lower padding waste,
more compiles) against fewer (slower prefill, faster boot), or int8 vs
bf16 (compile cost vs tokens/sec).
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Any

CSV_COLUMNS = [
    "model",
    "slots",
    "max_seq",
    "prefill_bucket",
    "quantization",
    "compile_prefill_s",
    "compile_decode_s",
    "compile_total_s",
    "decode_tokens_per_sec",
    "params_mib",
    "status",
    "error",
]


@dataclass
class CompileConfig:
    model: str = "llama-tiny"
    slots: int = 8
    max_seq: int = 512
    prefill_bucket: int = 128
    quantization: str = "none"   # none | int8


def measure_config(cc: CompileConfig, decode_steps: int = 32) -> dict[str, Any]:
    """AOT-compile prefill + decode for one config; measure compile seconds
    and post-compile decode throughput."""
    import jax
    import jax.numpy as jnp

    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import forward, init_kv_cache, init_params

    cfg = get_config(cc.model, max_seq_len=cc.max_seq)
    params = init_params(jax.random.PRNGKey(0), cfg)
    if cc.quantization == "int8":
        from kserve_vllm_mini_tpu.ops.quant import quantize_params

        params = quantize_params(params)
    params_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params)
        if hasattr(x, "dtype")
    )

    S, B = cc.slots, cc.prefill_bucket
    cache = init_kv_cache(cfg, S, max_seq=cc.max_seq)
    toks = jnp.zeros((S, B), dtype=jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32), (S, B))
    lengths = jnp.full((S,), B, dtype=jnp.int32)

    @partial(jax.jit, donate_argnums=(1,))
    def prefill(params, cache, toks, pos):
        logits, cache = forward(params, cfg, toks, pos, cache,
                                jnp.zeros((S,), jnp.int32))
        return cache, jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

    def make_decode_n(n_steps: int):
        """N greedy decode steps fused into ONE dispatch via lax.fori_loop —
        the timing unit: per-dispatch host overhead is comparable with the
        step time for small models, and a fused loop puts all the work
        behind a single dispatch + readback."""

        @partial(jax.jit, donate_argnums=(1,))
        def decode_n(params, cache, tokens, lengths):
            def body(_, carry):
                cache, tokens, lengths = carry
                lengths = lengths + 1
                logits, cache = forward(params, cfg, tokens[:, None],
                                        lengths[:, None], cache, lengths)
                nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
                return cache, nxt, lengths

            return jax.lax.fori_loop(0, n_steps, body, (cache, tokens, lengths))

        return decode_n

    t0 = time.time()
    prefill_exe = prefill.lower(params, cache, toks, pos).compile()
    compile_prefill_s = time.time() - t0

    tokens0 = jnp.zeros((S,), dtype=jnp.int32)
    t0 = time.time()
    decode_n1 = make_decode_n(decode_steps).lower(
        params, cache, tokens0, lengths).compile()
    decode_n2 = make_decode_n(2 * decode_steps).lower(
        params, cache, tokens0, lengths).compile()
    compile_decode_s = time.time() - t0

    # Timing: each fused run ends in a host readback (the completion
    # barrier), and differencing the N-step and 2N-step runs cancels the
    # fixed dispatch + readback cost.
    import numpy as np

    cache, tokens = prefill_exe(params, cache, toks, pos)
    _ = np.asarray(tokens)  # warm the readback path
    t0 = time.time()
    cache, tokens, lengths = decode_n1(params, cache, tokens, lengths)
    _ = np.asarray(tokens)
    d1 = time.time() - t0
    t0 = time.time()
    cache, tokens, lengths = decode_n2(params, cache, tokens, lengths)
    _ = np.asarray(tokens)
    d2 = time.time() - t0
    if d2 > d1:
        step_s = (d2 - d1) / decode_steps
    else:
        # RTT jitter swamped the difference; fall back to the 2N run as an
        # upper bound on per-step time (reported tok/s is then a lower bound)
        step_s = d2 / (2 * decode_steps)
    tok_per_s = S / step_s

    return {
        "model": cc.model,
        "slots": S,
        "max_seq": cc.max_seq,
        "prefill_bucket": B,
        "quantization": cc.quantization,
        "compile_prefill_s": round(compile_prefill_s, 3),
        "compile_decode_s": round(compile_decode_s, 3),
        "compile_total_s": round(compile_prefill_s + compile_decode_s, 3),
        "decode_tokens_per_sec": round(tok_per_s, 1),
        "params_mib": round(params_bytes / 2**20, 1),
    }


def run_compile_sweep(
    configs: list[CompileConfig], csv_path: Path, decode_steps: int = 32
) -> list[dict[str, Any]]:
    from kserve_vllm_mini_tpu.sweeps.base import write_row

    csv_path.unlink(missing_ok=True)
    rows = []
    for cc in configs:
        row: dict[str, Any]
        try:
            row = measure_config(cc, decode_steps=decode_steps)
            row["status"], row["error"] = "ok", ""
        except Exception as e:  # noqa: BLE001 — record-and-continue
            row = {
                "model": cc.model, "slots": cc.slots, "max_seq": cc.max_seq,
                "prefill_bucket": cc.prefill_bucket, "quantization": cc.quantization,
                "status": "failed", "error": f"{type(e).__name__}: {e}",
            }
        rows.append(row)
        write_row(csv_path, row, CSV_COLUMNS)
    return rows


# -- CLI ---------------------------------------------------------------------

def register(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--model", default="llama-tiny")
    parser.add_argument("--slots", default="4,8", help="Comma list")
    parser.add_argument("--buckets", default="64,128", help="Prefill buckets, comma list")
    parser.add_argument("--max-seq", type=int, default=512)
    parser.add_argument("--quantization", default="none,int8", help="Comma list")
    parser.add_argument("--decode-steps", type=int, default=32)
    parser.add_argument("--output", default="compile_sweep.csv")


def run(args: argparse.Namespace) -> int:
    configs = [
        CompileConfig(model=args.model, slots=int(s), max_seq=args.max_seq,
                      prefill_bucket=int(b), quantization=q)
        for s in args.slots.split(",")
        for b in args.buckets.split(",")
        for q in args.quantization.split(",")
    ]
    rows = run_compile_sweep(configs, Path(args.output), decode_steps=args.decode_steps)
    ok = [r for r in rows if r["status"] == "ok"]
    for r in ok:
        print(
            f"{r['model']} slots={r['slots']} bucket={r['prefill_bucket']} "
            f"quant={r['quantization']}: compile {r['compile_total_s']:.1f}s, "
            f"decode {r['decode_tokens_per_sec']:.0f} tok/s"
        )
    print(f"compile-sweep: {len(ok)}/{len(rows)} ok -> {args.output}")
    return 0 if ok else 1

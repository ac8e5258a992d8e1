#!/usr/bin/env python3
"""chip_smoke.py — the serving path, once, on the attached TPU.

The quickest proof that the system still starts on the chip: one process,
three legs, set-up seconds only, no performance claim.

- **main** — ``start_local_server`` (``build_engine`` -> ``Engine`` -> aiohttp)
  with mistral-7b at its published width and depth (int8 weights, bf16 KV,
  dense layout, 8 slots, 2048 context), one warm-up request per prompt
  bucket, then 24 seeded streaming requests through ``loadgen`` (open-loop
  poisson, prompts ~30-900 tokens in one queue, 32 output tokens) and
  ``analyze_run``. Pass: every request ok with exactly the tokens asked
  for, a repeated greedy prompt returns the same tokens, no engine
  executable compiled inside the counted window, every ``prefill[*]`` and
  ``decode[*]`` executable holds a Mosaic call, results.json written, and the flash
  prefill agrees with the jnp attention path on the same weights.
- **kernels** — the four Pallas kernels compiled (``interpret=False``) at
  KVH 8 / G 4 / D 128 and the engine's default blocks (the dense decode
  kernel also at KVH 32 / G 1), bf16 and int8-KV, against the jnp oracle.
- **engines** — llama-1b served with ``kv_layout=paged``, then with
  ``kv_cache_dtype=int8`` + ``prefill_chunk=256``: the paged decode and the
  prefill / chunk-prefill executables must hold a Mosaic call (the dense
  decode step of 64-wide heads keeps the eager read), and the kernel path
  must agree with the jnp path on the same weights. Then olmo-hybrid-7b at
  its published widths, two of its eight periods (6 linear-attention + 2
  full-attention layers, int8 weights, prompts prefilled in pieces of 256):
  its full layers' prefill and decode executables must hold their Mosaic
  calls, and its decode step must update the linear layers' recurrent state
  in place through ``kvmini_linear_decode`` (``state_copy_bytes`` 0, the
  gauge 1, logits as the jnp recurrence's). Then nemotron-3-super-120b
  at its published widths as one of four chips' share, seven blocks
  (``MEM*EME``: Mamba-2, attention without rotary embedding, 128 of the 512
  latent experts): the same asks. Then falcon-h1-34b at its published
  widths and multipliers, two of its two-mixer blocks (a Mamba-2 mixer of
  state 256 and rotary attention side by side on one normed input) and a
  quarter of its vocabulary's rows: the same asks. Then lfm2-8b-a1b at its
  published widths, six layers (convolution, convolution, attention,
  convolution, attention, convolution; a dense MLP then five layers of all
  32 SwiGLU experts): the flash prefill takes its 64-wide heads, the decode
  step keeps the eager read, every weight stack is read where it lies.

With no TPU it exits non-zero before doing anything; it never carries on on
the CPU. ``--cpu-dry-run`` is the explicit exception for debugging the
script itself in a sandbox: ``*-tiny`` presets, interpreted kernels, every
line labelled ``platform=cpu``, ``"ok": false`` in the summary.

Everything it writes goes under ``chiprun_out/chip_smoke/`` (ignored by git).
The last two stdout lines: ``summary {...}`` (versions, per-leg set-up
seconds, HBM, compile cache; ends with ``"claim": null``), then the verdict,
one JSON object with exactly these keys, the device as JAX reports it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
``"ok": true`` only when all three legs passed on a TPU; a failed leg prints
``"ok": false`` and exits 1; with no TPU nothing is printed to stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time
import traceback
import urllib.request
from functools import partial
from importlib import metadata
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parent / "chiprun_out" / "chip_smoke"

# bf16 carries 8 mantissa bits (rounding 2^-8 ~ 0.4%). A kernel and the jnp
# oracle round at different points — the kernel rounds p to bf16 before the
# PV matmul (bf16 KV) and its output to bf16, the MXU multiplies in bf16 —
# so they differ by a few multiples of 2^-8 of the output's scale. The error
# is taken relative to max(1, max|oracle|) (the int8-KV cases dequantize to
# values of magnitude ~10); 2e-2 leaves ~5x margin, and a wrong mask, head,
# scale row or block is O(1).
KERNEL_TOL = 2e-2
# Whole-model, relative L2 of the logits over the vocabulary axis. The jnp
# path (ops/attention.py) takes its QK logits out of the einsum in bf16
# before the f32 softmax, the kernels keep them in f32; that difference
# enters at every layer and grows roughly linearly with depth through
# random weights: 2.6-3.1e-2 at llama-1b's 16 layers, 6.3e-2 at mistral-7b's
# 32 (my chip run, PR 21). Unrelated logits (a misrouted block table, a
# wrong scale row, a wrong mask) give ~1.4, so 0.2 separates the two cleanly.
MODEL_REL_L2 = 2e-1


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def verdict(ok: bool, device: dict) -> None:
    """The last stdout line: exactly ``ok`` and ``device``, nothing else."""
    print(json.dumps({"ok": ok, "device": device}), flush=True)


def cache_entries(cache_dir: str | None) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for _ in os.scandir(cache_dir))


def hbm_line(tag: str) -> dict:
    from kserve_vllm_mini_tpu.profiling.headroom import hbm_watermarks

    hbm = hbm_watermarks()
    say(f"hbm[{tag}] " + " ".join(f"{k}={v}" for k, v in sorted(hbm.items())))
    return hbm


# -- serving helpers -----------------------------------------------------------

def load_phases(server, phases: list[dict], out: Path, *, max_tokens: int,
                window_s: float, seed: int, read_timeout_s: float,
                concurrent: bool) -> list[list]:
    """Drive ``phases`` through the load generator, each into its own run
    dir, and analyze each. ``concurrent``: all phases share one event loop,
    so their poisson arrivals interleave into one mixed-length queue."""
    from kserve_vllm_mini_tpu.analysis.analyzer import analyze_run
    from kserve_vllm_mini_tpu.core.rundir import RunDir
    from kserve_vllm_mini_tpu.loadgen.runner import LoadConfig, run_load_async

    jobs = []
    for i, ph in enumerate(phases):
        rd = RunDir(out / ph["name"])
        rd.path.mkdir(parents=True, exist_ok=True)
        cfg = LoadConfig(
            url=server.url, model=server.model_name,
            num_requests=ph["requests"], concurrency=ph["requests"],
            pattern="poisson", duration_s=window_s, streaming=True,
            max_tokens=max_tokens, temperature=0.0,
            base_prompt="x", input_tokens=ph["input_tokens"],
            seed=seed + i, read_timeout_s=read_timeout_s,
            timeout_s=read_timeout_s, max_retries=0,
        )
        jobs.append((cfg, rd))

    async def _all() -> list[list]:
        if concurrent:
            return list(await asyncio.gather(
                *(run_load_async(c, rd) for c, rd in jobs)))
        return [await run_load_async(c, rd) for c, rd in jobs]

    records = asyncio.run(_all())
    for (_cfg, rd), ph, recs in zip(jobs, phases, records):
        bad = [r for r in recs if not r.ok]
        check(len(recs) == ph["requests"] and not bad,
              f"{ph['name']}: {len(recs) - len(bad)}/{ph['requests']} ok "
              f"(first error: {bad[0].error if bad else 'missing records'})")
        for r in recs:
            check(r.tokens_out == max_tokens and not r.truncated,
                  f"{ph['name']} {r.request_id}: {r.tokens_out} tokens out, "
                  f"asked for {max_tokens} (truncated={r.truncated})")
        analyze_run(rd, endpoint=server.url)
        check(rd.results_json.exists(), f"{ph['name']}: no results.json")
    return records


def greedy_tokens(server, prompt: str, max_tokens: int) -> list:
    """One non-streaming greedy completion; the emitted tokens as their
    byte lists (the logprobs block carries them per token)."""
    body = json.dumps({
        "model": server.model_name, "max_tokens": max_tokens,
        "temperature": 0.0, "logprobs": True,
        "messages": [{"role": "user", "content": prompt}],
    }).encode()
    req = urllib.request.Request(
        server.url + "/v1/chat/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        doc = json.loads(resp.read())
    return [e["bytes"] for e in doc["choices"][0]["logprobs"]["content"]]


def executables(engine) -> dict[str, dict]:
    snap = engine.compile_stats_snapshot()
    out: dict[str, dict] = {}
    for e in snap["executables"]:
        check(e["label"] not in out, f"{e['label']} compiled twice")
        out[e["label"]] = e
    return out


def require_mosaic(exes: dict[str, dict], prefix: str, dry: bool) -> None:
    hit = [lab for lab in exes if lab.startswith(prefix)]
    check(bool(hit), f"no executable labelled {prefix}* was compiled")
    for lab in hit:
        n = exes[lab]["mosaic_calls"]
        say(f"  {lab}: mosaic_calls={n} compile_s={exes[lab]['compile_wall_s']}")
        if not dry:
            check(n > 0, f"{lab} holds no Mosaic call (tpu_custom_call)")


def require_weights_in_place(exes: dict[str, dict], dry: bool) -> None:
    """``weight_copy_bytes`` of every executable (profiling/compile_stats.py:
    per-layer integer weight slices copied, i.e. transposed, before their
    matmul); no decode and no prefill program may hold one."""
    for lab in sorted(exes):
        n = exes[lab]["weight_copy_bytes"]
        say(f"  {lab}: weight_copy_bytes={n}")
        if not dry and (lab.startswith("decode[") or "prefill[" in lab):
            check(n == 0, f"{lab} copies {n} bytes of weight slices a layer")


def require_state_in_place(engine, exes: dict[str, dict], dry: bool) -> None:
    """``state_copy_bytes`` of every executable (profiling/compile_stats.py:
    layers of a recurrent state sliced out of their stack, written back or
    copied outside a Mosaic call), beside the two gauges that say which
    decode program the engine runs. Where a stack keeps a recurrent state,
    the decode program on the chip took its kernel (``kvmini_linear_decode``
    for the delta rule, ``kvmini_ssm_decode`` for Mamba-2) and holds no such
    copy: a silent fall-back to the jnp recurrence, which the parity check
    below would pass, fails here."""
    stats = engine.snapshot_stats()
    took = {"linear": stats["linear_state_kernel"], "ssm": stats["ssm_state_kernel"]}
    say(f"  kvmini_tpu_linear_state_kernel={took['linear']} "
        f"kvmini_tpu_ssm_state_kernel={took['ssm']}")
    for lab in sorted(exes):
        say(f"  {lab}: state_copy_bytes={exes[lab]['state_copy_bytes']}")
    cfg = engine.cfg
    kind = "ssm" if cfg.n_ssm_layers else "linear" if cfg.n_linear_layers else None
    if dry or kind is None:
        return          # no layer of the plan keeps a recurrent state (a tail, a ring: no kernel)
    check(took[kind] == 1, f"the decode program did not take the {kind} state's kernel")
    n = exes["decode[chunk=1]"]["state_copy_bytes"]
    check(n == 0, f"decode[chunk=1] moves {n} bytes of state layers outside the kernel")


# -- model-level parity: kernel path vs jnp path on the engine's own weights ----

def forward_parity(engine, tag: str, *, chunks: tuple[int, ...], decode: bool,
                   block_size: int | None, kv_int8: bool, dry: bool) -> None:
    """Prefill ``chunks`` (chunk 0 fresh, the rest against the cache), then
    one decode step, once with the Pallas kernels and once with the jnp
    paths, on the served weights; logits must agree at every stage."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kserve_vllm_mini_tpu.models.llama import (
        forward,
        init_kv_cache,
        init_paged_kv_cache,
    )

    cfg, params = engine.cfg, engine.params
    total = sum(chunks)
    max_seq = 2 * total
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, size=(1, total + 1)),
                         jnp.int32)
    kw = {}
    if block_size is not None:
        maxb = max_seq // block_size
        kw["block_table"] = jnp.asarray(
            rng.permutation(maxb + 3)[:maxb][None], jnp.int32)

    steps = [(f"prefill[{chunks[0]}]", chunks[0])]
    steps += [(f"chunk[{t}]", t) for t in chunks[1:]]
    steps += [("decode", 1)] if decode else []

    def run(kernels: str) -> list:
        if block_size is not None:
            cache = init_paged_kv_cache(cfg, maxb + 3, block_size,
                                        quantized=kv_int8)
        else:
            cache = init_kv_cache(cfg, 1, max_seq=max_seq, quantized=kv_int8)
        outs, off = [], 0
        for i, (_name, t) in enumerate(steps):
            step = jax.jit(partial(
                forward, cfg=cfg, fresh_prefill=(i == 0), kernels=kernels, **kw))
            pos = off + jnp.arange(t, dtype=jnp.int32)[None]
            logits, cache = step(
                params, tokens=tokens[:, off:off + t], positions=pos,
                kv_cache=cache, cache_offsets=jnp.asarray([off], jnp.int32),
                logit_index=jnp.asarray([t - 1], jnp.int32),
            )
            outs.append(np.asarray(logits[0, 0], np.float32))
            off += t
        return outs

    got = run("interpret" if dry else "auto")
    want = run("off")
    for (name, _t), a, b in zip(steps, got, want):
        check(bool(np.isfinite(a).all()), f"{tag} {name}: non-finite logits")
        rel = float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
        say(f"  parity {tag} {name}: rel_l2={rel:.2e} (limit {MODEL_REL_L2})")
        check(rel <= MODEL_REL_L2,
              f"{tag} {name}: kernel vs jnp logits rel_l2 {rel:.3e} > {MODEL_REL_L2}")


# -- leg: main -------------------------------------------------------------------

# (name, loadgen input_tokens): filler-word counts that land the byte-tokenized
# prompt mid-bucket — ~30, 45, 85, 175, 360, 900 tokens -> buckets 32..1024
MAIN_PHASES = [("p32", 0), ("p64", 5), ("p128", 16), ("p256", 39),
               ("p512", 85), ("p1024", 224)]


def sizes(dry: bool) -> dict:
    """What each leg runs at. The dry run's ``*-tiny`` presets stop at 256
    positions, so it keeps the three short phases and shrinks the rest."""
    if dry:
        return dict(main_model="mistral-tiny", small_model="llama-tiny",
                    hybrid_model="olmo-hybrid-tiny", pattern_model="nemotron-h-tiny",
                    parallel_model="falcon-h1-tiny", conv_model="lfm2-moe-tiny",
                    phases=MAIN_PHASES[:3], out_tokens=8, main_parity=(32,),
                    paged_block=16, paged_parity=(32,), engine_words=20,
                    chunk=32, int8_parity=(32, 32))
    return dict(main_model="mistral-7b", small_model="llama-1b",
                hybrid_model=hybrid_preset(periods=2),
                pattern_model=pattern_preset("MEM*EME"),
                parallel_model=parallel_preset(blocks=2),
                conv_model=conv_preset(("conv", "conv", "full_attention", "conv",
                                        "full_attention", "conv")),
                phases=MAIN_PHASES, out_tokens=32, main_parity=(128,),
                paged_block=64, paged_parity=(128,), engine_words=160,
                chunk=256, int8_parity=(256, 256))


def leg_main(dry: bool, topology: str | None) -> dict:
    import jax

    from kserve_vllm_mini_tpu.runtime.local import start_local_server

    sz = sizes(dry)
    phases, out_tokens = sz["phases"], sz["out_tokens"]
    profile = {
        "model": sz["main_model"],
        "quantization": "int8", "kv_layout": "dense",
        "max_slots": 8, "max_model_len": 2048,
    }
    if topology:
        profile["jax_topology"] = topology
    out = OUT_DIR / "main"
    compiles_seen: list[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles_seen.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)

    server = start_local_server(profile, ready_timeout_s=900.0)
    try:
        eng = server.engine
        say(f"main: boot_s={server.boot_seconds:.1f} model={server.model_name} "
            f"layers={eng.cfg.n_layers} d_model={eng.cfg.d_model} "
            f"slots={eng.ecfg.max_slots} max_seq={eng.ecfg.max_seq_len}")
        hbm_line("main:booted")

        t0 = time.time()
        load_phases(
            server,
            [{"name": f"warm-{n}", "input_tokens": k, "requests": 1}
             for n, k in phases],
            out, max_tokens=out_tokens, window_s=0.1, seed=1, read_timeout_s=900.0,
            concurrent=False,
        )
        warm_s = time.time() - t0
        warm = executables(eng) if eng.mesh is None else {}
        compile_s = sum(e["compile_wall_s"] for e in warm.values())
        say(f"main: warmup_s={warm_s:.1f} engine_compiles={len(warm)} "
            f"engine_compile_s={compile_s:.1f}")

        # counted window: 4 requests per prompt length, all in ONE queue
        n_backend = len(compiles_seen)
        records = load_phases(
            server,
            [{"name": f"win-{n}", "input_tokens": k, "requests": 4}
             for n, k in phases],
            out, max_tokens=out_tokens, window_s=8.0, seed=42, read_timeout_s=120.0,
            concurrent=True,
        )
        n_req = sum(len(r) for r in records)
        lens = sorted(r.tokens_in for recs in records for r in recs)
        say(f"main: window requests={n_req} ok={n_req} "
            f"prompt_tokens min={lens[0]} max={lens[-1]}")
        check(n_req == 4 * len(phases), f"window served {n_req} requests")
        if eng.mesh is None:
            after = executables(eng)
            new = sorted(set(after) - set(warm))
            check(not new, f"compiled inside the counted window: {new}")
            check(len(warm) >= len(phases) + 1,
                  f"only {len(warm)} executables recorded for "
                  f"{len(phases)} buckets + decode: {sorted(warm)}")
            require_mosaic(after, "prefill[", dry)
            # 128-wide heads, bf16 KV: the step reads through
            # kvmini_dense_decode (models/llama.py dense_decode_kernel_block)
            require_mosaic(after, "decode[", dry)
            require_weights_in_place(after, dry)
        say(f"main: backend compiles inside the window (incl. eager host "
            f"helpers; informational)={len(compiles_seen) - n_backend}")

        a = greedy_tokens(server, "the same prompt, twice", out_tokens)
        b = greedy_tokens(server, "the same prompt, twice", out_tokens)
        check(len(a) == out_tokens and a == b,
              f"repeated greedy prompt diverged or short: {len(a)}/{len(b)} tokens")
        say(f"main: repeated greedy prompt -> identical {out_tokens} tokens")

        if eng.mesh is None:
            forward_parity(eng, "mistral", chunks=sz["main_parity"], decode=False,
                           block_size=None, kv_int8=False, dry=dry)
        else:
            spread = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
            say(f"main: per-device bytes_in_use={spread}")
            check(max(spread) < 2 * min(spread),
                  f"weights/KV not spread over the mesh: {spread}")
        hbm = hbm_line("main:served")
        return {"boot_s": round(server.boot_seconds, 1),
                "warmup_s": round(warm_s, 1),
                "engine_compile_s": round(compile_s, 1),
                "engine_compiles": len(warm), "requests": n_req,
                "hbm_peak_bytes": hbm.get("peak_bytes_in_use")}
    finally:
        server.stop()


# -- leg: kernels ----------------------------------------------------------------

def leg_kernels(dry: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kserve_vllm_mini_tpu.ops.attention import attention, causal_mask
    from kserve_vllm_mini_tpu.ops.flash_attention import (
        cached_prefill_attention,
        flash_attention,
    )
    from kserve_vllm_mini_tpu.ops.paged_attention import (
        dense_decode_attention,
        paged_decode_attention,
    )

    KVH, G, D, L, LAYER = (2, 2, 128, 2, 1) if dry else (8, 4, 128, 2, 1)
    H = KVH * G
    SEQ, BLK, SLOTS = (128, 16, 2) if dry else (2048, 64, 8)
    TS = (32,) if dry else (128, 512)
    rng = np.random.default_rng(0)
    bf16 = jnp.bfloat16

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape), bf16)

    def kv(shape, quantized):
        """(k, v, k_scale, v_scale, k_f32, v_f32) for a layer-stacked cache."""
        if not quantized:
            k, v = normal(shape), normal(shape)
            return k, v, None, None, k.astype(jnp.float32), v.astype(jnp.float32)
        k = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, size=shape[:-1]), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, size=shape[:-1]), jnp.float32)
        # the eager read path's dequantize-on-read (models/llama.py _read_layer)
        return (k, v, ks, vs, k.astype(jnp.float32) * ks[..., None],
                v.astype(jnp.float32) * vs[..., None])

    def oracle(q, k, v, mask):
        with jax.default_matmul_precision("highest"):
            return attention(q.astype(jnp.float32), k, v, mask)

    worst = 0.0

    def compare(name, got, want):
        nonlocal worst
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))
        worst = max(worst, err)
        say(f"  kernel {name}: scaled_max_err={err:.2e} (limit {KERNEL_TOL})")
        check(err <= KERNEL_TOL, f"{name}: scaled max err {err:.3e} > {KERNEL_TOL}")

    n = 0
    for t in TS:
        q, k, v = normal((1, H, t, D)), normal((1, KVH, t, D)), normal((1, KVH, t, D))
        out = flash_attention(q, k, v, interpret=dry)
        compare(f"flash_prefill T={t}", out,
                oracle(q, k.astype(jnp.float32), v.astype(jnp.float32),
                       causal_mask(t, t)[None, None]))
        n += 1

    kj = jnp.arange(SEQ)[None, None, None, :]
    qpos = jnp.asarray(rng.integers(0, SEQ, size=(SLOTS,)), jnp.int32)
    qpos = qpos.at[0].set(0).at[-1].set(SEQ - 1)   # first and last position
    dec_mask = kj <= qpos[:, None, None, None]
    for quantized in (False, True):
        tag = "int8kv" if quantized else "bf16"
        # paged decode: SLOTS x MAXB table over a shuffled pool
        maxb = SEQ // BLK
        pool = SLOTS * maxb + 1
        k, v, ks, vs, kf, vf = kv((L, pool, KVH, BLK, D), quantized)
        table = jnp.asarray(
            rng.permutation(pool)[: SLOTS * maxb].reshape(SLOTS, maxb), jnp.int32)
        q = normal((SLOTS, KVH, G, D))
        out = paged_decode_attention(q, k, v, table, qpos, layer=LAYER,
                                     k_scale=ks, v_scale=vs, interpret=dry)

        def gathered(x):
            g = x[LAYER][table].transpose(0, 2, 1, 3, 4)
            return g.reshape(SLOTS, KVH, SEQ, D)

        want = oracle(q.reshape(SLOTS, H, 1, D), gathered(kf), gathered(vf), dec_mask)
        compare(f"paged_decode {tag}", out.reshape(SLOTS, H, 1, D), want)

        # dense decode + cached prefill over one [L, B, KVH, SEQ, D] cache
        k, v, ks, vs, kf, vf = kv((L, SLOTS, KVH, SEQ, D), quantized)
        out = dense_decode_attention(q, k, v, qpos, layer=LAYER,
                                     k_scale=ks, v_scale=vs, interpret=dry)
        want = oracle(q.reshape(SLOTS, H, 1, D), kf[LAYER], vf[LAYER], dec_mask)
        compare(f"dense_decode {tag}", out.reshape(SLOTS, H, 1, D), want)
        # the same under a window a quarter of the stripe: most slots' first
        # live block is not block 0
        win = SEQ // 4
        out = dense_decode_attention(q, k, v, qpos, layer=LAYER, window=win,
                                     k_scale=ks, v_scale=vs, interpret=dry)
        want = oracle(q.reshape(SLOTS, H, 1, D), kf[LAYER], vf[LAYER],
                      dec_mask & (kj > qpos[:, None, None, None] - win))
        compare(f"dense_decode {tag} window={win}", out.reshape(SLOTS, H, 1, D), want)
        n += 3
        for t in TS:
            qc = normal((1, H, t, D))
            off = jnp.asarray([SEQ // 2 - 7], jnp.int32)  # mid-block offset
            sub = [None if x is None else x[:, :1] for x in (k, v, ks, vs)]
            out = cached_prefill_attention(qc, sub[0], sub[1], off, layer=LAYER,
                                           k_scale=sub[2], v_scale=sub[3],
                                           interpret=dry)
            qi = (off[:, None] + jnp.arange(t)[None, :])[:, None, :, None]
            want = oracle(qc, kf[LAYER, :1], vf[LAYER, :1], kj <= qi)
            compare(f"cached_prefill {tag} T={t}", out, want)
            n += 1
        # an MHA cache (codellama-7b: 32 kv heads, G 1): four times the
        # heads a block, so the block rule halves it (dense_decode_block;
        # at 512 keys Mosaic's scoped VMEM refuses the kernel)
        mha = 4 * KVH
        qm = normal((SLOTS, mha, 1, D))
        k, v, ks, vs, kf, vf = kv((L, SLOTS, mha, SEQ, D), quantized)
        out = dense_decode_attention(qm, k, v, qpos, layer=LAYER,
                                     k_scale=ks, v_scale=vs, interpret=dry)
        want = oracle(qm.reshape(SLOTS, mha, 1, D), kf[LAYER], vf[LAYER], dec_mask)
        compare(f"dense_decode {tag} KVH={mha} G=1", out.reshape(SLOTS, mha, 1, D), want)
        n += 1
    return {"cases": n, "worst_scaled_err": round(worst, 5)}


# -- leg: engines ----------------------------------------------------------------

def hybrid_preset(periods: int) -> str:
    """olmo-hybrid-7b cut to ``periods`` whole periods of its layer pattern
    (every width as published), registered under a name of its own so that
    ``build_engine`` serves it like any preset."""
    from kserve_vllm_mini_tpu.models.config import PRESETS, get_config

    full = get_config("olmo-hybrid-7b")
    n = periods * full.layer_period
    name = f"olmo-hybrid-7b-{periods}p"
    PRESETS[name] = full.scaled(name=name, n_layers=n, layer_types=full.layer_types[:n])
    return name


def pattern_preset(blocks: str) -> str:
    """nemotron-3-super-120b cut to ``blocks`` of its kinds of block (every
    width as published), one of four chips' share of each layer: 128 of the
    512 experts and a quarter of the vocabulary."""
    from kserve_vllm_mini_tpu.models.config import PRESETS, get_config

    name = f"nemotron-3-super-{len(blocks)}b-ep4"
    PRESETS[name] = get_config("nemotron-3-super-120b").scaled(
        name=name, hybrid_override_pattern=blocks, n_experts_held=128, vocab_size=32768)
    return name


def parallel_preset(blocks: int) -> str:
    """falcon-h1-34b cut to ``blocks`` of its two-mixer blocks (every width
    and multiplier as published) and a quarter of its vocabulary's rows."""
    from kserve_vllm_mini_tpu.models.config import PRESETS, get_config

    name = f"falcon-h1-34b-{blocks}b"
    PRESETS[name] = get_config("falcon-h1-34b").scaled(
        name=name, hybrid_override_pattern="P" * blocks, vocab_size=65280)
    return name


def conv_preset(kinds: tuple[str, ...]) -> str:
    """lfm2-8b-a1b cut to layers of these ``kinds`` (every width as
    published, all 32 experts held), the dense MLP under the first alone."""
    from kserve_vllm_mini_tpu.models.config import PRESETS, get_config

    name = f"lfm2-8b-a1b-{len(kinds)}l"
    PRESETS[name] = get_config("lfm2-8b-a1b").scaled(
        name=name, n_layers=len(kinds), layer_types=kinds, num_dense_layers=1)
    return name


def leg_engines(dry: bool) -> dict:
    from kserve_vllm_mini_tpu.runtime.local import start_local_server

    sz = sizes(dry)
    runs = [
        ("paged", {"kv_layout": "paged", "kv_block_size": sz["paged_block"]},
         # (executable label prefixes that must hold a Mosaic call)
         ["paged_prefill[", "decode[chunk=1].paged"],
         dict(chunks=sz["paged_parity"], decode=True,
              block_size=sz["paged_block"], kv_int8=False)),
        # no "decode[": llama-1b's 64-wide heads keep the eager decode read
        # (ops/paged_attention.py dense_decode_block)
        ("int8kv", {"kv_cache_dtype": "int8", "prefill_chunk": sz["chunk"]},
         ["prefill[", "chunk_prefill["],
         dict(chunks=sz["int8_parity"], decode=True, block_size=None,
              kv_int8=True)),
        # the hybrid's full-attention layers (30 KV heads x 128) take the
        # flash prefill and the dense decode kernel; continuation pieces of a
        # bf16 cache keep the eager read
        ("hybrid", {"model": sz["hybrid_model"], "quantization": "int8",
                    "prefill_chunk": sz["chunk"]},
         ["prefill[", "decode[chunk=1]"],
         dict(chunks=sz["int8_parity"], decode=True, block_size=None,
              kv_int8=False)),
        # Mamba-2, attention without rotary embedding (2 KV heads x 128: flash
        # prefill and the dense decode kernel) and expert blocks, 128 of 512
        # experts held; continuation pieces carry state and tail
        ("ssm_moe", {"model": sz["pattern_model"], "quantization": "int8",
                     "prefill_chunk": sz["chunk"]},
         ["prefill[", "decode[chunk=1]"],
         dict(chunks=sz["int8_parity"], decode=True, block_size=None,
              kv_int8=False)),
        # a Mamba-2 mixer (state 256) and rotary attention (20 / 4 heads x 128:
        # flash prefill, the dense decode kernel at G = 5) side by side in every
        # block, the muP multipliers; K/V, state and tail for every layer
        ("two_mixer", {"model": sz["parallel_model"], "quantization": "int8",
                       "prefill_chunk": sz["chunk"]},
         ["prefill[", "decode[chunk=1]"],
         dict(chunks=sz["int8_parity"], decode=True, block_size=None,
              kv_int8=False)),
        # gated short convolutions (a two-position tail, no state) and rotary
        # attention with a per-head QK-norm (32 / 8 heads x 64: flash prefill;
        # 64-wide heads keep the eager decode read, so "decode[" is not asked
        # for here: what it holds since PR 46 is the experts' sweep), a dense MLP
        # then SwiGLU experts, all 32 held; continuation pieces carry the tail
        ("conv_moe", {"model": sz["conv_model"], "quantization": "int8",
                      "prefill_chunk": sz["chunk"]},
         ["prefill["],
         dict(chunks=sz["int8_parity"], decode=True, block_size=None,
              kv_int8=False)),
    ]
    summary = {}
    for name, extra, need, parity in runs:
        profile = {"model": sz["small_model"], "max_slots": 8,
                   "max_model_len": 2048, **extra}
        server = start_local_server(profile, ready_timeout_s=900.0)
        try:
            say(f"engines[{name}]: boot_s={server.boot_seconds:.1f} "
                f"model={server.model_name} {extra}")
            # ~650-token prompts: with prefill_chunk=256 each runs one fresh
            # chunk and two continuation chunks
            load_phases(
                server, [{"name": name, "input_tokens": sz["engine_words"],
                          "requests": 4}],
                OUT_DIR / "engines", max_tokens=sz["out_tokens"] // 2, window_s=2.0, seed=3,
                read_timeout_s=900.0, concurrent=False,
            )
            exes = executables(server.engine)
            for prefix in need:
                require_mosaic(exes, prefix, dry)
            require_weights_in_place(exes, dry)
            require_state_in_place(server.engine, exes, dry)
            forward_parity(server.engine, name, dry=dry, **parity)
            hbm_line(f"engines:{name}")
            summary[name] = {"boot_s": round(server.boot_seconds, 1),
                             "engine_compiles": len(exes)}
        finally:
            server.stop()
        del server
        gc.collect()
    return summary


# -- driver ----------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default="main,kernels,engines",
                    help="comma-separated subset (a partial run never prints ok=true)")
    ap.add_argument("--topology", default=None,
                    help="serve the main leg over this mesh preset (e.g. v5e-4)")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="debug the script on the CPU: tiny presets, interpreted "
                         "kernels; never ok=true")
    args = ap.parse_args()
    legs = [x for x in args.legs.split(",") if x]
    dry = args.cpu_dry_run

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not dry:
        print(f"chip_smoke: no TPU (platform={dev.platform}); this script "
              "does not run on the CPU", file=sys.stderr)
        return 2
    if dry and dev.platform != "cpu":
        print("chip_smoke: --cpu-dry-run needs JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2

    from kserve_vllm_mini_tpu.runtime.server import configure_compile_cache

    cache_dir = configure_compile_cache()
    cache_before = cache_entries(cache_dir)
    versions = {p: metadata.version(p) for p in ("jax", "jaxlib", "libtpu")}
    say(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"count={device['count']} " + " ".join(f"{k}={v}" for k, v in versions.items()))
    say(f"compile_cache dir={cache_dir} entries_before={cache_before} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    hbm_line("start")
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    fns = {"main": partial(leg_main, dry, args.topology),
           "kernels": partial(leg_kernels, dry),
           "engines": partial(leg_engines, dry)}
    results: dict[str, dict] = {}
    t_start = time.time()
    for leg in legs:
        t0 = time.time()
        try:
            results[leg] = fns[leg]()
        except Exception as e:
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
            say(f"leg {leg}: FAIL — {e!r}")
            verdict(False, device)
            return 1
        results[leg]["leg_s"] = round(time.time() - t0, 1)
        say(f"leg {leg}: PASS ({results[leg]['leg_s']}s, set-up included)")
        gc.collect()

    hbm = hbm_line("end")
    cache_after = cache_entries(cache_dir)
    say(f"compile_cache dir={cache_dir} entries_before={cache_before} "
        f"entries_after={cache_after}")
    complete = (not dry and args.topology is None
                and legs == ["main", "kernels", "engines"])
    say("summary " + json.dumps({
        "ok": complete,
        "device": device,
        "versions": versions,
        "dry_run": dry,
        "legs": results,
        "total_s": round(time.time() - t_start, 1),
        "hbm_limit_bytes": hbm.get("bytes_limit"),
        "hbm_peak_bytes": hbm.get("peak_bytes_in_use"),
        "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                          "entries_after": cache_after},
        "claim": None,
    }))
    verdict(complete, device)
    return 0


if __name__ == "__main__":
    sys.exit(main())

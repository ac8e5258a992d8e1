"""OpenAI-compatible HTTP server over the in-repo engine.

Endpoints:

- ``POST /v1/chat/completions`` — streaming (SSE) and non-streaming, with a
  ``metrics.server_ttft_ms`` extension carrying the engine's true first-token
  latency (the loadgen records it next to the client-side TTFT; the reference
  can only approximate TTFT client-side, SURVEY.md §7.3.5)
- ``GET /v1/models`` — model listing
- ``GET /healthz`` — readiness (KServe-style probe target)
- ``GET /metrics`` — Prometheus text format: token counters, duty cycle,
  queue depth, slot occupancy. This is the runtime leg of the telemetry
  fallback chain (analysis/telemetry.py) replacing DCGM.

Run: ``kvmini-tpu serve --model llama-tiny --port 8000`` (random weights) or
``--checkpoint /path/to/hf_dir`` for real ones.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import threading
import time
import uuid
from pathlib import Path
from typing import Any, Optional

from kserve_vllm_mini_tpu.runtime.engine import (
    ADMIT_DRAIN_REASONS,
    Engine,
    EngineConfig,
    GenRequest,
)
from kserve_vllm_mini_tpu.runtime.tokenizer import Tokenizer, load_tokenizer


# <checkout>/.jax_cache: fixed (the directory is part of every cache key, so
# a path built from a temp name, a pid or the time never hits) and inside the
# tree the chip tool copies. .gitignore lists it.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache for a serving process and
    return the directory in effect (None = no persistent cache).

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — nothing is set
    in code, so whoever runs the program (a chip tool that keeps one
    directory warm between calls) decides where the cache lives. Unset, on
    an accelerator: ``DEFAULT_COMPILE_CACHE_DIR``. Unset, on the CPU
    backend: none — jaxlib 0.9.0's XLA:CPU cache can SIGILL/segfault on
    entries written by a different host CPU (tests/conftest.py), and the
    default directory travels with the checkout to the chip machine, so
    CPU runs (the test suite, a dev box) stay opt-in through the variable."""
    import os

    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE_DIR))
    # every executable, not only those over JAX's 1 s default: a step that
    # compiles in about a second is otherwise written by one run and not by
    # the next (35 vs 33 entries on two cold runs, and a warm run that still
    # added one — my chip run, PR 21), so a warm process could still compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(DEFAULT_COMPILE_CACHE_DIR)


def build_engine(
    model: str = "llama-tiny",
    checkpoint: Optional[str] = None,
    tokenizer_path: Optional[str] = None,
    max_slots: int = 8,
    max_seq_len: int = 1024,
    topology: Optional[str] = None,
    seed: int = 0,
    quantization: str = "none",
    quant_mode: str = "dequant",   # how quantized matmuls contract
                                   # (ops/qmatmul.py QUANT_MODES):
                                   # "dequant" = cast-to-bf16 epilogue,
                                   # "w8a8" = int8 MXU contraction
    kv_cache_dtype: Optional[str] = None,
    decode_chunk: int = 1,
    prefill_chunk: Optional[int] = None,  # tokens per interleaved prefill
                                   # chunk (EngineConfig.prefill_chunk);
                                   # None = monolithic admission
    disagg: bool = False,          # disaggregated prefill/decode lanes
                                   # (EngineConfig.disagg; docs/
                                   # DISAGGREGATION.md)
    disagg_min_prompt: int = 0,    # prompts below this prefill colocated
    prefill_lane_devices: int = 0, # >0: split the device set into a
                                   # prefill submesh of this many devices
                                   # + a decode mesh over the rest
                                   # (parallel/mesh.lane_meshes); needs
                                   # disagg and no other mesh source
    drafter: Optional[str] = None,
    spec_tokens: int = 0,
    pp: int = 0,
    pp_microbatches: int = 1,
    scan_unroll: int = 1,
    mesh=None,
    prefix_cache: bool = False,
    kv_layout: str = "dense",
    kv_block_size: int = 64,
    kv_pool_blocks: Optional[int] = None,
    kv_host_tier_bytes: Optional[int] = None,
    lora_adapters: Optional[dict[str, str]] = None,  # name -> PEFT dir
    lora_demo: int = 0,       # N random adapters "demo-1..N" (bench/testing)
    lora_rank: int = 8,       # rank for the demo bank (PEFT dirs carry theirs)
    lora_slots: int = 4,      # runtime-load bank capacity (load_adapter)
    request_tracing: bool = True,  # phase-span recorder (docs/TRACING.md)
    trace_buffer: int = 4096,      # span ring-buffer capacity
    faults: Optional[str] = None,  # KVMINI_FAULTS-syntax injection config
    fault_seed: int = 0,           # deterministic fault triggers
    watchdog: bool = False,        # wedged-sweep watchdog (docs/RESILIENCE.md)
    default_deadline_s: Optional[float] = None,  # deadline-aware shedding
    econ_accelerator: Optional[str] = None,  # price the live economics
                                   # rail as this chip (docs/ECONOMICS.md);
                                   # None = TPU auto-detect, no rail on CPU
) -> tuple[Engine, Tokenizer, str]:
    """Construct (engine, tokenizer, model_name) from a preset or checkpoint.

    ``drafter`` is a preset name or checkpoint dir for the speculative-decode
    draft model (reference knob: runners/profiles/speculative-decoding.yaml);
    ``spec_tokens`` is the fused propose/verify depth per round (0 disables).
    ``mesh`` overrides topology/pp mesh construction — the multi-host path
    passes the process-spanning global mesh (parallel/distributed.py).
    """
    import jax

    configure_compile_cache()

    from kserve_vllm_mini_tpu.models.config import get_config
    from kserve_vllm_mini_tpu.models.llama import init_params, init_params_quantized

    if quantization not in ("none", "int8", "int4", "int4-awq"):
        raise ValueError(
            f"unknown quantization {quantization!r}; known: none, int8, "
            "int4, int4-awq"
        )
    from kserve_vllm_mini_tpu.ops.qmatmul import validate_quant_mode

    quant_mode = validate_quant_mode(quant_mode or "dequant")
    if quantization == "none":
        # documented no-op: without quantized leaves there is nothing to
        # contract in int8, and folding w8a8 into cfg anyway would make
        # the headroom guard price a phantom activation-quant workspace
        quant_mode = "dequant"
    if kv_cache_dtype == "auto":
        # profile sentinel for "model default" (profiles/quantization/*.yaml
        # mirror the reference's 'auto'); the deploy layer drops it too
        kv_cache_dtype = None
    if kv_cache_dtype not in (None, "bfloat16", "float32", "float16", "int8"):
        raise ValueError(
            f"unsupported kv_cache_dtype {kv_cache_dtype!r}; "
            "known: auto, bfloat16, float32, float16, int8 (scaled)"
        )

    prefill_mesh = None
    if prefill_lane_devices:
        # disaggregated per-lane meshes (docs/DISAGGREGATION.md): a
        # disjoint prefill submesh + decode mesh over one device set —
        # mutually exclusive with every other mesh source, which would
        # otherwise claim the same devices twice
        if not disagg:
            raise ValueError("prefill_lane_devices requires disagg=True")
        if mesh is not None or (pp and pp > 1) or topology:
            raise ValueError(
                "prefill_lane_devices is its own mesh source; drop "
                "mesh/pp/topology (the lanes split the device set "
                "themselves via parallel/mesh.lane_meshes)"
            )
        from kserve_vllm_mini_tpu.parallel.mesh import lane_meshes

        prefill_mesh, mesh = lane_meshes(prefill_lane_devices)
    if mesh is not None and prefill_mesh is None:
        pass  # caller-provided (multi-host global mesh)
    elif prefill_mesh is not None:
        pass  # lane split above
    elif pp and pp > 1:
        # serving pipeline parallelism: layer-range stages over a pure-pp
        # mesh (parallel/serving_pp.py); needs exactly pp devices
        from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec(pp=pp))
    elif topology:
        from kserve_vllm_mini_tpu.parallel.mesh import mesh_for_topology

        mesh = mesh_for_topology(topology)

    tok = load_tokenizer(tokenizer_path or checkpoint)
    if checkpoint:
        from kserve_vllm_mini_tpu.models.loader import load_hf_checkpoint

        # quantize-as-you-load: the bf16 8B tree must never fully exist on
        # device (it would not fit a 16 GB v5e).
        # int4-awq is the exception: calibration needs the fp tree + one
        # eager forward (ops/awq.py memory note) — calibrate 8B off-chip.
        params, cfg = load_hf_checkpoint(
            checkpoint,
            quantize="none" if quantization == "int4-awq" else quantization,
        )
        if scan_unroll > 1:
            cfg = cfg.scaled(scan_unroll=scan_unroll)
        name = cfg.name
    else:
        cfg = get_config(model)
        if tok.vocab_size > cfg.vocab_size:
            cfg = cfg.scaled(vocab_size=tok.vocab_size)
        if scan_unroll > 1:
            cfg = cfg.scaled(scan_unroll=scan_unroll)
        # int8 presets init straight into int8 leaves: materializing the bf16
        # 8B tree first is itself an OOM on a 16 GB v5e
        if quantization in ("int8", "int4"):
            from functools import partial as _p

            init_fn = _p(init_params_quantized,
                         bits=4 if quantization == "int4" else 8)
        else:
            init_fn = init_params
        if mesh is not None:
            # init DIRECTLY into the mesh layout (out_shardings on the jitted
            # init) — a full single-device tree + device_put would OOM the
            # very deployments the mesh exists for
            from functools import partial as _partial

            from kserve_vllm_mini_tpu.parallel.sharding import param_shardings

            tree = jax.eval_shape(_partial(init_fn, cfg=cfg), jax.random.PRNGKey(seed))
            shardings = param_shardings(cfg, mesh, params=tree)
            params = jax.jit(
                _partial(init_fn, cfg=cfg), out_shardings=shardings
            )(jax.random.PRNGKey(seed))
        else:
            params = init_fn(jax.random.PRNGKey(seed), cfg)
        name = cfg.name
    if quant_mode != "dequant":
        # static trace-time knob: every execution path threads cfg, so the
        # config is where the mode rides (models/config.py quant_mode)
        cfg = cfg.scaled(quant_mode=quant_mode)
    if quantization == "int4-awq":
        # activation-aware calibration (ops/awq.py): stats from one eager
        # forward of the embedded corpus through the live tokenizer, then
        # per-layer alpha-searched scales; the fp tree is dropped after
        from kserve_vllm_mini_tpu.ops.awq import (
            calibration_tokens,
            quantize_params_awq,
        )

        cal = calibration_tokens(cfg.vocab_size, tok)
        params = quantize_params_awq(params, cfg, tokens=cal, bits=4)

    if mesh is not None and (checkpoint or quantization == "int4-awq"):
        from kserve_vllm_mini_tpu.parallel.sharding import shard_params

        params = shard_params(params, cfg, mesh)

    drafter_pair = None
    if drafter and spec_tokens > 0:
        import os

        if os.path.isdir(drafter):
            from kserve_vllm_mini_tpu.models.loader import load_hf_checkpoint

            # the drafter rides the target's quantization: spec decode and
            # quantization compose (the engine folds quant_mode into the
            # drafter cfg too, so w8a8 rounds contract the drafter int8)
            dparams, dcfg = load_hf_checkpoint(
                drafter,
                quantize="none" if quantization == "int4-awq" else quantization,
            )
        else:
            dcfg = get_config(drafter)
            if tok.vocab_size > dcfg.vocab_size:
                dcfg = dcfg.scaled(vocab_size=tok.vocab_size)
            if quantization in ("int8", "int4"):
                dparams = init_params_quantized(
                    jax.random.PRNGKey(seed + 1), dcfg,
                    bits=4 if quantization == "int4" else 8,
                )
            else:
                dparams = init_params(jax.random.PRNGKey(seed + 1), dcfg)
        if dcfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"drafter vocab {dcfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}; speculative verify compares token ids"
            )
        drafter_pair = (dparams, dcfg)

    # multi-LoRA bank: PEFT checkpoint adapters, or a random demo bank so
    # multi-adapter serving can be benchmarked without fine-tuned weights
    lora_bank = None
    if lora_adapters:
        from kserve_vllm_mini_tpu.ops.lora import (
            LORA_TARGETS_ALL,
            install_adapter,
            load_peft_adapter,
            zero_lora_bank,
        )

        loaded = {
            nm: load_peft_adapter(path, cfg, targets=LORA_TARGETS_ALL)
            for nm, path in lora_adapters.items()
        }
        ranks = {
            # max over EVERY target: PEFT rank_pattern adapters carry
            # per-target ranks, and the bank must fit the largest (the
            # engine hot-swap path computes in_rank the same way)
            nm: max(a.shape[-1] for a, _b in ad.values())
            for nm, ad in loaded.items()
        }
        # mixed ranks share one bank at the MAX rank: zero-padding a
        # lower-rank adapter's factors is exact (the padding contributes
        # nothing to A @ B), same mechanism hot-swap growth uses
        from kserve_vllm_mini_tpu.ops.lora import pad_adapter_rank

        rank = max(ranks.values())
        targets = sorted({t for ad in loaded.values() for t in ad})
        bank = zero_lora_bank(cfg, len(loaded), rank, targets=targets,
                              dtype=cfg.jnp_dtype)
        names: dict[str, int] = {}
        for i, (nm, ad) in enumerate(sorted(loaded.items()), start=1):
            bank = install_adapter(bank, i, pad_adapter_rank(ad, rank))
            names[nm] = i
        bank["names"] = names
        lora_bank = bank
    elif lora_demo:
        from kserve_vllm_mini_tpu.ops.lora import init_lora_bank

        lora_bank = init_lora_bank(
            jax.random.PRNGKey(seed + 1), cfg, lora_demo, rank=lora_rank,
            dtype=cfg.jnp_dtype,
        )
        lora_bank["names"] = {f"demo-{i}": i for i in range(1, lora_demo + 1)}

    ecfg = EngineConfig(
        max_slots=max_slots,
        max_seq_len=min(max_seq_len, cfg.max_seq_len),
        max_prefill_len=min(max_seq_len, cfg.max_seq_len) // 2,
        seed=seed,
        kv_cache_dtype=kv_cache_dtype,
        quant_mode=quant_mode,
        decode_chunk=decode_chunk,
        prefill_chunk=prefill_chunk,
        disagg=disagg,
        disagg_min_prompt=disagg_min_prompt,
        spec_tokens=spec_tokens if drafter_pair is not None else 0,
        pp_microbatches=pp_microbatches,
        prefix_cache=prefix_cache,
        kv_layout=kv_layout,
        kv_block_size=kv_block_size,
        kv_pool_blocks=kv_pool_blocks,
        kv_host_tier_bytes=kv_host_tier_bytes,
        lora_slots=lora_slots,
        request_tracing=request_tracing,
        trace_buffer=trace_buffer,
        faults=faults,
        fault_seed=fault_seed,
        watchdog=watchdog,
        default_deadline_s=default_deadline_s,
        econ_accelerator=econ_accelerator,
    )
    engine = Engine(
        params, cfg, ecfg, mesh=mesh, pad_id=tok.pad_id, drafter=drafter_pair,
        lora=lora_bank, prefill_mesh=prefill_mesh,
    )
    return engine, tok, name


def make_app(engine: Engine, tok: Tokenizer, model_name: str,
             multihost: bool = False, alive_check=None,
             allow_fault_injection: bool = False):
    # default health gate: the engine's own scheduler liveness — a crashed
    # _loop drops _running and the frontend must refuse, not enqueue
    # forever. The multihost primary overrides with its driver thread's
    # liveness (the engine thread never starts in that mode).
    if alive_check is None:
        alive_check = lambda: engine._running  # noqa: E731
    from aiohttp import web

    started = time.time()

    def _shed_response(message: str) -> "web.Response":
        """ONE wire shape for every shed site (docs/RESILIENCE.md): the
        at-the-door 429, the non-streaming queue-expiry conversion, and
        the streaming first-event peek all speak this, so the loadgen's
        retry contract can never fork between them."""
        return web.json_response(
            {"error": {
                "message": message,
                "type": "overloaded_error",
                "code": "request_shed",
            }},
            status=429,
            headers={"Retry-After": str(max(
                1, int(engine.estimate_wait_s() + 0.999)
            ))},
        )

    def _messages_to_prompt(messages: list[dict[str, Any]]) -> str:
        parts = []
        for m in messages:
            parts.append(f"{m.get('role', 'user')}: {m.get('content', '')}")
        parts.append("assistant:")
        return "\n".join(parts)

    def _stable_len(text: str) -> int:
        """Chars of ``text`` that no future token can revise: a TRAILING
        run of U+FFFD is an incomplete multibyte sequence still being
        assembled (byte-level tokens split UTF-8 chars across tokens) and
        must not be emitted — the next token may resolve it to the real
        char. Interior replacements are final (later bytes cannot rewrite
        already-decoded output) and flush normally; a genuinely invalid
        trailing sequence flushes in the done-event tail."""
        n = len(text)
        while n > 0 and text[n - 1] == "�":
            n -= 1
        return n

    def _first_stop_hit(text: str, stops: list[str]) -> Optional[int]:
        """Character index of the earliest stop-sequence occurrence."""
        best: Optional[int] = None
        for s in stops:
            i = text.find(s)
            if i >= 0 and (best is None or i < best):
                best = i
        return best

    def _lp_entry(token_id: int, lp_info, top_n: int) -> dict[str, Any]:
        """OpenAI logprobs.content entry for one emitted token. -inf
        alternatives (grammar-masked bytes) are dropped: json.dumps would
        render them as '-Infinity', which is not RFC-valid JSON."""
        import math

        text = tok.decode([token_id])
        lp, top = lp_info
        return {
            "token": text,
            "logprob": lp,
            "bytes": list(text.encode()),
            "top_logprobs": [
                {"token": tok.decode([tid]), "logprob": tlp,
                 "bytes": list(tok.decode([tid]).encode())}
                for tid, tlp in top[:top_n]
                if math.isfinite(tlp)
            ],
        }

    # HF-vocab grammar table: one precomputation per server (token id ->
    # byte expansion + single-byte/string-safe indexes), built EAGERLY at
    # app construction — on the request path it would block the event loop
    # for the full ~vocab-size expansion. False = tokenizer can't support
    # grammar masking (the reason is appended); None = ByteTokenizer server
    # (no table needed).
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    _hf_vocab_cache: list[Any] = [None]
    if not isinstance(tok, ByteTokenizer):
        from kserve_vllm_mini_tpu.runtime.token_grammar import (
            HFVocabTable,
            token_bytes_table,
        )

        try:
            _hf_vocab_cache[0] = HFVocabTable(token_bytes_table(tok))
        except Exception as e:  # noqa: BLE001 — degrade to honest reject
            _hf_vocab_cache[0] = False
            _hf_vocab_cache.append(str(e))

    def _wrap_machine(machine, tool_names=()):
        """Lift a byte automaton to the engine's token protocol for this
        server's tokenizer (runtime/token_grammar.py): identity byte
        mapping for the ByteTokenizer, byte-expansion table for real HF
        vocabularies. Returns (wrapped, err)."""
        from kserve_vllm_mini_tpu.runtime.token_grammar import (
            ByteTokenMachine,
            HFTokenMachine,
        )

        if isinstance(tok, ByteTokenizer):
            return ByteTokenMachine(machine, engine.cfg.vocab_size), None
        if _hf_vocab_cache[0] is False:
            return None, (
                "tools/json_mode unavailable for this tokenizer: "
                f"{_hf_vocab_cache[-1]}"
            )
        # a tool-name byte with no single-token representation would leave
        # the template grammar's forced path unmaskable (deadlock) — reject
        # the request up front instead
        missing = sorted({
            c for n in tool_names for c in n.encode()
            if c not in _hf_vocab_cache[0].single
        })
        if missing:
            return None, (
                "tool name characters lack single-token representations in "
                f"this tokenizer: {[chr(c) for c in missing]!r}"
            )
        try:
            return HFTokenMachine(
                machine, _hf_vocab_cache[0], engine.cfg.vocab_size
            ), None
        except ValueError as e:
            return None, str(e)

    def _build_constraint(body: dict[str, Any], max_tokens: int):
        """Constraint machine + tool flag from the request, or an error str.

        The byte automata (runtime/constrain.py) define the grammar; the
        token_grammar adapter maps it onto this server's vocabulary, so
        json_mode/tools work for the ByteTokenizer AND real HF checkpoints
        (VERDICT round-3 weak #3)."""
        from kserve_vllm_mini_tpu.runtime.constrain import (
            json_constraint,
            tool_call_constraint,
        )

        import re

        tools = body.get("tools") or []
        tool_choice = body.get("tool_choice", "auto" if tools else "none")
        wants_tools = bool(tools) and tool_choice != "none"
        rf = (body.get("response_format") or {}).get("type")
        if rf not in (None, "text", "json_object"):
            # e.g. json_schema: unsupported — reject rather than return
            # unconstrained output under a structured-output contract
            return None, False, f"response_format type {rf!r} is not supported"
        wants_json = rf == "json_object"
        if not (wants_tools or wants_json):
            return None, False, None
        if multihost:
            # constraint masks are host-built per token; the lockstep
            # channel does not carry them yet (runtime/multihost.py v1)
            return None, False, (
                "tools/json_mode are not yet supported in multi-host serving"
            )
        if wants_tools:
            names = [
                t.get("function", {}).get("name", "")
                for t in tools if t.get("type") == "function"
            ]
            names = [n for n in names if n]
            bad = [n for n in names if not re.fullmatch(r"[a-zA-Z0-9_-]{1,64}", n)]
            if bad:
                # names are interpolated into the byte-template grammar; a
                # quote or backslash would break the emitted JSON (OpenAI
                # enforces this same charset)
                return None, False, f"invalid tool name(s): {bad!r}"
            if isinstance(tool_choice, dict):  # {"type":"function","function":{"name":...}}
                forced = tool_choice.get("function", {}).get("name")
                if forced not in names:
                    return None, False, (
                        f"tool_choice names {forced!r} which is not in tools"
                    )
                names = [forced]
            if not names:
                return None, False, "tools given but no function names"
            machine = tool_call_constraint(
                names, parallel=bool(body.get("parallel_tool_calls")) and len(names) > 1
            )
        else:
            machine = json_constraint()
        if max_tokens < machine.min_close():
            return None, False, (
                f"max_tokens={max_tokens} cannot fit the constrained format "
                f"(needs >= {machine.min_close()})"
            )
        wrapped, werr = _wrap_machine(
            machine, tool_names=names if wants_tools else ()
        )
        if werr:
            return None, False, werr
        return wrapped, wants_tools, None

    def _constrained_text(ids: list[int]) -> str:
        """Constrained output must be reconstructed from the SAME byte
        expansions the automaton validated: ``tok.decode`` may join tokens
        with separators (WordLevel) or apply cleanup that desyncs the text
        from the grammar-approved byte string. ByteTokenizer servers have
        no table — their decode IS the byte expansion."""
        if _hf_vocab_cache[0]:
            table = _hf_vocab_cache[0].table
            raw = b"".join(
                (table[t] or b"") if t < len(table) else b"" for t in ids
            )
            return raw.decode("utf-8", errors="replace")
        return tok.decode(ids)

    def _tool_calls_from_text(text: str) -> Optional[list[dict[str, Any]]]:
        """Parse our canonical constrained transcript back into OpenAI
        tool_calls entries."""
        try:
            calls = json.loads(text)
        except json.JSONDecodeError:  # kvmini: workload-ok — unconstrained
            # runs may emit free text; the response then carries `content`
            # instead of tool_calls, which IS the surfaced outcome
            return None
        if not isinstance(calls, list):
            return None
        out = []
        for i, c in enumerate(calls):
            if not isinstance(c, dict) or "name" not in c:
                return None
            out.append({
                "id": f"call_{uuid.uuid4().hex[:8]}_{i}",
                "type": "function",
                "function": {
                    "name": c["name"],
                    "arguments": json.dumps(c.get("arguments", {})),
                },
            })
        return out

    async def chat(request: "web.Request"):
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": {"message": "invalid JSON body"}}, status=400)
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            return web.json_response(
                {"error": {"message": "'messages' must be a non-empty list"}}, status=400
            )
        if not alive_check():
            # a dead scheduler must refuse, not enqueue forever — the load
            # balancer sees 503 here and on /healthz and rotates the replica
            return web.json_response(
                {"error": {"message": "scheduler is not running"}}, status=503
            )
        # Deadline-aware admission (docs/RESILIENCE.md): the client's
        # deadline_ms (body field or x-request-deadline-ms header) or the
        # server default. A request whose estimated COMPLETION time —
        # queue depth x rolling service time — already exceeds its
        # deadline is shed HERE with 429 + Retry-After instead of timing
        # out after burning decode steps on work nobody can use.
        raw_deadline = body.get("deadline_ms")
        if raw_deadline is None:
            raw_deadline = request.headers.get("x-request-deadline-ms")
        deadline_s: Optional[float] = None
        if raw_deadline is not None:
            try:
                deadline_s = float(raw_deadline) / 1000.0
            except (TypeError, ValueError):
                return web.json_response(
                    {"error": {"message": "deadline_ms must be a number"}},
                    status=400,
                )
            if deadline_s <= 0:
                return web.json_response(
                    {"error": {"message": "deadline_ms must be > 0"}},
                    status=400,
                )
        if deadline_s is None:
            deadline_s = engine.ecfg.default_deadline_s
        if deadline_s is not None:
            est = engine.estimate_wait_s()
            if est > deadline_s:
                engine.count_shed()
                return _shed_response(
                    f"shed: estimated completion {est:.2f}s exceeds "
                    f"the {deadline_s:.2f}s deadline at current load"
                )
        max_tokens = int(body.get("max_tokens", 64))
        machine, wants_tools, err = _build_constraint(body, max_tokens)
        if err:
            return web.json_response({"error": {"message": err}}, status=400)
        want_logprobs = bool(body.get("logprobs", False))
        top_lp = int(body.get("top_logprobs", 0) or 0)
        if top_lp < 0:
            return web.json_response(
                {"error": {"message": "top_logprobs must be >= 0"}}, status=400
            )
        top_lp = min(top_lp, 5)
        # OpenAI sampling knobs the reference's loadgen sends to vLLM
        # (reference scripts/loadtest.py:260-342): presence/frequency
        # penalties and n/best_of fan-out. The in-repo engine must honor
        # what the load generator exercises — silently dropping them would
        # measure a different workload than the one requested.
        try:
            pres = float(body.get("presence_penalty", 0.0) or 0.0)
            freq = float(body.get("frequency_penalty", 0.0) or 0.0)
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "penalties must be numbers"}}, status=400
            )
        if not (-2.0 <= pres <= 2.0 and -2.0 <= freq <= 2.0):
            return web.json_response(
                {"error": {"message":
                           "presence_penalty/frequency_penalty must be in "
                           "[-2, 2]"}}, status=400
            )
        try:
            _n_raw = body.get("n")
            n_choices = 1 if _n_raw is None else int(_n_raw)
            _bo_raw = body.get("best_of")
            fanout = n_choices if _bo_raw is None else int(_bo_raw)
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "n/best_of must be integers"}}, status=400
            )
        if n_choices < 1 or fanout < n_choices:
            return web.json_response(
                {"error": {"message": "need 1 <= n <= best_of"}}, status=400
            )
        if fanout > engine.ecfg.max_slots:
            return web.json_response(
                {"error": {"message":
                           f"best_of={fanout} exceeds the engine's "
                           f"{engine.ecfg.max_slots} slots"}}, status=400
            )
        if body.get("stream", False) and fanout > n_choices:
            # OpenAI semantics: best_of ranking needs every candidate
            # complete before any can stream
            return web.json_response(
                {"error": {"message": "best_of > n cannot stream"}}, status=400
            )
        prompt = _messages_to_prompt(messages)
        prompt_ids = tok.encode(prompt)
        # multi-LoRA routing (vLLM convention): "model" names either the
        # base model or a loaded adapter. The loadgen's placeholder
        # "default" always means the base, and with NO adapters loaded
        # unknown names keep the legacy ignore-the-field behavior (every
        # pre-LoRA profile sends "default"); once adapters exist, a name
        # that matches nothing 404s — silently serving the base where a
        # fine-tune was requested would be a measurement lie
        req_model = body.get("model")
        adapter = None
        adapter_names = getattr(engine, "_lora_names", {})
        if (
            adapter_names
            and req_model
            and req_model not in (model_name, "default")
        ):
            if req_model in adapter_names:
                adapter = req_model
            else:
                return web.json_response(
                    {"error": {
                        "message": (
                            f"model {req_model!r} not found; available: "
                            f"{[model_name, *sorted(adapter_names)]}"
                        ),
                        "type": "invalid_request_error",
                        "code": "model_not_found",
                    }},
                    status=404,
                )
        # best_of ranking needs per-token logprobs even when the client did
        # not ask for them (they are stripped from the response)
        # OpenAI stop sequences (vLLM honors them; the loadgen sends them
        # when a profile sets params.stop — a dropped knob measures a
        # different workload). Detection is server-side over decoded text;
        # a hit cancels the engine slot (Engine.cancel) so the remaining
        # budget isn't decoded into the batch. Grammar-constrained and
        # tool requests ignore stop: the grammar defines completion.
        stop_raw = body.get("stop")
        stops: list[str] = []
        if stop_raw is not None and machine is None and not wants_tools:
            if isinstance(stop_raw, str):
                stops = [stop_raw] if stop_raw else []
            elif isinstance(stop_raw, list) and all(
                isinstance(s, str) for s in stop_raw
            ):
                stops = [s for s in stop_raw if s]
            else:
                return web.json_response(
                    {"error": {"message":
                               "'stop' must be a string or list of strings"}},
                    status=400,
                )
            if len(stops) > 4:
                return web.json_response(
                    {"error": {"message": "'stop' supports at most 4 sequences"}},
                    status=400,
                )
        max_stop_len = max((len(s) for s in stops), default=0)

        # W3C trace context: parent the engine's phase spans under the
        # client's http.request span so /traces joins the loadgen's trace
        # by trace_id (docs/TRACING.md). Malformed headers are ignored —
        # the engine mints a local trace id instead.
        from kserve_vllm_mini_tpu.runtime.tracing import parse_traceparent

        trace_ctx = parse_traceparent(request.headers.get("traceparent"))
        rank_lp = fanout > n_choices
        req = GenRequest(
            prompt_tokens=prompt_ids or [tok.bos_id],
            max_new_tokens=max_tokens,
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            presence_penalty=pres,
            frequency_penalty=freq,
            eos_id=None if machine is not None else tok.eos_id,
            logprobs=want_logprobs or rank_lp,
            top_logprobs=top_lp,
            constraint=machine,
            adapter=adapter,
            trace_id=trace_ctx[0] if trace_ctx else None,
            parent_span_id=trace_ctx[1] if trace_ctx else None,
            deadline_s=deadline_s,
        )
        all_reqs = [req]
        for _ in range(fanout - 1):
            # each candidate needs its OWN grammar machine (stateful) and
            # its own prompt list (submit rebinds it on truncation)
            m_i = None
            if machine is not None:
                m_i, _, err_i = _build_constraint(body, max_tokens)
                if err_i:  # cannot happen if the first build succeeded
                    return web.json_response(
                        {"error": {"message": err_i}}, status=400
                    )
            all_reqs.append(dataclasses.replace(
                req,
                prompt_tokens=list(req.prompt_tokens),
                request_id=uuid.uuid4().hex[:16],
                constraint=m_i,
            ))
        handles = [engine.submit(r) for r in all_reqs]
        handle = handles[0]
        rid = f"chatcmpl-{uuid.uuid4().hex[:20]}"
        created = int(time.time())
        # OpenAI semantics: echo the served model — the adapter name when
        # the request was routed to one, else the base
        resp_model = adapter or model_name
        loop = asyncio.get_running_loop()

        async def next_event():
            return await loop.run_in_executor(None, handle.events.get)

        if not body.get("stream", False):
            def collect_sync(h: Any) -> tuple:
                """Drain one candidate: (token ids, logprob entries,
                cumulative chosen-token logprob, done info, stop-cut char
                index or None). On a stop-sequence hit the engine slot is
                cancelled — the drain continues (events already queued
                still arrive) but the budget stops burning device steps.
                Runs in a DEDICATED thread per candidate so every
                candidate's stop detection is live concurrently — a
                sequential drain would not cancel candidate k's hit until
                candidates 0..k-1 finished their whole budgets."""
                ids: list[int] = []
                entries: list[dict[str, Any]] = []
                lp_sum = 0.0
                stop_cut: Optional[int] = None
                while True:
                    kind, *rest = h.events.get()
                    if kind == "token":
                        if stop_cut is not None:
                            # surplus between the stop hit and the
                            # scheduler processing the cancel: dropped
                            # everywhere (ids/lp_sum/usage), or best_of
                            # ranking would depend on scheduler timing
                            continue
                        ids.append(rest[0])
                        if len(rest) > 2 and rest[2] is not None:
                            lp_sum += rest[2][0]
                            if want_logprobs:
                                entries.append(
                                    _lp_entry(rest[0], rest[2], top_lp)
                                )
                        if stops:
                            hit = _first_stop_hit(tok.decode(ids), stops)
                            if hit is not None:
                                stop_cut = hit
                                engine.cancel(h)
                    else:
                        return ids, entries, lp_sum, rest[0], stop_cut

            async def collect_all() -> list:
                futs = [loop.create_future() for _ in handles]

                def worker(h: Any, fut: Any) -> None:
                    try:
                        res = collect_sync(h)
                    except BaseException as e:  # noqa: BLE001 — must reach
                        # the awaiting coroutine, not die in the thread
                        loop.call_soon_threadsafe(fut.set_exception, e)
                        return
                    loop.call_soon_threadsafe(fut.set_result, res)

                for h, f in zip(handles, futs):
                    threading.Thread(
                        target=worker, args=(h, f), daemon=True
                    ).start()
                return list(await asyncio.gather(*futs))

            collected = await collect_all()
            for _ids, _e, _lp, info, _cut in collected:
                if info.get("finish_reason") == "error":
                    # e.g. the constrained grammar cannot close inside the
                    # KV window — surface the engine's message, don't 200 it
                    return web.json_response(
                        {"error": {"message": info.get("error", "engine error")}},
                        status=400,
                    )
                if info.get("finish_reason") == "shed":
                    # deadline expired while queued (docs/RESILIENCE.md):
                    # same wire contract as the at-the-door shed — a 200
                    # with zero tokens would count as a healthy request
                    return _shed_response(info.get("error", "request shed"))
            # usage counts EVERY candidate actually generated (OpenAI/vLLM
            # accounting): best_of work that ranking discards was still
            # decoded, and a benchmark computing tokens/sec from usage must
            # see the served work, not the kept subset
            completion_tokens = sum(len(c[0]) for c in collected)
            if fanout > n_choices:
                # best_of: keep the n candidates with the highest log
                # probability PER TOKEN (OpenAI's documented ranking —
                # length-normalized, so a short early-EOS candidate cannot
                # beat a longer, better-average one on raw sum; stable sort
                # keeps submission order on ties)
                collected = sorted(
                    collected, key=lambda c: -c[2] / max(len(c[0]), 1)
                )[:n_choices]
            choices: list[dict[str, Any]] = []
            for idx, (out_ids, lp_entries, _lp_sum, info, stop_cut) in \
                    enumerate(collected):
                text = (
                    _constrained_text(out_ids) if machine is not None
                    else tok.decode(out_ids)
                )
                finish = info.get("finish_reason", "stop")
                if stop_cut is not None:
                    # OpenAI semantics: output ends BEFORE the matched stop
                    # sequence (the match itself is not returned); surfaced
                    # to the client via finish_reason
                    text = text[:stop_cut]
                    finish = "stop"
                message: dict[str, Any] = {"role": "assistant", "content": text}
                if wants_tools:
                    calls = _tool_calls_from_text(text)
                    if calls is not None:
                        message = {"role": "assistant", "content": None,
                                   "tool_calls": calls}
                        finish = "tool_calls"
                choice: dict[str, Any] = {
                    "index": idx,
                    "message": message,
                    "finish_reason": finish,
                }
                if want_logprobs:
                    choice["logprobs"] = {"content": lp_entries}
                choices.append(choice)
            info0 = collected[0][3]  # noqa: E501 — done info of choice 0
            return web.json_response(
                {
                    "id": rid,
                    "object": "chat.completion",
                    "created": created,
                    "model": resp_model,
                    "choices": choices,
                    "usage": {
                        "prompt_tokens": len(prompt_ids),
                        "completion_tokens": completion_tokens,
                        "total_tokens": len(prompt_ids) + completion_tokens,
                    },
                    "metrics": {
                        "server_ttft_ms": handle.server_ttft_ms,
                        "truncated": bool(info0.get("truncated", False)),
                        "truncated_tokens": int(info0.get("truncated_tokens", 0)),
                    },
                }
            )

        # Streaming (n==1 included — ONE emitter for every n, so chunk
        # shape can never drift between a single- and a multi-choice
        # path): merge the candidates' event queues and tag every chunk
        # with its choice index — the OpenAI interleaved-stream shape.
        # Identical submit-time parameters mean a submit rejection hits
        # every candidate, so peeking choice 0 covers the
        # 400-before-SSE case (a 400 is impossible once stream headers
        # have gone out).
        first_event = await next_event()
        if (
            first_event[0] == "done"
            and first_event[1].get("finish_reason") == "error"
        ):
            return web.json_response(
                {"error": {"message":
                           first_event[1].get("error", "engine error")}},
                status=400,
            )
        if (
            first_event[0] == "done"
            and first_event[1].get("finish_reason") == "shed"
        ):
            # engine-side deadline shed lands BEFORE any token, so the
            # peek catches it while a 429 can still go out (same
            # contract as the non-streaming path)
            return _shed_response(first_event[1].get("error", "request shed"))
        merged: asyncio.Queue = asyncio.Queue()

        # DEDICATED daemon threads, not the shared default executor: a
        # pump blocks on events.get for its candidate's whole lifetime,
        # and a few concurrent n=8 streams would otherwise pin every
        # worker of the shared pool and stall unrelated handlers. Each
        # thread exits at its candidate's 'done'; on client disconnect
        # the engine still finishes the slot, so the thread is bounded.
        def pump(idx: int, h: Any) -> None:
            while True:
                evt = h.events.get()
                loop.call_soon_threadsafe(merged.put_nowait, (idx, evt))
                if evt[0] == "done":
                    return

        # choice 0's first event was consumed by the peek — replay it,
        # then pump every queue (pump 0 resumes from its second event;
        # if the peeked event already WAS its 'done', there is nothing
        # left to pump for it)
        await merged.put((0, tuple(first_event)))
        for _i, _h in enumerate(handles):
            if _i > 0 or first_event[0] != "done":
                threading.Thread(
                    target=pump, args=(_i, _h), daemon=True
                ).start()

        resp = web.StreamResponse(
            status=200,
            headers={"Content-Type": "text/event-stream",
                     "Cache-Control": "no-cache"},
        )
        await resp.prepare(request)
        # sse_disconnect injection (docs/RESILIENCE.md): when the point
        # fires for this stream, drop the transport after after_tokens
        # streamed chunks — a mid-stream network fault, exercised by the
        # local chaos harness. None on every un-armed server.
        sse_cut: Optional[int] = None
        cut_spec = engine.check_fault("sse_disconnect")
        if cut_spec is not None:
            sse_cut = max(int(cut_spec.after_tokens), 1)
        sse_streamed = 0
        per_out = [0] * len(handles)
        per_first = [False] * len(handles)
        per_tools: list[list[int]] = [[] for _ in handles]
        # Incremental detokenization state: the authoritative text is the
        # FULL re-decode of the ids so far (per-token decode([id]) loses
        # HF-tokenizer spacing — 'Ġn' decodes alone as 'n' but in context
        # as ' n' — so piece concatenation would drift from the
        # non-streaming text). per_sent tracks chars already emitted; with
        # stop sequences a tail of (max stop length - 1) chars is held
        # back so a stop split across tokens is never partially emitted.
        # Full re-decode is O(n²) tokens per request — bounded by
        # max_seq_len (tens of ms of host work at 2k tokens, in the event
        # loop, far under the device step time it overlaps); a trailing-
        # window decode would need per-tokenizer prefix-artifact handling
        # for chars the window boundary perturbs.
        per_ids: list[list[int]] = [[] for _ in handles]
        per_full = [""] * len(handles)
        per_sent = [0] * len(handles)
        per_stopped = [False] * len(handles)
        # logprob entries of tokens whose text is currently held back:
        # carried to the next chunk that actually emits for the choice, so
        # the stream's entry count matches the non-streaming response
        per_lp_pending: list[list[dict[str, Any]]] = [[] for _ in handles]
        done_count = 0
        try:
            while done_count < len(handles):
                idx, (kind, *rest) = await merged.get()
                if kind == "token":
                    if per_stopped[idx]:
                        # surplus decoded between the stop hit and the
                        # scheduler processing the cancel: swallowed AND
                        # uncounted, so streamed usage matches the
                        # non-streaming accounting deterministically
                        continue
                    per_out[idx] += 1
                    if wants_tools:
                        per_tools[idx].append(rest[0])
                        if not per_first[idx]:
                            await resp.write((
                                "data: " + json.dumps({
                                    "id": rid,
                                    "object": "chat.completion.chunk",
                                    "created": created,
                                    "model": resp_model,
                                    "choices": [{"index": idx, "delta": {},
                                                 "finish_reason": None}],
                                    "metrics": {"server_ttft_ms":
                                                handles[idx].server_ttft_ms},
                                }) + "\n\n").encode())
                            per_first[idx] = True
                        continue
                    if want_logprobs and len(rest) > 2 and rest[2] is not None:
                        # recorded BEFORE any hold-back: a held token's
                        # entry rides the next emitted chunk
                        per_lp_pending[idx].append(
                            _lp_entry(rest[0], rest[2], top_lp)
                        )
                    if machine is not None:
                        # the byte machine's transcript is byte-exact; stop
                        # is disabled for constrained requests at parse time
                        piece = _constrained_text([rest[0]])
                    else:
                        per_ids[idx].append(rest[0])
                        per_full[idx] = tok.decode(per_ids[idx])
                        hit = (_first_stop_hit(per_full[idx], stops)
                               if stops else None)
                        if hit is not None:
                            per_stopped[idx] = True
                            engine.cancel(handles[idx])
                            cut = max(hit, per_sent[idx])
                            piece = per_full[idx][per_sent[idx]:cut]
                            per_sent[idx] = cut
                        else:
                            holdback = max_stop_len - 1 if stops else 0
                            safe = min(
                                len(per_full[idx]) - holdback,
                                _stable_len(per_full[idx]),
                            )
                            if safe > per_sent[idx]:
                                piece = per_full[idx][per_sent[idx]:safe]
                                per_sent[idx] = safe
                            else:
                                piece = ""
                        if not piece and per_first[idx]:
                            continue  # held back; metrics already sent
                    chunk_choice = {
                        "index": idx, "delta": {"content": piece},
                        "finish_reason": None,
                    }
                    if want_logprobs and per_lp_pending[idx]:
                        chunk_choice["logprobs"] = {
                            "content": per_lp_pending[idx]
                        }
                        per_lp_pending[idx] = []
                    evt = {
                        "id": rid, "object": "chat.completion.chunk",
                        "created": created, "model": resp_model,
                        "choices": [chunk_choice],
                    }
                    if not per_first[idx]:
                        evt["metrics"] = {
                            "server_ttft_ms": handles[idx].server_ttft_ms
                        }
                        per_first[idx] = True
                    await resp.write(f"data: {json.dumps(evt)}\n\n".encode())
                    sse_streamed += 1
                    if sse_cut is not None and sse_streamed >= sse_cut:
                        # injected mid-stream disconnect: drop the
                        # transport the way a network fault would, then
                        # run the normal client-gone cleanup below
                        if request.transport is not None:
                            request.transport.close()
                        raise ConnectionResetError("injected sse_disconnect")
                else:
                    done_count += 1
                    info = rest[0]
                    final_delta: dict[str, Any] = {}
                    finish = info.get("finish_reason", "stop")
                    if per_stopped[idx]:
                        finish = "stop"
                    elif machine is None:
                        # flush the held-back tail (stop never matched) /
                        # any decode-revision residue
                        tail = per_full[idx][per_sent[idx]:]
                        if tail:
                            final_delta = {"content": tail}
                            per_sent[idx] = len(per_full[idx])
                    if wants_tools:
                        calls = _tool_calls_from_text(
                            _constrained_text(per_tools[idx])
                        )
                        if calls is not None:
                            final_delta = {"tool_calls": calls}
                            finish = "tool_calls"
                    final_choice: dict[str, Any] = {
                        "index": idx, "delta": final_delta,
                        "finish_reason": finish,
                    }
                    if want_logprobs and per_lp_pending[idx]:
                        # entries for tokens whose text only flushes here
                        final_choice["logprobs"] = {
                            "content": per_lp_pending[idx]
                        }
                        per_lp_pending[idx] = []
                    final = {
                        "id": rid, "object": "chat.completion.chunk",
                        "created": created, "model": resp_model,
                        "choices": [final_choice],
                        # same metrics block as the single-stream final
                        # chunk: the loadgen must not lose truncation /
                        # server-TTFT telemetry just because n>1
                        "metrics": {
                            "server_ttft_ms": handles[idx].server_ttft_ms,
                            "truncated": bool(info.get("truncated", False)),
                            "truncated_tokens": int(
                                info.get("truncated_tokens", 0)
                            ),
                        },
                    }
                    if done_count == len(handles):
                        total_out = sum(per_out)
                        final["usage"] = {
                            "prompt_tokens": len(prompt_ids),
                            "completion_tokens": total_out,
                            "total_tokens": len(prompt_ids) + total_out,
                        }
                    await resp.write(f"data: {json.dumps(final)}\n\n".encode())
            await resp.write(b"data: [DONE]\n\n")
        except (ConnectionResetError, asyncio.CancelledError):
            # client went away mid-stream: cancel every still-running
            # candidate — nobody is reading, and n big-budget slots would
            # otherwise burn decode steps and block admissions until their
            # budgets ran out
            for h in handles:
                engine.cancel(h, reason="cancelled")
        try:
            await resp.write_eof()
        except ConnectionResetError:  # kvmini: workload-ok — client already
            pass                      # gone; the cancel above surfaced it
        return resp

    async def models(_request):
        data = [
            {"id": model_name, "object": "model", "created": int(started),
             "owned_by": "kvmini-tpu"}
        ]
        for name in sorted(getattr(engine, "_lora_names", {})):
            data.append(
                {"id": name, "object": "model", "created": int(started),
                 "owned_by": "kvmini-tpu", "parent": model_name,
                 "root": model_name}
            )
        return web.json_response({"object": "list", "data": data})

    async def healthz(_request):
        if not alive_check():
            return web.json_response(
                {"status": "unhealthy", "reason": "scheduler not running"},
                status=503,
            )
        s = engine.snapshot_stats()
        return web.json_response({
            "status": "ok",
            "uptime_s": time.time() - started,
            # probe-visible pipeline state (docs/DECODE_PIPELINE.md): lets a
            # readiness/debug probe distinguish "idle" from "pipelining"
            # without parsing the Prometheus exposition
            "decode_pipeline": {
                "dispatch_depth": s["dispatch_depth"],
                "inflight_sweeps": s["inflight_sweeps"],
            },
        })

    profile_lock = threading.Lock()
    profile_root = Path("runs").resolve()

    async def profile(request: "web.Request"):
        """Capture a jax.profiler (TensorBoard) trace of the live engine —
        the runtime-side profiling leg SURVEY.md §5.1 calls for; the
        client-side OTLP tracer covers the other leg. POST {"seconds": N,
        "out_dir": runs-relative path}; returns the trace directory. Point
        TensorBoard's profile plugin at it to see the decode timeline."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        if not isinstance(body, dict):
            body = {}
        try:
            seconds = float(body.get("seconds", 3.0))
        except (TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "'seconds' must be a number"}}, status=400
            )
        if not 0.1 <= seconds <= 60.0:
            return web.json_response(
                {"error": {"message": "'seconds' must be in [0.1, 60]"}}, status=400
            )
        # traces land under runs/ only: the write path must not be client-
        # controlled (SECURITY.md input-handling stance)
        sub = str(body.get("out_dir") or f"profile-{int(time.time())}")
        out_path = (profile_root / sub).resolve()
        if not out_path.is_relative_to(profile_root):
            return web.json_response(
                {"error": {"message": "'out_dir' must stay under runs/"}}, status=400
            )
        if not profile_lock.acquire(blocking=False):
            return web.json_response(
                {"error": {"message": "a profile capture is already running"}},
                status=409,
            )

        def capture() -> None:
            import jax

            try:
                jax.profiler.start_trace(str(out_path))
                try:
                    time.sleep(seconds)
                finally:
                    jax.profiler.stop_trace()
            finally:
                profile_lock.release()

        loop = asyncio.get_running_loop()
        try:
            await loop.run_in_executor(None, capture)
        except Exception as e:  # start_trace can fail on unwritable dirs
            return web.json_response(
                {"error": {"message": f"profile capture failed: {e}"}}, status=500
            )
        return web.json_response(
            {"trace_dir": str(out_path), "seconds": seconds, "format": "tensorboard"}
        )

    async def metrics(_request):
        s = engine.snapshot_stats()
        lines = [
            "# TYPE kvmini_tpu_decode_tokens_total counter",
            f"kvmini_tpu_decode_tokens_total {s['decode_tokens']}",
            "# TYPE kvmini_tpu_prefill_tokens_total counter",
            f"kvmini_tpu_prefill_tokens_total {s['prefill_tokens']}",
            "# TYPE kvmini_tpu_requests_completed_total counter",
            f"kvmini_tpu_requests_completed_total {s['requests_completed']}",
            "# TYPE kvmini_tpu_duty_cycle gauge",
            f"kvmini_tpu_duty_cycle {s['duty_cycle']:.6f}",
            # raw busy-time as a counter so consumers can compute WINDOWED
            # duty (delta busy / delta wall) — the gauge above is
            # cumulative-since-start and flattens mid-run stalls; the live
            # monitor (docs/MONITORING.md) and Prometheus rate() need this
            "# TYPE kvmini_tpu_busy_seconds_total counter",
            f"kvmini_tpu_busy_seconds_total {s['busy_s']:.6f}",
            "# TYPE kvmini_tpu_queue_depth gauge",
            f"kvmini_tpu_queue_depth {s['queue_depth']}",
            "# TYPE kvmini_tpu_active_slots gauge",
            f"kvmini_tpu_active_slots {s['active_slots']}",
            "# TYPE kvmini_tpu_free_slots gauge",
            f"kvmini_tpu_free_slots {s['free_slots']}",
            "# TYPE kvmini_tpu_decode_steps_total counter",
            f"kvmini_tpu_decode_steps_total {s['decode_steps']}",
            # of those, the steps whose batch made the sampler sort
            "# TYPE kvmini_tpu_sampler_filter_steps_total counter",
            "kvmini_tpu_sampler_filter_steps_total "
            f"{s['sampler_filter_steps']}",
            "# TYPE kvmini_tpu_prefills_total counter",
            f"kvmini_tpu_prefills_total {s['prefills']}",
            # chunked-prefill rail (docs/TROUBLESHOOTING.md "Long prompts
            # stall streaming"): compiled prefill piece dispatches, and
            # the prefill wall that ran while decode work was live
            "# TYPE kvmini_tpu_prefill_chunks_total counter",
            f"kvmini_tpu_prefill_chunks_total {s['prefill_chunks']}",
            "# TYPE kvmini_tpu_prefill_chunk_stall_seconds_total counter",
            "kvmini_tpu_prefill_chunk_stall_seconds_total "
            f"{s['prefill_chunk_stall_s']:.6f}",
            # what those pieces were padded to (their bucket sizes, target
            # pieces only): with prefill_tokens_total, the padding's share
            "# TYPE kvmini_tpu_prefill_bucket_tokens_total counter",
            f"kvmini_tpu_prefill_bucket_tokens_total {s['prefill_bucket_tokens']}",
            # the bucket tokens of pieces whose program dispatched its tokens
            # to the routed experts grouped by expert (models/moe.py)
            "# TYPE kvmini_tpu_prefill_grouped_bucket_tokens_total counter",
            "kvmini_tpu_prefill_grouped_bucket_tokens_total "
            f"{s['prefill_grouped_bucket_tokens']}",
            # the dense decode kernel's block selection: key/value blocks
            # decode steps fetched against slots x S/BLK (0 / 0 where the
            # eager read runs)
            "# TYPE kvmini_tpu_decode_kv_blocks_fetched_total counter",
            "kvmini_tpu_decode_kv_blocks_fetched_total "
            f"{s['decode_kv_blocks_fetched']}",
            "# TYPE kvmini_tpu_decode_kv_blocks_total counter",
            f"kvmini_tpu_decode_kv_blocks_total {s['decode_kv_blocks']}",
            # the same by kind of layer where a stack has two K/V leaves
            # (docs/TRACING.md "Window and global layers"): a layer of each
            # kind booked on its own, the two above their sums; 0 elsewhere
            "# TYPE kvmini_tpu_decode_kv_blocks_fetched_by_kind_total counter",
            *(f"kvmini_tpu_decode_kv_blocks_fetched_by_kind_total{{kind=\"{kind}\"}} "
              f"{s['decode_kv_blocks_fetched_' + kind]}" for kind in ("global", "window")),
            "# TYPE kvmini_tpu_decode_kv_blocks_by_kind_total counter",
            *(f"kvmini_tpu_decode_kv_blocks_by_kind_total{{kind=\"{kind}\"}} "
              f"{s['decode_kv_blocks_' + kind]}" for kind in ("global", "window")),
            # a hybrid stack's recurrent state (docs/TRACING.md "Hybrid
            # stacks"): what the cache tree reserves by kind of state, the
            # chunks its prefill scans ran and padded, the slot-steps its
            # decode sweeps moved state for and held, and the fresh
            # prefills that zeroed a slot's state. 0 for every other model
            "# TYPE kvmini_tpu_cache_bytes gauge",
            *(f"kvmini_tpu_cache_bytes{{kind=\"{kind}\"}} {s['cache_bytes'][kind]}"
              for kind in s["cache_bytes"]),
            "# TYPE kvmini_tpu_linear_scan_chunks_total counter",
            f"kvmini_tpu_linear_scan_chunks_total {s['linear_scan_chunks']}",
            "# TYPE kvmini_tpu_linear_scan_pad_chunks_total counter",
            f"kvmini_tpu_linear_scan_pad_chunks_total {s['linear_scan_pad_chunks']}",
            "# TYPE kvmini_tpu_linear_state_slot_steps_total counter",
            f"kvmini_tpu_linear_state_slot_steps_total {s['linear_state_slot_steps']}",
            "# TYPE kvmini_tpu_linear_state_held_slot_steps_total counter",
            "kvmini_tpu_linear_state_held_slot_steps_total "
            f"{s['linear_state_held_slot_steps']}",
            "# TYPE kvmini_tpu_linear_state_resets_total counter",
            f"kvmini_tpu_linear_state_resets_total {s['linear_state_resets']}",
            # fresh prefills of a short-convolution stack: each starts its
            # slot's tails (kvmini_tpu_cache_bytes{kind="conv_tail"}) from zero
            "# TYPE kvmini_tpu_conv_tail_resets_total counter",
            f"kvmini_tpu_conv_tail_resets_total {s['conv_tail_resets']}",
            # 1 where the decode program updates that state in place through
            # kvmini_linear_decode, 0 where it runs the jnp recurrence
            "# TYPE kvmini_tpu_linear_state_kernel gauge",
            f"kvmini_tpu_linear_state_kernel {s['linear_state_kernel']}",
            # the same for a pattern stack's Mamba-2 state and kvmini_ssm_decode
            "# TYPE kvmini_tpu_ssm_state_kernel gauge",
            f"kvmini_tpu_ssm_state_kernel {s['ssm_state_kernel']}",
            # mixers a block runs on one normed input: 2 where a state-space
            # mixer and attention sit side by side, 1 for every other stack
            "# TYPE kvmini_tpu_block_mixers gauge",
            f"kvmini_tpu_block_mixers {s['block_mixers']}",
            "# TYPE kvmini_tpu_moe_assignments_total counter",
            f"kvmini_tpu_moe_assignments_total {s['moe_assignments']}",
            "# TYPE kvmini_tpu_moe_local_assignments_total counter",
            f"kvmini_tpu_moe_local_assignments_total {s['moe_local_assignments']}",
            "# TYPE kvmini_tpu_moe_expert_touches_total counter",
            f"kvmini_tpu_moe_expert_touches_total {s['moe_expert_touches']}",
            "# TYPE kvmini_tpu_moe_expert_load_max_total counter",
            f"kvmini_tpu_moe_expert_load_max_total {s['moe_expert_load_max']}",
            # held experts whose weights the decode steps' dispatch multiplied:
            # touches where only the touched are visited, held x layers x steps
            # where every held expert runs every row
            "# TYPE kvmini_tpu_moe_expert_visits_total counter",
            f"kvmini_tpu_moe_expert_visits_total {s['moe_expert_visits']}",
            # decode-pipeline telemetry (docs/DECODE_PIPELINE.md): depth >= 2
            # + low bubble = the double-buffered steady state is engaged
            "# TYPE kvmini_tpu_dispatch_depth gauge",
            f"kvmini_tpu_dispatch_depth {s['dispatch_depth']}",
            "# TYPE kvmini_tpu_inflight_sweeps gauge",
            f"kvmini_tpu_inflight_sweeps {s['inflight_sweeps']}",
            "# TYPE kvmini_tpu_pipelined_sweeps_total counter",
            f"kvmini_tpu_pipelined_sweeps_total {s['pipelined_sweeps']}",
            "# TYPE kvmini_tpu_host_overlap_seconds_total counter",
            f"kvmini_tpu_host_overlap_seconds_total {s['host_overlap_s']:.6f}",
            "# TYPE kvmini_tpu_bubble_seconds_total counter",
            f"kvmini_tpu_bubble_seconds_total {s['bubble_s']:.6f}",
            # sync-fallback attribution (docs/DECODE_PIPELINE.md): which
            # constraint broke the double-buffered steady state, labeled so
            # PromQL can aggregate (the scrape parser sums label series)
            "# TYPE kvmini_tpu_pipeline_fallback_total counter",
            "kvmini_tpu_pipeline_fallback_total"
            f"{{reason=\"constrained\"}} {s['pipeline_fallback_constrained']}",
            "kvmini_tpu_pipeline_fallback_total"
            f"{{reason=\"spec\"}} {s['pipeline_fallback_spec']}",
            "kvmini_tpu_pipeline_fallback_total"
            f"{{reason=\"active_set\"}} {s['pipeline_fallback_active_set']}",
            "kvmini_tpu_pipeline_fallback_total"
            f"{{reason=\"headroom\"}} {s['pipeline_fallback_headroom']}",
            # admissions dispatched behind the sweep in flight, and why the
            # others drained it (each of those is an active_set fallback)
            "# TYPE kvmini_tpu_admissions_inflight_total counter",
            f"kvmini_tpu_admissions_inflight_total {s['admissions_inflight']}",
            "# TYPE kvmini_tpu_admissions_drained_total counter",
            *(f"kvmini_tpu_admissions_drained_total{{reason=\"{why}\"}} "
              f"{s['admissions_drained_' + why]}" for why in ADMIT_DRAIN_REASONS),
            "# TYPE kvmini_tpu_spec_rounds_total counter",
            f"kvmini_tpu_spec_rounds_total {s['spec_rounds']}",
            "# TYPE kvmini_tpu_spec_accepted_total counter",
            f"kvmini_tpu_spec_accepted_total {s['spec_accepted']}",
            "# TYPE kvmini_tpu_spec_proposed_total counter",
            f"kvmini_tpu_spec_proposed_total {s['spec_proposed']}",
            "# TYPE kvmini_tpu_spec_accept_ratio gauge",
            f"kvmini_tpu_spec_accept_ratio {s['spec_accept_ratio']:.6f}",
            "# TYPE kvmini_tpu_prefix_hits_total counter",
            f"kvmini_tpu_prefix_hits_total {s['prefix_hits']}",
            "# TYPE kvmini_tpu_prefix_tokens_reused_total counter",
            f"kvmini_tpu_prefix_tokens_reused_total {s['prefix_tokens_reused']}",
            # prefix-reuse counters under the generic cache names the
            # analysis fallback chain scrapes (analysis/telemetry.py
            # cache_hit_ratio) — before these lines the runtime branch of
            # that chain silently yielded nothing
            "# TYPE kvmini_tpu_cache_hits_total counter",
            f"kvmini_tpu_cache_hits_total {s['prefix_hits']}",
            "# TYPE kvmini_tpu_cache_lookups_total counter",
            f"kvmini_tpu_cache_lookups_total {s['prefix_lookups']}",
            # compile-stats capture (docs/PROFILING.md): explicit
            # lower().compile() facts for every executable the engine
            # built — wall time plus the XLA cost model's per-invocation
            # FLOPs/bytes and the buffer-assignment peak estimate
            "# TYPE kvmini_tpu_compiles_total counter",
            f"kvmini_tpu_compiles_total {s['compiles']}",
            "# TYPE kvmini_tpu_compile_seconds_total counter",
            f"kvmini_tpu_compile_seconds_total {s['compile_s']:.6f}",
            "# TYPE kvmini_tpu_compiled_flops_total counter",
            f"kvmini_tpu_compiled_flops_total {s['compiled_flops']:.6g}",
            "# TYPE kvmini_tpu_compiled_bytes_total counter",
            f"kvmini_tpu_compiled_bytes_total {s['compiled_bytes']:.6g}",
            "# TYPE kvmini_tpu_compile_peak_bytes gauge",
            f"kvmini_tpu_compile_peak_bytes {s['compile_peak_bytes']}",
            # resilience rail (docs/RESILIENCE.md): admission sheds,
            # watchdog trips, recovered engine faults, the degrade-ladder
            # position, and the armed-injection-point gauge — the monitor
            # timeline's overload_shedding / engine_fault event inputs
            "# TYPE kvmini_tpu_requests_shed_total counter",
            f"kvmini_tpu_requests_shed_total {s['requests_shed']}",
            "# TYPE kvmini_tpu_watchdog_trips_total counter",
            f"kvmini_tpu_watchdog_trips_total {s['watchdog_trips']}",
            "# TYPE kvmini_tpu_engine_faults_total counter",
            f"kvmini_tpu_engine_faults_total {s['engine_faults']}",
            "# TYPE kvmini_tpu_degrade_level gauge",
            f"kvmini_tpu_degrade_level {s['degrade_level']}",
            "# TYPE kvmini_tpu_faults_armed gauge",
            f"kvmini_tpu_faults_armed {s['faults_armed']}",
            # fleet-router placement input (docs/FLEET.md): seconds a
            # request submitted NOW would take to complete at this
            # replica — the deadline-shed estimate promoted to a scraped
            # signal so a fleet router can score replicas by load
            "# TYPE kvmini_tpu_estimated_wait_seconds gauge",
            f"kvmini_tpu_estimated_wait_seconds {s['estimated_wait_s']:.6f}",
            # KV-cache lifecycle + prefix-cache attribution (docs/
            # TROUBLESHOOTING.md "HBM pressure & KV thrash"): allocator
            # churn counters the point-in-time pool gauges cannot show,
            # hit-depth percentiles from one consistent scheduler-thread
            # snapshot, and the byte-denominated reuse view
            "# TYPE kvmini_tpu_kv_blocks_allocated_total counter",
            f"kvmini_tpu_kv_blocks_allocated_total {s['kv_blocks_allocated']}",
            "# TYPE kvmini_tpu_kv_retained_evictions_total counter",
            f"kvmini_tpu_kv_retained_evictions_total {s['kv_retained_evictions']}",
            "# TYPE kvmini_tpu_kv_share_reclaims_total counter",
            f"kvmini_tpu_kv_share_reclaims_total {s['kv_share_reclaims']}",
            "# TYPE kvmini_tpu_kv_prefix_hit_depth_p50 gauge",
            f"kvmini_tpu_kv_prefix_hit_depth_p50 {s['kv_prefix_hit_depth_p50']}",
            "# TYPE kvmini_tpu_kv_prefix_hit_depth_p95 gauge",
            f"kvmini_tpu_kv_prefix_hit_depth_p95 {s['kv_prefix_hit_depth_p95']}",
            "# TYPE kvmini_tpu_kv_bytes_per_token gauge",
            f"kvmini_tpu_kv_bytes_per_token {s['kv_bytes_per_token']}",
            "# TYPE kvmini_tpu_kv_reused_bytes_total counter",
            f"kvmini_tpu_kv_reused_bytes_total {s['kv_reused_bytes']}",
            # per-device analytic footprint (profiling/headroom.py): the
            # admission model's estimate, exported so headroom_error_pct
            # is derivable from a scrape next to the observed watermark
            "# TYPE kvmini_tpu_hbm_headroom_estimate_bytes gauge",
            f"kvmini_tpu_hbm_headroom_estimate_bytes {s['hbm_headroom_estimate_bytes']}",
        ]
        if "kv_handoffs" in s:  # disaggregated engines only (docs/
            # DISAGGREGATION.md): the prefill-lane handoff rail — volume,
            # block/wait accounting, tombstoned drops, lane busy wall,
            # the lane backlog gauge the handoff_stall monitor rule
            # watches, and the degrade-ladder position
            lines += [
                "# TYPE kvmini_tpu_kv_handoffs_total counter",
                f"kvmini_tpu_kv_handoffs_total {s['kv_handoffs']}",
                "# TYPE kvmini_tpu_kv_handoff_blocks_total counter",
                f"kvmini_tpu_kv_handoff_blocks_total {s['kv_handoff_blocks']}",
                "# TYPE kvmini_tpu_kv_handoff_wait_seconds_total counter",
                "kvmini_tpu_kv_handoff_wait_seconds_total "
                f"{s['kv_handoff_wait_s']:.6f}",
                "# TYPE kvmini_tpu_kv_handoff_drops_total counter",
                f"kvmini_tpu_kv_handoff_drops_total {s['kv_handoff_drops']}",
                "# TYPE kvmini_tpu_prefill_lane_busy_seconds_total counter",
                "kvmini_tpu_prefill_lane_busy_seconds_total "
                f"{s['prefill_lane_busy_s']:.6f}",
                "# TYPE kvmini_tpu_disagg_colocated_fallbacks_total counter",
                "kvmini_tpu_disagg_colocated_fallbacks_total "
                f"{s['disagg_colocated_fallbacks']}",
                "# TYPE kvmini_tpu_kv_handoff_queue_depth gauge",
                f"kvmini_tpu_kv_handoff_queue_depth {s['kv_handoff_queue_depth']}",
                "# TYPE kvmini_tpu_disagg_degraded gauge",
                f"kvmini_tpu_disagg_degraded {s['disagg_degraded']}",
                # KV bytes the handoff physically copied: the v1 dense
                # stripe's nbytes per inject; 0 forever on the v2
                # block-table path — the A/B the ISSUE 16 acceptance
                # criterion reads straight off this counter
                "# TYPE kvmini_tpu_kv_handoff_bytes_copied_total counter",
                "kvmini_tpu_kv_handoff_bytes_copied_total "
                f"{s['kv_handoff_bytes_copied']}",
            ]
        if "kv_pool_blocks" in s:  # paged layout only
            lines += [
                "# TYPE kvmini_tpu_kv_pool_blocks gauge",
                f"kvmini_tpu_kv_pool_blocks {s['kv_pool_blocks']}",
                "# TYPE kvmini_tpu_kv_free_blocks gauge",
                f"kvmini_tpu_kv_free_blocks {s['kv_free_blocks']}",
                "# TYPE kvmini_tpu_kv_retained_blocks gauge",
                f"kvmini_tpu_kv_retained_blocks {s['kv_retained_blocks']}",
                "# TYPE kvmini_tpu_kv_used_blocks gauge",
                f"kvmini_tpu_kv_used_blocks {s['kv_used_blocks']}",
                "# TYPE kvmini_tpu_kv_block_size gauge",
                f"kvmini_tpu_kv_block_size {s['kv_block_size']}",
                "# TYPE kvmini_tpu_kv_occupancy gauge",
                f"kvmini_tpu_kv_occupancy {s['kv_occupancy']:.6f}",
                "# TYPE kvmini_tpu_kv_retained_fraction gauge",
                f"kvmini_tpu_kv_retained_fraction {s['kv_retained_fraction']:.6f}",
                "# TYPE kvmini_tpu_kv_fragmentation gauge",
                f"kvmini_tpu_kv_fragmentation {s['kv_fragmentation']:.6f}",
                "# TYPE kvmini_tpu_kv_logical_bytes gauge",
                f"kvmini_tpu_kv_logical_bytes {s['kv_logical_bytes']}",
                "# TYPE kvmini_tpu_kv_physical_bytes gauge",
                f"kvmini_tpu_kv_physical_bytes {s['kv_physical_bytes']}",
                # host-RAM KV tier (docs/TROUBLESHOOTING.md "Host-RAM KV
                # tier thrash"): demote/promote/hit counters plus the
                # pool/capacity gauges and the thrash-guard disable flag
                "# TYPE kvmini_tpu_kv_tier_demotions_total counter",
                f"kvmini_tpu_kv_tier_demotions_total {s['kv_tier_demotions']}",
                "# TYPE kvmini_tpu_kv_tier_promotions_total counter",
                f"kvmini_tpu_kv_tier_promotions_total {s['kv_tier_promotions']}",
                "# TYPE kvmini_tpu_kv_tier_hits_total counter",
                f"kvmini_tpu_kv_tier_hits_total {s['kv_tier_hits']}",
                "# TYPE kvmini_tpu_kv_tier_blocks gauge",
                f"kvmini_tpu_kv_tier_blocks {s['kv_tier_blocks']}",
                "# TYPE kvmini_tpu_kv_tier_bytes gauge",
                f"kvmini_tpu_kv_tier_bytes {s['kv_tier_bytes']}",
                "# TYPE kvmini_tpu_kv_tier_capacity_bytes gauge",
                f"kvmini_tpu_kv_tier_capacity_bytes {s['kv_tier_capacity_bytes']}",
                "# TYPE kvmini_tpu_kv_tier_disabled gauge",
                f"kvmini_tpu_kv_tier_disabled {s['kv_tier_disabled']}",
                # cross-replica prefix migration (docs/FLEET.md): what
                # this replica shipped (/kv/export) and installed
                # (/kv/import)
                "# TYPE kvmini_tpu_kv_migrated_blocks_total counter",
                f"kvmini_tpu_kv_migrated_blocks_total {s['kv_migrated_blocks']}",
                "# TYPE kvmini_tpu_kv_migrated_bytes_total counter",
                f"kvmini_tpu_kv_migrated_bytes_total {s['kv_migrated_bytes']}",
                "# TYPE kvmini_tpu_kv_export_blocks_total counter",
                f"kvmini_tpu_kv_export_blocks_total {s['kv_export_blocks']}",
            ]
        if "hbm_bytes_in_use" in s:  # device reports memory_stats only
            lines += [
                "# TYPE kvmini_tpu_hbm_bytes_in_use gauge",
                f"kvmini_tpu_hbm_bytes_in_use {s['hbm_bytes_in_use']}",
                "# TYPE kvmini_tpu_hbm_peak_bytes gauge",
                f"kvmini_tpu_hbm_peak_bytes {s['hbm_peak_bytes']}",
            ]
        if "hbm_bytes_limit" in s:
            lines += [
                "# TYPE kvmini_tpu_hbm_bytes_limit gauge",
                f"kvmini_tpu_hbm_bytes_limit {s['hbm_bytes_limit']}",
            ]
        if "econ_usd_per_hour" in s:  # live economics rail (docs/
            # ECONOMICS.md): priced engines only (TPU backend or an
            # explicit econ_accelerator). The $/hr accrual is always
            # present once the rail exists; the rolling-window rates
            # appear only after the window sees token progress — a CPU
            # dev box or an idle engine never exports a fabricated $0
            lines += [
                "# TYPE kvmini_tpu_econ_usd_per_hour gauge",
                f"kvmini_tpu_econ_usd_per_hour {s['econ_usd_per_hour']:.6f}",
            ]
            if "econ_usd_per_1k_tokens" in s:
                lines += [
                    "# TYPE kvmini_tpu_econ_usd_per_1k_tokens gauge",
                    "kvmini_tpu_econ_usd_per_1k_tokens "
                    f"{s['econ_usd_per_1k_tokens']:.6f}",
                    "# TYPE kvmini_tpu_econ_wh_per_1k_tokens gauge",
                    "kvmini_tpu_econ_wh_per_1k_tokens "
                    f"{s['econ_wh_per_1k_tokens']:.6f}",
                    "# TYPE kvmini_tpu_econ_tokens_per_sec gauge",
                    "kvmini_tpu_econ_tokens_per_sec "
                    f"{s['econ_tokens_per_sec']:.6f}",
                ]
        # the scheduler ledger (docs/TRACING.md "Scheduler ledger"): the
        # engine thread's time by phase — self time, the part of it with
        # work live and nothing in flight, entries (seconds over entries: a
        # phase's mean cost), and the self time of entries that took longer
        # than two decode sweeps (at least 50 ms). Spelled out family by
        # family: kvmini-lint pairs a stats key with its series through
        # the literal f-string (KVM031)
        lines.append("# TYPE kvmini_tpu_sched_seconds_total counter")
        lines += [
            f"kvmini_tpu_sched_seconds_total{{phase=\"{p}\"}} {s['sched_s'][p]:.6f}"
            for p in s["sched_s"]
        ]
        lines.append("# TYPE kvmini_tpu_sched_exposed_seconds_total counter")
        lines += [
            f"kvmini_tpu_sched_exposed_seconds_total{{phase=\"{p}\"}} "
            f"{s['sched_exposed_s'][p]:.6f}"
            for p in s["sched_exposed_s"]
        ]
        lines.append("# TYPE kvmini_tpu_sched_slow_seconds_total counter")
        lines += [
            f"kvmini_tpu_sched_slow_seconds_total{{phase=\"{p}\"}} "
            f"{s['sched_slow_s'][p]:.6f}"
            for p in s["sched_slow_s"]
        ]
        lines.append("# TYPE kvmini_tpu_sched_spans_total counter")
        lines += [
            f"kvmini_tpu_sched_spans_total{{phase=\"{p}\"}} {s['sched_spans'][p]}"
            for p in s["sched_spans"]
        ]
        # the decode gaps the ledger booked (docs/TRACING.md "The decode
        # gap"): by cause; the slot-time they cost; an admission gap's time
        # by phase (a partition of gap_seconds{cause="admission"}) and the
        # admissions inside. kvmini_tpu_bubble_seconds_total above is the
        # sum of gap_seconds over the causes
        lines.append("# TYPE kvmini_tpu_sched_gaps_total counter")
        lines += [
            f"kvmini_tpu_sched_gaps_total{{cause=\"{c}\"}} {s['sched_gaps'][c]}"
            for c in s["sched_gaps"]
        ]
        lines.append("# TYPE kvmini_tpu_sched_gap_seconds_total counter")
        lines += [
            f"kvmini_tpu_sched_gap_seconds_total{{cause=\"{c}\"}} "
            f"{s['sched_gap_s'][c]:.6f}"
            for c in s["sched_gap_s"]
        ]
        lines.append("# TYPE kvmini_tpu_sched_admit_gap_seconds_total counter")
        lines += [
            f"kvmini_tpu_sched_admit_gap_seconds_total{{phase=\"{p}\"}} "
            f"{s['sched_admit_gap_s'][p]:.6f}"
            for p in s["sched_admit_gap_s"]
        ]
        lines += [
            "# TYPE kvmini_tpu_sched_gap_slot_seconds_total counter",
            f"kvmini_tpu_sched_gap_slot_seconds_total {s['sched_gap_slot_s']:.6f}",
            "# TYPE kvmini_tpu_sched_admit_gap_admissions_total counter",
            "kvmini_tpu_sched_admit_gap_admissions_total "
            f"{s['sched_admit_gap_admissions']}",
        ]
        # per-phase latency histograms (docs/TRACING.md): queue / prefill /
        # decode / emit durations the engine observes at phase transitions
        from kserve_vllm_mini_tpu.runtime.tracing import render_phase_histograms

        lines += render_phase_histograms(engine._phase_hist)
        return web.Response(text="\n".join(lines) + "\n", content_type="text/plain")

    async def traces(_request):
        """Runtime-side span buffer, OTLP-shaped JSON (the same schema the
        loadgen's traces.json uses — analysis/traces.py joins the two by
        trace_id). The buffer is a bounded ring: spans past the capacity
        evict oldest-first, and 'droppedSpans' reports how many did. An
        engine with tracing disabled serves an empty document, not a 404,
        so scrapers need no capability probe."""
        if engine.tracer is None:
            return web.json_response({"resourceSpans": [], "droppedSpans": 0,
                                      "tracing": "disabled"})
        return web.json_response(engine.traces_otlp())

    def _reject_multihost_admin() -> "Optional[web.Response]":
        """Multi-host serving rejects LoRA entirely at startup
        (runtime/multihost.check_multihost_engine): admin ops run only on
        the primary and are NOT replayed over the command channel, so a
        load would leave followers serving base weights (silent lockstep
        divergence). These endpoints reject up front — BEFORE body
        parsing, so multihost callers get the real reason rather than an
        incidental JSON error."""
        if multihost:
            return web.json_response(
                {"error": {"message":
                           "adapter hot-swap is not supported under "
                           "multi-host serving (v1)"}}, status=400,
            )
        return None

    async def load_lora(request: "web.Request"):
        # vLLM dynamic-LoRA surface: {"lora_name": ..., "lora_path": <PEFT dir>}
        rej = _reject_multihost_admin()
        if rej is not None:
            return rej
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        name = body.get("lora_name")
        path = body.get("lora_path")
        if not name or not path:
            return web.json_response(
                {"error": {"message": "lora_name and lora_path are required"}},
                status=400,
            )
        from kserve_vllm_mini_tpu.ops.lora import LORA_TARGETS_ALL, load_peft_adapter

        loop = asyncio.get_running_loop()
        try:
            # file IO + host->device transfer + the blocking scheduler-op
            # wait all leave the event loop (like the chat path) — a slow
            # load must not freeze in-flight streams or /healthz
            adapter = await loop.run_in_executor(
                None,
                lambda: load_peft_adapter(path, engine.cfg,
                                          targets=LORA_TARGETS_ALL),
            )
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            return web.json_response(
                {"error": {"message": f"loading {path!r}: {e}"}}, status=400
            )
        err = await loop.run_in_executor(
            None, lambda: engine.load_adapter(name, adapter)
        )
        if err:
            return web.json_response({"error": {"message": err}}, status=409)
        return web.json_response({"status": "ok", "loaded": name})

    async def unload_lora(request: "web.Request"):
        rej = _reject_multihost_admin()
        if rej is not None:
            return rej
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        name = body.get("lora_name")
        if not name:
            return web.json_response(
                {"error": {"message": "lora_name is required"}}, status=400
            )
        err = await asyncio.get_running_loop().run_in_executor(
            None, lambda: engine.unload_adapter(name)
        )
        if err:
            status = 404 if "unknown adapter" in err else 409
            return web.json_response({"error": {"message": err}}, status=status)
        return web.json_response({"status": "ok", "unloaded": name})

    async def faults_get(_request: "web.Request"):
        """Armed injection points (docs/RESILIENCE.md). Always readable —
        an operator must be able to SEE armed faults even on a server
        that refuses to arm new ones."""
        return web.json_response({
            "enabled": allow_fault_injection,
            "active": engine.active_faults(),
        })

    async def faults_post(request: "web.Request"):
        """Arm/clear a named injection point: {"name": ..., "action":
        "arm"|"clear", <params>}. Gated behind --allow-fault-injection —
        a production server must not expose a kill switch."""
        if not allow_fault_injection:
            return web.json_response(
                {"error": {"message":
                           "fault injection is disabled; start the server "
                           "with --allow-fault-injection"}}, status=403,
            )
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        if not isinstance(body, dict):
            return web.json_response({"error": {"message": "body must be an "
                                                "object"}}, status=400)
        action = body.get("action", "arm")
        name = body.get("name")
        if action == "clear":
            engine.clear_fault(name)
            return web.json_response({"status": "ok",
                                      "cleared": name or "all"})
        if action != "arm" or not name:
            return web.json_response(
                {"error": {"message": "need action 'arm'|'clear' and, for "
                           "arm, a fault 'name'"}}, status=400,
            )
        params = {k: v for k, v in body.items() if k not in ("action", "name")}
        try:
            spec = engine.arm_fault(name, **params)
        except (ValueError, TypeError) as e:
            return web.json_response({"error": {"message": str(e)}},
                                     status=400)
        return web.json_response({"status": "ok", "armed": spec})

    async def kv_export(request: "web.Request"):
        """Cross-replica prefix migration, donor side (docs/FLEET.md):
        {"budget_bytes": N} -> a bounded, root-first wire snapshot of
        this replica's registered prefix blocks (int8-KV on the wire).
        The engine walk runs on its scheduler thread; the (possibly
        slow) rendezvous runs in an executor so the event loop never
        blocks on a sweep. 400 on dense engines — migration is a paged
        block-pool operation."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        budget = int((body or {}).get("budget_bytes", 16 * 1024 * 1024))
        loop = asyncio.get_running_loop()
        try:
            payload = await loop.run_in_executor(
                None, engine.kv_export, budget
            )
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}},
                                     status=400)
        except RuntimeError as e:
            return web.json_response({"error": {"message": str(e)}},
                                     status=503)
        return web.json_response(payload)

    async def kv_import(request: "web.Request"):
        """Cross-replica prefix migration, target side: install a
        sibling's /kv/export payload into FREE pool blocks (never
        evicts) and register the keys as retained prefix blocks. 400 on
        dense engines or geometry mismatches (block_size/leaf shapes)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": {"message": "invalid JSON"}},
                                     status=400)
        if not isinstance(body, dict):
            return web.json_response(
                {"error": {"message": "body must be an object"}}, status=400
            )
        loop = asyncio.get_running_loop()
        try:
            res = await loop.run_in_executor(None, engine.kv_import, body)
        except (ValueError, KeyError) as e:
            return web.json_response({"error": {"message": str(e)}},
                                     status=400)
        except RuntimeError as e:
            return web.json_response({"error": {"message": str(e)}},
                                     status=503)
        return web.json_response(res)

    app = web.Application()
    app.router.add_post("/v1/chat/completions", chat)
    app.router.add_get("/v1/models", models)
    app.router.add_post("/v1/load_lora_adapter", load_lora)
    app.router.add_post("/v1/unload_lora_adapter", unload_lora)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/traces", traces)
    app.router.add_post("/profile", profile)
    app.router.add_get("/faults", faults_get)
    app.router.add_post("/faults", faults_post)
    app.router.add_post("/kv/export", kv_export)
    app.router.add_post("/kv/import", kv_import)
    return app


# -- CLI ---------------------------------------------------------------------

def register(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--model", default="llama-tiny", help="Model preset name")
    parser.add_argument("--checkpoint", default=None, help="Local HF checkpoint dir")
    parser.add_argument("--tokenizer", default=None, help="Local tokenizer dir")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--max-slots", type=int, default=None,
                        help="Decode slots (default: $KVMINI_MAX_BATCH or 8)")
    parser.add_argument("--max-seq-len", type=int, default=None,
                        help="Per-slot KV window (default: $KVMINI_MAX_MODEL_LEN "
                             "or 1024)")
    parser.add_argument("--topology", default=None,
                        help="Mesh topology preset (e.g. v5e-8); default single-device")
    parser.add_argument("--pp", type=int, default=0,
                        help="Serving pipeline-parallel stages (layer-range "
                             "sharding over a pure-pp mesh; overrides --topology)")
    parser.add_argument("--pp-microbatches", type=int, default=None,
                        help="Slot groups pipelined per step with --pp "
                             "(GPipe-style; shrinks the stage bubble). "
                             "Default: $KVMINI_PP_MICROBATCHES or 1")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--quantization", default="none",
                        choices=["none", "int8", "int4", "int4-awq"],
                        help="Weight quantization (int8 = W8A16, int4 = W4A16 "
                             "per-channel; XLA packs int4 two-per-byte in HBM; "
                             "int4-awq = activation-aware calibrated scales)")
    parser.add_argument("--kv-cache-dtype", default=None,
                        help="KV cache dtype: bfloat16/float32/float16/int8 "
                             "(int8 = scaled per-position) or 'auto'")
    parser.add_argument("--quant-mode", default=None,
                        choices=["dequant", "w8a8"],
                        help="How quantized matmuls contract: 'dequant' "
                             "casts the int weight to the activation dtype "
                             "before the dot (W8A16/W4A16); 'w8a8' "
                             "quantizes activations per token and runs the "
                             "contraction int8 x int8 on the MXU "
                             "(ops/qmatmul.py). Default: $KVMINI_QUANT_MODE "
                             "or dequant. No-op with --quantization none")
    parser.add_argument("--scan-unroll", type=int, default=1,
                        help="lax.scan unroll over the layer stack (XLA "
                             "schedule knob; results equivalent)")
    parser.add_argument("--decode-chunk", type=int, default=1,
                        help="Decode steps fused per dispatch (throughput vs "
                             "streaming granularity)")
    parser.add_argument("--prefill-chunk", type=int, default=None,
                        help="Tokens per interleaved prefill chunk: prompts "
                             "above this threshold are chunk-prefilled "
                             "BETWEEN decode sweeps instead of stalling "
                             "them behind one monolithic call (TTFT/ITL "
                             "tail; docs/TROUBLESHOOTING.md). Default: "
                             "$KVMINI_PREFILL_CHUNK or monolithic")
    parser.add_argument("--disagg", action="store_true",
                        help="Disaggregated prefill/decode serving "
                             "(docs/DISAGGREGATION.md): prompt prefills "
                             "run on a dedicated prefill lane and hand "
                             "finished KV blocks to the decode engine, so "
                             "long prefills never stall the decode sweep "
                             "loop. Dense KV only (v1); excludes drafter/"
                             "LoRA/prefix-cache. Also $KVMINI_DISAGG=1")
    parser.add_argument("--disagg-min-prompt", type=int, default=None,
                        help="Prompts shorter than this many tokens "
                             "prefill colocated even with --disagg (a "
                             "short prefill is cheaper than its handoff "
                             "round-trip). Default: "
                             "$KVMINI_DISAGG_MIN_PROMPT or 0 = route all")
    parser.add_argument("--prefill-lane-devices", type=int, default=None,
                        help="With --disagg: split the device set into a "
                             "prefill submesh of this many devices plus a "
                             "decode mesh over the rest (parallel/mesh."
                             "lane_meshes; e.g. 2 on an 8-device slice = "
                             "a 2+6 split). Default: "
                             "$KVMINI_PREFILL_LANE_DEVICES or 0 = the "
                             "lane shares the engine's devices on its "
                             "own thread")
    parser.add_argument("--drafter", default=None,
                        help="Drafter model preset/checkpoint for speculative "
                             "decoding (default: $KVMINI_DRAFTER)")
    parser.add_argument("--spec-tokens", type=int, default=None,
                        help="Speculative propose/verify depth per round "
                             "(default: $KVMINI_SPEC_TOKENS or 4 when a "
                             "drafter is set)")
    parser.add_argument("--kv-layout", default="dense",
                        choices=["dense", "paged"],
                        help="KV cache layout: dense per-slot stripes, or a "
                             "paged block pool (PagedAttention-style) where "
                             "admission reserves ceil((prompt+max_tokens)/"
                             "block) blocks — long --max-seq-len stops "
                             "multiplying across slots")
    parser.add_argument("--kv-block-size", type=int, default=64,
                        help="Positions per paged-KV block")
    parser.add_argument("--kv-pool-blocks", type=int, default=None,
                        help="Paged-KV pool size in blocks (default "
                             "slots x ceil(max_seq/block), memory-equal to "
                             "dense; set lower to cap KV HBM)")
    parser.add_argument("--kv-host-tier-bytes", type=int, default=None,
                        help="Host-RAM KV tier capacity in bytes (paged "
                             "layout only): retained-LRU evictions demote "
                             "to host memory and promote back on prefix "
                             "match; 0/absent disables the tier "
                             "(docs/TROUBLESHOOTING.md)")
    parser.add_argument("--lora", action="append", default=None,
                        metavar="NAME=PEFT_DIR",
                        help="Load a LoRA adapter (PEFT safetensors dir) "
                             "servable via the request's 'model' field; "
                             "repeatable — one jitted step serves mixed "
                             "adapters (ops/lora.py)")
    parser.add_argument("--lora-demo", type=int, default=0,
                        help="Create N random adapters 'demo-1..N' for "
                             "multi-LoRA benchmarking without fine-tuned "
                             "weights")
    parser.add_argument("--lora-rank", type=int, default=8,
                        help="Rank of the --lora-demo bank (PEFT adapters "
                             "carry their own rank)")
    parser.add_argument("--lora-slots", type=int, default=4,
                        help="Adapter-bank capacity for adapters loaded at "
                             "RUNTIME (/v1/load_lora_adapter) on an engine "
                             "that started without any --lora")
    parser.add_argument("--no-request-tracing", action="store_true",
                        help="Disable the request-lifecycle span recorder "
                             "(GET /traces; docs/TRACING.md). Also "
                             "KVMINI_REQUEST_TRACING=0. Phase histograms "
                             "on /metrics stay on either way")
    parser.add_argument("--trace-buffer", type=int, default=4096,
                        help="Span ring-buffer capacity for /traces "
                             "(bounded memory; oldest spans evict)")
    parser.add_argument("--prefix-cache", action="store_true",
                        help="Automatic prefix caching: finished requests "
                             "retain their KV and new prompts sharing a "
                             "token prefix reuse it (slot-affinity APC; "
                             "repeat-heavy traffic skips most prefill)")
    parser.add_argument("--distributed", action="store_true",
                        help="Join a multi-host jax.distributed runtime "
                             "(KVMINI_COORDINATOR / KVMINI_NUM_PROCESSES / "
                             "KVMINI_PROCESS_ID or TPU-pod autodiscovery); "
                             "process 0 serves HTTP, others follow in "
                             "lockstep (runtime/multihost.py)")
    parser.add_argument("--tp", type=int, default=None,
                        help="Tensor-parallel width for --distributed "
                             "(default: all global devices; dp must stay 1)")
    parser.add_argument("--command-port", type=int, default=None,
                        help="Multi-host scheduler-command channel port "
                             "(default: $KVMINI_COMMAND_PORT or 8470)")
    parser.add_argument("--faults", default=None,
                        help="Arm in-process fault injection points at "
                             "startup (docs/RESILIENCE.md), e.g. "
                             "'sweep_stall:after=50,duration=3;"
                             "device_error:after=200'. Also $KVMINI_FAULTS. "
                             "Default: none (zero overhead)")
    parser.add_argument("--fault-seed", type=int, default=None,
                        help="Seed for probabilistic fault triggers — a "
                             "fixed seed makes a scripted chaos scenario "
                             "deterministic (default: $KVMINI_FAULT_SEED "
                             "or 0)")
    parser.add_argument("--watchdog", action="store_true",
                        help="Arm the wedged-sweep watchdog: no retire "
                             "within watchdog-factor x the rolling sweep "
                             "time fails the in-flight batch with "
                             "finish_reason=engine_fault and degrades "
                             "(sync pipeline -> chunk 1 -> no spec) "
                             "instead of hanging clients. Also "
                             "$KVMINI_WATCHDOG=1 (docs/RESILIENCE.md)")
    parser.add_argument("--watchdog-min-s", type=float, default=2.0,
                        help="Watchdog floor: a wedge shorter than this "
                             "never trips (first compiles excepted — arm "
                             "the watchdog on warmed servers)")
    parser.add_argument("--default-deadline-ms", type=float, default=None,
                        help="Server default per-request deadline for "
                             "deadline-aware admission: requests that "
                             "cannot meet it at current load are shed "
                             "with 429 + Retry-After. Clients override "
                             "per request via deadline_ms / the "
                             "x-request-deadline-ms header. Also "
                             "$KVMINI_DEFAULT_DEADLINE_MS. Default: no "
                             "shedding")
    parser.add_argument("--allow-fault-injection", action="store_true",
                        help="Enable POST /faults (arm/clear injection "
                             "points at runtime — what `kvmini-tpu chaos "
                             "--target local` drives). Also "
                             "$KVMINI_ALLOW_FAULT_INJECTION=1. Never "
                             "enable on a production server")
    parser.add_argument("--econ-accelerator", default=None,
                        help="Price the live economics rail "
                             "($/1K-tok, Wh/1K-tok on /metrics) as this "
                             "chip from tpu-cost.yaml (e.g. 'v5e'). "
                             "Default: auto-detect on TPU backends; CPU "
                             "backends export NO economics. Also "
                             "$KVMINI_ECON_ACCELERATOR "
                             "(docs/ECONOMICS.md)")


def _parse_lora_args(items: Optional[list]) -> Optional[dict[str, str]]:
    """--lora NAME=PEFT_DIR (repeatable) -> {name: dir}."""
    if not items:
        return None
    out: dict[str, str] = {}
    for it in items:
        if "=" not in it:
            raise SystemExit(f"--lora expects NAME=PEFT_DIR, got {it!r}")
        name, path = it.split("=", 1)
        out[name] = path
    return out


def run(args: argparse.Namespace) -> int:
    import os

    from aiohttp import web

    drafter = args.drafter or os.environ.get("KVMINI_DRAFTER")
    # container contract: the deploy layer (deploy/backends.py _jax_native_env)
    # configures the runtime through KVMINI_* env; explicit CLI flags win
    # (including --pp-microbatches 1 to force unpipelined decode)
    pp = args.pp or int(os.environ.get("KVMINI_PP", "0") or 0)
    pp_mb = (
        args.pp_microbatches
        if args.pp_microbatches is not None
        else int(os.environ.get("KVMINI_PP_MICROBATCHES", "1") or 1)
    )
    max_slots = args.max_slots or int(os.environ.get("KVMINI_MAX_BATCH", "8") or 8)
    max_seq = args.max_seq_len or int(
        # kvmini: config-ok — deploy manifests default 4096 by design
        os.environ.get("KVMINI_MAX_MODEL_LEN", "1024") or 1024
    )
    quantization = (
        args.quantization
        if args.quantization != "none"
        else os.environ.get("KVMINI_QUANTIZATION", "none")
    )
    kv_dtype = args.kv_cache_dtype or os.environ.get("KVMINI_KV_CACHE_DTYPE")
    quant_mode = (
        args.quant_mode or os.environ.get("KVMINI_QUANT_MODE") or "dequant"
    )
    spec_tokens = args.spec_tokens
    if spec_tokens is None:
        spec_tokens = int(os.environ.get("KVMINI_SPEC_TOKENS", "4" if drafter else "0"))
    prefill_chunk = args.prefill_chunk
    if prefill_chunk is None:
        env_pc = os.environ.get("KVMINI_PREFILL_CHUNK")
        prefill_chunk = int(env_pc) if env_pc else None
    disagg = bool(
        args.disagg or os.environ.get("KVMINI_DISAGG", "") in ("1", "true")
    )
    disagg_min_prompt = args.disagg_min_prompt
    if disagg_min_prompt is None:
        disagg_min_prompt = int(
            os.environ.get("KVMINI_DISAGG_MIN_PROMPT", "0") or 0
        )
    prefill_lane_devices = args.prefill_lane_devices
    if prefill_lane_devices is None:
        prefill_lane_devices = int(
            os.environ.get("KVMINI_PREFILL_LANE_DEVICES", "0") or 0
        )
    faults = args.faults or os.environ.get("KVMINI_FAULTS") or None
    fault_seed = (
        args.fault_seed
        if args.fault_seed is not None
        else int(os.environ.get("KVMINI_FAULT_SEED", "0") or 0)
    )
    watchdog = bool(
        args.watchdog
        or os.environ.get("KVMINI_WATCHDOG", "") in ("1", "true")
    )
    default_deadline_ms = args.default_deadline_ms
    if default_deadline_ms is None:
        env_dl = os.environ.get("KVMINI_DEFAULT_DEADLINE_MS")
        default_deadline_ms = float(env_dl) if env_dl else None
    allow_faults = bool(
        args.allow_fault_injection
        or os.environ.get("KVMINI_ALLOW_FAULT_INJECTION", "") in ("1", "true")
    )

    # multi-host: join the process group BEFORE any device is touched, then
    # shard the engine over the global mesh (runtime/multihost.py lockstep)
    multihost = False
    mesh_override = None
    if args.distributed:
        from kserve_vllm_mini_tpu.parallel import distributed as dist

        joined = dist.initialize()
        multihost = joined and dist.process_count() > 1
        if multihost:
            import jax

            from kserve_vllm_mini_tpu.parallel.mesh import MeshSpec

            n_global = len(jax.devices())
            topo_name = args.topology or os.environ.get("KVMINI_TOPOLOGY")
            if pp and pp > 1:
                spec = MeshSpec(pp=pp)
            elif topo_name:
                # a layout preset (e.g. v5p-16-longctx: tp4 x sp4) names the
                # GLOBAL mesh across hosts — without this, multi-host serving
                # would silently fall back to plain tp and drop the layout
                from kserve_vllm_mini_tpu.parallel.mesh import TOPOLOGY_PRESETS

                if topo_name not in TOPOLOGY_PRESETS:
                    raise SystemExit(f"unknown topology preset {topo_name!r}")
                pr = TOPOLOGY_PRESETS[topo_name]
                if pr["chips"] != n_global:
                    raise SystemExit(
                        f"topology {topo_name} is {pr['chips']} chips but the "
                        f"process group has {n_global} devices"
                    )
                spec = MeshSpec.fill(n_global, tp=pr.get("tp"),
                                     sp=pr.get("sp", 1))
            else:
                spec = MeshSpec.fill(n_global, tp=args.tp or n_global)
            if spec.dp > 1:
                raise SystemExit(
                    f"--distributed needs dp == 1 (got tp={spec.tp} over "
                    f"{n_global} devices -> dp={spec.dp}); raise --tp or use --pp"
                )
            if drafter:
                raise SystemExit("--distributed does not support --drafter (v1)")
            if disagg:
                # the prefill lane is host-local; a lockstep follower
                # cannot replay its handoff timing (same rule as
                # prefill_chunk / deadline sheds — but loud, because
                # silently colocating would bench the wrong architecture)
                raise SystemExit("--distributed does not support --disagg (v1)")
            mesh_override = dist.global_mesh(spec)

    engine, tok, name = build_engine(
        model=args.model,
        checkpoint=args.checkpoint,
        tokenizer_path=args.tokenizer,
        max_slots=max_slots,
        decode_chunk=args.decode_chunk,
        prefill_chunk=prefill_chunk,
        disagg=disagg,
        disagg_min_prompt=disagg_min_prompt,
        prefill_lane_devices=prefill_lane_devices,
        max_seq_len=max_seq,
        topology=args.topology or os.environ.get("KVMINI_TOPOLOGY") or None,
        pp=pp,
        pp_microbatches=pp_mb,
        scan_unroll=args.scan_unroll,
        seed=args.seed,
        quantization=quantization,
        quant_mode=quant_mode,
        kv_cache_dtype=kv_dtype,
        drafter=drafter,
        spec_tokens=spec_tokens,
        mesh=mesh_override,
        prefix_cache=bool(
            args.prefix_cache
            or os.environ.get("KVMINI_PREFIX_CACHE", "") in ("1", "true")
        ),
        kv_layout=args.kv_layout,
        kv_block_size=args.kv_block_size,
        kv_pool_blocks=args.kv_pool_blocks,
        kv_host_tier_bytes=args.kv_host_tier_bytes,
        lora_adapters=_parse_lora_args(args.lora),
        lora_demo=args.lora_demo,
        lora_rank=args.lora_rank,
        lora_slots=args.lora_slots,
        request_tracing=not (
            args.no_request_tracing
            or os.environ.get("KVMINI_REQUEST_TRACING", "").lower()
            in ("0", "false", "off")
        ),
        trace_buffer=args.trace_buffer,
        faults=faults,
        fault_seed=fault_seed,
        watchdog=watchdog,
        default_deadline_s=(
            default_deadline_ms / 1000.0 if default_deadline_ms else None
        ),
        econ_accelerator=(
            args.econ_accelerator
            or os.environ.get("KVMINI_ECON_ACCELERATOR") or None
        ),
    )
    if watchdog and args.watchdog_min_s is not None:
        engine.ecfg.watchdog_min_s = float(args.watchdog_min_s)

    if multihost:
        from kserve_vllm_mini_tpu.parallel import distributed as dist
        from kserve_vllm_mini_tpu.runtime import multihost as mh

        cmd_port = args.command_port or int(
            os.environ.get("KVMINI_COMMAND_PORT", "8470")
        )
        # process-0's reachable host, NOT loopback: on a TPU pod the
        # coordinator comes from autodiscovery (TPU_WORKER_HOSTNAMES), and
        # followers on other hosts must dial that machine
        coord_host = dist.coordinator_host()
        if dist.is_primary():
            handle = mh.serve_multihost(
                engine, primary=True, coordinator_host=coord_host,
                command_port=cmd_port, n_followers=dist.process_count() - 1,
            )
            app = make_app(engine, tok, name, multihost=True,
                           alive_check=handle.is_alive,
                           allow_fault_injection=allow_faults)
            print(f"kvmini-tpu serve: {name} on http://{args.host}:{args.port} "
                  f"(slots={max_slots}, max_seq={max_seq}, "
                  f"multihost primary, {dist.process_count()} processes, "
                  f"mesh={dict(engine.mesh.shape)})", flush=True)
            try:
                web.run_app(app, host=args.host, port=args.port, print=None)
            finally:
                # synchronous: followers must get the stop command even as
                # the interpreter tears down this daemon thread's world
                handle.shutdown()
            return 0
        print(f"kvmini-tpu serve: follower {dist.process_index()}/"
              f"{dist.process_count()} (mesh={dict(engine.mesh.shape)})",
              flush=True)
        mh.serve_multihost(
            engine, primary=False, coordinator_host=coord_host,
            command_port=cmd_port, n_followers=0,
        )
        return 0

    engine.start()
    app = make_app(engine, tok, name, allow_fault_injection=allow_faults)
    print(f"kvmini-tpu serve: {name} on http://{args.host}:{args.port} "
          f"(slots={max_slots}, max_seq={max_seq})")
    try:
        web.run_app(app, host=args.host, port=args.port, print=None)
    finally:
        engine.stop()
    return 0

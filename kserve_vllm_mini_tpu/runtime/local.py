"""In-process local serving: boot the JAX runtime + OpenAI HTTP server.

One helper shared by the bench pipeline, every sweep, the backend comparator,
and the chaos harness — the reference has no analog because its engines are
external container images (SURVEY.md §0); here "deploy" can mean "start a
thread", which is what makes the whole framework runnable with no cluster.
"""

from __future__ import annotations

import asyncio
import os
import socket
import threading
import time
import urllib.request
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional


def _free_port() -> int:
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    return port


@dataclass
class LocalServer:
    url: str
    engine: Any
    tokenizer: Any
    model_name: str
    boot_began: float            # cold-start instant (pod-startedAt analog)
    boot_seconds: float = 0.0
    _stop: Optional[Any] = field(default=None, repr=False)

    def stop(self) -> None:
        if self._stop is not None:
            self._stop()
            self._stop = None


def start_local_server(
    profile: dict[str, Any],
    host: str = "127.0.0.1",
    ready_timeout_s: float = 120.0,
) -> LocalServer:
    """Boot engine + aiohttp server from a bench profile dict. The measured
    boot window (model init + first readiness) is the run's cold start."""
    from aiohttp import web

    from kserve_vllm_mini_tpu.runtime.server import build_engine, make_app

    port = _free_port()
    t0 = time.time()
    engine, tok, name = build_engine(
        model=profile.get("model", "llama-tiny"),
        checkpoint=profile.get("checkpoint"),
        max_slots=int(profile.get("max_slots", 8)),
        max_seq_len=int(profile.get("max_model_len", 1024)),
        topology=profile.get("jax_topology"),
        quantization=profile.get("quantization", "none") or "none",
        quant_mode=profile.get("quant_mode", "dequant") or "dequant",
        kv_cache_dtype=profile.get("kv_cache_dtype"),
        decode_chunk=int(profile.get("decode_chunk", 1)),
        prefill_chunk=(
            int(profile["prefill_chunk"])
            if profile.get("prefill_chunk") is not None
            else None
        ),
        # disaggregated prefill/decode lanes (docs/DISAGGREGATION.md)
        disagg=bool(profile.get("disagg", False)),
        disagg_min_prompt=int(profile.get("disagg_min_prompt", 0)),
        prefill_lane_devices=int(profile.get("prefill_lane_devices", 0)),
        scan_unroll=int(profile.get("scan_unroll", 1)),
        pp=int(profile.get("pp", 0)),
        pp_microbatches=int(profile.get("pp_microbatches", 1)),
        drafter=profile.get("drafter"),
        spec_tokens=int(
            profile.get("spec_tokens", 4 if profile.get("drafter") else 0)
        ),
        prefix_cache=bool(profile.get("prefix_cache", False)),
        kv_layout=profile.get("kv_layout", "dense"),
        kv_block_size=int(profile.get("kv_block_size", 64)),
        kv_pool_blocks=(
            int(profile["kv_pool_blocks"])
            if profile.get("kv_pool_blocks") is not None
            else None
        ),
        # live economics (docs/ECONOMICS.md): same precedence as the
        # serve CLI — profile key, then env — so a self-serve bench can
        # price itself on any backend; TPU backends auto-detect anyway
        econ_accelerator=(
            profile.get("econ_accelerator")
            or os.environ.get("KVMINI_ECON_ACCELERATOR") or None
        ),
        lora_adapters=profile.get("lora"),
        lora_demo=int(profile.get("lora_demo", 0)),
        lora_rank=int(profile.get("lora_rank", 8)),
        lora_slots=int(profile.get("lora_slots", 4)),
        # resilience knobs (docs/RESILIENCE.md): fault injection config,
        # the wedged-sweep watchdog, and deadline-aware shedding
        faults=profile.get("faults"),
        fault_seed=int(profile.get("fault_seed", 0)),
        watchdog=bool(profile.get("watchdog", False)),
        default_deadline_s=(
            float(profile["default_deadline_s"])
            if profile.get("default_deadline_s") is not None
            else None
        ),
    )
    if profile.get("watchdog_min_s") is not None:
        engine.ecfg.watchdog_min_s = float(profile["watchdog_min_s"])
    engine.start()
    app = make_app(
        engine, tok, name,
        allow_fault_injection=bool(profile.get("allow_fault_injection", False)),
    )
    runner = web.AppRunner(app)
    loop = asyncio.new_event_loop()

    def _serve() -> None:
        asyncio.set_event_loop(loop)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, host, port)
        loop.run_until_complete(site.start())
        loop.run_forever()

    thread = threading.Thread(target=_serve, daemon=True, name="local-server")
    thread.start()
    url = f"http://{host}:{port}"

    deadline = time.time() + ready_timeout_s
    last_err: Optional[Exception] = None
    while time.time() < deadline:
        try:
            urllib.request.urlopen(url + "/healthz", timeout=1)
            break
        except Exception as e:  # noqa: BLE001 — readiness probe, any failure retries
            last_err = e
            time.sleep(0.2)
    else:
        engine.stop()
        raise TimeoutError(f"local server not ready after {ready_timeout_s}s: {last_err}")

    def _stop() -> None:
        engine.stop()
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=5.0)

    return LocalServer(
        url=url,
        engine=engine,
        tokenizer=tok,
        model_name=name,
        boot_began=t0,
        boot_seconds=time.time() - t0,
        _stop=_stop,
    )


@contextmanager
def local_server(profile: dict[str, Any], **kwargs: Any) -> Iterator[LocalServer]:
    srv = start_local_server(profile, **kwargs)
    try:
        yield srv
    finally:
        srv.stop()

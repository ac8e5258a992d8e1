"""The system under test, one engine behind its HTTP app: the only file of the
benchmark that imports the program. A configuration names its system
(``"system": "engine"``); another (replicas behind the router) is another file
of ``bench/systems/`` with the same three functions and the same ``Served``.

``boot(config, seed)`` serves the program's normal path in this process: the
benchmark's weights (``bench/weights.py``) arranged into the program's
checkpoint tree, the program's ``Engine``, the program's aiohttp app
(``runtime.server.make_app``) on a thread, as ``runtime.local`` starts it.
What it takes from the program besides that: compile statistics, and the
exported counters, which ``bench/run.py`` reads over HTTP from ``/metrics``.

The tokenizer handed to the app is the program's byte tokenizer with two
changes a deployment's own tokenizer file would bring, both needed to measure
at all: every token id decodes to visible text (``<id>``; the byte tokenizer
drops ids past 258, so a 32k-vocabulary model would stream empty chunks and
the client could neither time tokens nor recover them for ``correct``), and
no id is the end-of-sequence token (random weights emit it at random; the
traffic fixes every output length, as ``ignore_eos`` does elsewhere).
"""

from __future__ import annotations

import asyncio
import dataclasses
import re
import socket
import threading
import time
import urllib.request
from dataclasses import dataclass
from typing import Any, Sequence

from bench import config as bench_config

_TOKEN = re.compile(r"<(\d+)>")


def served_token_ids(text: str) -> list[int]:
    """Token ids back from the text the app streamed."""
    return [int(x) for x in _TOKEN.findall(text)]


def model_config(m: dict):
    from kserve_vllm_mini_tpu.models.config import ModelConfig

    if "head_dim" not in m:
        raise KeyError("the configuration's file gives no head_dim: write the head's width "
                       "there (and under `assumed` where the source leaves it out)")
    # the head's width is the file's; where it is not hidden_size / num_attention_heads
    # (attention narrower or wider than the residual stream) the program is told so
    extra = dict(m.get("model_extra", {}))
    if m["head_dim"] != m["hidden_size"] // m["num_attention_heads"]:
        extra["explicit_head_dim"] = m["head_dim"]
    return ModelConfig(
        name=m["name"], vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        max_seq_len=m["max_position_embeddings"], rope_theta=float(m["rope_theta"]),
        rms_eps=float(m["rms_norm_eps"]), dtype=m["torch_dtype"],
        tie_embeddings=bool(m.get("tie_word_embeddings")),
        sliding_window=m.get("sliding_window"),
        attn_bias=bool(m.get("attention_bias")),
        # what only the program names (a block type, expert counts): by field name
        **extra,
    )


def make_tokenizer(vocab_size: int):
    from kserve_vllm_mini_tpu.runtime.tokenizer import ByteTokenizer

    class BenchTokenizer(ByteTokenizer):
        def __init__(self) -> None:
            super().__init__()
            self.vocab_size = vocab_size
            self.eos_id = -1

        def decode(self, ids: Sequence[int]) -> str:
            return "".join(f"<{i}>" for i in ids)

    return BenchTokenizer()


def chat_prompt_ids(tok, content: str) -> list[int]:
    """What the app makes of one user message (its ``_messages_to_prompt``
    is a closure, so the template is repeated here; the served
    ``usage.prompt_tokens`` is checked against it for every request, and a
    wrong template would fail ``correct`` outright)."""
    return tok.encode(f"user: {content}\nassistant:")


@dataclass
class Served:
    url: str
    engine: Any
    tokenizer: Any
    slots: int         # sequences decoded together
    context: int       # positions one slot reserves
    boot_s: float
    weights_s: float
    _loop: Any
    _thread: Any

    def compiled(self) -> dict[str, dict]:
        snap = self.engine.compile_stats_snapshot()
        return {e["label"]: e for e in snap["executables"]}

    def stop(self) -> None:
        self.engine.stop()
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10.0)


# keys of a configuration's ``engine`` group that ``boot`` itself acts on; every
# other key is a field of the program's ``EngineConfig``, passed by name
OWN_KEYS = ("quantization", "topology")


def engine_config(e: dict):
    """The program's ``EngineConfig`` from the configuration's ``engine``
    group, field by field. A key that is no field is an error: a setting that
    is silently dropped would print as a sound run of something else."""
    from kserve_vllm_mini_tpu.runtime.engine import EngineConfig

    known = {f.name for f in dataclasses.fields(EngineConfig)}
    given = {k: v for k, v in e.items() if k not in OWN_KEYS}
    unknown = sorted(set(given) - known)
    if unknown:
        raise KeyError(f"engine settings {unknown} are no fields of EngineConfig "
                       f"(known: {sorted(known)}; boot's own: {list(OWN_KEYS)})")
    # build_engine's one derived default: half the context may be one prefill
    given.setdefault("max_prefill_len", int(given["max_seq_len"]) // 2)
    return EngineConfig(**given)


def mesh_for(chips: int, topology):
    """The mesh a cell's chips are served over: none for one chip, else the
    program's own for the configuration's ``topology``. A cell on several
    chips that would be served on one is an error, never a quiet fallback."""
    if chips == 1 and topology is None:
        return None
    if topology is None:
        raise ValueError(f"a cell on {chips} chips needs the configuration's engine.topology")
    from kserve_vllm_mini_tpu.parallel.mesh import mesh_for_topology

    mesh = mesh_for_topology(topology)
    if mesh.size != chips:
        raise ValueError(f"topology {topology!r} spans {mesh.size} devices; the cell asks for {chips}")
    return mesh


def boot(config: dict, seed: int, chips: int = 1, ready_timeout_s: float = 600.0) -> Served:
    import jax
    from aiohttp import web

    from kserve_vllm_mini_tpu.runtime.engine import Engine
    from kserve_vllm_mini_tpu.runtime.server import configure_compile_cache, make_app

    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache (fixed): before
    # anything compiles, so the weights, the engine's steps and the reference all land there
    configure_compile_cache()
    m, e = config["model"], config["engine"]
    cfg = model_config(m)
    ecfg = engine_config(e)
    mesh = mesh_for(chips, e.get("topology"))
    shardings = None
    if mesh is not None:
        from kserve_vllm_mini_tpu.parallel.sharding import param_shardings

        def shardings(tree):
            return param_shardings(cfg, mesh, params=tree)
    t0 = time.monotonic()
    # the configuration's reference module names the weights that go with it
    # (bench/weights.py for the dense decoder): laid out as the program's checkpoint,
    # each matmul as the configuration's quantization stores it (bench/weight_kinds/)
    arch = bench_config.load_module("references", m["reference"])
    params = arch.weights.all_weights(seed, m, shardings)
    jax.block_until_ready(params)
    weights_s = time.monotonic() - t0
    tok = make_tokenizer(cfg.vocab_size)
    engine = Engine(params, cfg, ecfg, mesh=mesh, pad_id=tok.pad_id)
    engine.start()
    app = make_app(engine, tok, m["name"])
    runner = web.AppRunner(app)
    loop = asyncio.new_event_loop()
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]

    def serve() -> None:
        asyncio.set_event_loop(loop)
        loop.run_until_complete(runner.setup())
        loop.run_until_complete(web.SockSite(runner, sock).start())
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())

    thread = threading.Thread(target=serve, daemon=True, name="bench-server")
    thread.start()
    url = f"http://127.0.0.1:{port}"
    deadline = time.monotonic() + ready_timeout_s
    while True:
        try:
            urllib.request.urlopen(url + "/healthz", timeout=1).close()
            break
        except OSError:
            if time.monotonic() > deadline:
                engine.stop()
                raise TimeoutError(f"server not ready after {ready_timeout_s}s")
            time.sleep(0.05)
    return Served(url=url, engine=engine, tokenizer=tok,
                  slots=ecfg.max_slots, context=ecfg.max_seq_len,
                  boot_s=time.monotonic() - t0, weights_s=weights_s,
                  _loop=loop, _thread=thread)

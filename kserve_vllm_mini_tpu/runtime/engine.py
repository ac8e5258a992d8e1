"""Continuous-batching serving engine under XLA's static-shape constraint.

Core design (SURVEY.md §7.3.1 — this is the subsystem the reference
outsources to vLLM/TGI/Triton images):

- **Slots, not dynamic batches**: the KV cache is one static array
  [L, max_slots, KVH, max_seq, D]; a request occupies a slot from admission
  to completion, so the decode step is a single jitted call of fixed shape
  regardless of which requests are live (inactive slots compute padding).
- **Bucketed prefill**: prompts pad to power-of-two buckets; one compiled
  executable per bucket, cached. Prefill writes KV directly into the slot's
  cache region and returns the first sampled token.
- **Donated decode state**: cache arrays are donated through every jitted
  step, so XLA updates them in place in HBM — no cache copies per token.
- **Host scheduler thread**: admission (free slot + pending request ->
  prefill) interleaved with decode sweeps; tokens stream to per-request
  thread-safe queues; true server-side TTFT is recorded here and surfaced
  through the API (the reference can only approximate TTFT client-side,
  SURVEY.md §7.3.5).
"""

from __future__ import annotations

import queue
import threading
import time
import uuid
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.models.linear_attention import CHUNK as LINEAR_CHUNK
from kserve_vllm_mini_tpu.models.linear_attention import linear_decode_kernel_block
from kserve_vllm_mini_tpu.models.llama import (
    dense_decode_kernel_block,
    forward,
    window_decode_kernel_blocks,
)
from kserve_vllm_mini_tpu.models.mamba import ssm_decode_kernel_block
from kserve_vllm_mini_tpu.profiling.compile_stats import (
    CompileRecorder,
    InstrumentedJit,
)
from kserve_vllm_mini_tpu.runtime import steps
from kserve_vllm_mini_tpu.runtime import tracing as rt_tracing

# request timestamps, phase durations, watchdog beats: all on the runtime's
# one monotonic clock (runtime/tracing.py); only /traces converts to Unix time
_now = rt_tracing.now_s

# What makes an admission drain the decode pipeline instead of being
# dispatched behind the sweep in flight (Engine._behind_refusal;
# docs/DECODE_PIPELINE.md), the ``reason`` label of
# ``kvmini_tpu_admissions_drained_total``.
ADMIT_DRAIN_REASONS = (
    "lockstep", "paged", "disagg", "drafter", "lora", "prefix_cache",
    "prefill_chunk", "constrained", "sweep",
)

# Constrained decoding speaks the TOKEN protocol (runtime/token_grammar.py):
# machines expose token_mask(budget) -> bool[V] / advance_token(id). Raw
# byte automata (runtime/constrain.py) passed as GenRequest.constraint are
# auto-wrapped for the ByteTokenizer id mapping in submit().


@dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq_len: int = 1024           # per-request cap (cache length)
    max_prefill_len: int = 512
    min_prefill_bucket: int = 16
    # Chunked prefill (ROADMAP item 3, the TTFT/ITL-tail fix): prompts
    # whose un-reused remainder exceeds this many tokens are split into
    # fixed-size chunks that the SCHEDULER advances one per iteration,
    # interleaved with decode sweeps — a long prompt no longer freezes
    # every streaming client behind one monolithic compile+execute. The
    # chunk writes KV at its running offset (the same continuation-chunk
    # executables the over-budget path already uses, so greedy streams
    # stay byte-identical to monolithic admission) and only the final
    # chunk's last-position logits feed sampling. None = monolithic
    # admission (the seed behavior). Clamped into
    # [min_prefill_bucket, max_prefill_len]; lockstep multihost engines
    # ignore it (chunk advancement is a host-local scheduling decision
    # the follower replay stream does not carry — same rule as deadline
    # sheds).
    prefill_chunk: Optional[int] = None
    # Disaggregated prefill/decode (docs/DISAGGREGATION.md, ROADMAP
    # item 1): admissions route to a dedicated prefill lane
    # (runtime/disagg.py PrefillLane — its own thread, optionally its own
    # mesh submesh) that stages the prompt's KV out-of-band and hands the
    # finished stripe back through the versioned KV-block handoff
    # protocol, so long prefills NEVER execute on the decode lane's
    # sweep loop. Greedy streams stay byte-identical to the colocated
    # engine (same forward/params/bucket schedule, stripe injected
    # verbatim). Every failure mode degrades to colocated prefill —
    # dropped handoffs tombstone, a dead lane flips routing off — never
    # a hung request. v1 composes with dense KV only and excludes
    # drafters, LoRA, and prefix_cache; lockstep multihost engines
    # reject it (the lane is host-local, same rule as prefill_chunk).
    disagg: bool = False
    # prompts whose length is below this many tokens prefill colocated
    # even with disagg on: a short prefill is cheaper than its handoff
    # round-trip. 0 = route everything (the measurement-friendly
    # default; bench/serving set a threshold per deployment).
    disagg_min_prompt: int = 0
    seed: int = 0
    kv_cache_dtype: Optional[str] = None  # None -> model dtype (e.g. "float32")
    # How quantized matmul leaves contract (ops/qmatmul.py QUANT_MODES):
    # "dequant" casts the int weight to the activation dtype before the
    # dot (W8A16/W4A16); "w8a8" quantizes activations per token and runs
    # the contraction int8 x int8 on the MXU, scales folded post-
    # accumulation. No-op on unquantized params. The engine folds it into
    # cfg.quant_mode so every compiled step sees it as a static config
    # attribute.
    quant_mode: str = "dequant"
    # decode steps fused into one dispatch. 1 = lowest per-token latency;
    # larger values amortize host dispatch + readback over several tokens
    # at the price of streaming granularity and up to chunk-1 wasted steps
    # when a request finishes mid-chunk. Whether that trade pays on a
    # directly attached chip is not measured (ROADMAP A5).
    decode_chunk: int = 1
    # speculative decoding: draft tokens proposed per round by the drafter
    # model (requires a drafter; 0 disables). Verification is rejection
    # sampling (build_spec_step_sampled): sampled requests speculate with
    # their output distribution preserved exactly, and temperature-0 rows
    # degenerate to the exact argmax accept rule, so greedy output stays
    # bit-identical to plain greedy decode. Penalized/constrained/logprob
    # slots fall back to the normal sweep (_spec_partition).
    spec_tokens: int = 0
    # serving-PP microbatches: slot groups pipelined GPipe-style through the
    # stages (parallel/serving_pp.py); 1 = unpipelined. Only used on pp>1
    # meshes; Engine rejects values that do not divide max_slots (a
    # non-dividing M would silently decode unpipelined).
    pp_microbatches: int = 1
    # Automatic prefix caching: a finished request's slot RETAINS its KV,
    # and a new request whose prompt shares a token prefix with a retained
    # slot is admitted INTO that slot, prefilling only the suffix (vLLM's
    # APC, re-thought for slot-contiguous caches: reuse = slot affinity,
    # zero copies). Generated tokens are part of the reusable prefix
    # (multi-turn chat appends to its own transcript). Off by default:
    # reused rows were computed by whatever executable shape the ORIGINAL
    # request used, so outputs can differ from a cold run by bf16 rounding
    # — the oracle tests pin the cold paths bit-exactly and opt in where
    # reuse itself is under test. Disabled when a drafter is configured
    # (the drafter cache retains proposal garbage a new request's drafter
    # would attend).
    prefix_cache: bool = False
    # Paged KV ("paged") vs per-slot dense stripes ("dense"). Paged is the
    # TPU re-think of vLLM's PagedAttention (the reference stack's namesake
    # mechanism, reference README.md:26): the cache is a pool of
    # kv_block_size-position blocks (models/llama.init_paged_kv_cache) and
    # each request owns an ordered block list, so HBM is reserved per
    # TOKENS IN FLIGHT — admission takes ceil((prompt+max_new)/BLK) blocks
    # — instead of max_slots x max_seq_len up front. 64 slots x 4096
    # max_seq of 8B bf16 KV is 34 GB (unservable on one v5e); the same
    # load at 256-token requests pages in ~1 GB. Requests that don't fit
    # the free pool wait in the queue (admission backpressure, no
    # mid-flight preemption — reservations are worst-case).
    # v1 limits: incompatible with meshes (sharded pools), drafters
    # (spec decode), and prefix_cache (block-level sharing is the planned
    # merge of the two).
    kv_layout: str = "dense"
    kv_block_size: int = 64
    # pool size in blocks; None sizes it to max_slots x ceil(max_seq/BLK)
    # (memory-equal to dense — set it LOWER to realize the savings)
    kv_pool_blocks: Optional[int] = None
    # Host-RAM KV tier (docs/TROUBLESHOOTING.md "Host-RAM KV tier
    # thrash"): byte budget of host memory that catches _retained_lru
    # evictions instead of discarding them. Evicted registered blocks
    # DEMOTE to the tier (device -> host copy, content key kept) and
    # PROMOTE back on a prefix-key match at admission (fresh block +
    # host -> device upload). Priced by profiling/headroom.py as host
    # bytes only — never counted against the HBM estimate. 0/None = no
    # tier. The tier disables itself when eviction churn crosses the
    # kv_thrash monitor thresholds (demoting under thrash just moves
    # the churn to PCIe) — the kv_tier_disabled gauge records it.
    kv_host_tier_bytes: Optional[int] = None
    # Double-buffered decode (docs/DECODE_PIPELINE.md): in steady state the
    # scheduler dispatches sweep N+1 from the ON-DEVICE sampled-token carry
    # before retiring sweep N, so host-side token emission/admission work
    # overlaps device compute instead of serializing with it. Emitted
    # streams are identical to the synchronous loop's (the dispatch-ahead
    # guard keeps chunk sizes and the rng split sequence aligned); grammar-
    # constrained slots, speculative partitions, and iterations where the
    # active set changes fall back to the synchronous sweep. False forces
    # the seed's fully synchronous dispatch->readback->emit loop.
    decode_pipeline: bool = True
    # multi-LoRA bank capacity for adapters loaded AT RUNTIME into an
    # engine that started without a bank (load_adapter creates a zero bank
    # of this many adapter slots; the bank's array shapes are fixed once
    # created, so growing past it needs a restart). Engines built with a
    # preset bank keep that bank's capacity instead.
    lora_slots: int = 4
    # Request lifecycle tracing (docs/TRACING.md): per-request phase spans
    # (queue wait, prefill, decode, cancellation) plus engine-lane
    # dispatch->retire window spans, recorded into a bounded ring buffer
    # served at GET /traces. On by default — the recorder is post-hoc (at
    # most tracing.MAX_REQUEST_SPANS tuples per request, never per-token)
    # and the buffer evicts at trace_buffer spans. False disables span
    # recording entirely; the phase histograms (plain counters) stay on.
    request_tracing: bool = True
    trace_buffer: int = 4096
    # Compile-stats capture (docs/PROFILING.md): the engine's compiled
    # steps go through an explicit lower().compile() wrapper
    # (profiling.compile_stats.InstrumentedJit) so compile wall time, the
    # XLA cost model's FLOPs/bytes, and peak-buffer estimates accumulate
    # into snapshot_stats / /metrics. One compile total per executable
    # (the wrapper caches what it built); a compile error raises to the
    # step's caller. Disabled automatically on meshes (AOT calls don't
    # auto-reshard arguments the way jit does).
    compile_stats: bool = True
    # In-process fault injection (docs/RESILIENCE.md): a KVMINI_FAULTS-
    # syntax string ("sweep_stall:after=5,duration=2;device_error:...")
    # parsed into a runtime/faults.py registry at build. None/empty =
    # NO registry object at all, so every hot-path site pays exactly one
    # `is not None` check (off by default, zero overhead when disabled).
    # Points are also armable at runtime through the server's /faults
    # endpoint (gated behind --allow-fault-injection).
    faults: Optional[str] = None
    # seed for any probabilistic fault trigger: two runs of the same
    # scripted scenario observe the identical event sequence
    fault_seed: int = 0
    # Engine watchdog (docs/RESILIENCE.md): a side thread that declares
    # the scheduler WEDGED when no sweep retires within
    # max(watchdog_factor x rolling sweep EMA, watchdog_min_s) while
    # work is live, immediately fails the in-flight batch with
    # finish_reason="engine_fault" (clients unblock even while the
    # scheduler thread is still stuck), and — once the loop resumes —
    # drains the poisoned pipeline and DEGRADES one ladder level per
    # trip (sync pipeline -> decode_chunk 1 -> spec off) before giving
    # up. Off by default: a cold engine's first XLA compiles stall the
    # loop legitimately for tens of seconds, so arming the watchdog is
    # a warmed-serving deployment decision.
    watchdog: bool = False
    watchdog_factor: float = 10.0
    watchdog_min_s: float = 2.0
    # Server default for per-request deadlines (seconds, submit-to-done
    # budget) used by deadline-aware admission shedding
    # (docs/RESILIENCE.md): a request that cannot meet its deadline
    # given the current queue burn-rate is 429-shed at the door instead
    # of timing out after burning decode steps. None = no server
    # default; client-supplied deadlines still apply.
    default_deadline_s: Optional[float] = None
    # Live economics rail (docs/ECONOMICS.md): accelerator label used to
    # price the deployment against tpu-cost.yaml. None = auto-detect
    # (the device-table row of a TPU backend's device_kind; CPU backends
    # get NO rail — the absent-not-zero rule, a fabricated $0/1K-tok on a
    # dev box would poison fleet aggregation). Setting it explicitly turns
    # the rail on regardless of backend, which is how tests and mock fleets
    # price a CPU engine as if it were the named chip.
    econ_accelerator: Optional[str] = None


@dataclass
class GenRequest:
    prompt_tokens: list[int]
    max_new_tokens: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    # OpenAI presence/frequency penalties over generated tokens (vLLM
    # semantics: output-only, prompt excluded). Applied device-side from a
    # per-slot token-count table before sampling; 0.0 = bit-exact identity.
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    eos_id: Optional[int] = None
    request_id: str = field(default_factory=lambda: uuid.uuid4().hex[:16])
    # set by Engine.submit when the prompt was cut to max_prefill_len: the
    # request served is not the request sent, and every downstream record
    # (stream events, requests.csv, results.json) must carry the flag — a
    # measurement framework must not silently measure a different workload
    truncated: bool = False
    truncated_tokens: int = 0
    # logprobs in the OpenAI sense: when True, each streamed token event
    # carries (logprob, top-k ids, top-k logprobs); top_logprobs <= 5
    logprobs: bool = False
    top_logprobs: int = 0
    # grammar-constrained decoding: json_object mode and tool calls. Either
    # a token-protocol machine (runtime/token_grammar.py — works for any
    # tokenizer/vocab) or a raw byte automaton (runtime/constrain.py),
    # which submit() wraps with the ByteTokenizer id mapping. The engine
    # masks device-side; the machine runs host-side.
    constraint: Optional[Any] = None
    # multi-LoRA: adapter name from the engine's bank registry (None = base
    # model). Resolved to a bank index at submit; each slot decodes with
    # its own adapter inside the same jitted step (ops/lora.py).
    adapter: Optional[str] = None
    # W3C trace context from the client's traceparent header
    # (runtime/server.py parses it): the engine's phase spans share
    # trace_id with the client's trace and parent under parent_span_id,
    # so /traces output joins the loadgen's traces.json by trace_id
    # (docs/TRACING.md). None = a fresh trace id is minted at submit when
    # tracing is enabled (the request still shows up in /traces).
    trace_id: Optional[str] = None
    parent_span_id: Optional[str] = None
    # per-request deadline (seconds, measured from submit): a queued
    # request whose deadline expires before the scheduler admits it is
    # finished with finish_reason="shed" WITHOUT spending a prefill
    # (docs/RESILIENCE.md). The server also sheds at the door (429 +
    # Retry-After) when the admission estimate says the deadline cannot
    # be met. None = no deadline.
    deadline_s: Optional[float] = None


@dataclass
class _AdminOp:
    """Engine-state mutation executed ON the scheduler thread between
    sweeps (single-writer discipline for bank/registry swaps). ``fn`` runs
    with no args; the result/error lands in the fields and ``done`` fires."""

    fn: Any
    done: threading.Event = field(default_factory=threading.Event)
    error: Optional[str] = None

    def run(self) -> None:
        try:
            self.fn()
        except Exception as e:  # noqa: BLE001 — error goes to the caller
            # Event-ordered handoff: written on the scheduler thread
            # BEFORE done.set(); callers read only after done.wait()
            # kvmini: thread-ok — see above
            self.error = f"{type(e).__name__}: {e}"
        finally:
            self.done.set()


def _require_tp_only_mesh(mesh) -> None:
    """Multi-LoRA's replicated-bank design assumes tp-only meshes — ONE
    check shared by Engine init (preset banks) and load_adapter (hot-swap)
    so the two paths can never drift on which mesh shapes they accept."""
    if mesh is not None and any(
        mesh.shape.get(ax, 1) > 1 for ax in ("dp", "sp", "pp", "ep")
    ):
        raise ValueError(
            "multi-LoRA composes with tp-only meshes (replicated "
            "banks); dp/sp/pp/ep need a LoRA-free engine"
        )


class RequestHandle:
    """Streamed results: ('token', id, ts) events then ('done', info)."""

    def __init__(self, req: GenRequest) -> None:
        self.request = req
        self.events: "queue.Queue[tuple]" = queue.Queue()
        self.t_submit = _now()  # the runtime's monotonic clock, like every t_*
        self.t_admit: float = 0.0   # queue wait ends / prefill begins
        self.t_first_token: float = 0.0
        self.t_done: float = 0.0
        self.tokens: list[int] = []
        self.logprobs: list[tuple] = []  # (logprob, [(id, lp) x K]) per token
        self.finish_reason: str = ""
        # set by Engine.cancel (any thread): the scheduler finishes the
        # slot with this reason at its next iteration. Server-side stop-
        # sequence detection and client disconnects use this — the slot's
        # remaining budget would otherwise keep decoding into the batch.
        self.cancelled: Optional[str] = None

    @property
    def server_ttft_ms(self) -> float:
        if self.t_first_token:
            return (self.t_first_token - self.t_submit) * 1000.0
        return 0.0


class Engine:
    """Slot-based continuous-batching engine over a (possibly sharded) model."""

    def __init__(
        self,
        params: dict[str, Any],
        cfg: ModelConfig,
        engine_cfg: Optional[EngineConfig] = None,
        mesh=None,
        pad_id: int = 0,
        drafter: Optional[tuple[dict[str, Any], ModelConfig]] = None,
        lora: Optional[dict[str, Any]] = None,  # ops/lora.py bank; its
                                 # "names" dict maps adapter name -> index
                                 # (index 0 = base, always available)
        prefill_mesh=None,       # disaggregated prefill lane's own submesh
                                 # (parallel/mesh.lane_meshes; needs
                                 # ecfg.disagg — docs/DISAGGREGATION.md)
    ) -> None:
        self.cfg = cfg
        self.ecfg = engine_cfg or EngineConfig()
        from kserve_vllm_mini_tpu.ops.qmatmul import validate_quant_mode

        validate_quant_mode(self.ecfg.quant_mode)
        if self.ecfg.quant_mode != cfg.quant_mode:
            # one source of truth at trace time: the config every compiled
            # step closes over (callers that pre-scaled cfg and left the
            # EngineConfig default keep their cfg — default never demotes)
            if self.ecfg.quant_mode != "dequant":
                self.cfg = cfg = cfg.scaled(quant_mode=self.ecfg.quant_mode)
            else:
                self.ecfg.quant_mode = cfg.quant_mode
        self.ecfg.max_seq_len = min(self.ecfg.max_seq_len, cfg.max_seq_len)
        # prefill bucket must fit inside the cache with at least one decode slot
        self.ecfg.max_prefill_len = min(
            self.ecfg.max_prefill_len, self.ecfg.max_seq_len - 1
        )
        if self.ecfg.prefill_chunk is not None:
            if self.ecfg.prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk={self.ecfg.prefill_chunk} must be >= 1 "
                    "(or None to disable chunked prefill)"
                )
            # a chunk below one bucket would pad up to the bucket anyway;
            # above max_prefill_len it is the monolithic budget — and a
            # non-bucket value is rounded UP to the bucket its pieces
            # would compile at, so no piece carries permanent pad waste
            # (and the headroom estimate prices the real executable width)
            self.ecfg.prefill_chunk = steps.prefill_bucket(min(
                max(self.ecfg.prefill_chunk, self.ecfg.min_prefill_bucket),
                self.ecfg.max_prefill_len,
            ), self.ecfg)
        self.mesh = mesh
        self.pad_id = pad_id
        self.params = params
        self._hybrid = cfg.is_hybrid
        if self._hybrid:
            # (a ring of K/V is refused in its own words)
            refuse = self._refuse_for_ring if cfg.is_window_stack else self._refuse_for_hybrid
            refuse(mesh, drafter, lora, prefill_mesh)

        # Pallas kernel selection for every compiled step (models/llama.py
        # forward ``kernels``): steps jitted over a mesh are partitioned by
        # GSPMD, which refuses a Mosaic call ("cannot be automatically
        # partitioned") — those run attention through XLA
        self._kernels = "off" if mesh is not None else "auto"
        self._grouped_buckets: dict[int, bool] = {}
        # key-block size of the dense decode kernel where decode steps take
        # it (models/llama.py dense_decode_kernel_block), resolved in
        # start(): what _count_decode_kv_blocks counts by
        self._dense_decode_blk: Optional[int] = None
        # the same for a sliding_window_layout stack's two leaves, (global,
        # ring) (models/llama.py window_decode_kernel_blocks)
        self._window_decode_blks: Optional[tuple[int, int]] = None
        # whether decode steps update a hybrid's delta-rule state through
        # kvmini_linear_decode (models/linear_attention.py
        # linear_decode_kernel_block), resolved in start(): the gauge
        self._linear_state_kernel = False
        # the same for a pattern stack's Mamba-2 state and kvmini_ssm_decode
        # (models/mamba.py ssm_decode_kernel_block)
        self._ssm_state_kernel = False
        # model executor: plain forward, or the pp-sharded drop-in when the
        # mesh pipelines layers (parallel/serving_pp.py — same signature, so
        # every compiled step below is executor-agnostic)
        self._fwd = forward
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            from kserve_vllm_mini_tpu.parallel.serving_pp import make_pp_forward

            mb = max(self.ecfg.pp_microbatches, 1)
            if mb > 1 and self.ecfg.max_slots % mb:
                raise ValueError(
                    f"pp_microbatches={mb} must divide max_slots="
                    f"{self.ecfg.max_slots}, or every decode sweep would "
                    "silently fall back to unpipelined"
                )
            self._fwd = make_pp_forward(cfg, mesh, microbatches=mb)
            if drafter is not None:
                raise ValueError(
                    "speculative decoding is not supported with serving "
                    "pipeline parallelism (pp > 1); drop the drafter or pp"
                )

        from kserve_vllm_mini_tpu.models.llama import init_kv_cache, init_paged_kv_cache

        S = self.ecfg.max_slots
        kv_quant = self.ecfg.kv_cache_dtype == "int8"
        kv_dt = (
            jnp.dtype(self.ecfg.kv_cache_dtype)
            if (self.ecfg.kv_cache_dtype and not kv_quant)
            else None
        )

        # Serializes every `self._cache = fn(self._cache, ...)` read-
        # dispatch-assign against the paged prefill lane (docs/
        # DISAGGREGATION.md v2): with HANDOFF_VERSION=2 the lane thread
        # dispatches paged prefills INTO the shared pool cache, and an
        # unserialized interleave could dispatch two donations of the
        # same buffer (the assign is not atomic with the read). JAX
        # async dispatch keeps the critical section microseconds —
        # device execution is ordered by buffer dependencies, not the
        # lock. Uncontended (colocated/dense engines never race it).
        self._cache_lock = threading.Lock()

        self.paged = self.ecfg.kv_layout == "paged"
        if self.ecfg.kv_layout not in ("dense", "paged"):
            raise ValueError(
                f"unknown kv_layout {self.ecfg.kv_layout!r}; known: dense, paged"
            )
        # how a prefill step reaches a slot's cache (steps.build_prefill)
        self._cache_by = "table" if self.paged else "slot"

        # Disaggregated prefill/decode (docs/DISAGGREGATION.md): validate
        # compositions up front — the lane is constructed further down,
        # once the compile recorder and fault registry it threads exist.
        if self.ecfg.disagg:
            if self.paged and prefill_mesh is not None:
                raise ValueError(
                    "paged disagg (HANDOFF_VERSION=2) shares ONE block "
                    "pool between the lanes, so the lane must run on the "
                    "engine's own mesh/devices; per-lane meshes compose "
                    "with kv_layout=dense only"
                )
            if drafter is not None:
                raise ValueError(
                    "disagg does not support speculative drafters yet "
                    "(the drafter's shadow prefill writes the decode "
                    "lane's drafter cache); drop the drafter or disagg"
                )
            if lora is not None:
                raise ValueError(
                    "disagg does not support multi-LoRA yet (the lane "
                    "would need the adapter bank); drop --lora or disagg"
                )
            if self.ecfg.prefix_cache and not self.paged:
                raise ValueError(
                    "disagg and the DENSE prefix_cache are mutually "
                    "exclusive: slot-level reuse matching happens at the "
                    "decode lane's slot index, which the prefill lane "
                    "cannot see. The paged layout composes — block "
                    "reuse is claimed at routing time on the scheduler "
                    "thread and the lane prefills only the suffix"
                )
            if mesh is not None and any(
                mesh.shape.get(ax, 1) > 1 for ax in ("dp", "sp", "pp")
            ):
                raise ValueError(
                    "disagg composes with tp-only decode meshes; "
                    "dp/sp/pp need a colocated engine"
                )
        elif prefill_mesh is not None:
            raise ValueError("prefill_mesh requires EngineConfig.disagg=True")
        if self.paged:
            if mesh is not None and any(
                mesh.shape.get(ax, 1) > 1 for ax in ("dp", "sp", "pp")
            ):
                raise ValueError(
                    "paged KV composes with tp-only meshes; dp/sp/pp need "
                    "kv_layout=dense (block gathers don't partition over "
                    "a sharded slot/seq/layer axis)"
                )
            if drafter is not None:
                raise ValueError("paged KV does not support speculative "
                                 "decoding yet; drop the drafter or use dense")
            # prefix_cache + paged = BLOCK-LEVEL prefix sharing (vLLM-style
            # hash-based APC): full prompt blocks are content-addressed and
            # shared across requests by table reference. Sharing full
            # blocks only means writes always land PAST the reused region
            # in private blocks — no copy-on-write needed. State below.
            blk = self.ecfg.kv_block_size
            if blk < 1:
                raise ValueError(f"kv_block_size={blk} must be >= 1")
            self._blk = blk
            self._maxb = -(-self.ecfg.max_seq_len // blk)
            # explicit None check: 0 must be rejected below, not silently
            # fall back to the memory-equal-to-dense default pool
            n_user = (
                self.ecfg.kv_pool_blocks
                if self.ecfg.kv_pool_blocks is not None
                else S * self._maxb
            )
            if n_user < 1:
                raise ValueError(f"kv_pool_blocks={n_user} must be >= 1")
            # a pool smaller than one max-length request is allowed: submit()
            # error-rejects any request whose worst case exceeds the pool,
            # so undersizing shrinks the admissible request size, not safety
            # +1: the last block is SCRATCH — freed slots' table rows point
            # at it so their harmless in-flight decode writes (the sweep
            # dispatches all S slots, active or not) can never land in a
            # block that was reassigned to another request
            self._scratch_block = n_user
            if mesh is not None:
                # allocate DIRECTLY into the tp layout (same rationale as
                # the dense mesh cache below: the pool may only fit HBM
                # sharded)
                from kserve_vllm_mini_tpu.parallel.sharding import (
                    paged_kv_cache_shardings,
                )

                self._cache = jax.jit(
                    partial(init_paged_kv_cache, cfg, n_user + 1, blk,
                            dtype=kv_dt, quantized=kv_quant),
                    out_shardings=paged_kv_cache_shardings(
                        cfg, mesh, quantized=kv_quant
                    ),
                )()
            else:
                self._cache = init_paged_kv_cache(
                    cfg, n_user + 1, blk, dtype=kv_dt, quantized=kv_quant
                )
            self._free_blocks: list[int] = list(range(n_user))
            self._slot_blocks: list[list[int]] = [[] for _ in range(S)]
            self._block_table = np.full((S, self._maxb), self._scratch_block,
                                        dtype=np.int32)
            self._table_dev: Optional[jnp.ndarray] = None  # lazy device mirror
            # head-of-line request that didn't fit the free pool; retried
            # first so admission stays FIFO
            self._deferred: Optional[RequestHandle] = None
            # block-level prefix sharing (prefix_cache=True): a FULL prompt
            # block's content key (sha256 of the token prefix up to its
            # end) maps to the pool block holding its KV. _block_rc counts
            # slot ownerships; rc==0 registered blocks park in the
            # _retained_lru (insertion order = recency) until the
            # allocator evicts them for fresh allocations.
            self._hash_block: dict[bytes, int] = {}
            self._block_hash: dict[int, bytes] = {}
            self._block_rc: dict[int, int] = {}
            # bumped whenever the content index CHANGES (registration /
            # eviction) — plans memoized on requests stay valid between
            # bumps, so a deferred head-of-line request's per-sweep fit
            # recheck is O(1) instead of re-hashing its whole prompt
            self._prefix_epoch = 0
            from collections import OrderedDict

            self._retained_lru: "OrderedDict[int, None]" = OrderedDict()
            # chain depth (1-based block index within the prompt chain
            # that registered it) per registered block: the migration
            # exporter orders blocks root-first by this so a bounded
            # byte budget truncates the LEAVES of a chain, never its
            # roots (plans match root-outward and stop at the first
            # miss — an orphaned leaf would be dead weight on the wire)
            self._block_depth: dict[int, int] = {}
            # Host-RAM KV tier (EngineConfig.kv_host_tier_bytes): content
            # key -> {"depth", "kv": host leaves}, insertion order =
            # recency (popitem(last=False) evicts the oldest). Scheduler-
            # thread-only, like every other pool structure. Tier
            # mutations bump _prefix_epoch: a memoized plan that counted
            # a tier hit must not survive the entry's eviction.
            self._tier: "OrderedDict[bytes, dict]" = OrderedDict()
            self._tier_bytes = 0
            self._tier_cap_bytes = int(self.ecfg.kv_host_tier_bytes or 0)
            self._tier_disabled = False
            # thrash guard window state (rate of kv_retained_evictions
            # over ~1 s windows, same thresholds as the monitor's
            # kv_thrash rule): (window start, eviction count at start),
            # consecutive over-threshold windows
            self._tier_thrash_win = (time.time(), 0)
            self._tier_thrash_hits = 0
            # paged-v2 handoff/abort bookkeeping: blocks owned by a slot
            # that was aborted while its prompt was still ON the lane.
            # They must not return to the free pool until the lane's
            # payload orphans at consume (the lane may still have
            # dispatches in flight writing them) — keyed by handle id.
            self._orphan_blocks: dict[int, list[int]] = {}

        def make_cache():
            return init_kv_cache(
                cfg, S, max_seq=self.ecfg.max_seq_len, dtype=kv_dt, quantized=kv_quant
            )

        if self.paged:
            pass  # pool allocated above
        elif mesh is not None:
            from kserve_vllm_mini_tpu.parallel.sharding import kv_cache_shardings

            # allocate DIRECTLY into the mesh layout: materializing the full
            # cache on one device first and device_put-ting after would OOM
            # exactly the deployments sharding exists for (a pp/tp mesh
            # because model+cache exceed one chip's HBM)
            sh = kv_cache_shardings(cfg, mesh, quantized=kv_quant)
            self._cache = jax.jit(make_cache, out_shardings=sh)()
        else:
            self._cache = make_cache()
        # what the cache tree reserves, by kind of state (gauge, fixed at build)
        kinds = {"lin_state": "linear_state", "lin_conv": "conv_state",
                 "ssm_state": "ssm_state", "ssm_conv": "ssm_conv_state",
                 "conv_tail": "conv_tail"}
        self._cache_bytes = dict.fromkeys(("kv", *kinds.values()), 0)
        if cfg.is_window_stack:
            # two kinds of K/V of its own: the window layers' ring and, beside
            # it, the stripes, which are the global layers' alone ("kv" stays 0)
            kinds.update(k="kv_global", v="kv_global", k_win="kv_window", v_win="kv_window")
            self._cache_bytes.update(kv_window=0, kv_global=0)
        for name, leaf in self._cache.items():
            self._cache_bytes[kinds.get(name, "kv")] += int(leaf.nbytes)
        # positions a prefill piece's scan solves at once, and what the span
        # of a piece calls its count: the Mamba-2 mixer's or the delta rule's,
        # whichever the plan's layers run (a short convolution and attention
        # scan nothing: 0)
        self._scan_chunk, self._scan_arg = (
            (cfg.chunk_size, "ssm_chunks") if cfg.n_ssm_layers
            else (LINEAR_CHUNK, "linear_chunks") if cfg.n_linear_layers
            else (0, ""))
        # which rows of a decode sweep may move a hybrid's recurrent state:
        # the device mask of the last dispatch, keyed by its active set
        self._live_dev: Optional[tuple[tuple[int, ...], jnp.ndarray]] = None

        # speculative decoding: the drafter keeps its own KV cache with the
        # same slot/seq geometry so slot bookkeeping is shared
        self._drafter_params: Optional[dict[str, Any]] = None
        self._drafter_cfg: Optional[ModelConfig] = None
        if drafter is not None:
            self._drafter_params, self._drafter_cfg = drafter
            if (
                self.ecfg.quant_mode != "dequant"
                and self._drafter_cfg.quant_mode != self.ecfg.quant_mode
            ):
                # speculative decoding and quantization COMPOSE: the
                # drafter's projections ride the same quant_mode as the
                # target (w8a8 = int8 x int8 on the MXU when its leaves
                # are quantized; a documented no-op on plain weights —
                # ops/quant.linear), so spec rounds stream the drafter's
                # int8 bytes instead of silently excluding each other
                self._drafter_cfg = self._drafter_cfg.scaled(
                    quant_mode=self.ecfg.quant_mode
                )
            self._dcache = init_kv_cache(
                self._drafter_cfg, S, max_seq=self.ecfg.max_seq_len,
                dtype=kv_dt, quantized=kv_quant,
            )

        # multi-LoRA bank: per-slot adapter index decoded inside the same
        # jitted step; index 0 is the base (zero) adapter
        if lora is not None:
            _require_tp_only_mesh(mesh)
            if mesh is not None:
                # replicate the bank over the mesh BEFORE it becomes engine
                # state: factor banks are MBs at serving ranks, and a
                # replicated delta lets GSPMD join it with the tp-sharded
                # base projections however each target is partitioned (no
                # per-target spec bookkeeping to get wrong). Hot-swap
                # (load_adapter) applies the same replication.
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(mesh, PartitionSpec())
                lora = {
                    **lora,
                    "layers": jax.device_put(lora["layers"], rep),
                }
        self._lora = lora
        self._lora_names: dict[str, int] = dict(lora.get("names", {})) if lora else {}
        if lora is not None:
            if drafter is not None:
                # the drafter proposes from base weights; verification would
                # accept base-model continuations for adapted slots. The
                # per-slot gate below excludes adapted slots from spec, but
                # mixing the features is untested — reject loudly for now.
                raise ValueError("multi-LoRA with a speculative drafter is "
                                 "not supported yet")
            if self.ecfg.prefix_cache:
                # retained KV is matched by TOKENS only; K/V rows computed
                # under adapter a's wk/wv deltas must never be reused by a
                # base or adapter-b request sharing the same prompt prefix
                raise ValueError("multi-LoRA and prefix_cache are mutually "
                                 "exclusive: retained KV carries no record "
                                 "of the adapter that computed it")
        self._slot_adapter = [0] * S
        self._adapter_ids_dev: Optional[jnp.ndarray] = None
        # live adapter load/unload ops, drained by the scheduler between
        # sweeps; bank capacity for a runtime-created bank comes from
        # ecfg.lora_slots
        self._admin: "queue.Queue[_AdminOp]" = queue.Queue()

        # host-side slot state
        self._slot_req: list[Optional[RequestHandle]] = [None] * S
        self._slot_len = [0] * S
        self._slot_remaining = [0] * S
        self._last_tokens = [pad_id] * S
        self._slot_machine: list[Optional[Any]] = [None] * S  # constraints
        self._free = list(range(S))
        # prefix cache: tokens whose KV occupies the slot's rows 0..len-1
        # while live, and the retained (trimmed-to-written) prefix once the
        # slot is freed — matched against new prompts at admission
        self._slot_tokens: list[list[int]] = [[] for _ in range(S)]
        self._retained: list[list[int]] = [[] for _ in range(S)]

        # chunked-prefill state (EngineConfig.prefill_chunk): a slot whose
        # prompt is being chunk-prefilled is OCCUPIED (_slot_req set, so
        # cancellation / watchdog / drain all see its handle) but not
        # decode-ACTIVE — _decode_active() excludes it until the final
        # chunk's logits feed sampling. _slot_len doubles as the prefill
        # FRONTIER while the entry is live: concurrent sweeps' garbage
        # writes land at >= the frontier and the next chunk overwrites
        # them (dispatch order) before they can ever be attended.
        # _prefill_fifo orders advancement: the OLDEST admission advances
        # one chunk per scheduler iteration (completion order matches the
        # monolithic path's serial admissions). Scheduler-thread-only.
        self._slot_prefill: list[Optional[dict]] = [None] * S
        self._prefill_fifo: list[int] = []

        # disaggregated-prefill state (docs/DISAGGREGATION.md): a slot
        # whose prompt is prefilling ON THE LANE is OCCUPIED (_slot_req
        # set — cancellation/watchdog/drain all see the handle) but not
        # decode-ACTIVE until its handoff is consumed and _activate_slot
        # samples the first token. The dict holds {"handle", "t_route"}
        # (route time anchors the server.handoff span and the consume-
        # side never-hang timeout). Scheduler-thread-only.
        self._slot_handoff: list[Optional[dict]] = [None] * S

        self._pending: "queue.Queue[RequestHandle]" = queue.Queue()
        self._rng = jax.random.PRNGKey(self.ecfg.seed)
        # per-slot generated-token counts [S, V] int32, device-resident:
        # the presence/frequency-penalty state (sampling.apply_penalties).
        # int32 at the 8B headline geometry (80 x 128k) is 41 MB — 0.5% of
        # the per-step weight stream, cheap enough to keep unconditional so
        # the decode executable never re-traces when the first penalized
        # request arrives. Rows are reset at admission, not at finish.
        self._counts = jnp.zeros((S, self.cfg.vocab_size), jnp.int32)
        self._step_counter = 0
        # every compiled step, built on first use (_step)
        self._steps: dict[tuple, Any] = {}
        self._running = False
        self._thread: Optional[threading.Thread] = None
        # sampling-parameter device arrays, rebuilt only on admit/finish —
        # never on the per-token hot path
        self._sampling_arrays: Optional[tuple] = None
        # whether any row of those arrays asks filter_logits for its sort
        self._sampler_filters = False

        # double-buffered decode state (docs/DECODE_PIPELINE.md):
        # _tokens_dev mirrors _last_tokens on device — in steady state it is
        # the previous sweep's sampled-token carry, so the per-sweep
        # host->device token transfer disappears; None = rebuild from host.
        # _tokens_dev_slots is the set of slots whose carry rows are REAL
        # (they emitted through the sweep that produced the carry): a slot
        # outside it — e.g. a spec slot whose round was skipped — has a
        # garbage row and must be fed from _last_tokens instead.
        self._tokens_dev: Optional[jnp.ndarray] = None
        self._tokens_dev_slots: frozenset = frozenset()
        # dispatched-but-not-retired sweeps, oldest first; each record holds
        # the stacked per-step device outputs plus the host snapshot needed
        # to emit them (active slots, handle identities, chunk, rng rewind)
        self._inflight: list[dict] = []
        # decode positions the in-flight sweeps have already written past
        # the host-visible _slot_len (chunk per unretired sweep)
        self._pending_steps = 0
        # of those, the steps dispatched BEFORE the slot's occupant was
        # admitted behind them (_admit_behind): its first decode write
        # lands at its prompt's length, not past sweeps it was never part
        # of. Zero everywhere but between such an admission and the retire
        # of the sweep it was taken behind.
        self._slot_behind = [0] * S
        # callers admitted behind the sweep in flight whose first token is
        # still on the device: read back at the end of the same iteration
        # (_read_first_tokens), after that sweep is retired
        self._first_pending: list[dict] = []
        self._no_mask_dev: Optional[jnp.ndarray] = None
        self._t_last_ready = 0.0   # when the device last finished a sweep
        # a synchronous sweep (masked, speculative) between its dispatch and
        # the end of its readback: in flight, though never in _inflight
        self._sync_inflight = False
        # multihost lockstep mode (set by runtime/multihost.py drivers):
        # disables the retire-time cancelled-handle emission skip, whose
        # trigger is a host-local race the follower cannot observe —
        # lockstep cancellation latency is one published decision instead
        self._lockstep = False

        # compile-stats capture (docs/PROFILING.md): every compiled step
        # below registers its lower().compile() facts here; exported via
        # snapshot_stats -> /metrics (compile_* keys). Thread-safe — the
        # scheduler thread records, server threads snapshot.
        self._compile_recorder = CompileRecorder()

        # KV/HBM observability (docs/TROUBLESHOOTING.md "HBM pressure &
        # KV thrash"): prefix-hit depths (tokens reused per admission) in
        # a bounded ring, appended on the scheduler thread only; the
        # p50/p95 gauges are computed ON that thread too, inside the
        # _kv_admin_snapshot admin op, so no derived ratio is ever built
        # from torn cross-thread reads. _kv_gauges caches the last
        # consistent snapshot (served when the admin op can't run, e.g.
        # mid-shutdown) and _hbm_peak_seen tracks the high-water
        # bytes_in_use across scrapes for backends whose memory_stats
        # lacks a native peak counter; both move under _obs_lock because
        # any scraper thread may update them.
        from collections import deque

        self._hit_depths: "deque[int]" = deque(maxlen=4096)
        self._obs_lock = threading.Lock()
        self._kv_gauges: dict[str, Any] = {}
        self._kv_gauges_t = 0.0          # last refresh (scheduler clock)
        self._hbm_peak_seen = 0

        # Live economics rail (docs/ECONOMICS.md): rolling-window $/1K-tok,
        # Wh/1K-tok, and the $/hr accrual derived from the busy/token
        # counters this engine already keeps, priced by tpu-cost.yaml.
        # Auto-detected on TPU backends through the device table
        # (profiling/devices.py: the exact device_kind names the price and
        # TDP row; a TPU kind it does not hold raises rather than being
        # priced at the sheet's default), forced on any backend by
        # ecfg.econ_accelerator, and absent — no object, no keys, no
        # fabricated $0 — everywhere else. Fed/read under _obs_lock only
        # (the PR 8 gauge-cache discipline: published under a lock, not
        # annotated away).
        self._econ = None
        accel = self.ecfg.econ_accelerator
        if not accel:
            dev = jax.devices()[0]
            if dev.platform == "tpu":
                from kserve_vllm_mini_tpu.profiling.devices import device_facts

                accel = device_facts(dev.device_kind).name
        if accel:
            from kserve_vllm_mini_tpu.costs.live import LiveEconomics

            self._econ = LiveEconomics(
                accelerator=accel,
                chips=self.mesh.size if self.mesh is not None else 1,
            )

        # Resilience state (docs/RESILIENCE.md). ONE lock guards every
        # cross-thread field: the scheduler beats/EMAs, the watchdog's
        # trip bookkeeping, the server's shed counter, the degrade
        # ladder, and the published live-handle snapshot — watchdog,
        # scheduler, and server threads all touch them (KVM05x
        # discipline: published under a lock, not annotated away). The
        # fault registry is created ONCE here (internally locked, never
        # reassigned); an un-armed registry costs one uncontended lock
        # acquire + dict miss per hot-path check.
        from kserve_vllm_mini_tpu.runtime.faults import FaultRegistry

        self._res_lock = threading.Lock()
        self._faults = FaultRegistry(seed=self.ecfg.fault_seed,
                                     config=self.ecfg.faults or "")
        self._watch_beat = _now()   # last scheduler progress mark
        self._sweep_ema_s = 0.0          # rolling dispatch->retire wall
        self._service_ema_s = 0.0        # rolling admit->done wall
        self._watchdog_trips = 0
        self._engine_faults = 0          # recovered engine faults (all paths)
        self._degrade_level = 0          # 0 normal .. 3 spec off; 4 = dead
        self._requests_shed = 0          # deadline/admission sheds
        self._fault_pending: Optional[str] = None
        self._faulted_ids: set[str] = set()  # handles the watchdog already
        #                                      sent a terminal event to
        # the scheduler republishes its live handles here each iteration
        # so the watchdog/estimator never read the scheduler-owned slot
        # list directly
        self._live_handles: list[RequestHandle] = []
        self._watch_thread: Optional[threading.Thread] = None
        self._watch_stop = threading.Event()
        # scheduler-thread-only: paged admission backpressure window the
        # kv_alloc_fail injection opens (epoch seconds); expires by its
        # armed duration
        self._kv_fault_until = 0.0

        # disaggregated prefill lane (docs/DISAGGREGATION.md): built here
        # so it can thread the compile recorder (its executables land in
        # the compile-stats rail as disagg_prefill[...]) and the fault
        # registry (the kv_handoff_drop injection point). Degrade state
        # is scheduler-owned: consecutive tombstoned handoffs flip
        # _disagg_degraded and routing falls back to colocated prefill
        # for the rest of the run.
        self._disagg = None
        self._disagg_degraded = False
        self._disagg_drop_run = 0
        if self.ecfg.disagg:
            from kserve_vllm_mini_tpu.runtime.disagg import PrefillLane

            self._disagg = PrefillLane(
                self.params, cfg, self.ecfg, pad_id=pad_id,
                instrument=(
                    self._instrument if prefill_mesh is None else None
                ),
                faults=self._faults,
                prefill_mesh=prefill_mesh,
                kernels="off" if prefill_mesh is not None else self._kernels,
                # paged engines hand the lane the ENGINE's paged prefill
                # path (shared pool, zero-copy v2 block-table handoff)
                # instead of a staging cache + stripe
                paged_prefill=(
                    self._lane_paged_prefill if self.paged else None
                ),
            )

        # stats for /metrics and duty-cycle telemetry
        self.stats = {
            "prefill_tokens": 0,
            "decode_tokens": 0,
            "decode_steps": 0,
            "prefills": 0,
            # chunked-prefill telemetry (ROADMAP item 3): compiled prefill
            # piece dispatches (target + drafter shadow, monolithic and
            # chunked admissions alike), and the prefill wall that ran
            # while decode work was live — the direct measurement of the
            # stall chunking exists to break up (docs/TROUBLESHOOTING.md
            # "Long prompts stall streaming")
            "prefill_chunks": 0,
            "prefill_chunk_stall_s": 0.0,
            # what the pieces were padded to: prefill_tokens over this is
            # the share of the prefill programs' work that was real prompt
            "prefill_bucket_tokens": 0,
            # those of them whose program dispatches its tokens to the
            # routed experts grouped by expert (models/moe.py
            # grouped_prefill: a piece longer than the kernel's row tile, with
            # the kernels on); a program is one or the other by its shape
            "prefill_grouped_bucket_tokens": 0,
            # how often the dense decode kernel's block selection engages
            # (ops/paged_attention.py live_block_range): key/value blocks
            # the target's plain and masked decode steps fetched, summed
            # over slots, against slots x S/BLK; a layer's worth (x layers
            # is a constant). Both stay 0 where the eager read runs.
            "decode_kv_blocks_fetched": 0,
            "decode_kv_blocks": 0,
            # the same by kind of layer, a sliding_window_layout stack's alone
            # (a layer of each kind booked on its own; the two above hold
            # their sums there)
            **{f"decode_kv_blocks{f}_{kind}": 0
               for f in ("_fetched", "") for kind in ("global", "window")},
            # of decode_steps (plain and masked sweeps, booked with them
            # at retire), those dispatched while some occupied slot sampled
            # with a top-k or top-p filter: the steps in which the
            # sampler's full-vocabulary sort ran (sampling.filter_logits)
            "sampler_filter_steps": 0,
            # a hybrid stack's recurrent state (models/linear_attention.py;
            # all 0 for every other model). Chunks of 64 positions the
            # target's prefill pieces ran through the chunk-parallel scan,
            # and those of them that held only padding (a layer's worth);
            # slot-steps the decode sweeps moved state for (slots x fused
            # steps: a sweep reads and writes every slot's state), and
            # those whose slot was not live, so its state was held; fresh
            # prefills, each of which starts its slot's state from zero
            "linear_scan_chunks": 0,
            "linear_scan_pad_chunks": 0,
            "linear_state_slot_steps": 0,
            "linear_state_held_slot_steps": 0,
            "linear_state_resets": 0,
            # fresh prefills of a short-convolution stack, each of which
            # starts its slot's tails from zero (models/short_conv.py)
            "conv_tail_resets": 0,
            # routed expert blocks (models/moe.py moe_latent_block; all 0 for
            # every other model), over the decode steps retired, summed over
            # the expert layers: assignments the live rows made (rows x
            # experts a token), those that landed on an expert held here,
            # held experts with at least one token, and the fullest held
            # expert's tokens; then the held experts whose weights the
            # steps' dispatch multiplied (every held one where it is dense,
            # the touched where the kernel sweeps them: models/moe.py
            # grouped_blocks); the last four counted on the device and read
            # back with the step's tokens
            "moe_assignments": 0,
            "moe_local_assignments": 0,
            "moe_expert_touches": 0,
            "moe_expert_load_max": 0,
            "moe_expert_visits": 0,
            "requests_completed": 0,
            "busy_s": 0.0,        # exported: busy_seconds_total + duty_cycle
            "started_at": _now(),  # kvmini: metrics-ok — raw input; exposed as duty_cycle
            "queue_depth": 0,
            "spec_rounds": 0,       # fused drafter-propose/target-verify rounds
            "spec_accepted": 0,     # draft tokens accepted across all rounds
            "spec_proposed": 0,     # draft tokens proposed (rounds x k-1)
            "prefix_hits": 0,       # admissions that reused a retained prefix
            "prefix_lookups": 0,    # admissions that ATTEMPTED prefix reuse
            "prefix_tokens_reused": 0,  # prompt tokens NOT re-prefilled
            # paged-block lifecycle (docs/TROUBLESHOOTING.md "HBM pressure
            # & KV thrash"): allocator churn the point-in-time pool gauges
            # cannot show — all three only move on the scheduler thread
            "kv_blocks_allocated": 0,    # fresh pool-block allocations
            "kv_retained_evictions": 0,  # retained-pool LRU evictions
            "kv_share_reclaims": 0,      # shared-block 0->1 rc claims
            # decode-pipeline telemetry (docs/DECODE_PIPELINE.md):
            "dispatch_depth": 0,    # high-water concurrently in-flight sweeps
            "pipelined_sweeps": 0,  # sweeps dispatched ahead of a retire
            "host_overlap_s": 0.0,  # host emit/bookkeeping under device compute
            "pipeline_fallback_constrained": 0,  # grammar mask forced sync
            "pipeline_fallback_spec": 0,         # spec partition forced sync
            "pipeline_fallback_active_set": 0,   # admission forced retire
            "pipeline_fallback_headroom": 0,     # cache window forced sync
            # admissions dispatched behind the sweep in flight, and those
            # that drained it instead (active_set above), by what refused
            "admissions_inflight": 0,
            **{f"admissions_drained_{why}": 0 for why in ADMIT_DRAIN_REASONS},
        }
        if self.paged:
            # KV-block economy rail (ISSUE 16), paged engines only (same
            # conditional contract as the pool gauges): host-tier
            # lifecycle and cross-replica migration accounting — all
            # scheduler-thread writes (demotion/promotion at alloc/admit,
            # import/export inside _run_admin ops), single-writer.
            self.stats.update({
                "kv_tier_demotions": 0,   # evictions caught by the tier
                "kv_tier_promotions": 0,  # tier blocks uploaded back
                "kv_tier_hits": 0,        # admissions that matched the tier
                "kv_migrated_blocks": 0,  # blocks installed via kv_import
                "kv_migrated_bytes": 0,   # wire bytes installed via kv_import
                "kv_export_blocks": 0,    # blocks shipped via kv_export
            })
        if self._disagg is not None:
            # disaggregated-serving rail (docs/DISAGGREGATION.md), present
            # only on disagg engines (same conditional contract as the
            # paged pool gauges): handoffs consumed, block/wait/lane-busy
            # accounting, tombstoned drops, and colocated fallbacks (the
            # degrade ladder's visible steps). All consumed into stats on
            # the scheduler thread (_consume_handoffs), single-writer.
            self.stats.update({
                "kv_handoffs": 0,            # handoffs consumed into slots
                "kv_handoff_blocks": 0,      # KV blocks handed across lanes
                "kv_handoff_wait_s": 0.0,    # lane-done -> consume wall
                "kv_handoff_drops": 0,       # tombstones (drop/error/timeout)
                # physical KV bytes the consume side copied per landed
                # handoff: the v1 dense stripe's nbytes, 0 on the v2
                # block-table path — the handoff-tax byte measurement
                "kv_handoff_bytes_copied": 0,
                "prefill_lane_busy_s": 0.0,  # lane compute wall
                "disagg_colocated_fallbacks": 0,  # prefills degraded back
            })

        # request lifecycle tracing (docs/TRACING.md): bounded ring of
        # completed phase spans served at GET /traces, plus per-phase
        # duration histograms for /metrics (kvmini_tpu_phase_seconds).
        # The histograms are plain counters and stay on even when span
        # recording is disabled (request_tracing=False).
        self.tracer: Optional[rt_tracing.SpanRecorder] = (
            rt_tracing.SpanRecorder(self.ecfg.trace_buffer)
            if self.ecfg.request_tracing else None
        )
        # engine-lane spans (decode dispatch->retire windows) accrue one
        # PER SWEEP — orders of magnitude faster than request spans. They
        # get their OWN ring so a long run's sweep spans can never evict
        # the per-request phase spans the analyzer joins; they share one
        # synthetic trace per engine lifetime and land in /traces beside
        # the request spans (traces_otlp merges the two rings).
        self._engine_tracer: Optional[rt_tracing.SpanRecorder] = (
            rt_tracing.SpanRecorder(min(1024, self.ecfg.trace_buffer))
            if self.ecfg.request_tracing else None
        )
        self._engine_trace_id = rt_tracing.new_trace_id()
        self._phase_hist = {
            p: rt_tracing.PhaseHistogram() for p in rt_tracing.PHASES
        }
        # /traces speaks Unix nanoseconds; every t_* is monotonic seconds
        self._unix_offset_ns = rt_tracing.unix_offset_ns()
        # the scheduler ledger (docs/TRACING.md): every phase of the engine
        # thread a kvmini.* span on the profiler's clock and a
        # kvmini_tpu_sched_* counter. Always on: it has no switch.
        self._sched = rt_tracing.SchedLedger(
            annotate=jax.profiler.TraceAnnotation,
            exposed=self._host_exposed,
            # asked on the scheduler thread, which is the one that writes it
            sweep_s=lambda: self._sweep_ema_s,
            on_gap=self._trace_gap if self._engine_tracer is not None else None,
        )

        # Per-device analytic HBM footprint for headroom-model validation
        # (profiling/headroom.py; docs/TROUBLESHOOTING.md): the guard's
        # formula shape — weights + KV + workspace, x1.15 fusion margin —
        # but with the weights term taken from the ACTUAL loaded tree
        # (quant guessing validated separately by the guard's own tests)
        # and the KV term priced by kv_bytes_per_token, so what
        # headroom_error_pct measures is the analytic KV/workspace/margin
        # model — the part whose underestimate is the OOM direction.
        from kserve_vllm_mini_tpu.profiling.headroom import estimate_serving_bytes

        weight_bytes = sum(
            int(getattr(leaf, "nbytes", 0))
            for leaf in jax.tree_util.tree_leaves(self.params)
        )
        if drafter is not None:
            weight_bytes += sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree_util.tree_leaves(self._drafter_params)
            )
        analytic = estimate_serving_bytes(
            cfg, S, self.ecfg.max_seq_len, kv_quant=kv_quant,
            quant_mode=cfg.quant_mode,
            prefill_chunk=self.ecfg.prefill_chunk,
        )
        kv_bytes = S * self.ecfg.max_seq_len * self.kv_bytes_per_token()
        n_dev = self.mesh.size if self.mesh is not None else 1
        self._headroom_estimate_bytes = int(
            (weight_bytes + kv_bytes + analytic["workspace_bytes"]) * 1.15
        ) // n_dev

        # seed the consistent-gauge cache with a build-time snapshot (the
        # scheduler isn't running yet, so _run_admin executes inline):
        # /metrics served before the first sweep must still carry the
        # paged pool gauges rather than an empty fallback dict
        self._kv_admin_snapshot()

    def _refuse_for_ring(self, mesh, drafter, lora, prefill_mesh) -> None:
        """A ``sliding_window_layout`` stack keeps its window layers' K/V in
        a ring of R positions a slot, addressed ``p mod R``: once a slot has
        wrapped, the keys of its first positions are gone. What needs them
        back, or needs more than one query against the ring, is an error at
        construction, each by the setting's name."""
        e = self.ecfg
        why = None
        if e.kv_layout != "dense":
            why = (f"kv_layout={e.kv_layout!r}: the ring is a per-slot leaf beside the "
                   "global layers' stripes; nothing cuts it into pool blocks")
        elif e.prefix_cache:
            why = ("prefix_cache: a slot that has wrapped no longer holds its prefix's "
                   "window keys, so a retained slot cannot be resumed from a shared prefix")
        elif e.kv_host_tier_bytes:
            why = ("kv_host_tier_bytes: the host tier holds paged K/V blocks, and the "
                   "ring has none")
        elif e.disagg or prefill_mesh is not None:
            why = ("disagg: the handoff ships one K/V stripe per layer of one length, "
                   "not a ring beside stripes")
        elif drafter is not None or e.spec_tokens > 0:
            why = ("a drafter / spec_tokens: a verify step is several queries against "
                   "the ring, and a rejected token's write has pushed a live key out")
        elif mesh is not None:
            why = ("a mesh (tp/dp/sp/pp): nothing shards the two K/V leaves or splits "
                   "the unrolled walk over pipeline stages")
        elif lora is not None:
            why = "a LoRA bank: the unrolled walk takes no adapters"
        elif e.prefill_chunk is not None:
            why = ("prefill_chunk: a continuation piece would attend ring + piece, "
                   "which no program here computes (a prompt is one fresh piece of "
                   "up to max_prefill_len)")
        elif e.kv_cache_dtype == "int8":
            why = "kv_cache_dtype='int8': the ring has no int8 leaves"
        if why is not None:
            raise ValueError(
                f"model {self.cfg.name!r} is a sliding_window_layout stack (window "
                "layers whose K/V is a ring beside global layers' stripes) and "
                f"cannot be served with {why}"
            )

    def _refuse_for_hybrid(self, mesh, drafter, lora, prefill_mesh) -> None:
        """A hybrid stack (cfg.layer_types, cfg.hybrid_override_pattern)
        keeps, per slot and beside its K/V, a recurrent state, a convolution
        tail or both (a short-convolution stack: the tail alone). Neither
        has positions: it cannot be rewound
        to a shared prefix, cut into blocks, verified-and-rolled-back by a
        drafter, or handed over as a stripe, and nothing here shards it.
        Each such setting is an error at construction, never a fallback."""
        e = self.ecfg
        why = None
        if e.prefix_cache:
            why = ("prefix_cache: a retained slot's state is the state after "
                   "its LAST token; reusing a prefix needs a snapshot of the "
                   "state at the prefix's end, which nothing keeps")
        elif e.kv_layout != "dense":
            why = (f"kv_layout={e.kv_layout!r}: the recurrent state has no "
                   "positions to page; it lives per slot beside dense stripes")
        elif drafter is not None or e.spec_tokens > 0:
            why = ("a drafter / spec_tokens: rejected draft tokens would have "
                   "to be rolled back out of the state, and it has no rewind")
        elif e.disagg or prefill_mesh is not None:
            why = ("disagg: the handoff protocol ships K/V stripes or block "
                   "tables, not a recurrent state and convolution tail")
        elif lora is not None:
            why = ("a LoRA bank: the recurrent and convolution mixers' projections "
                   "(in_proj, out_proj and their like) take no adapters")
        elif e.kv_host_tier_bytes:
            why = ("kv_host_tier_bytes: the host tier holds paged K/V blocks, "
                   "and a hybrid has none")
        elif mesh is not None:
            why = ("a mesh (tp/dp/sp/pp): nothing shards the recurrent state "
                   "or splits the period walk over pipeline stages")
        if why is not None:
            raise ValueError(
                f"model {self.cfg.name!r} is a hybrid stack (layers that keep a "
                "recurrent state or a convolution tail beside full attention) "
                f"and cannot be served with {why}"
            )

    # -- paged-KV block accounting ----------------------------------------

    def _blocks_needed(self, req: GenRequest) -> int:
        """Worst-case pool blocks a request can touch: prompt + budgeted
        new tokens, plus up to decode_chunk-1 surplus writes from the fused
        sweep that logically finishes it, capped by the KV window."""
        worst = min(
            len(req.prompt_tokens) + req.max_new_tokens + self.ecfg.decode_chunk,
            self.ecfg.max_seq_len,
        )
        return -(-worst // self._blk)

    def _prefix_keys(self, prompt: list[int], n_blocks: int) -> list[bytes]:
        """Content keys of the prompt's first 1..n_blocks full blocks in
        ONE incremental pass: the KV of position p depends on ALL tokens
        <= p, so block i's key hashes the whole prefix up to its end — a
        running sha256 snapshot per block boundary keeps this O(len), not
        O(len^2/blk)."""
        import hashlib

        h = hashlib.sha256()
        keys: list[bytes] = []
        for i in range(n_blocks):
            for t in prompt[i * self._blk : (i + 1) * self._blk]:
                h.update(t.to_bytes(8, "little", signed=True))
            keys.append(h.copy().digest())
        return keys

    def _paged_plan(self, req: GenRequest) -> tuple[list[int], int]:
        """(reusable shared block ids for the longest cached prompt
        prefix, new blocks the request still needs). At least the final
        prompt token always prefills (its last-position logits feed the
        first sample), so reuse caps at (len-1)//BLK full blocks; and —
        same rule as the dense APC's slot matching — a match below
        max(min_prefill_bucket, len/4) doesn't count: it would move the
        big remainder off the flash fresh-prefill path onto the masked
        chunk path for a trivial saving.

        The plan (and the prompt's full key list, reused by registration)
        memoizes on the request, keyed by _prefix_epoch: stale plans must
        never survive an index change — an evicted block id in a cached
        plan would reuse a reallocated block's garbage KV."""
        cached = getattr(req, "_plan_cache", None)
        if cached is not None and cached[0] == self._prefix_epoch:
            return list(cached[2]), cached[3]
        prompt = req.prompt_tokens
        reuse: list[int] = []
        keys: list[bytes] = []
        tier_keys: list[bytes] = []
        if self.ecfg.prefix_cache:
            keys = self._prefix_keys(prompt, len(prompt) // self._blk)
            max_b = (len(prompt) - 1) // self._blk
            for key in keys[:max_b]:
                bid = self._hash_block.get(key)
                if bid is None:
                    break
                reuse.append(bid)
            if self._tier and not self._tier_disabled:
                # host-tier extension of the chain: demoted blocks whose
                # keys continue the match beyond the device-resident
                # prefix promote back at admission (read-only here — the
                # upload happens in _paged_admit_blocks). Contiguity
                # matters: a tier hit PAST a miss would leave a KV hole
                # the prefill would never fill.
                for key in keys[len(reuse):max_b]:
                    if key not in self._tier:
                        break
                    tier_keys.append(key)
            floor = max(self.ecfg.min_prefill_bucket, len(prompt) // 4)
            if (len(reuse) + len(tier_keys)) * self._blk < floor:
                reuse = []
                tier_keys = []
        # tier-promoted blocks still consume FRESH device blocks (the
        # upload targets a new allocation), so they count in need_new
        need_new = self._blocks_needed(req) - len(reuse)
        req._plan_cache = (
            self._prefix_epoch, keys, list(reuse), need_new, list(tier_keys),
        )
        return reuse, need_new

    def _paged_fits(self, req: GenRequest) -> bool:
        # kv_alloc_fail injection (docs/RESILIENCE.md): an armed fault
        # opens a backpressure window (expiring by its armed duration) —
        # admission behaves exactly as if the pool were exhausted
        # (head-of-line defer, queue growth, deadline sheds), which is
        # the graceful handling under test. Scheduler-thread-only state;
        # the registry check is internally locked.
        spec = self._faults.check("kv_alloc_fail")
        if spec is not None:
            self._kv_fault_until = time.time() + max(spec.duration, 0.0)
        if self._kv_fault_until and time.time() < self._kv_fault_until:
            return False
        reuse, need_new = self._paged_plan(req)
        reused_retained = sum(1 for b in reuse if self._block_rc.get(b, 0) == 0)
        available = (
            len(self._free_blocks) + len(self._retained_lru) - reused_retained
        )
        return need_new <= available

    def _paged_alloc(self) -> int:
        """One fresh block: free list first, then evict the least-recently
        retained shared block (dropping its content-key registration —
        demoted to the host-RAM tier first when one is configured)."""
        self.stats["kv_blocks_allocated"] += 1
        if self._free_blocks:
            return self._free_blocks.pop()
        bid, _ = self._retained_lru.popitem(last=False)  # oldest
        self.stats["kv_retained_evictions"] += 1  # LRU churn (kv_thrash)
        key = self._block_hash.pop(bid, None)
        depth = self._block_depth.pop(bid, 0)
        if key is not None:
            self._hash_block.pop(key, None)
            self._prefix_epoch += 1  # index changed: cached plans expire
            if self._tier_cap_bytes and not self._tier_disabled:
                self._tier_demote(bid, key, depth)
        self._block_rc.pop(bid, None)
        return bid

    def _tier_block_bytes(self) -> int:
        """Host bytes one demoted block occupies (the per-block slice of
        every cache leaf — int8 caches demote their scales alongside)."""
        return sum(
            int(leaf.nbytes) // leaf.shape[1]
            # aval metadata only (nbytes/shape are static across the
            # dispatch swaps the lock orders; the dict reference read is
            # atomic), never the buffer contents
            for leaf in self._cache.values()  # kvmini: lock-ok
        )

    def _tier_demote(self, bid: int, key: bytes, depth: int) -> None:
        """Catch an eviction in the host-RAM tier: copy the block's KV to
        host (bounded by kv_host_tier_bytes — oldest tier entries make
        room, a tier too small for even one block stays empty) and file
        it under its content key for promotion at a future admission.
        Scheduler-thread-only; the device fetch synchronizes, which is
        exactly the price the capacity knob exists to bound."""
        blob_bytes = self._tier_block_bytes()
        if blob_bytes > self._tier_cap_bytes:
            return
        while self._tier_bytes + blob_bytes > self._tier_cap_bytes:
            _, old = self._tier.popitem(last=False)  # oldest demotion
            self._tier_bytes -= old["bytes"]
            self._prefix_epoch += 1
        self._tier[key] = {
            "depth": depth,
            "bytes": blob_bytes,
            "kv": self._read_block_host(bid),
        }
        self._tier_bytes += blob_bytes
        self._prefix_epoch += 1  # tier keys now match: plans must replan
        self.stats["kv_tier_demotions"] += 1

    def _tier_thrash_tick(self) -> None:
        """Self-disabling thrash guard, run from the scheduler loop's
        gauge-republish cadence: when retained-eviction churn crosses the
        monitor's kv_thrash thresholds (>= 4.0 evictions/s over 3
        consecutive ~1 s windows — monitor/events.py's defaults, kept in
        lockstep so the chart marker and the tier agree on what churn
        means), demoting is just moving the thrash onto PCIe — the tier
        empties and disables for the rest of the run (sticky; the
        kv_tier_disabled gauge records it)."""
        if not self._tier_cap_bytes or self._tier_disabled:
            return
        now = time.time()
        t0, ev0 = self._tier_thrash_win
        if now - t0 < 1.0:
            return
        rate = (self.stats["kv_retained_evictions"] - ev0) / (now - t0)
        self._tier_thrash_win = (now, self.stats["kv_retained_evictions"])
        self._tier_thrash_hits = (
            self._tier_thrash_hits + 1 if rate >= 4.0 else 0
        )
        if self._tier_thrash_hits >= 3:
            self._tier_disabled = True
            if self._tier:
                self._tier.clear()
                self._tier_bytes = 0
                self._prefix_epoch += 1

    def _read_block_host(self, bid: int) -> dict[str, Any]:
        """One pool block's KV as host numpy leaves (block axis sliced
        out). Used by tier demotion and the migration exporter; stubbed
        by the JAX-free harness tests."""
        with self._cache_lock:
            out = {
                name: np.asarray(leaf[:, bid])
                for name, leaf in self._cache.items()
            }
        return out

    def _write_block_dev(self, bid: int, leaves: dict[str, Any]) -> None:
        """Install host KV leaves into pool block ``bid`` (the inverse of
        _read_block_host) with the cache donated — tier promotion and the
        migration importer both land through here."""
        sub = {
            name: jnp.asarray(arr)[:, None] for name, arr in leaves.items()
        }
        with self._cache_lock:
            self._cache = self._step("kv_block_write")(
                self._cache, sub, jnp.int32(bid)
            )

    def _paged_register_keys(
        self, blks: list[int], keys: list[bytes]
    ) -> None:
        """Register content keys for ``blks`` (parallel lists; first
        registration wins — a key already mapped keeps its block) and
        record each block's chain depth for the migration exporter."""
        registered = False
        for i, key in enumerate(keys):
            if key not in self._hash_block and blks[i] not in self._block_hash:
                self._hash_block[key] = blks[i]
                self._block_hash[blks[i]] = key
                self._block_depth[blks[i]] = i + 1
                registered = True
        if registered:
            self._prefix_epoch += 1

    def _paged_admit_blocks(
        self, slot: int, req: GenRequest, register: bool = True
    ) -> int:
        """Reserve the request's blocks (caller checked fit): claim the
        cached prefix's shared blocks by reference, promote any host-tier
        continuation of the chain, allocate the rest, and point the
        slot's table row at them (scratch beyond). Registers the prompt's
        full blocks for future sharing — unless ``register=False`` (the
        disaggregated route: the lane fills the blocks ASYNCHRONOUSLY,
        so registering at admission would let a later admission reuse KV
        that does not exist yet; the consume side registers instead).
        Returns the reused token count (the prefill's start offset)."""
        prompt = req.prompt_tokens
        reuse, need_new = self._paged_plan(req)
        tier_keys: list[bytes] = list(req._plan_cache[4])
        # claim shared blocks FIRST: a 0->1 refcount leaves the retained
        # pool before eviction for the new allocations can touch it
        for bid in reuse:
            rc = self._block_rc.get(bid, 0)
            if rc == 0:
                self._retained_lru.pop(bid, None)
                self.stats["kv_share_reclaims"] += 1  # 0->1: left the pool
            self._block_rc[bid] = rc + 1
        new_blocks = [self._paged_alloc() for _ in range(need_new)]
        for bid in new_blocks:
            self._block_rc[bid] = 1
        # host-tier promotion: the plan's contiguous tier continuation
        # uploads into the first fresh blocks — positionally they ARE the
        # chain's next blocks, so the prefill can start past them. The
        # plan is epoch-memoized, but an eviction between plan and admit
        # (the _paged_alloc above can clear the tier under cap pressure)
        # must degrade to prefilling those positions, never to attending
        # a hole — re-check membership per key and stop at the first gap.
        promoted = 0
        for i, key in enumerate(tier_keys):
            entry = self._tier.pop(key, None) if not self._tier_disabled else None
            if entry is None:
                break
            self._tier_bytes -= entry["bytes"]
            self._prefix_epoch += 1
            self._write_block_dev(new_blocks[i], entry["kv"])
            promoted += 1
        if promoted:
            self.stats["kv_tier_promotions"] += promoted
            self.stats["kv_tier_hits"] += 1
        blks = reuse + new_blocks
        self._slot_blocks[slot] = blks
        row = np.full((self._maxb,), self._scratch_block, dtype=np.int32)
        row[: len(blks)] = blks
        self._block_table[slot] = row
        self._table_dev = None
        if self.ecfg.prefix_cache and register:
            # register this prompt's full blocks (content exists once the
            # synchronous prefill below runs; admissions are serialized on
            # the scheduler thread, so no reader can arrive earlier). The
            # key list comes from the memoized plan — no third hash pass.
            self._paged_register_keys(blks, req._plan_cache[1])
        reused_len = (len(reuse) + promoted) * self._blk
        if self.ecfg.prefix_cache:
            # a lookup only happened if block reuse was attempted at all —
            # counting otherwise would pin cache_hit_ratio to a hard 0
            # instead of letting the TTFT probe fall through
            self.stats["prefix_lookups"] += 1
        if reused_len:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += reused_len
            self._hit_depths.append(reused_len)
        return reused_len

    def _paged_release(self, slot: int) -> None:
        """Drop the slot's block ownerships and park its row on the scratch
        block, so the sweep's all-slots dispatch can never write a stale
        position into a block that was handed to another request. Shared
        blocks whose refcount reaches zero go to the retained pool (still
        content-addressed, evictable); unregistered blocks free outright.

        Before releasing, full blocks covering GENERATED tokens register
        too (prompt blocks registered at admission): KV at position p
        depends only on tokens <= p, so a multi-turn follow-up whose
        prompt replays the transcript (old prompt + emitted tokens + new
        turn) hits the whole previous conversation — the paged analog of
        the dense APC retaining generated tokens. Only blocks with
        (i+1)*BLK <= slot_len qualify: the fused sweep's surplus writes
        land at positions >= slot_len, which is always past the last full
        block's end."""
        if self.ecfg.prefix_cache and self._slot_blocks[slot]:
            tokens = self._slot_tokens[slot][: self._slot_len[slot]]
            n_full = len(tokens) // self._blk
            if n_full:
                self._paged_register_keys(
                    self._slot_blocks[slot][:n_full],
                    self._prefix_keys(tokens, n_full),
                )
        self._paged_release_blocks(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._block_table[slot] = self._scratch_block
        self._table_dev = None

    def _paged_release_blocks(self, blks: list[int]) -> None:
        """Drop one ownership reference per block in ``blks`` — the
        shared tail of _paged_release and the orphaned-handoff release
        (a paged-v2 slot aborted mid-lane frees its blocks only when the
        lane's payload lands, so in-flight lane writes can never hit a
        reallocated block). Reversed: the chain's LEAF blocks enter the
        LRU first (oldest end), so eviction takes leaves before roots —
        evicting a root first would orphan every still-retained
        descendant (plans match prefixes root-outward and stop at the
        first miss)."""
        for bid in reversed(blks):
            rc = self._block_rc.get(bid, 1) - 1
            if rc > 0:
                self._block_rc[bid] = rc
                continue
            if bid in self._block_hash:
                self._block_rc[bid] = 0
                self._retained_lru[bid] = None  # most-recent end
            else:
                self._block_rc.pop(bid, None)
                self._block_depth.pop(bid, None)
                self._free_blocks.append(bid)

    # -- cross-replica KV migration (docs/FLEET.md; POST /kv/export|import)

    def _wire_encode_block(self, leaves: dict[str, Any]) -> dict[str, Any]:
        """One block's host leaves -> the JSON wire format: int8 values
        with f32 per-row wire scales for unquantized k/v (int8-KV on the
        wire regardless of the resident dtype — migration is a warmup
        transfer, the same accuracy trade --kv-cache-dtype int8 makes),
        verbatim bytes for already-int8 leaves and scale leaves."""
        import base64

        wire: dict[str, Any] = {}
        for name, arr in leaves.items():
            a = np.asarray(arr)
            if name in ("k", "v") and a.dtype != np.int8:
                f = a.astype(np.float32)
                amax = np.max(np.abs(f), axis=-1)
                scale = np.where(amax > 0.0, amax / 127.0, 1.0).astype(
                    np.float32
                )
                q = np.clip(
                    np.round(f / scale[..., None]), -127, 127
                ).astype(np.int8)
                wire[name] = {
                    "b64": base64.b64encode(q.tobytes()).decode(),
                    "dtype": "int8",
                    "shape": list(q.shape),
                    "wire_scale_b64": base64.b64encode(
                        scale.tobytes()
                    ).decode(),
                }
            else:
                wire[name] = {
                    "b64": base64.b64encode(a.tobytes()).decode(),
                    "dtype": str(a.dtype),
                    "shape": list(a.shape),
                }
        return wire

    def _wire_decode_block(self, wire: dict[str, Any]) -> dict[str, Any]:
        """Inverse of _wire_encode_block, validated against THIS engine's
        cache geometry — a donor with different layer/head/block shapes
        must fail loudly, never scatter-write garbage."""
        import base64

        leaves: dict[str, Any] = {}
        for name, leaf in self._cache.items():
            spec = wire.get(name)
            if spec is None:
                raise ValueError(f"kv wire payload missing leaf {name!r}")
            want = (leaf.shape[0],) + tuple(leaf.shape[2:])
            if tuple(spec["shape"]) != want:
                raise ValueError(
                    f"kv wire leaf {name!r} shape {spec['shape']} does "
                    f"not match this engine's block shape {list(want)}"
                )
            raw = np.frombuffer(
                base64.b64decode(spec["b64"]), dtype=np.dtype(spec["dtype"])
            ).reshape(spec["shape"])
            if "wire_scale_b64" in spec:
                scale = np.frombuffer(
                    base64.b64decode(spec["wire_scale_b64"]), np.float32
                ).reshape(spec["shape"][:-1])
                raw = (raw.astype(np.float32) * scale[..., None]).astype(
                    np.asarray(leaf[:1, :1]).dtype
                )
            leaves[name] = raw
        return leaves

    def kv_export(self, budget_bytes: int) -> dict[str, Any]:
        """Bounded wire snapshot of this engine's registered (shareable)
        blocks, root-first by chain depth so budget truncation drops
        LEAVES (a shipped leaf without its roots could never match —
        plans walk root-outward and stop at the first miss). Thread-safe:
        the pool walk and device reads run on the scheduler thread via
        _run_admin. Raises on dense engines — the caller (POST
        /kv/export) turns that into a 400."""
        if not self.paged:
            raise ValueError("kv_export requires kv_layout=paged")
        out: dict[str, Any] = {
            "block_size": self._blk,
            "blocks": [],
            "bytes": 0,
            "truncated": False,
        }

        def _collect() -> None:
            budget = max(int(budget_bytes), 0)
            spent = 0
            cands = sorted(
                self._block_hash.items(),
                key=lambda item: self._block_depth.get(item[0], 0),
            )
            for bid, key in cands:
                wire = self._wire_encode_block(self._read_block_host(bid))
                nbytes = sum(
                    len(spec["b64"]) * 3 // 4
                    + len(spec.get("wire_scale_b64", "")) * 3 // 4
                    for spec in wire.values()
                )
                if spent + nbytes > budget:
                    out["truncated"] = True
                    break
                spent += nbytes
                out["blocks"].append({
                    "key": key.hex(),
                    "depth": self._block_depth.get(bid, 0),
                    "kv": wire,
                })
            out["bytes"] = spent
            self.stats["kv_export_blocks"] += len(out["blocks"])

        err = self._run_admin(_collect, timeout_s=30.0)
        if err is not None:
            raise RuntimeError(f"kv_export failed: {err}")
        return out

    def kv_import(self, payload: dict[str, Any]) -> dict[str, Any]:
        """Install a sibling's kv_export payload: each block takes a
        FREE pool block (never evicts — warming must not thrash the
        target's own cache), uploads through the block-write executable,
        and registers as a retained (rc=0, evictable) prefix block.
        Already-known keys skip; a dry free list stops the import early.
        Runs on the scheduler thread via _run_admin."""
        if not self.paged:
            raise ValueError("kv_import requires kv_layout=paged")
        if int(payload.get("block_size", -1)) != self._blk:
            raise ValueError(
                f"kv_import block_size {payload.get('block_size')} does "
                f"not match this engine's kv_block_size {self._blk}"
            )
        res = {"imported": 0, "skipped": 0, "bytes": 0, "exhausted": False}

        def _install() -> None:
            registered = False
            for entry in payload.get("blocks", []):
                key = bytes.fromhex(entry["key"])
                if key in self._hash_block:
                    res["skipped"] += 1
                    continue
                if not self._free_blocks:
                    res["exhausted"] = True
                    break
                leaves = self._wire_decode_block(entry["kv"])
                bid = self._free_blocks.pop()
                self.stats["kv_blocks_allocated"] += 1
                self._write_block_dev(bid, leaves)
                self._hash_block[key] = bid
                self._block_hash[bid] = key
                self._block_depth[bid] = int(entry.get("depth", 0))
                self._block_rc[bid] = 0
                self._retained_lru[bid] = None
                registered = True
                res["imported"] += 1
                res["bytes"] += sum(
                    len(spec["b64"]) * 3 // 4
                    + len(spec.get("wire_scale_b64", "")) * 3 // 4
                    for spec in entry["kv"].values()
                )
            if registered:
                self._prefix_epoch += 1
            self.stats["kv_migrated_blocks"] += res["imported"]
            self.stats["kv_migrated_bytes"] += res["bytes"]

        err = self._run_admin(_install, timeout_s=60.0)
        if err is not None:
            raise RuntimeError(f"kv_import failed: {err}")
        return res

    def _table(self) -> jnp.ndarray:
        """Device mirror of the block table, rebuilt only when allocation
        changed — never on the per-token hot path."""
        if self._table_dev is None:
            self._table_dev = jnp.asarray(self._block_table)
        return self._table_dev

    def _adapter_ids(self) -> jnp.ndarray:
        """Device mirror of per-slot adapter indices (multi-LoRA), rebuilt
        only when the slot population changes."""
        if self._adapter_ids_dev is None:
            self._adapter_ids_dev = jnp.asarray(self._slot_adapter, jnp.int32)
        return self._adapter_ids_dev

    # -- live adapter management (vLLM dynamic-LoRA analog) ----------------

    def _run_admin(self, fn, timeout_s: float = 60.0) -> Optional[str]:
        """Execute ``fn`` on the scheduler thread (between sweeps) and
        return its error string, or None on success. Direct call when the
        scheduler isn't running (build-time / tests) — or when the caller
        IS the scheduler thread (an op enqueued from the thread that
        drains the queue would deadlock waiting on itself)."""
        if not self._running or threading.current_thread() is self._thread:
            op = _AdminOp(fn)
            op.run()
            return op.error
        op = _AdminOp(fn)
        self._admin.put(op)
        # stop() can flip _running and drain the queue BETWEEN the check
        # above and the put — the op would then sit in a dead queue and
        # hang its caller for the full timeout. Re-check and self-drain:
        # with the scheduler gone, nothing else will. Only ops still IN
        # the queue are failed — an op absent from the queue was dequeued
        # by the scheduler (it either ran or is running right now), so its
        # own done/error must be awaited below, not overwritten with a
        # fabricated failure for work that actually applied.
        if not self._running and not op.done.is_set():
            while True:
                try:
                    q_op = self._admin.get_nowait()
                except queue.Empty:
                    break
                q_op.error = "engine stopped"
                q_op.done.set()
        if not op.done.wait(timeout=timeout_s):
            return f"admin op timed out after {timeout_s:.0f}s"
        return op.error

    def load_adapter(self, name: str, adapter: dict[str, Any]) -> Optional[str]:
        """Install a LoRA adapter under ``name`` without restarting the
        engine. ``adapter`` is the ops/lora.py install format (target ->
        (A [L, in, r], B [L, r, out]), B pre-scaled). On an engine started
        without a bank, the first load creates a zero bank with
        ``ecfg.lora_slots`` capacity and that adapter's rank/targets; the
        bank's shapes are then fixed (capacity/rank growth = restart).
        Returns an error string, or None on success."""

        def _apply():
            from kserve_vllm_mini_tpu.ops.lora import (
                grow_bank_rank,
                install_adapter,
                pad_adapter_rank,
                zero_lora_bank,
            )

            if self._drafter_params is not None or self.ecfg.prefix_cache:
                raise ValueError(
                    "multi-LoRA excludes drafters and prefix_cache"
                )
            if self._hybrid:
                raise ValueError(
                    "multi-LoRA excludes hybrid stacks: the linear mixer's "
                    "projections take no adapters"
                )
            _require_tp_only_mesh(self.mesh)
            # TRANSACTIONAL: every mutation lands on a local bank and
            # self._lora is only reassigned after install_adapter succeeds
            # — a rank/target mismatch raising mid-update must leave the
            # old adapter's weights serving, not a zeroed slot that is
            # still routable by name
            cur = self._lora
            if cur is None:
                rank = next(iter(adapter.values()))[0].shape[-1]
                cur = zero_lora_bank(
                    self.cfg, self.ecfg.lora_slots, rank,
                    targets=sorted(adapter), dtype=self.cfg.jnp_dtype,
                )
                cur["names"] = {}
            names = cur["names"]
            if name in names:
                idx = names[name]
                why = self._adapter_in_use(idx, name)
                if why:
                    raise ValueError(
                        f"{why}; updating its weights mid-stream would "
                        "corrupt them"
                    )
            else:
                capacity = next(iter(cur["layers"].values())).shape[1] - 1
                used = set(names.values())
                free = [i for i in range(1, capacity + 1) if i not in used]
                if not free:
                    raise ValueError(
                        f"adapter bank is full ({capacity} slots, "
                        f"{sorted(names)}); unload one or restart with a "
                        "larger bank (lora_slots / --lora-slots)"
                    )
                idx = free[0]
            # rank flexibility without a restart: a higher-rank adapter
            # grows the whole bank (zero-padding preserves installed
            # deltas exactly; the next decode dispatch retraces once), a
            # lower-rank adapter pads itself up to the bank
            in_rank = max(a.shape[-1] for a, _b in adapter.values())
            if in_rank > cur["rank"]:
                cur = grow_bank_rank(cur, in_rank)
            padded = pad_adapter_rank(adapter, cur["rank"])
            # zero the index first: the incoming adapter may cover FEWER
            # targets than the index's previous occupant, and install only
            # writes the targets it has — leftovers would silently blend
            # two fine-tunes
            bank = self._zero_bank_index(cur, idx)
            bank = install_adapter(bank, idx, padded)
            if self.mesh is not None:
                # same replication as the preset-bank init path: the delta
                # joins the tp-sharded base projections however each
                # target is partitioned. Eager .at[].set updates preserve
                # sharding, but the freshly-built bank (first load) and
                # the host-side adapter arrays do not — normalize here.
                from jax.sharding import NamedSharding, PartitionSpec

                rep = NamedSharding(self.mesh, PartitionSpec())
                bank = {
                    **bank,
                    "layers": jax.device_put(bank["layers"], rep),
                }
            bank["names"] = dict(names, **{name: idx})
            self._lora = bank
            self._lora_names = bank["names"]

        return self._run_admin(_apply)

    @staticmethod
    def _zero_bank_index(bank: dict[str, Any], idx: int) -> dict[str, Any]:
        layers = {
            k: v.at[:, idx].set(0) for k, v in bank["layers"].items()
        }
        return {**bank, "layers": layers}

    def _adapter_in_use(self, idx: int, name: str) -> Optional[str]:
        """Why adapter ``idx`` can't be replaced/removed right now, or
        None. Checks live slots AND queued work — a pending request whose
        adapter vanishes before admission would otherwise be silently
        served by the base model."""
        if any(
            self._slot_adapter[i] == idx
            for i in range(self.ecfg.max_slots)
            if self._slot_req[i] is not None
        ):
            return f"adapter {name!r} is serving active requests"
        with self._pending.mutex:
            queued = any(
                h.request.adapter == name for h in self._pending.queue
            )
        if queued or (
            self.paged
            and self._deferred is not None
            and self._deferred.request.adapter == name
        ):
            return f"adapter {name!r} has queued requests waiting for it"
        return None

    def unload_adapter(self, name: str) -> Optional[str]:
        """Remove ``name`` from the registry, freeing its bank slot for a
        future load. Refused while any active request uses it. Returns an
        error string, or None on success."""

        def _apply():
            if self._lora is None or name not in self._lora["names"]:
                raise ValueError(
                    f"unknown adapter {name!r}; loaded: "
                    f"{sorted(self._lora['names']) if self._lora else []}"
                )
            idx = self._lora["names"][name]
            why = self._adapter_in_use(idx, name)
            if why:
                raise ValueError(why)
            names = dict(self._lora["names"])
            del names[name]
            self._lora["names"] = names
            self._lora_names = names

        return self._run_admin(_apply)

    # -- compiled steps ----------------------------------------------------

    def _instrument(self, fn, label: str):
        """Route a compiled step through the compile-stats wrapper
        (docs/PROFILING.md). Meshes stay on the plain jit path: an AOT
        executable requires pre-placed arguments, while jit transparently
        reshards — the sharded engines keep that behavior."""
        if not self.ecfg.compile_stats or self.mesh is not None:
            return fn
        return InstrumentedJit(fn, self._compile_recorder, label=label)

    def _step(self, kind: str, *key, draft: bool = False):
        """The engine's one cache of compiled steps: ``(kind, *key)`` -> the
        program of runtime/steps.py, built once and routed through the
        compile-stats wrapper under the label that ``bench/run.py``,
        ``chip_smoke.py`` and ``/metrics`` read it by. Kinds and keys:
        ``prefill`` (bucket, fresh), ``decode`` (n_steps), ``decode_masked``,
        ``spec``, ``first``, ``reset`` (carry: the variant that merges the
        token into the decode carry), ``inject``, ``kv_block_write``.
        ``draft``: the drafter's shadow of a prefill step, over its own
        config and plain ``forward``."""
        k = (kind, *key, draft)
        fn = self._steps.get(k)
        if fn is not None:
            return fn
        cfg = self._drafter_cfg if draft else self.cfg
        fwd = forward if draft else self._fwd
        paged = ".paged" if self.paged else ""
        label = None  # first, reset: outside the compile-stats rail
        if kind == "prefill":
            bucket, fresh = key
            fn = steps.build_prefill(cfg, fwd, self._kernels, self._cache_by, fresh)
            label = steps.prefill_label(self._cache_by, fresh, bucket)
        elif kind == "decode":
            (n_steps,) = key
            fn = steps.build_decode(cfg, fwd, self._kernels, n_steps)
            label = f"decode[chunk={n_steps}]{paged}"
        elif kind == "decode_masked":
            fn = steps.build_decode(cfg, fwd, self._kernels, masked=True)
            label = f"decode.masked{paged}"
        elif kind == "spec":
            # the rejection-sampling round serves greedy AND sampled slots in
            # one executable: temperature-0 rows degenerate exactly to the
            # greedy accept rule (see build_spec_step_sampled), so greedy
            # output stays bit-identical to plain decode
            fn = steps.build_spec_step_sampled(
                cfg, self._drafter_cfg, self.ecfg.spec_tokens,
                kernels=self._kernels,
            )
            label = f"spec[k={self.ecfg.spec_tokens}]"
        elif kind == "first":
            fn = steps.build_first(cfg)
        elif kind == "reset":
            fn = steps.build_reset(*key)
        elif kind == "inject":
            fn = steps.build_cache_write(kind)
            label = "disagg_inject"
        elif kind == "kv_block_write":
            fn = steps.build_cache_write(kind)
            label = kind
        else:
            raise KeyError(kind)
        if label is not None:
            fn = self._instrument(fn, label + (".draft" if draft else ""))
        self._steps[k] = fn
        return fn

    # -- public API --------------------------------------------------------

    def submit(self, req: GenRequest) -> RequestHandle:
        # prompts longer than one prefill bucket run as CHUNKED prefill
        # (_admit_one), so the only hard cap is the slot's KV window itself
        # (one position must remain for decode). Only past that does the
        # tail-keeping truncation — still flagged end-to-end — apply.
        prompt_cap = self.ecfg.max_seq_len - 1
        if len(req.prompt_tokens) > prompt_cap:
            req.truncated = True
            req.truncated_tokens = len(req.prompt_tokens) - prompt_cap
            req.prompt_tokens = req.prompt_tokens[-prompt_cap:]
        handle = RequestHandle(req)
        if self.cfg.is_window_stack and len(req.prompt_tokens) > self.ecfg.max_prefill_len:
            # a longer prompt's continuation piece would attend ring + piece
            handle.events.put(("done", {
                "finish_reason": "error",
                "error": (
                    f"prompt of {len(req.prompt_tokens)} tokens exceeds max_prefill_len="
                    f"{self.ecfg.max_prefill_len}: a sliding_window_layout stack "
                    "prefills a prompt as one fresh piece"
                ),
            }))
            return handle
        if req.constraint is not None and not hasattr(req.constraint, "token_mask"):
            # raw byte automaton -> ByteTokenizer token mapping
            from kserve_vllm_mini_tpu.runtime.token_grammar import ByteTokenMachine

            req.constraint = ByteTokenMachine(req.constraint, self.cfg.vocab_size)
        if req.constraint is not None:
            # the grammar must be closable inside BOTH the token budget and
            # the slot's remaining KV window — otherwise format compliance
            # is impossible and the request must fail up front, not emit
            # truncated pseudo-JSON
            budget = min(
                req.max_new_tokens,
                self.ecfg.max_seq_len - 1 - len(req.prompt_tokens),
            )
            need = req.constraint.min_close()
            if budget < need:
                handle.events.put(("done", {
                    "finish_reason": "error",
                    "error": (
                        f"constrained format needs >= {need} tokens but only "
                        f"{budget} fit (max_tokens / cache window)"
                    ),
                }))
                return handle
        # Registry lookup from the submitting thread; admin ops swap the
        # whole dict reference atomically, so the worst case is a
        # just-loaded adapter 404ing for one request.
        # kvmini: thread-ok — atomic reference swap, benign stale read
        if req.adapter is not None and req.adapter not in self._lora_names:
            handle.events.put(("done", {
                "finish_reason": "error",
                "error": (
                    f"unknown adapter {req.adapter!r}; available: "
                    f"{sorted(self._lora_names) or '(none loaded)'}"
                ),
            }))
            return handle
        if self.paged and self._blocks_needed(req) > self._scratch_block:
            # can NEVER fit the pool (scratch_block == total user blocks) —
            # failing now beats deadlocking the admission queue forever
            handle.events.put(("done", {
                "finish_reason": "error",
                "error": (
                    f"request needs {self._blocks_needed(req)} KV blocks "
                    f"but the pool has {self._scratch_block}; raise "
                    "kv_pool_blocks or lower max_tokens"
                ),
            }))
            return handle
        if self.tracer is not None and req.trace_id is None:
            # no client trace context: mint one so the request still shows
            # in /traces (it just won't join a client-side trace)
            req.trace_id = rt_tracing.new_trace_id()
        self._pending.put(handle)
        # Gauge write from the submitting thread while the scheduler owns
        # every other stats key; dict setitem is GIL-atomic and the
        # scheduler recomputes this key each iteration anyway.
        # kvmini: thread-ok — GIL-atomic gauge write, scheduler refreshes
        self.stats["queue_depth"] = self._queue_depth()
        return handle

    def _queue_depth(self) -> int:
        """Requests waiting for admission: the pending queue PLUS the
        paged-backpressure head-of-line handle (_deferred), which sits in
        neither _pending nor a slot — without it, reported depth is one
        low whenever paged backpressure is active."""
        n = self._pending.qsize()
        # Racy read of the scheduler-owned deferred handle from the stats
        # path; depth is a monitoring gauge and the `is not None` check is
        # atomic under the GIL.
        # kvmini: thread-ok — monitoring gauge, GIL-atomic None check
        if self.paged and self._deferred is not None:
            n += 1
        return n

    # -- request lifecycle tracing (docs/TRACING.md) -----------------------

    def _trace_span(
        self,
        handle: RequestHandle,
        name: str,
        t0: float,
        t1: float,
        ok: bool = True,
        attrs: Optional[dict[str, Any]] = None,
    ) -> None:
        """Record one completed per-request phase span. All phase spans
        parent DIRECTLY under the client's http.request span
        (parent_span_id from the traceparent header), so the joined trace
        reads client http.request -> server queue/prefill/decode. At most
        tracing.MAX_REQUEST_SPANS of these per request — the recorder-
        overhead contract."""
        req = handle.request
        # trace context is deliberately host-local telemetry: followers see
        # None here and return before recording (kvmini: protocol-ok)
        if self.tracer is None or req.trace_id is None:
            return
        a = {"request_id": req.request_id}
        if attrs:
            a.update(attrs)
        self.tracer.record(
            name, req.trace_id, self._unix_ns(t0), self._unix_ns(t1),
            # host-local span parentage, same None-gate (kvmini: protocol-ok)
            parent_span_id=req.parent_span_id, ok=ok, attrs=a,
        )

    def _trace_engine_span(
        self, name: str, t0: float, t1: float,
        attrs: Optional[dict[str, Any]] = None,
    ) -> None:
        """Engine-lane span (dispatch->retire windows): not tied to one
        request, recorded under the engine's own trace id into the
        engine-lane ring (never competes with request spans for slots)."""
        if self._engine_tracer is None:
            return
        self._engine_tracer.record(
            name, self._engine_trace_id, self._unix_ns(t0), self._unix_ns(t1),
            attrs=attrs,
        )

    def _trace_gap(self, cause: str, t0_ns: int, t1_ns: int, waiting: int,
                   admissions: int, request_ids: list) -> None:
        """The ledger's ``on_gap``: each decode gap it books is one completed
        ``engine.decode.gap`` span of the engine lane, beside the windows."""
        self._engine_tracer.record(
            "engine.decode.gap", self._engine_trace_id,
            t0_ns + self._unix_offset_ns, t1_ns + self._unix_offset_ns,
            attrs={"cause": cause, "waiting_slots": waiting,
                   "admissions": admissions,
                   "request_ids": ",".join(str(r) for r in request_ids)},
        )

    def _unix_ns(self, t: float) -> int:
        """A monotonic ``_now()`` reading as the Unix nanoseconds /traces
        speaks, through the one offset captured when the engine was built."""
        return int(t * 1e9) + self._unix_offset_ns

    def _observe_phase(self, phase: str, seconds: float) -> None:
        self._phase_hist[phase].observe(seconds)

    def snapshot_phase_hist(self) -> dict[str, Any]:
        """Per-phase histogram snapshots for /metrics
        (kvmini_tpu_phase_seconds) and tests."""
        return {p: h.snapshot() for p, h in self._phase_hist.items()}

    def traces_otlp(self) -> dict[str, Any]:
        """One OTLP doc for GET /traces: the request-span ring plus the
        engine-lane ring as a second scopeSpans entry (same resource).
        droppedSpans sums both rings' evictions."""
        doc = self.tracer.to_otlp()
        if self._engine_tracer is not None and len(self._engine_tracer):
            eng_doc = self._engine_tracer.to_otlp()
            eng_scope = eng_doc["resourceSpans"][0]["scopeSpans"][0]
            eng_scope["scope"] = {"name": rt_tracing.SERVER_SCOPE + ".engine"}
            doc["resourceSpans"][0]["scopeSpans"].append(eng_scope)
            doc["droppedSpans"] += eng_doc["droppedSpans"]
        return doc

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        if self.cfg.is_window_stack:
            # both leaves go through the kernel or neither does, each by the
            # block of its own length
            self._window_decode_blks = window_decode_kernel_blocks(
                self.cfg, self._kernels, self._cache)
        else:
            self._dense_decode_blk = dense_decode_kernel_block(
                self.cfg, self._kernels, self._cache, self.paged
            )
        self._linear_state_kernel = "lin_state" in self._cache and (
            linear_decode_kernel_block(self._kernels, self._cache["lin_state"], 1)
            is not None)
        self._ssm_state_kernel = "ssm_state" in self._cache and (
            ssm_decode_kernel_block(self._kernels, self._cache["ssm_state"], 1)
            is not None)
        if self._disagg is not None:
            self._disagg.start()
        self._thread = threading.Thread(target=self._loop, daemon=True, name="engine-loop")
        self._thread.start()
        if self.ecfg.watchdog:
            self._watch_stop.clear()
            self._watch_thread = threading.Thread(
                target=self._watchdog_loop, daemon=True, name="engine-watchdog"
            )
            self._watch_thread.start()

    def stop(self) -> None:
        started = self._thread is not None
        self._running = False
        self._watch_stop.set()
        if self._thread:
            self._thread.join(timeout=10.0)
        if self._disagg is not None:
            # after the scheduler drained (mid-handoff slots got their
            # terminal events there); the lane flushes any leftover jobs
            # as tombstones on its own way out
            self._disagg.stop()
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=2.0)
        # an admin op enqueued around shutdown would otherwise hang its
        # caller for the full wait timeout
        while True:
            try:
                op = self._admin.get_nowait()
            except queue.Empty:
                break
            op.error = "engine stopped"
            op.done.set()
        # Graceful drain (docs/RESILIENCE.md, the shutdown contract):
        # the scheduler thread itself drains slots/blocks as its LAST
        # act before exiting (_loop -> _drain_requests), so slot state
        # keeps its single-writer owner. Here we only cover the
        # never-started engine: requests queued against it must still
        # get their terminal event rather than hang a client forever
        # (the pending queue is thread-safe; no slot state exists yet).
        if not started:
            while True:
                try:
                    h = self._pending.get_nowait()
                except queue.Empty:
                    break
                h.events.put(("done", {
                    "finish_reason": "cancelled", "tokens_out": 0,
                }))

    # -- scheduler loop ----------------------------------------------------

    def _constraint_mask(self, machine, budget: int) -> np.ndarray:
        """Bit-packed [ceil(vocab/8)] uint8 mask (bit set = token allowed)
        from the token-protocol machine, padded/cut to the MODEL's logit
        width. Packed because it rides host->device every constrained
        step; the jitted steps unpack on device (steps.unpack_mask)."""
        m = machine.token_mask(budget)
        V = self.cfg.vocab_size
        if m.shape[0] != V:
            out = np.zeros((V,), dtype=bool)
            out[: min(m.shape[0], V)] = m[:V]
            m = out
        return np.packbits(m, bitorder="little")

    def _pop_slot_for(self, prompt: list[int]) -> tuple[int, int]:
        """(slot, reused_prefix_len): with prefix caching on, prefer the
        free slot whose retained tokens share the longest prefix with the
        new prompt (capped at len(prompt)-1 — at least one position must
        run so the last-token logits exist); otherwise plain pop().

        Matches below min_prefill_bucket don't count: reusing k tokens
        moves the remaining n-k off the flash fresh-prefill path onto the
        positional-masked chunk path, so a trivial match (a shared chat-
        template first byte) would make prefill SLOWER while reporting a
        cache hit. Comparison is slice-equality (C speed) with a bisect on
        mismatch, not a per-token Python loop — this runs on the scheduler
        thread."""
        if (
            not self.ecfg.prefix_cache
            or self.paged  # paged reuse is BLOCK-level (_paged_admit_blocks)
            or self._drafter_params is not None
            or not self._free
        ):
            return self._free.pop(), 0
        target = prompt[:-1]
        # default victim = the OLDEST-freed slot (front of the list): a
        # no-match admission must evict the least-recently-retained prefix,
        # not the newest one (pop() from the tail would wipe the freshest
        # cache entry on every miss)
        best_i, best_k = 0, 0
        for i, s in enumerate(self._free):
            retained = self._retained[s]
            limit = min(len(retained), len(target))
            if limit <= best_k:
                continue  # cannot beat the current best
            if retained[:limit] == target[:limit]:
                k = limit
            else:
                lo, hi = 0, limit - 1  # [:lo] matches; [:limit] doesn't
                while lo < hi:
                    mid = (lo + hi + 1) // 2
                    if retained[:mid] == target[:mid]:
                        lo = mid
                    else:
                        hi = mid - 1
                k = lo
            if k > best_k:
                best_i, best_k = i, k
                if best_k == len(target):
                    break  # perfect match
        # floor: absolute (one full bucket) AND relative (a quarter of the
        # prompt) — a shared 20-token chat header on a 500-token prompt
        # must not move the other 480 tokens off the flash prefill path
        floor = max(self.ecfg.min_prefill_bucket, len(target) // 4)
        if best_k < floor:
            best_k = 0
            best_i = 0  # LRU victim (see above)
        slot = self._free.pop(best_i)
        # accounting contract shared with the block-level path
        # (_paged_admit_blocks): exactly one lookup per admission, a hit
        # iff reused tokens > 0, and prefix_tokens_reused grows by the
        # EXACT reused token count — pinned by the cross-path regression
        # test (tests/test_kv_observability.py)
        self.stats["prefix_lookups"] += 1
        if best_k > 0:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_reused"] += best_k
            self._hit_depths.append(best_k)
        return slot, best_k

    def _prefill_piece(self, piece: list[int], slot: int, off: int,
                       draft: bool = False, adapter_idx: int = 0):
        """ONE compiled prefill dispatch: ``piece`` written at absolute
        offset ``off`` of the slot's cache — offset 0 on the flash
        fresh-prefill path, continuation pieces on the positional-masked
        chunk path (int8-KV caches stream through the cached-prefill
        kernel on TPU, ops/flash_attention.py). Returns the piece's
        last-position logits [V] f32. ``adapter_idx`` picks the request's
        LoRA adapter (0 = base) when the engine carries a bank."""
        lkw = {}
        if self._lora is not None and not draft:
            lkw = {
                "lora": self._lora["layers"],
                "ids": jnp.asarray([adapter_idx], jnp.int32),
            }
        if self.paged:
            addr = jnp.asarray(self._block_table[slot : slot + 1])
        else:
            addr = jnp.int32(slot)
        return self._dispatch_piece(piece, off, addr, draft=draft, **lkw)

    def _dispatch_piece(self, piece: list[int], off: int, addr,
                        draft: bool = False, **lkw):
        """Pad ``piece`` to its bucket, pick the prefill step (fresh at
        offset 0, continuation past it; ``addr`` a slot index or a
        block-table row by the engine's layout) and call it. The scheduler
        thread and the v2 prefill lane both dispatch through here, and the
        read-dispatch-assign of the cache must be atomic between them —
        dispatch is async, so the critical section stays tiny."""
        bucket = steps.prefill_bucket(len(piece), self.ecfg)
        operands = steps.piece_operands(
            self._cache_by, piece, bucket, self.pad_id, off, addr
        )
        params = self._drafter_params if draft else self.params
        with self._cache_lock:
            step = self._step("prefill", bucket, off == 0, draft=draft)
            cache_in = self._dcache if draft else self._cache
            cache, last_logits = step(params, cache_in, *operands, **lkw)
            if draft:
                self._dcache = cache
            else:
                self._cache = cache
        return last_logits

    def _lane_paged_prefill(self, handle: RequestHandle, meta: dict):
        """HANDOFF_VERSION=2 lane prefill — runs ON the prefill-lane
        thread. Writes the prompt suffix straight into the shared-pool
        blocks the scheduler reserved at routing (meta["row"]), through
        the SAME compiled paged executables and piece schedule the
        colocated path uses (_dispatch_piece), so greedy streams stay
        byte-identical and the handoff carries a block table instead of
        KV bytes. Actual device execution orders by buffer dependencies
        on the single stream. Returns (last_logits, chunks) — the lane
        wraps them into the handoff."""
        prompt = handle.request.prompt_tokens
        row_dev = jnp.asarray(meta["row"][None])
        pos = int(meta["off"])
        budget = self.ecfg.max_prefill_len
        chunks = 0
        last_logits = None
        while pos < len(prompt):
            piece = prompt[pos : pos + budget]
            last_logits = self._dispatch_piece(piece, pos, row_dev)
            chunks += 1
            pos += len(piece)
        # the lane must not report the handoff complete until the block
        # writes landed: consume swaps the row in with zero copies, so
        # this sync IS the v2 payload barrier
        jax.block_until_ready(last_logits)
        return last_logits, chunks

    def _prefill_step(self, slot: int, st: dict, budget: int,
                      wait: bool = True) -> bool:
        """Advance one prefill piece for ``st`` (the per-slot chunked-
        prefill state): target pieces first, then — once the target cache
        holds the whole prompt — the drafter's shadow pieces. Blocks until
        the dispatch completes so the stall accounting is honest wall
        time, and counts the piece into ``prefill_chunks`` (and into
        ``prefill_chunk_stall_s`` when decode work was live — the decode
        tail this piece's execution stood in front of). ``wait=False``
        (an admission behind the sweep in flight) only dispatches: the
        wall time is booked where the first token is read back
        (_read_first_tokens). Returns True when every piece (target and
        draft) has run."""
        handle = st["handle"]
        prompt = handle.request.prompt_tokens
        n = len(prompt)
        draft = st["off"] >= n
        off = st["draft_off"] if draft else st["off"]
        piece = prompt[off : off + budget]
        bucket = steps.prefill_bucket(len(piece), self.ecfg)
        span_args = {}
        if self._hybrid:
            if self._scan_chunk:
                # chunks this piece runs through each recurrent layer's scan,
                # and those of them that hold only padding
                chunks = -(-bucket // self._scan_chunk)
                span_args[self._scan_arg] = chunks
                self.stats["linear_scan_chunks"] += chunks
                self.stats["linear_scan_pad_chunks"] += (
                    chunks - -(-len(piece) // self._scan_chunk))
            if self.cfg.is_window_stack:
                # a piece longer than the ring leaves only its last R positions
                # in the window layers' leaf (models/llama.py run_cached_layers)
                span_args.update(
                    window_layers=self.cfg.n_window_layers,
                    global_layers=self.cfg.n_global_layers,
                    ring_wrapped=int(len(piece) > self._cache["k_win"].shape[3]))
            elif off == 0:
                self.stats["linear_state_resets" if self._scan_chunk
                           else "conv_tail_resets"] += 1
            if self.cfg.n_expert_layers:
                # what the piece offers its expert blocks; how many land on
                # an expert held here is known only once the program has run
                span_args["moe_assignments"] = (
                    len(piece) * self.cfg.num_experts_per_tok * self.cfg.n_expert_layers)
        with self._sched.phase(
            "prefill", request_id=handle.request.request_id,
            prompt_tokens=len(piece), bucket=bucket, offset=off, **span_args,
        ) as ph:
            last_logits = self._prefill_piece(
                piece, slot, off, draft=draft, adapter_idx=st["adapter_idx"]
            )
            if wait:
                jax.block_until_ready(last_logits)
        self.stats["prefill_chunks"] += 1
        if wait:
            wall = ph.seconds
            self.stats["busy_s"] += wall
            if self._inflight or self._decode_active():
                self.stats["prefill_chunk_stall_s"] += wall
        else:
            st.setdefault("t_dispatch", ph.t_start)
        if draft:
            st["draft_off"] = off + len(piece)
            st["draft_chunks"] += 1
        else:
            st["off"] = off + len(piece)
            st["chunks"] += 1
            st["logits"] = last_logits
            # target pieces only, like prefill_tokens
            self.stats["prefill_bucket_tokens"] += bucket
            if self._bucket_is_grouped(bucket):
                self.stats["prefill_grouped_bucket_tokens"] += bucket
            if self._slot_prefill[slot] is not None:
                # interleaved mode: advance the frontier so concurrent
                # sweeps' garbage writes stay >= it (see _slot_prefill)
                self._slot_len[slot] = st["off"]
        return st["off"] >= n and (
            st["draft_off"] is None or st["draft_off"] >= n
        )

    def _bucket_is_grouped(self, bucket: int) -> bool:
        """Whether the prefill program of ``bucket`` tokens holds the grouped
        expert dispatch: decided by the shapes when the program is built
        (models/moe.py), asked again here by the same rule and kept."""
        if bucket not in self._grouped_buckets:
            from kserve_vllm_mini_tpu.models.moe import grouped_prefill

            self._grouped_buckets[bucket] = grouped_prefill(
                self.cfg, self.params["layers"], bucket, self._kernels)
        return self._grouped_buckets[bucket]

    def _advance_prefills(self, on_decision=None) -> None:
        """Advance the OLDEST in-progress chunked prefill by ONE piece
        this scheduler iteration, so decode sweeps interleave with a long
        prompt instead of stalling behind it (EngineConfig.prefill_chunk).
        When the final piece lands, the slot is activated — sampled and
        joined to the decode set — via _activate_slot. Head-of-line only:
        FIFO completion order matches the monolithic path's serial
        admissions."""
        if not self._prefill_fifo:
            return
        slot = self._prefill_fifo[0]
        st = self._slot_prefill[slot]
        if st is None or st["handle"].cancelled is not None:
            # cancelled mid-prefill: the cancel pass in _schedule_once
            # aborts it (and pops the fifo) — nothing to advance here
            return
        if on_decision is not None:
            # never reached in lockstep (chunked admission is gated off
            # there), published for the decision-stream convention — no
            # follower replay arm needed (kvmini: protocol-ok)
            on_decision(("prefill_chunk", st["handle"].request.request_id))
        if self._prefill_step(slot, st, self.ecfg.prefill_chunk):
            self._prefill_fifo.pop(0)
            self._slot_prefill[slot] = None
            if self._inflight:
                # the slot joins the decode active set: in-flight sweeps
                # were dispatched under the old set, and the global
                # _pending_steps would misplace its first decode write —
                # retire against settled state (the admission invariant)
                self.stats["pipeline_fallback_active_set"] += 1
                self._retire_all(on_decision)
            self._activate_slot(slot, st)

    # -- disaggregated prefill: handoff consumption (docs/DISAGGREGATION.md)

    def _consume_handoffs(self, on_decision=None) -> None:
        if self._disagg is not None:
            with self._sched.phase("handoff"):
                self._drain_handoffs(on_decision)

    def _drain_handoffs(self, on_decision=None) -> None:
        """Drain finished prefill-lane handoffs between sweeps: inject
        each staged stripe into its slot's cache region and activate the
        slot under the existing admission invariant (in-flight sweeps
        retire first — a newly active slot must never receive a stale
        token from a sweep dispatched before it joined). Tombstones and
        version mismatches degrade to colocated prefill; a routed slot
        whose handoff never arrives at all hits the HANDOFF_TIMEOUT_S
        last resort — no path leaves a client hanging."""
        from kserve_vllm_mini_tpu.runtime.disagg import (
            DENSE_HANDOFF_VERSION,
            DROPS_TO_DEGRADE,
            HANDOFF_TIMEOUT_S,
            HANDOFF_VERSION,
        )

        # version negotiation (docs/DISAGGREGATION.md): each layout
        # speaks exactly one payload format — paged consumes v2 block
        # tables, dense consumes v1 staged stripes. Anything else walks
        # the same degrade ladder as a drop.
        expected = HANDOFF_VERSION if self.paged else DENSE_HANDOFF_VERSION
        while True:
            ho = self._disagg.pop_ready()
            if ho is None:
                break
            slot = next(
                (i for i in range(self.ecfg.max_slots)
                 if self._slot_handoff[i] is not None
                 and self._slot_handoff[i]["handle"] is ho.handle),
                None,
            )
            if slot is None:
                # the slot was aborted (cancel/drain/fault recovery)
                # before the handoff landed: the payload is an orphan.
                # Paged orphans also return their quarantined blocks to
                # the pool — only now is it certain no lane write to
                # them is still in flight.
                self.stats["prefill_lane_busy_s"] += ho.busy_s
                self._reap_orphans(ho.handle)
                continue
            handle: RequestHandle = ho.handle
            if ho.dropped or ho.version != expected:
                # lost/injected-drop/stale-protocol handoff: count it,
                # climb the degrade ladder, and re-prefill colocated —
                # the request completes either way
                self.stats["kv_handoff_drops"] += 1
                self.stats["prefill_lane_busy_s"] += ho.busy_s
                self._disagg_drop_run += 1
                if self._disagg_drop_run >= DROPS_TO_DEGRADE:
                    self._disagg_degraded = True
                self._colocated_fallback(slot, on_decision)
                self._reap_orphans(handle)
                continue
            self._disagg_drop_run = 0
            if handle.cancelled is not None:
                # cancelled after the lane finished: the compute still
                # happened — account it before dropping the payload
                self.stats["prefill_lane_busy_s"] += ho.busy_s
                self._abort_handoff(slot, handle.cancelled)
                self._reap_orphans(handle)
                continue
            if self._inflight:
                # activation joins the decode set — retire against
                # settled state (the admission invariant)
                self.stats["pipeline_fallback_active_set"] += 1
                self._retire_all(on_decision)
            t_route = self._slot_handoff[slot]["t_route"]
            now = _now()
            wait = max(now - ho.t_enqueued, 0.0)
            self.stats["kv_handoffs"] += 1
            self.stats["kv_handoff_blocks"] += ho.n_blocks
            self.stats["kv_handoff_wait_s"] += wait
            self.stats["prefill_lane_busy_s"] += ho.busy_s
            self.stats["prefill_chunks"] += ho.chunks
            hstate = self._slot_handoff[slot]
            if self.paged:
                # v2 block-table handoff: the lane already wrote the KV
                # into this slot's pool blocks — install the table row
                # the route parked on scratch and register the prompt's
                # content keys now that the blocks hold real KV. ZERO
                # bytes of cache move here.
                self._block_table[slot] = hstate["row"]
                self._table_dev = None
                if self.ecfg.prefix_cache and hstate["keys"]:
                    self._paged_register_keys(
                        self._slot_blocks[slot][: len(hstate["keys"])],
                        hstate["keys"],
                    )
            else:
                # v1 dense staged stripe: one device-side inject copy,
                # measured so the A/B against v2 is a stats read
                self.stats["kv_handoff_bytes_copied"] += sum(
                    int(leaf.nbytes) for leaf in ho.kv.values()
                )
                with self._cache_lock:
                    self._cache = self._step("inject")(
                        self._cache, ho.kv, jnp.int32(slot)
                    )
            self._observe_phase("handoff", now - t_route)
            self._trace_span(
                handle, "server.handoff", t_route, now,
                attrs={"blocks": ho.n_blocks, "version": ho.version,
                       "wait_s": round(wait, 6),
                       "lane_busy_s": round(ho.busy_s, 6)},
            )
            self._slot_handoff[slot] = None
            st = {
                "handle": handle,
                "off": len(handle.request.prompt_tokens),
                "reused": ho.reused_prefix_tokens,
                "adapter_idx": 0,
                "chunks": ho.chunks,
                "draft_chunks": 0,
                "draft_off": None,
                "logits": jnp.asarray(ho.logits),
            }
            self._activate_slot(slot, st)
        # never-hang last resort: a routed slot whose handoff (payload OR
        # tombstone) never arrived — lane wedged past even its own flush
        # machinery — re-prefills colocated after the timeout. Never
        # reached in lockstep: multihost refuses disaggregated engines
        # outright (check_multihost_engine), so _disagg is None there
        # and _consume_handoffs returns before any clock read.
        now = _now()
        for slot in range(self.ecfg.max_slots):
            hstate = self._slot_handoff[slot]
            if hstate is None or now - hstate["t_route"] <= HANDOFF_TIMEOUT_S:
                continue
            self.stats["kv_handoff_drops"] += 1
            self._disagg_drop_run += 1
            if self._disagg_drop_run >= DROPS_TO_DEGRADE:
                self._disagg_degraded = True
            self._colocated_fallback(slot, on_decision)

    def _reap_orphans(self, handle: RequestHandle) -> None:
        """Return a handle's quarantined v2 blocks to the pool once its
        lane work is provably finished — the payload (or tombstone) has
        arrived, so no lane write to them can still be in flight. No-op
        for dense engines and handles with nothing quarantined."""
        if not self.paged:
            return
        blks = self._orphan_blocks.pop(id(handle), None)
        if blks:
            self._paged_release_blocks(blks)

    def _colocated_fallback(self, slot: int, on_decision=None) -> None:
        """Degrade-to-colocated (the handoff ladder's recovery step): the
        routed prompt's handoff was lost, so its prefill runs right here
        on the scheduler thread — the monolithic piece loop the colocated
        engine would have used — and the slot activates normally. The
        request never observes the drop beyond added latency."""
        hstate = self._slot_handoff[slot]
        handle: RequestHandle = hstate["handle"]
        if handle.cancelled is not None:
            self._abort_handoff(slot, handle.cancelled)
            return
        self._slot_handoff[slot] = None
        self.stats["disagg_colocated_fallbacks"] += 1
        if self._inflight:
            self.stats["pipeline_fallback_active_set"] += 1
            self._retire_all(on_decision)
        off = 0
        if self.paged:
            # the v2 route already allocated this slot's blocks and
            # parked the table row on scratch: re-install the row and
            # re-prefill from the reused frontier right here. If a
            # wedged (not dead) lane is still writing the same blocks,
            # both writers produce identical bytes from identical
            # inputs through the same executables — benign overlap.
            self._block_table[slot] = hstate["row"]
            self._table_dev = None
            off = hstate.get("reused", 0)
        st = {
            "handle": handle,
            "off": off,
            "reused": off,
            "adapter_idx": 0,
            "chunks": 0,
            "draft_chunks": 0,
            "draft_off": None,
            "logits": None,
        }
        while not self._prefill_step(slot, st, self.ecfg.max_prefill_len):
            pass
        if self.paged and self.ecfg.prefix_cache and hstate.get("keys"):
            # the route deferred key registration to consume; the
            # fallback prefill just wrote the real KV, so register here
            self._paged_register_keys(
                self._slot_blocks[slot][: len(hstate["keys"])],
                hstate["keys"],
            )
        self._activate_slot(slot, st)

    def _abort_handoff(self, slot: int, reason: str) -> None:
        """Finish a slot cancelled (or drained) while its prompt was on
        the prefill lane: no token was ever sampled, the stream ends with
        zero tokens, and the slot frees. The lane's payload, when it
        lands, is dropped as an orphan by the consume identity check."""
        handle = self._slot_req[slot]
        if self.paged and self._slot_blocks[slot]:
            # v2: the lane may still have writes in flight against this
            # slot's blocks — quarantine them out of the free pool until
            # the lane's payload (or tombstone) orphans at consume, else
            # a reallocation could race the lane's stores
            self._orphan_blocks[id(handle)] = self._slot_blocks[slot]
            self._slot_blocks[slot] = []
        handle.t_done = _now()
        handle.finish_reason = reason
        self._observe_phase("prefill", handle.t_done - handle.t_admit)
        self._trace_span(
            handle, "server.prefill", handle.t_admit, handle.t_done,
            ok=False, attrs={"cancelled": reason, "disagg": True},
        )
        handle.events.put(("done", {
            "finish_reason": reason,
            "tokens_out": 0,
            "truncated": handle.request.truncated,
            "truncated_tokens": handle.request.truncated_tokens,
        }))
        self.stats["requests_completed"] += 1
        self._release_slot(slot)

    def cancel(self, handle: RequestHandle, reason: str = "stop") -> None:
        """Finish ``handle``'s generation early (thread-safe; effective at
        the scheduler's next iteration). Tokens already emitted stand; the
        'done' event carries ``reason``. A still-queued handle is finished
        at admission instead of prefilling; a handle mid-chunked-prefill
        is aborted at the scheduler's next iteration (_abort_prefill)."""
        handle.cancelled = reason

    def _admit_one(self, handle: RequestHandle, behind: bool = False) -> None:
        """``behind``: a sweep is in flight and nothing refuses
        (_behind_refusal): the admission is dispatched behind it instead
        of after a drain (_admit_behind)."""
        req = handle.request
        with self._sched.phase(
            "admit", request_id=req.request_id,
            prompt_tokens=len(req.prompt_tokens), inflight=behind,
        ):
            self._admit(handle, behind)

    def _admit(self, handle: RequestHandle, behind: bool = False) -> None:
        req = handle.request
        if handle.cancelled is not None:
            # cancelled while queued: report done without spending a
            # prefill (no tokens were produced)
            handle.t_done = _now()
            handle.finish_reason = handle.cancelled
            self._observe_phase("queue", handle.t_done - handle.t_submit)
            self._trace_span(
                handle, "server.queue", handle.t_submit, handle.t_done,
                ok=False, attrs={"cancelled": handle.cancelled},
            )
            handle.events.put(("done", {
                "finish_reason": handle.cancelled,
                "tokens_out": 0,
            }))
            return
        # Deadline shed (docs/RESILIENCE.md). Lockstep-DISABLED: followers
        # replay this method and a wall-clock branch would diverge their
        # slot state from the primary's; multihost deadline sheds need a
        # published decision (v2).
        deadline_expired = (
            req.deadline_s is not None
            and not self._lockstep
            and _now() - handle.t_submit > req.deadline_s
        )
        if deadline_expired:
            # deadline expired while queued: shed WITHOUT spending a
            # prefill (docs/RESILIENCE.md) — the client's budget is gone
            # either way, and burning decode steps on it would push
            # every queued neighbor past its own deadline too
            handle.t_done = _now()
            handle.finish_reason = "shed"
            self._observe_phase("queue", handle.t_done - handle.t_submit)
            self._trace_span(
                handle, "server.queue", handle.t_submit, handle.t_done,
                ok=False, attrs={"shed": "deadline expired in queue"},
            )
            with self._res_lock:
                self._requests_shed += 1
            handle.events.put(("done", {
                "finish_reason": "shed",
                "tokens_out": 0,
                "error": (
                    f"deadline {req.deadline_s:.3f}s expired after "
                    f"{handle.t_done - handle.t_submit:.3f}s in queue"
                ),
            }))
            return
        handle.t_admit = _now()
        # queue phase: submit -> the scheduler picking the request up
        self._observe_phase("queue", handle.t_admit - handle.t_submit)
        self._trace_span(handle, "server.queue", handle.t_submit, handle.t_admit)
        if (
            self._disagg is not None
            and not self._disagg_degraded
            and not self._lockstep
            and len(req.prompt_tokens) >= self.ecfg.disagg_min_prompt
            and self._disagg.accepts()
        ):
            # disaggregated route (docs/DISAGGREGATION.md): occupy a slot
            # now (cancel/watchdog/drain all see the handle) and hand the
            # prompt to the prefill lane; _consume_handoffs injects the
            # staged KV and activates the slot when the handoff lands.
            # Backpressure/degrade fall through to the colocated path
            # below — a saturated or dead lane sheds work back to the
            # decode lane, it never queues requests unboundedly.
            slot = self._free.pop()
            self._slot_req[slot] = handle
            self._slot_len[slot] = 0
            meta = None
            if self.paged:
                # HANDOFF_VERSION=2 (docs/DISAGGREGATION.md): allocate
                # the slot's blocks from the SHARED pool right here on
                # the scheduler thread (prefix reuse + tier promotion
                # both settle now), but park the slot's table row on
                # scratch while the lane writes — decode sweeps dispatch
                # all S slots, and this len-0 slot's garbage writes must
                # land in scratch, not in blocks the lane is filling.
                # register=False: content keys are registered at consume,
                # after the KV actually exists — registering now would
                # let a later admission reuse blocks not yet written.
                reused = self._paged_admit_blocks(slot, req, register=False)
                blks = list(self._slot_blocks[slot])
                row = np.full((self._maxb,), self._scratch_block, np.int32)
                row[: len(blks)] = blks
                self._block_table[slot] = self._scratch_block
                self._table_dev = None
                meta = {
                    "row": row,
                    "off": reused,
                    "keys": list(req._plan_cache[1]),
                }
            self._slot_handoff[slot] = {
                "handle": handle, "t_route": handle.t_admit,
                "reused": (meta or {}).get("off", 0),
                "row": None if meta is None else meta["row"],
                "keys": None if meta is None else meta["keys"],
            }
            self._disagg.submit(handle, meta)
            return
        slot, reused = self._pop_slot_for(req.prompt_tokens)
        if self.paged:
            # fit is the caller's job: _schedule_once defers a non-fitting
            # head-of-line request before calling here, and the idle path
            # only runs with zero active slots, where the whole pool is
            # free and submit()'s never-fit rejection guarantees the fit.
            # _paged_alloc pops _free_blocks / evicts retained and would
            # fail loudly on a (multihost-divergence) violation.
            # Block-level prefix sharing may cover a prompt prefix; the
            # prefill below starts after it, exactly like the dense APC.
            reused = self._paged_admit_blocks(slot, req)
        adapter_idx = 0
        # multihost submit refuses adapter requests outright, so in lockstep
        # this branch is dead on both sides (kvmini: protocol-ok)
        if req.adapter is not None:
            if req.adapter not in self._lora_names:
                # the registry is also checked at submit and unload refuses
                # while requests are queued — but if the name still vanished
                # (defensive), failing beats silently serving the base model
                if self.paged:
                    self._paged_release(slot)
                self._free.append(slot)
                handle.events.put(("done", {
                    "finish_reason": "error",
                    "error": f"adapter {req.adapter!r} was unloaded before "
                             "this request could be admitted",
                }))
                return
            adapter_idx = self._lora_names[req.adapter]
        n = len(req.prompt_tokens)
        st = {
            "handle": handle,
            "off": reused,          # target-prefill frontier (next position)
            "reused": reused,
            "adapter_idx": adapter_idx,
            "chunks": 0,            # target pieces dispatched
            "draft_chunks": 0,      # drafter shadow pieces dispatched
            # None = no drafter shadow prefill; 0 = pending from offset 0
            # (the drafter cache never carries a reused prefix —
            # prefix_cache and drafters are mutually exclusive)
            "draft_off": (
                0 if self._drafter_params is not None
                and self.ecfg.spec_tokens > 0 else None
            ),
            "logits": None,         # last target piece's [V] f32 logits
        }
        chunk = self.ecfg.prefill_chunk
        if chunk is not None and not self._lockstep and n - reused > chunk:
            # interleaved chunked prefill: occupy the slot now, advance
            # one piece per scheduler iteration (_advance_prefills) so
            # decode sweeps ride between pieces; sampling happens when
            # the final piece lands (_activate_slot)
            self._slot_req[slot] = handle
            self._slot_len[slot] = reused  # prefill frontier (see init)
            if self.ecfg.prefix_cache and not self.paged:
                # rows past the reused prefix are being overwritten with
                # THIS prompt's KV: the old occupant's retained match
                # must not outlive its rows (an abort mid-prefill re-
                # retains the new prompt up to the frontier instead)
                self._retained[slot] = list(req.prompt_tokens[:reused])
            self._slot_prefill[slot] = st
            self._prefill_fifo.append(slot)
            return
        # monolithic admission: every piece back-to-back (budget =
        # max_prefill_len), then the drafter's shadow pieces, then sample
        if behind:
            self._admit_behind(slot, st)
            return
        while not self._prefill_step(slot, st, self.ecfg.max_prefill_len):
            pass
        self._activate_slot(slot, st)

    def _activate_slot(self, slot: int, st: dict) -> None:
        """Prefill is complete: sample the first token from the final
        piece's last-position logits and join the slot to the decode set.
        The shared tail of monolithic admission (_admit_one) and chunked-
        prefill completion (_advance_prefills). Callers settle the
        in-flight pipeline first: _schedule_once retires before admitting
        (unless the admission goes behind the sweep, _admit_behind) and
        _advance_prefills retires before activating."""
        with self._sched.phase("first_token") as ph:
            self._sample_first(slot, st)
        # the phase holds jit_first's device time (the readback waits for
        # it), which is what makes it busy time
        self.stats["busy_s"] += ph.seconds

    def _dispatch_first(self, st: dict, mask, constrained: bool):
        """Sample the first token from the last piece's logits, on the
        device: (token, its logprob, top ids, top logprobs). Consumes one
        rng split, like every decode dispatch."""
        req = st["handle"].request
        self._rng, sub = jax.random.split(self._rng)
        return self._step("first")(
            st["logits"], sub,
            jnp.float32(req.temperature),
            jnp.int32(req.top_k),
            jnp.float32(req.top_p),
            mask,
            jnp.bool_(constrained),
        )

    def _occupy_slot(self, slot: int, st: dict, machine) -> None:
        """The slot's host state once its prompt is in the cache: what an
        admission knows without the first token's value."""
        req = st["handle"].request
        self._slot_req[slot] = st["handle"]
        self._slot_len[slot] = len(req.prompt_tokens)
        self._slot_remaining[slot] = req.max_new_tokens - 1
        self._slot_machine[slot] = machine
        self._slot_adapter[slot] = st["adapter_idx"]
        self._adapter_ids_dev = None
        self._retained[slot] = []
        self._sampling_arrays = None  # slot population changed

    def _first_token_out(self, slot: int, st: dict, first_id: int, first_lp,
                         first_tids, first_tlps, t_read: Optional[float] = None) -> None:
        """The first token is on the host (since ``t_read``; now, if not
        given): counters, the prefill phase, the stream's first event and
        the slot's token lists."""
        handle: RequestHandle = st["handle"]
        req = handle.request
        n = len(req.prompt_tokens)
        reused = st["reused"]
        self.stats["prefills"] += 1
        # only tokens actually prefilled: reused prefix tokens are counted
        # in prefix_tokens_reused, not here (throughput math stays honest)
        self.stats["prefill_tokens"] += n - reused

        handle.t_first_token = _now() if t_read is None else t_read
        # prefill phase: admission -> first sampled token (chunked prefill
        # and the drafter's shadow prefill included; for interleaved
        # chunking this span also contains the decode sweeps that rode
        # between pieces, for an admission behind a sweep the rest of that
        # sweep — the request's real TTFT anatomy)
        self._observe_phase("prefill", handle.t_first_token - handle.t_admit)
        self._trace_span(
            handle, "server.prefill", handle.t_admit, handle.t_first_token,
            attrs={"prompt_tokens": n, "reused_prefix_tokens": reused,
                   "slot": slot, "prefill_chunks": st["chunks"]},
        )
        handle.tokens.append(first_id)
        lp_info = None
        if req.logprobs:
            lp_info = (
                float(first_lp),
                list(zip(np.asarray(first_tids).tolist(),
                         np.asarray(first_tlps).tolist())),
            )
            handle.logprobs.append(lp_info)
        handle.events.put(("token", first_id, handle.t_first_token, lp_info))
        self._last_tokens[slot] = first_id
        # rows 0..n-1 now hold the prompt's KV; emitted tokens append as
        # their KV lands (fed on the next step)
        self._slot_tokens[slot] = list(req.prompt_tokens) + [first_id]

    def _finish_at_first(self, slot: int, first_id: int) -> bool:
        """A first token that ends the request (EOS, a budget of one)
        finishes the slot; True if it did."""
        req = self._slot_req[slot].request
        hit_eos = req.eos_id is not None and first_id == req.eos_id
        if self._slot_remaining[slot] <= 0 or hit_eos:
            self._finish_slot(slot, "stop" if hit_eos else "length")
            return True
        return False

    def _sample_first(self, slot: int, st: dict) -> None:
        req = st["handle"].request
        # first token: sampled from the prompt's last-position logits,
        # grammar-masked when the request is constrained
        # multihost submit refuses constrained requests (req_payload has no
        # constraint field), dead on both sides there (kvmini: protocol-ok)
        machine = req.constraint
        if machine is not None:
            # budget = tokens the slot can actually emit: the grammar must
            # close before max_new_tokens AND before the KV window fills,
            # else out_of_space cuts the structure mid-emission
            budget = min(req.max_new_tokens,
                         self.ecfg.max_seq_len - 1 - len(req.prompt_tokens))
            mask = self._constraint_mask(machine, budget)
        else:
            mask = np.zeros(((self.cfg.vocab_size + 7) // 8,), dtype=np.uint8)
        first_tok, first_lp, first_tids, first_tlps = self._dispatch_first(
            st, jnp.asarray(mask), machine is not None)
        first_id = int(first_tok)
        self._first_token_out(slot, st, first_id, first_lp, first_tids, first_tlps)
        self._occupy_slot(slot, st, machine)
        self._tokens_dev = None  # host mutation: device token carry is stale
        # penalty state: clear the previous occupant's generated-token
        # counts and record the first token (it IS a generated token — the
        # next step's penalty must already see it)
        self._counts = self._step("reset")(
            self._counts, jnp.int32(slot), first_tok
        )
        if machine is not None:
            machine.advance_token(first_id)
            if machine.done:
                self._finish_slot(slot, "stop")
                return
        self._finish_at_first(slot, first_id)

    # -- admission behind the sweep in flight (docs/DECODE_PIPELINE.md) -----

    def _behind_refusal(self, handle: RequestHandle) -> Optional[str]:
        """Why ``handle`` cannot be admitted behind the sweep in flight
        (one of ADMIT_DRAIN_REASONS), or None if it can. It can when the
        next sweep may be dispatched ahead with its slot in it
        (_pipeline_eligible) and the admission is dense, colocated,
        monolithic and unconstrained: everything its dispatches need is
        known here, and every cache leaf it writes is safe behind a sweep
        that was dispatched under the slot's old occupant. Everything
        else drains the pipeline first, as the scheduler always did."""
        req = handle.request
        if self._lockstep:
            return "lockstep"   # followers replay settled decisions
        built = self._behind_refused_as_built()
        if built is not None:
            return built
        if req.constraint is not None:
            return "constrained"    # the mask needs the token on the host
        ok, _ = self._pipeline_eligible(self._decode_active())
        if (not ok or self.ecfg.max_seq_len - 1 - len(req.prompt_tokens)
                < self._full_chunk()):
            return "sweep"      # the next sweep cannot go ahead of a retire
        return None

    def _behind_refused_as_built(self) -> Optional[str]:
        """The refusals that follow from how the engine was built: no
        admission of such an engine ever goes behind a sweep."""
        if self.paged:
            return "paged"      # the block table is rebuilt on the host
        if self._disagg is not None:
            return "disagg"
        if self._drafter_params is not None:
            return "drafter"    # its shadow prefill and rounds are synchronous
        if self._lora is not None:
            return "lora"
        if self.ecfg.prefix_cache:
            return "prefix_cache"   # a sweep in flight may write a retained row
        if self.ecfg.prefill_chunk is not None:
            return "prefill_chunk"
        return None

    def _warm_behind(self) -> None:
        """Compile what only an admission behind a sweep runs (the count
        reset that also merges the token into the decode carry) before the
        first request is looked at (the scheduler thread's first act), not
        inside somebody's first such admission: every other program of the
        path is the drained admission's own. Run on a free slot's count
        row, which every admission resets first."""
        if (not self.ecfg.decode_pipeline or not self._free
                or self._behind_refused_as_built() is not None):
            return
        self._counts, _ = self._step("reset", True)(
            self._counts, jnp.int32(self._free[-1]), jnp.asarray(0, jnp.int32),
            jnp.asarray(np.zeros((self.ecfg.max_slots,), dtype=np.int32)),
        )

    def _admit_behind(self, slot: int, st: dict) -> None:
        """Monolithic admission without a drain: the prompt's pieces, the
        first token's sampler and the slot's count reset are dispatched
        behind the sweep in flight (the device orders them after it by the
        cache and count dependencies), the token goes into the decode
        carry ON the device, and the slot joins the decode set at once so
        that _sweep_phase dispatches the next sweep with it. The host sees
        the token at the end of this iteration (_read_first_tokens)."""
        handle: RequestHandle = st["handle"]
        # the carry the next sweep would feed, before this slot joins
        carry = self._feed_tokens(self._decode_active())
        while not self._prefill_step(
                slot, st, self.ecfg.max_prefill_len, wait=False):
            pass
        if self._no_mask_dev is None:      # an upload, kept: nothing compiles
            self._no_mask_dev = jnp.asarray(
                np.zeros(((self.cfg.vocab_size + 7) // 8,), dtype=np.uint8))
        out = self._dispatch_first(st, self._no_mask_dev, False)
        self._counts, self._tokens_dev = self._step("reset", True)(
            self._counts, jnp.int32(slot), out[0], carry
        )
        self._tokens_dev_slots = self._tokens_dev_slots | {slot}
        self._occupy_slot(slot, st, None)
        self._slot_tokens[slot] = list(handle.request.prompt_tokens)
        # none of the steps in flight are this occupant's
        self._slot_behind[slot] = self._pending_steps
        self._first_pending.append({"slot": slot, "st": st, "out": out})
        self.stats["admissions_inflight"] += 1

    def _read_first_tokens(self) -> None:
        """Read back the first tokens of the callers admitted behind a
        sweep this iteration, one transfer a caller in the order of their
        dispatch (each returns when that caller's pieces have run, while
        the device goes on with the next caller's: nobody's token waits for
        another's prompt), and do with each what _sample_first does once it
        has the value. The wait is the ``prefill`` phase's: it is the
        prompt's pieces the host waits on. If the next sweep went out
        behind them the decoding slots' gap ends where the last readback
        does (the device has begun that sweep); after a drain the next
        dispatch ends it, as ever."""
        pending, self._first_pending = self._first_pending, []
        for left, p in zip(range(len(pending), 0, -1), pending):
            slot, st = p["slot"], p["st"]
            handle: RequestHandle = st["handle"]
            request_id = handle.request.request_id
            with self._sched.phase("prefill", first_tokens=left) as ph:
                # (the token alone unless the caller asked for logprobs)
                tok, lp, tids, tlps = jax.device_get(
                    p["out"] if handle.request.logprobs
                    else (p["out"][0], None, None, None))
            # what the blocking waits of a drained admission feed
            # (_prefill_step, _activate_slot): the device ran the pieces and
            # the sampler from the later of their dispatch and the end of
            # what it ran before them, in front of whoever was decoding
            wall = ph.t_end - max(st["t_dispatch"], self._t_last_ready)
            self.stats["busy_s"] += wall
            self.stats["prefill_chunk_stall_s"] += wall
            self._t_last_ready = ph.t_end
            if left == 1 and self._inflight:
                # (the callers before this one entered their first_token
                # inside the gap; this one's follows its end)
                self._sched.gap_close(admitted=[request_id])
            with self._sched.phase("first_token", request_id=request_id):
                if handle.cancelled is not None:
                    # cancelled between the dispatch and this readback: the
                    # token is dropped, as a sweep's are (_retire_one)
                    self._abort_prefill(slot, handle.cancelled)
                    continue
                first_id = int(tok)
                self._first_token_out(slot, st, first_id, lp, tids, tlps, ph.t_end)
                self._finish_at_first(slot, first_id)
        if pending:
            self._drop_idle_inflight()

    def _abort_prefill(self, slot: int, reason: str) -> None:
        """Finish a slot that was cancelled (or drained) MID-chunked-
        prefill: no token was ever sampled, so the whole occupancy is the
        prefill phase and the stream ends with zero tokens. With the
        dense APC on, the rows already written hold THIS prompt's KV up
        to the frontier — retain that (exact) prefix rather than the old
        occupant's overwritten one."""
        handle = self._slot_req[slot]
        st = self._slot_prefill[slot]
        handle.t_done = _now()
        handle.finish_reason = reason
        self._observe_phase("prefill", handle.t_done - handle.t_admit)
        self._trace_span(
            handle, "server.prefill", handle.t_admit, handle.t_done,
            ok=False,
            attrs={"cancelled": reason,
                   "prefill_chunks": st["chunks"] if st else 0},
        )
        handle.events.put(("done", {
            "finish_reason": reason,
            "tokens_out": 0,
            "truncated": handle.request.truncated,
            "truncated_tokens": handle.request.truncated_tokens,
        }))
        self.stats["requests_completed"] += 1
        if self.ecfg.prefix_cache and not self.paged:
            self._retained[slot] = list(
                handle.request.prompt_tokens[: self._slot_len[slot]]
            )
        self._release_slot(slot)

    def _decode_active(self) -> list[int]:
        """Slots with a live request that is PAST prefill — the set decode
        sweeps cover. A slot mid-chunked-prefill (or awaiting its prefill
        lane handoff) is occupied but excluded until _activate_slot
        samples its first token."""
        return [
            i for i in range(self.ecfg.max_slots)
            if self._slot_req[i] is not None
            and self._slot_prefill[i] is None
            and self._slot_handoff[i] is None
        ]

    def _get_sampling_arrays(self) -> tuple:
        if self._sampling_arrays is None:
            S = self.ecfg.max_slots
            # the predicate filter_logits computes on the device, from the
            # same rows (a free slot's neutral row never asks)
            self._sampler_filters = any(
                h is not None and h.request.temperature > 0
                and (h.request.top_k > 0 or h.request.top_p < 1.0)
                for h in self._slot_req
            )
            self._sampling_arrays = (
                jnp.asarray(
                    [self._slot_req[i].request.temperature if self._slot_req[i] else 0.0
                     for i in range(S)], jnp.float32),
                jnp.asarray(
                    [self._slot_req[i].request.top_k if self._slot_req[i] else 0
                     for i in range(S)], jnp.int32),
                jnp.asarray(
                    [self._slot_req[i].request.top_p if self._slot_req[i] else 1.0
                     for i in range(S)], jnp.float32),
                jnp.asarray(
                    [self._slot_req[i].request.presence_penalty
                     if self._slot_req[i] else 0.0
                     for i in range(S)], jnp.float32),
                jnp.asarray(
                    [self._slot_req[i].request.frequency_penalty
                     if self._slot_req[i] else 0.0
                     for i in range(S)], jnp.float32),
            )
        return self._sampling_arrays

    def _finish_slot(self, slot: int, reason: str) -> None:
        handle = self._slot_req[slot]
        if handle is not None:
            handle.t_done = _now()
            handle.finish_reason = reason
            # decode phase: first token -> done (a first-token-only request
            # records a zero-length decode span — the phase still existed)
            self._observe_phase("decode", handle.t_done - handle.t_first_token)
            self._trace_span(
                handle, "server.decode", handle.t_first_token, handle.t_done,
                ok=reason in ("stop", "length"),
                attrs={"tokens_out": len(handle.tokens),
                       "finish_reason": reason},
            )
            if handle.cancelled is not None:
                # cancellation as its own zero-length marker span: the
                # joined trace shows WHEN the cancel landed, not just that
                # the decode span ended early
                self._trace_span(
                    handle, "server.cancel", handle.t_done, handle.t_done,
                    ok=False, attrs={"reason": handle.cancelled},
                )
            handle.events.put(("done", {
                "finish_reason": reason,
                "tokens_out": len(handle.tokens),
                "server_ttft_ms": handle.server_ttft_ms,
                "truncated": handle.request.truncated,
                "truncated_tokens": handle.request.truncated_tokens,
            }))
            self.stats["requests_completed"] += 1
            # admit->done service EMA: the admission estimate's denominator
            # (estimate_wait_s; docs/RESILIENCE.md deadline-aware shedding)
            if handle.t_admit:
                span = max(handle.t_done - handle.t_admit, 0.0)
                with self._res_lock:
                    self._service_ema_s = (
                        span if self._service_ema_s == 0.0
                        else 0.8 * self._service_ema_s + 0.2 * span
                    )
        if self.ecfg.prefix_cache and not self.paged:
            # dense slot-affinity APC: retain exactly the tokens whose KV
            # is WRITTEN (the last emitted token was never fed, so trim to
            # slot_len rows). Paged retention is block-level, inside
            # _paged_release.
            self._retained[slot] = self._slot_tokens[slot][: self._slot_len[slot]]
        self._release_slot(slot)

    def _count_decode_kv_blocks(self, lengths: np.ndarray, steps: int) -> None:
        """Book the key/value blocks that ``steps`` fused decode steps from
        ``lengths`` fetch through the dense decode kernel, by the kernel's
        own block rule; nothing where they take the eager read."""
        # kvmini: thread-ok — written once in start(), before this thread exists
        blk, by_kind = self._dense_decode_blk, self._window_decode_blks
        if blk is None and by_kind is None:
            return
        # (pallas is loaded only where a step holds the kernel)
        from kserve_vllm_mini_tpu.ops.paged_attention import live_block_range

        seq = self.ecfg.max_seq_len
        qpos = np.minimum(
            lengths[None, :] + np.arange(steps, dtype=np.int32)[:, None], seq - 1
        )
        if by_kind is not None:
            # Two leaves, each read by the kernel's rule at its own length: a
            # global layer's stripe up to the position, a window layer's ring
            # up to min(position, R - 1). Booked a LAYER of each kind, so the
            # totals weigh the kinds as a step reads them.
            ring = self._cache["k_win"].shape[3]
            for kind, n, span, reach, b in (
                    ("global", self.cfg.n_global_layers, seq, qpos, by_kind[0]),
                    ("window", self.cfg.n_window_layers, ring,
                     np.minimum(qpos, ring - 1), by_kind[1])):
                fetched = n * int((reach // b + 1).sum())
                total = n * qpos.size * (span // b)
                self.stats[f"decode_kv_blocks_fetched_{kind}"] += fetched
                self.stats[f"decode_kv_blocks_{kind}"] += total
                self.stats["decode_kv_blocks_fetched"] += fetched
                self.stats["decode_kv_blocks"] += total
            return
        first, last = live_block_range(qpos, self.cfg.sliding_window, blk)
        self.stats["decode_kv_blocks_fetched"] += int((last - first + 1).sum())
        self.stats["decode_kv_blocks"] += qpos.size * (seq // blk)

    def _release_slot(self, slot: int) -> None:
        """Slot-release bookkeeping shared by _finish_slot, engine-fault
        recovery, and the shutdown drain — ONE copy of the invariants so
        the rarely-exercised fault/drain paths can never drift from the
        normal finish path and leak a slot or block. Resets to the base
        adapter because the all-slots sweep still computes this slot's
        row, and a stale adapter id would gather a real adapter's factors
        for discarded garbage."""
        self._slot_req[slot] = None
        self._slot_machine[slot] = None
        if self._slot_prefill[slot] is not None:
            # releasing a slot mid-chunked-prefill (abort, fault recovery,
            # drain): drop the advancement state with it
            self._slot_prefill[slot] = None
            if slot in self._prefill_fifo:
                self._prefill_fifo.remove(slot)
        # releasing a slot mid-lane-handoff: the payload, when it lands,
        # is dropped by the consume identity check (orphan)
        self._slot_handoff[slot] = None
        if self.paged:
            self._paged_release(slot)
        self._slot_adapter[slot] = 0
        self._adapter_ids_dev = None
        # a free slot parks on the first row past what it retains for the
        # prefix cache (row 0 without one): the all-slots sweep writes its
        # discarded KV there, and the dense decode kernel fetches only the
        # blocks that reach that row instead of the slot's stale length
        self._slot_len[slot] = len(self._retained[slot])
        self._slot_behind[slot] = 0
        self._free.append(slot)
        self._sampling_arrays = None  # slot population changed

    def _emit_token(self, slot: int, tok: int, now: float, lp_info=None) -> bool:
        """Record one generated token for a live slot: cache-length/stat
        bookkeeping, stream event, constraint-automaton advance, and finish
        handling (EOS / budget / cache space / grammar completion). Returns
        True if the slot finished. The single state machine both the plain
        and speculative sweeps share."""
        handle = self._slot_req[slot]
        req = handle.request
        self._slot_len[slot] += 1      # the fed token is now in cache
        self._last_tokens[slot] = tok
        self._slot_tokens[slot].append(tok)
        handle.tokens.append(tok)
        if lp_info is not None and req.logprobs:
            handle.logprobs.append(lp_info)
        handle.events.put(
            ("token", tok, now, lp_info if req.logprobs else None)
        )
        self.stats["decode_tokens"] += 1
        self._slot_remaining[slot] -= 1
        machine = self._slot_machine[slot]
        if machine is not None:
            machine.advance_token(tok)
            if machine.done:
                self._finish_slot(slot, "stop")
                return True
        hit_eos = req.eos_id is not None and tok == req.eos_id
        out_of_space = self._slot_len[slot] + 1 >= self.ecfg.max_seq_len
        if self._slot_remaining[slot] <= 0 or hit_eos or out_of_space:
            self._finish_slot(slot, "stop" if hit_eos else "length")
            return True
        return False

    def _spec_partition(self, active: list[int]) -> tuple[list[int], list[int]]:
        """Per-slot speculative gating: split the active slots into
        (spec, plain). Spec slots run the fused rejection-sampling round
        (build_spec_step_sampled) — greedy AND sampled requests both
        qualify: rejection sampling preserves sampled output
        distributions exactly, and temperature-0 rows degenerate to the
        exact argmax accept rule (bit-identical to plain greedy decode).
        Penalized slots (the fused round carries no count table),
        constrained slots (fresh mask per token), and logprob slots
        (per-token distributions the verify doesn't produce) go to the
        plain sweep. One ineligible request no longer silently degrades
        every speculating neighbor (VERDICT round-3 weak #2).

        Cache-room caveat: the fused spec kernels write k positions into
        EVERY slot's cache region — including plain and free slots, whose
        results are discarded. Writes at >= slot_len are overwritten before
        they can be attended (the padding invariant), but a slot within k
        of its cache end would have the write CLAMPED backwards onto real
        KV. So if ANY active slot lacks k of headroom, speculation skips
        this sweep entirely (transient — such a slot is about to finish)."""
        k = self.ecfg.spec_tokens
        if k <= 0 or self._drafter_params is None:
            return [], active
        if any(self._slot_len[i] + k >= self.ecfg.max_seq_len for i in active):
            return [], active
        spec = [i for i in active if self._spec_capable(i)]
        if not spec:
            return [], active
        rest = [i for i in active if i not in spec]
        return spec, rest

    def _spec_capable(self, i: int) -> bool:
        """STATIC per-request spec eligibility (fixed for a slot's whole
        occupancy — unlike _spec_partition's transient cache-headroom
        gate). Sampled requests speculate too (rejection sampling keeps
        their output distribution exact; greedy rows degenerate to the
        exact-match rule). Penalties need the per-step count table the
        fused round doesn't carry; constrained slots need a fresh mask
        per token; logprob slots need per-token distributions the verify
        doesn't produce; adapted slots can't speculate — the drafter
        proposes from base weights (defensive: lora+drafter is rejected
        at init)."""
        req = self._slot_req[i].request
        return (
            req.presence_penalty == 0.0
            and req.frequency_penalty == 0.0
            and self._slot_machine[i] is None
            and not req.logprobs
            and self._slot_adapter[i] == 0
        )

    def _spec_sweep(self, active: list[int]) -> None:
        """One fused speculative round: drafter proposes k-1 tokens, target
        verifies in a single T=k forward, host emits the accepted prefix plus
        the target's bonus token. Rejected positions leave garbage KV beyond
        the new length in both caches; it is overwritten before it can ever
        be attended (the same overwrite-before-attend invariant that covers
        prompt padding)."""
        k = self.ecfg.spec_tokens
        spec = self._step("spec")
        tokens = jnp.asarray(self._last_tokens, dtype=jnp.int32)
        lengths = jnp.asarray(self._slot_len, dtype=jnp.int32)
        temps, topks, topps, _pres, _freqs = self._get_sampling_arrays()
        self._rng, sub = jax.random.split(self._rng)
        # dispatch, readback and emission are the same child phases as on
        # the plain path; what is left to spec_sweep itself is the preparation
        with self._sched.phase("decode_dispatch") as ph:
            with self._cache_lock:
                self._cache, self._dcache, emit = spec(
                    self.params, self._cache,
                    self._drafter_params, self._dcache,
                    tokens, lengths, temps, topks, topps, sub,
                )
        t0 = ph.t_start
        self._sync_inflight = True
        with self._sched.phase("decode_retire") as ph:
            # one transfer for the whole [S, k] block (same rationale as
            # decode); a spec round is synchronous  # kvmini: sync-ok
            emit_host = np.asarray(jax.device_get(emit))
            self._sync_inflight = False
        now = ph.t_end
        self._sched.gap_open()
        self.stats["busy_s"] += now - t0
        self._note_sweep(t0, now)
        self.stats["spec_rounds"] += 1
        self.stats["spec_proposed"] += (k - 1) * len(active)
        with self._sched.phase("emit") as ph:
            for i in active:
                n_emitted = 0
                for j in range(k):
                    tok = int(emit_host[i, j])
                    if tok < 0:
                        break
                    n_emitted += 1
                    if self._emit_token(i, tok, now):
                        break
                # accepted drafts = emitted minus the bonus token
                self.stats["spec_accepted"] += max(n_emitted - 1, 0)
        self._observe_phase("emit", ph.seconds)
        self._sched.gap_waiting(len(self._decode_active()))
        self._trace_engine_span(
            "engine.decode.window", t0, now,
            attrs={"chunk": k, "slots": len(active), "mode": "spec"},
        )
        # spec emission advanced _last_tokens host-side; the device carry
        # (if any) predates it, so the next plain dispatch must rebuild
        self._tokens_dev = None

    def _decode_sweep(self) -> None:
        """One SYNCHRONOUS dispatch->readback->emit sweep (the seed loop's
        shape). The pipelined steady state goes through _sweep_phase
        instead; this remains the fallback for spec partitions and
        grammar-constrained slots, and the follower replay target for the
        ('sweep',) decision."""
        active = self._decode_active()
        if not active:
            return
        spec_slots, plain_slots = self._spec_partition(active)
        if spec_slots:
            with self._sched.phase("spec_sweep"):
                self._spec_sweep(spec_slots)
        if plain_slots:
            constrained = [
                i for i in plain_slots if self._slot_machine[i] is not None
            ]
            if constrained:
                with self._sched.phase("masked_sweep"):
                    self._masked_sweep(plain_slots, constrained)
            else:
                self._dispatch_plain(plain_slots)
                self._retire_one()

    # -- double-buffered decode pipeline (docs/DECODE_PIPELINE.md) ---------

    def _feed_tokens(self, active: list[int]) -> jnp.ndarray:
        """Last-sampled tokens for the next decode dispatch over ``active``.
        In steady state this is the previous sweep's ON-DEVICE carry, so no
        host->device transfer happens per sweep. The carry is only usable
        when every slot being fed emitted through the sweep that produced
        it (_tokens_dev_slots): a slot outside that set — a spec slot whose
        fused round was skipped this iteration, say — holds a discarded
        garbage row, and feeding it would corrupt that slot's context. Any
        other case (host mutation invalidated the carry, new slot in the
        mix) rebuilds from _last_tokens, which the emit path keeps
        authoritative for all S slots."""
        if (
            self._tokens_dev is not None
            and self._tokens_dev_slots.issuperset(active)
        ):
            return self._tokens_dev
        self._tokens_dev = jnp.asarray(self._last_tokens, dtype=jnp.int32)
        self._tokens_dev_slots = frozenset(range(self.ecfg.max_slots))
        return self._tokens_dev

    def _live_mask(self, active: list[int], steps: int) -> jnp.ndarray:
        """[S] bool for a hybrid stack's decode dispatch: True for the
        slots in ``active``. A slot that is parked, or between two pieces
        of its prefill, is False and keeps its recurrent state through the
        sweep. Books the slot-steps the sweep moves state for."""
        S = self.ecfg.max_slots
        self.stats["linear_state_slot_steps"] += S * steps
        self.stats["linear_state_held_slot_steps"] += (S - len(active)) * steps
        key = tuple(active)
        if self._live_dev is None or self._live_dev[0] != key:
            mask = np.zeros((S,), dtype=bool)
            mask[active] = True
            self._live_dev = (key, jnp.asarray(mask))
        return self._live_dev[1]

    def _window(self, active: list[int]) -> int:
        """Positions left in the tightest active slot's cache window past
        those the in-flight sweeps have already claimed of it (a slot
        admitted behind a sweep has none of that sweep's: _slot_behind)."""
        return min(
            self.ecfg.max_seq_len - 1 - self._slot_len[i] + self._slot_behind[i]
            for i in active
        ) - self._pending_steps

    def _full_chunk(self) -> int:
        """decode_chunk rounded down to a power of two: what a sweep fuses
        when no cache window clamps it."""
        return 1 << (max(1, self.ecfg.decode_chunk).bit_length() - 1)

    def _chunk_for(self, active: list[int]) -> int:
        """Fused-step count for the next plain dispatch: decode_chunk
        clamped into every active slot's REMAINING cache window (minus
        positions in-flight sweeps have already claimed), rounded down to
        a power of two so at most log2(decode_chunk)+1 scan variants ever
        compile. Requests finishing mid-chunk surplus-discard on the host
        — shrinking instead would recompile per remaining-budget value."""
        chunk = max(1, min(self.ecfg.decode_chunk, self._window(active)))
        return 1 << (chunk.bit_length() - 1)

    def _pipeline_eligible(self, active: list[int]) -> tuple[bool, Optional[str]]:
        """Whether the next sweep may be dispatched ahead (before the
        previous one retires). The fallback-to-synchronous conditions,
        each pinned by a test (tests/test_decode_pipeline.py):

        - ``constrained``: a grammar-masked slot's next mask depends on the
          byte just emitted — the host must see sweep N before building
          sweep N+1's operands.
        - ``spec``: speculative rounds interleave drafter/target dispatches
          and emit a data-dependent number of tokens per round; the plain
          dispatch-ahead carry doesn't model them.
        - ``headroom``: the dispatched-ahead sweep must stay inside every
          active slot's cache window AND use the same chunk size the
          synchronous loop would pick — otherwise sampled streams diverge
          (different scan length => different per-step rng folds) and
          clamped writes could back onto real KV. Requiring a full
          decode_chunk of window past the in-flight positions guarantees
          both.

        The fourth condition — ``active_set`` (an admission landing
        mid-flight that cannot go behind the sweep, _behind_refusal) — is
        enforced by _schedule_once retiring all in-flight sweeps before
        such an admission."""
        if not self.ecfg.decode_pipeline or not active:
            return False, None
        if any(self._slot_machine[i] is not None for i in active):
            return False, "constrained"
        # STATIC spec capability, deliberately NOT _spec_partition: the
        # partition's transient cache-headroom gate can flip spec back ON
        # when a near-window-end slot finishes, and a plain sweep already
        # dispatched ahead would then replace the spec round the
        # synchronous loop runs at that index (rejection sampling consumes
        # rng differently — sampled streams would diverge). A statically
        # capable slot therefore pins the engine synchronous for its
        # whole residency.
        if (
            self.ecfg.spec_tokens > 0
            and self._drafter_params is not None
            and any(self._spec_capable(i) for i in active)
        ):
            return False, "spec"
        if self._window(active) < self._full_chunk():
            return False, "headroom"
        return True, None

    def _sweep_operands(self, active: list[int], chunk: int) -> dict[str, Any]:
        """The keyword operands of a decode step (steps.build_decode) that
        depend on the engine's layout, adapter bank and model: the block
        table of a paged pool, the LoRA bank with each slot's adapter, a
        hybrid stack's live rows."""
        lkw: dict[str, Any] = {}
        if self.paged:
            lkw["table"] = self._table()
        if self._lora is not None:
            lkw["lora"] = self._lora["layers"]
            lkw["ids"] = self._adapter_ids()
        if self._hybrid:
            lkw["live"] = self._live_mask(active, chunk)
        return lkw

    def _dispatch_plain(self, active: list[int]) -> None:
        """Dispatch one plain decode sweep WITHOUT waiting for results.
        The dispatch covers all S slots (static shapes); slots outside
        ``active`` get harmless overwritten-before-attend KV writes and
        their sampled tokens are discarded at retire. The sampled-token
        carry stays on device as the next dispatch's feed; the stacked
        per-step outputs ride in _inflight until _retire_one() reads them
        back and emits."""
        with self._sched.phase("decode_prepare"):
            if self._faults.check("device_error"):
                # dispatch-time device error (docs/RESILIENCE.md): raised as
                # DeviceFault so the loop runs the engine-fault RECOVERY
                # path (batch fails "engine_fault", engine degrades and
                # keeps serving) instead of the generic fail-everything
                # crash handler
                from kserve_vllm_mini_tpu.runtime.faults import DeviceFault

                raise DeviceFault("injected dispatch-time device error")
            chunk = self._chunk_for(active)
            tokens = self._feed_tokens(active)
            # The fed token occupies absolute position slot_len + the steps
            # in flight that are the slot's own (all of them, but for a slot
            # admitted behind a sweep); forward writes its KV there and
            # attends <=. The cap only ever binds on inactive rows
            # (eligibility guarantees active windows), whose writes are
            # masked-garbage either way.
            lengths = np.minimum(
                np.asarray(self._slot_len, dtype=np.int32) + self._pending_steps
                - np.asarray(self._slot_behind, dtype=np.int32),
                self.ecfg.max_seq_len - 1,
            )
            self._count_decode_kv_blocks(lengths, chunk)
            temps, topks, topps, pres, freqs = self._get_sampling_arrays()
            rng_prev = self._rng
            self._rng, sub = jax.random.split(self._rng)
            lkw = self._sweep_operands(active, chunk)
            decode = self._step("decode", chunk)
            # the jitted call alone: what bench/trace_reduce.py names gaps by
            with self._sched.phase("decode_dispatch") as ph:
                with self._cache_lock:
                    self._cache, self._counts, next_toks, ys = decode(
                        self.params, self._cache,
                        tokens, jnp.asarray(lengths, dtype=jnp.int32),
                        temps, topks, topps, sub,
                        self._counts, pres, freqs, **lkw,
                    )
            t0 = ph.t_start
            self._tokens_dev = next_toks
            self._tokens_dev_slots = frozenset(active)
            self._inflight.append({
                "ys": ys,
                "active": list(active),
                # handle identity per slot: retire must never emit into a
                # handle that replaced the one this sweep was dispatched for
                "handles": {i: self._slot_req[i] for i in active},
                "chunk": chunk,
                # whether a row of the sampling arrays this sweep was
                # dispatched with made the sampler sort
                "filters": self._sampler_filters,
                "t_dispatch": t0,
                # rng state BEFORE this dispatch's split: if every slot
                # finishes before this sweep is retired, the sweep is dropped
                # and the split rewound, keeping the dispatch/rng sequence
                # identical to the synchronous loop's
                "rng_prev": rng_prev,
            })
            self._pending_steps += chunk
            depth = len(self._inflight)
            if depth > 1:
                self.stats["pipelined_sweeps"] += 1
            if depth > self.stats["dispatch_depth"]:
                self.stats["dispatch_depth"] = depth

    def _retire_one(self) -> None:
        """Read back and emit the OLDEST in-flight sweep. Emission skips a
        slot when its handle was cancelled or replaced after the dispatch —
        in-flight results of a cancelled request must never reach its
        stream. When every slot finished, any younger in-flight sweep is
        pure garbage: drop it and rewind the rng split it consumed."""
        rec = self._inflight[0]
        # the device_get alone: what bench/trace_reduce.py names gaps by
        with self._sched.phase("decode_retire") as ph:
            # ONE host transfer for the whole chunk block — per-element
            # int(row[i]) costs a separate device readback each (chunk x
            # slots round-trips per sweep; this line was the serving
            # bottleneck, not the decode math)
            toks_h, lps_h, tids_h, tlps_h, *routed = (
                np.asarray(a) for a in jax.device_get(rec["ys"])
            )
            # in flight until its tokens are on the host: the wait for them
            # is never the ledger's ``exposed`` time
            self._inflight.pop(0)
        t_ready = ph.t_end
        # the last sweep in flight is read back: the slots that go on
        # decoding once it is emitted wait from here to the next dispatch.
        # Or callers were admitted behind this sweep: the device runs their
        # prefills next, and the slots wait from here until the first
        # tokens are read back (_read_first_tokens ends that gap)
        gap = not self._inflight or bool(self._first_pending)
        if gap:
            self._sched.gap_open()
        with self._sched.phase("emit", **self._routed_args(routed, rec["chunk"])) as ph:
            self.stats["busy_s"] += t_ready - max(
                rec["t_dispatch"], self._t_last_ready
            )
            self._t_last_ready = t_ready
            self._note_sweep(rec["t_dispatch"], t_ready)
            self._pending_steps -= rec["chunk"]
            if any(self._slot_behind):
                # sweeps retire oldest first: a slot admitted behind this
                # one has that much less in flight that is not its own
                self._slot_behind = [
                    max(b - rec["chunk"], 0) for b in self._slot_behind]
            self.stats["decode_steps"] += rec["chunk"]
            if rec["filters"]:
                self.stats["sampler_filter_steps"] += rec["chunk"]
            self._book_routed(routed, len(rec["active"]), rec["chunk"])
            overlapped = bool(self._inflight)  # device still computing N+1
            for step in range(toks_h.shape[0]):
                for i in rec["active"]:
                    h = self._slot_req[i]
                    if h is None or h is not rec["handles"][i]:
                        continue  # finished earlier in this chunk, or freed
                    if h.cancelled is not None and not self._lockstep:
                        # cancelled between dispatch and retire: drop its
                        # tokens. In lockstep the race is host-local (the
                        # follower can't see it) — there the cancel
                        # DECISION, which precedes the retire in the
                        # stream, is what stops emission on both sides.
                        continue
                    lp_info = None
                    if h.request.logprobs:
                        lp_info = (
                            float(lps_h[step, i]),
                            list(zip(tids_h[step, i].tolist(),
                                     tlps_h[step, i].tolist())),
                        )
                    self._emit_token(i, int(toks_h[step, i]), t_ready, lp_info)
        # emit phase: readback -> host emission done for this window; the
        # engine-lane span records the dispatch->retire window itself
        self._observe_phase("emit", ph.seconds)
        self._trace_engine_span(
            "engine.decode.window", rec["t_dispatch"], t_ready,
            attrs={"chunk": rec["chunk"], "slots": len(rec["active"]),
                   "pipelined": overlapped},
        )
        if overlapped:
            # emission ran while the device computed the next sweep — the
            # host time the synchronous loop would have serialized
            self.stats["host_overlap_s"] += ph.seconds
        if gap:
            # the slots that go on decoding, not those whose first token is
            # still to come
            self._sched.gap_waiting(
                len(self._decode_active()) - len(self._first_pending))
        self._drop_idle_inflight()

    def _drop_idle_inflight(self) -> None:
        """Every decode slot finished: the sweeps still in flight computed
        only garbage. Rewind to the oldest dropped sweep's pre-dispatch rng
        (their counts/KV pollution sits in freed rows, reset at admission)."""
        if self._inflight and not self._decode_active():
            self._rng = self._inflight[0]["rng_prev"]
            self._settle_pipeline()

    def _settle_pipeline(self) -> None:
        """Nothing is in flight any more, by retire, drop or fault: the
        bookkeeping of what was."""
        self._inflight.clear()
        self._pending_steps = 0
        self._slot_behind = [0] * self.ecfg.max_slots
        self._first_pending = []
        self._tokens_dev = None

    @staticmethod
    def _routed_args(routed: list, steps: int) -> dict[str, int]:
        """Arguments of the ``emit`` span of a sweep whose model has expert
        layers: the held experts its ``moe_steps`` steps touched, and those
        their dispatch multiplied (``moe_visited``), summed over the expert
        layers, so that a trace holds the counts beside the device time of
        the very steps they belong to. Nothing for any other model."""
        if not routed:
            return {}
        return {"moe_touched": int(routed[0][:, 1].sum()),
                "moe_visited": int(routed[0][:, 3].sum()), "moe_steps": steps}

    def _book_routed(self, routed: list, live: int, steps: int) -> None:
        """A retired sweep's expert-block counts: ``routed`` holds the
        decode program's [steps, 4] read-back (landed, touched, fullest,
        over its ``live`` rows, and visited), or nothing for a model without
        such blocks."""
        if not routed:
            return
        landed, touched, fullest, visited = routed[0].sum(axis=0).tolist()
        self.stats["moe_assignments"] += (
            live * steps * self.cfg.num_experts_per_tok * self.cfg.n_expert_layers)
        self.stats["moe_local_assignments"] += landed
        self.stats["moe_expert_touches"] += touched
        self.stats["moe_expert_load_max"] += fullest
        self.stats["moe_expert_visits"] += visited

    def _note_sweep(self, t_dispatch: float, t_ready: float) -> None:
        """Watchdog food (docs/RESILIENCE.md): a sweep read back IS scheduler
        progress, and its dispatch -> ready wall feeds the rolling EMA that
        the wedge threshold and the ledger's stall threshold scale from."""
        span = t_ready - t_dispatch
        with self._res_lock:
            self._watch_beat = t_ready
            self._sweep_ema_s = (
                span if self._sweep_ema_s == 0.0
                else 0.8 * self._sweep_ema_s + 0.2 * span
            )

    def _retire_all(self, on_decision=None) -> None:
        while self._inflight:
            if on_decision is not None:
                on_decision(("retire",))
            self._retire_one()

    def _sweep_phase(self, on_decision=None) -> None:
        """Dispatch/retire policy for one iteration with live slots. The
        double-buffered steady state dispatches sweep N+1 from the
        on-device carry BEFORE retiring sweep N, so emission (and the
        next iteration's admin/cancel/admission work) runs while the
        device computes. Ineligible mixes retire what's in flight and run
        the synchronous sweep, preserving the seed scheduler exactly."""
        # sweep_stall injection (docs/RESILIENCE.md): sleep on the
        # scheduler thread with work live — a wedged/slow device sweep,
        # exactly what the watchdog watches for. The sleep runs outside
        # the registry lock.
        self._faults.stall("sweep_stall")
        active = self._decode_active()
        ok, reason = self._pipeline_eligible(active)
        if not ok and reason is not None:
            # counted per sweep iteration on pipeline-enabled engines: how
            # often the steady state COULD NOT engage, and why
            self.stats[f"pipeline_fallback_{reason}"] += 1
        if self._inflight:
            if ok:
                if on_decision is not None:
                    on_decision(("dispatch",))
                self._dispatch_plain(active)
            if on_decision is not None:
                on_decision(("retire",))
            self._retire_one()
            return
        if ok:
            if on_decision is not None:
                on_decision(("dispatch",))
            self._dispatch_plain(active)
            return  # overlap begins: host work rides the device compute
        if on_decision is not None:
            on_decision(("sweep",))
        self._decode_sweep()

    def _replay_dispatch(self) -> None:
        """Multihost follower side of a published ('dispatch',): the
        active set is deterministic from the replayed decision stream, so
        operands and jitted-call order match the primary's."""
        self._dispatch_plain(self._decode_active())

    def _masked_sweep(self, active: list[int], constrained: list[int]) -> None:
        """Grammar-constrained decode sweep: single step, synchronous —
        the next mask depends on the byte just emitted, so there is
        nothing to dispatch ahead."""
        S = self.ecfg.max_slots
        tokens = self._feed_tokens(active)
        lengths = np.asarray(self._slot_len, dtype=np.int32)
        self._count_decode_kv_blocks(lengths, 1)
        temps, topks, topps, pres, freqs = self._get_sampling_arrays()
        self._rng, sub = jax.random.split(self._rng)
        mask = np.zeros((S, (self.cfg.vocab_size + 7) // 8), dtype=np.uint8)
        for i in constrained:
            budget = min(
                self._slot_remaining[i],
                self.ecfg.max_seq_len - 1 - self._slot_len[i],
            )
            mask[i] = self._constraint_mask(self._slot_machine[i], budget)
        use_mask = np.zeros((S,), dtype=bool)
        use_mask[constrained] = True
        lkw = self._sweep_operands(active, 1)
        decode = self._step("decode_masked")
        # dispatch, readback and emission are the same child phases as on
        # the plain path; masked_sweep's own time is the preparation above
        with self._sched.phase("decode_dispatch") as ph:
            with self._cache_lock:
                self._cache, self._counts, next_toks, ys = decode(
                    self.params, self._cache,
                    tokens, jnp.asarray(lengths), temps, topks, topps, sub,
                    self._counts, pres, freqs,
                    jnp.asarray(mask), jnp.asarray(use_mask), **lkw,
                )
        t0 = ph.t_start
        self._tokens_dev = next_toks
        self._tokens_dev_slots = frozenset(active)
        self._sync_inflight = True
        with self._sched.phase("decode_retire") as ph:
            toks_h, lps_h, tids_h, tlps_h, *routed = (
                # the constrained path is synchronous by design: the next
                # mask depends on the byte just emitted  # kvmini: sync-ok
                np.asarray(a) for a in jax.device_get(ys)
            )
            self._sync_inflight = False
        now = ph.t_end
        self._sched.gap_open()
        self.stats["busy_s"] += now - t0
        self._t_last_ready = now
        self._note_sweep(t0, now)
        self.stats["decode_steps"] += 1
        if self._sampler_filters:
            self.stats["sampler_filter_steps"] += 1
        self._book_routed(routed, len(active), 1)
        with self._sched.phase("emit", **self._routed_args(routed, 1)) as ph:
            for step in range(toks_h.shape[0]):
                for i in active:
                    if self._slot_req[i] is None:
                        continue  # finished earlier in this chunk
                    lp_info = None
                    if self._slot_req[i].request.logprobs:
                        # kvmini: sync-ok — lps/tids are host numpy already
                        top = zip(tids_h[step, i].tolist(), tlps_h[step, i].tolist())
                        lp_info = (float(lps_h[step, i]), list(top))
                    self._emit_token(i, int(toks_h[step, i]), now, lp_info)
        self._observe_phase("emit", ph.seconds)
        self._trace_engine_span(
            "engine.decode.window", t0, now,
            attrs={"chunk": 1, "slots": len(active), "mode": "masked"},
        )
        self._sched.gap_waiting(len(self._decode_active()))

    def _fail_all(self, exc: BaseException) -> None:
        """Push an error 'done' to every live/pending handle so no client
        blocks forever on a dead scheduler."""
        info = {"finish_reason": "error", "error": f"{type(exc).__name__}: {exc}"}
        # in-flight sweeps die with the scheduler; drop their bookkeeping so
        # a post-mortem snapshot_stats doesn't report phantom depth
        self._settle_pipeline()
        # half-prefilled slots die with it too (their handles error below
        # through the same _slot_req sweep), and so do slots awaiting a
        # prefill-lane handoff (their payloads orphan at consume)
        self._slot_prefill = [None] * self.ecfg.max_slots
        self._prefill_fifo.clear()
        self._slot_handoff = [None] * self.ecfg.max_slots
        for slot in range(self.ecfg.max_slots):
            h = self._slot_req[slot]
            if h is not None:
                h.events.put(("done", dict(info)))
                self._slot_req[slot] = None
        if self.paged and self._deferred is not None:
            # the backpressure-held head-of-line request is in neither a
            # slot nor _pending — it must fail too or its client hangs
            self._deferred.events.put(("done", dict(info)))
            self._deferred = None
        while True:  # pending adapter ops must error out, not time out
            try:
                op = self._admin.get_nowait()
            except queue.Empty:
                break
            op.error = f"engine failed: {info['error']}"
            op.done.set()
        while True:
            try:
                h = self._pending.get_nowait()
            except queue.Empty:
                break
            h.events.put(("done", dict(info)))

    def _schedule_once(self, on_decision=None) -> None:
        """One scheduler iteration: drain admissions into free slots, then
        advance decode — pipelined (dispatch sweep N+1, retire sweep N) in
        steady state, one synchronous sweep otherwise, or a short blocking
        wait when idle. The SINGLE source of scheduling policy —
        Engine._loop runs it directly and the multi-host primary
        (runtime/multihost.py) runs it with ``on_decision``, which receives
        every state-advancing decision (("admit", request) / ("sweep",) /
        ("dispatch",) / ("retire",) / ("cancel", ...)) BEFORE it executes,
        so followers can replay the identical stream."""
        # adapter load/unload ops run here — between DISPATCHES, on this
        # thread. An in-flight sweep holds references to the (immutable)
        # arrays it was dispatched with, so a bank/registry swap here only
        # affects future dispatches.
        while True:
            try:
                op = self._admin.get_nowait()
            except queue.Empty:
                break
            op.run()

        # cancellations first: a cancelled slot must not burn a sweep (and
        # its freed slot can admit in the same iteration below). Published
        # as a decision — a follower that kept the slot live would diverge
        # its free-list from the primary's at the next admission. Finishing
        # the slot is safe even with a sweep in flight: the retire path
        # checks handle identity and drops the freed slot's in-flight
        # tokens — deterministically on primary and follower alike (the
        # cancel decision precedes the retire decision in the stream), so
        # a cancelled request never receives a token sampled after its
        # cancellation landed.
        for slot in range(self.ecfg.max_slots):
            h = self._slot_req[slot]
            if h is not None and h.cancelled is not None:
                if on_decision is not None:
                    on_decision(("cancel", h.request.request_id, h.cancelled))
                if self._slot_prefill[slot] is not None:
                    # cancelled mid-chunked-prefill: no token was ever
                    # sampled — abort without a decode span or a sweep
                    self._abort_prefill(slot, h.cancelled)
                elif self._slot_handoff[slot] is not None:
                    # cancelled while its prompt was on the prefill lane:
                    # same zero-token abort; the lane's eventual payload
                    # orphans at the consume identity check
                    self._abort_handoff(slot, h.cancelled)
                else:
                    # the ("cancel") decision published above covers this
                    # branch too — it only selects the finish shape
                    # kvmini: lockstep-ok — see above
                    self._finish_slot(slot, h.cancelled)

        admitted = False
        while self._free:
            if self.paged and self._deferred is not None:
                handle, self._deferred = self._deferred, None
            else:
                try:
                    handle = self._pending.get_nowait()
                except queue.Empty:
                    break
            if handle.cancelled is not None:
                # cancelled while queued: finish locally WITHOUT publishing
                # an admit (followers would otherwise admit a request the
                # primary never did and their free-lists would diverge)
                self._admit_one(handle)  # kvmini: lockstep-ok — early-
                continue                 # returns with the done event
            if self.paged and not self._paged_fits(handle.request):
                # hold at the head of the line until decode frees blocks
                self._deferred = handle
                break
            behind = False
            if self._inflight:
                why = self._behind_refusal(handle)
                behind = why is None
                if not behind:
                    # this admission mutates the active set and cache
                    # bookkeeping the in-flight sweep was dispatched under
                    # in a way the sweep is not safe against — retire
                    # first, admit against settled state (a newly admitted
                    # slot must never receive a stale token from a sweep
                    # dispatched before its admission)
                    self.stats["pipeline_fallback_active_set"] += 1
                    self.stats[f"admissions_drained_{why}"] += 1
                    self._retire_all(on_decision)
                    # (first tokens of callers taken behind what was just
                    # drained: the host state must be whole again)
                    self._read_first_tokens()
            if on_decision is not None:
                on_decision(("admit", handle.request))
            self._admit_one(handle, behind)
            admitted = True
        self.stats["queue_depth"] = self._queue_depth()
        # republish the live-handle snapshot (docs/RESILIENCE.md): the
        # watchdog reads THIS under the lock to unblock clients on a
        # wedge, and the admission estimator counts occupancy from it —
        # neither ever touches the scheduler-owned slot list directly
        # (built OUTSIDE the lock: the slot list stays scheduler-owned)
        live_now = [h for h in self._slot_req if h is not None]
        with self._res_lock:
            self._live_handles = live_now
        # finished prefill-lane handoffs inject BETWEEN decode sweeps —
        # the decode lane's only disagg cost is one cache write per
        # admission (docs/DISAGGREGATION.md)
        self._consume_handoffs(on_decision)
        # chunked prefill rides BETWEEN decode sweeps: one piece of the
        # oldest in-progress prompt per iteration (docs/TROUBLESHOOTING.md
        # "Long prompts stall streaming")
        self._advance_prefills(on_decision)
        if self._decode_active():
            self._sweep_phase(on_decision)
            self._read_first_tokens()
            return
        # whoever waited in an open decode gap is gone (finished at its
        # first token, cancelled): no dispatch will end it
        self._sched.gap_close()
        if not admitted:
            if self._inflight:
                # every live slot was cancelled this iteration: whatever is
                # still in flight is garbage — retire (emissions all skip
                # on the freed slots) so the drop/rewind logic settles the
                # pipeline before the engine idles
                self._retire_all(on_decision)
            if self._prefill_fifo:
                # chunks still pending with no decode work: loop again
                # immediately — the next iteration advances the next piece
                return
            if not self._free:
                # every slot occupied but none decode-active: ONLY
                # possible with all slots awaiting a prefill-lane
                # handoff (docs/DISAGGREGATION.md) — popping a pending
                # request here would have no slot to hold it. Wait a
                # beat for a handoff to land instead (pre-disagg this
                # state was unreachable: occupied slots were always
                # decode-active or in the prefill fifo).
                with self._sched.phase("idle"):
                    time.sleep(0.02)
                return
            try:
                with self._sched.phase("idle"):
                    handle = self._pending.get(timeout=0.02)
            except queue.Empty:
                return
            if handle.cancelled is not None:
                # finish-without-admit, deliberately unpublished (see the
                # cancelled-while-queued note above)  # kvmini: lockstep-ok
                self._admit_one(handle)
                return
            if on_decision is not None:
                on_decision(("admit", handle.request))
            self._admit_one(handle)

    def _loop(self) -> None:
        from kserve_vllm_mini_tpu.runtime.faults import DeviceFault

        warm = False
        while self._running:
            try:
                if not warm:
                    # (inside the try: a failure must reach the waiting
                    # clients like any other of this thread's)
                    warm = True
                    self._warm_behind()
                # one iteration, gauge republish included: the ledger's
                # outermost phase, so the phases' self times add up
                # to this thread's wall time
                with self._sched.phase("loop"):
                    with self._res_lock:
                        pending = self._fault_pending
                    if pending is not None:
                        # the watchdog declared a wedge while this thread was
                        # stuck — drain the poisoned pipeline and degrade
                        # BEFORE touching new work
                        self._recover_engine_fault(pending)
                        continue
                    self._schedule_once()
                    with self._res_lock:
                        # watchdog beat: one full iteration IS progress (an
                        # idle engine must never look wedged)
                        self._watch_beat = _now()
                    # republish the derived KV gauges from THIS thread so
                    # /metrics & /healthz (event-loop handlers) can read a
                    # consistent snapshot without ever blocking on a sweep;
                    # ~4 Hz is plenty for the monitor's 1 Hz scrape
                    with self._obs_lock:
                        stale = time.time() - self._kv_gauges_t >= 0.25
                    if stale:
                        self._kv_admin_snapshot()
                        if self.paged:
                            # host-RAM tier thrash guard rides the same
                            # cadence as the gauge republish (~4 Hz)
                            self._tier_thrash_tick()
            except DeviceFault as exc:
                # injected (or classified) dispatch-time device error:
                # recoverable by design — fail the batch, degrade, keep
                # serving (docs/RESILIENCE.md)
                self._recover_engine_fault(f"device_error: {exc}")
            except Exception as exc:  # scheduler must never die silently
                import traceback

                traceback.print_exc()
                self._fail_all(exc)
                # start()/stop() write this flag from the control thread;
                # the loop only ever clears it on crash, and every reader
                # tolerates staleness.
                # kvmini: thread-ok — GIL-atomic bool flag
                self._running = False
        # graceful drain (docs/RESILIENCE.md): the loop's LAST act, on
        # THIS thread, so slot/block state never changes owner — every
        # in-flight and queued handle gets its terminal event exactly
        # once and every slot/block is released. After a crash the
        # _fail_all above already emptied everything; the drain then
        # finds nothing.
        self._drain_requests()

    def _drain_requests(self) -> None:
        """Shutdown drain (scheduler thread): finish live slots with
        their cancel reason (default "cancelled"), release blocks, and
        fail queued/deferred handles — exactly one terminal event per
        handle, no slot or block leak."""
        self._settle_pipeline()
        self._tokens_dev_slots = frozenset()
        with self._res_lock:
            faulted = set(self._faulted_ids)
        for slot in range(self.ecfg.max_slots):
            h = self._slot_req[slot]
            if h is None:
                continue
            if h.request.request_id in faulted:
                # the watchdog already sent this handle its terminal
                # event — release the slot without a second 'done'
                # (_release_slot also drops any chunked-prefill state)
                self._release_slot(slot)
                continue
            h.cancelled = h.cancelled or "cancelled"
            if self._slot_prefill[slot] is not None:
                self._abort_prefill(slot, h.cancelled)
            elif self._slot_handoff[slot] is not None:
                # drained mid-handoff: zero-token terminal event exactly
                # once; the lane's payload orphans at consume (or the
                # lane flushes it as a tombstone on its own stop)
                self._abort_handoff(slot, h.cancelled)
            else:
                self._finish_slot(slot, h.cancelled)
        if self.paged and self._deferred is not None:
            # the backpressure-held head-of-line handle sits in neither
            # a slot nor _pending — it must drain too
            self._deferred.events.put(("done", {
                "finish_reason": "cancelled", "tokens_out": 0,
            }))
            self._deferred = None
        while True:
            try:
                h = self._pending.get_nowait()
            except queue.Empty:
                break
            h.events.put(("done", {
                "finish_reason": "cancelled", "tokens_out": 0,
            }))

    # -- resilience: watchdog, engine-fault recovery, admission estimate ---
    # (docs/RESILIENCE.md)

    def _watchdog_loop(self) -> None:
        """Side thread: declare the scheduler WEDGED when no progress
        beat lands within max(factor x sweep EMA, min_s) while work is
        live. On a trip it sends every in-flight handle its terminal
        ``engine_fault`` event IMMEDIATELY (clients unblock even though
        the scheduler thread is still stuck) and parks the recovery
        reason for the loop to act on when it resumes. One trip per
        wedge: the same stuck beat never trips twice."""
        interval = max(min(self.ecfg.watchdog_min_s / 4.0, 0.25), 0.02)
        tripped_beat: Optional[float] = None
        while not self._watch_stop.wait(interval):
            with self._res_lock:
                beat = self._watch_beat
                ema = self._sweep_ema_s
                pending = self._fault_pending
                live = list(self._live_handles)
            if pending is not None:
                continue  # a trip is already waiting for recovery
            if not live:
                tripped_beat = None
                continue
            if ema <= 0.0:
                # not armed until the FIRST sweep retires: a cold engine's
                # first decode dispatch blocks in XLA compile for seconds,
                # and with no EMA the floor alone would trip on it. The
                # first retire seeds the EMA compile-inflated, so the
                # threshold self-decays toward warm sweep times.
                continue
            threshold = max(
                self.ecfg.watchdog_factor * ema, self.ecfg.watchdog_min_s
            )
            stalled = _now() - beat
            if stalled < threshold or beat == tripped_beat:
                continue
            tripped_beat = beat
            reason = (
                f"watchdog: no sweep retired for {stalled:.2f}s "
                f"(threshold {threshold:.2f}s, sweep EMA {ema:.3f}s)"
            )
            now = _now()
            faulted: list[str] = []
            for h in live:
                # cancel first: the wedged sweep's retire (when the
                # thread resumes) checks `cancelled` and drops this
                # handle's tokens — no token event can follow the
                # terminal event below
                h.cancelled = h.cancelled or "engine_fault"
                h.finish_reason = "engine_fault"
                h.t_done = now
                h.events.put(("done", {
                    "finish_reason": "engine_fault",
                    "tokens_out": len(h.tokens),
                    "error": reason,
                }))
                faulted.append(h.request.request_id)
            with self._res_lock:
                self._watchdog_trips += 1
                self._faulted_ids.update(faulted)
                self._fault_pending = reason

    def _recover_engine_fault(self, reason: str) -> None:
        """Scheduler-thread recovery from a wedge/device fault: drop the
        poisoned in-flight pipeline, finish every live slot with
        ``finish_reason="engine_fault"`` (exactly once — handles the
        watchdog already unblocked are only released), free slots and
        blocks, climb one degrade-ladder level, and keep serving. Past
        the ladder the engine gives up via the generic crash path."""
        import sys

        print(f"engine: recovering from fault: {reason}", file=sys.stderr)
        self._settle_pipeline()
        self._sync_inflight = False
        # every slot fails below: a wedge is nobody's decode gap
        self._sched.gap_waiting(0)
        self._tokens_dev_slots = frozenset()
        now = _now()
        with self._res_lock:
            faulted = set(self._faulted_ids)
            self._faulted_ids.clear()
            self._fault_pending = None
        for slot in range(self.ecfg.max_slots):
            h = self._slot_req[slot]
            if h is None:
                continue
            if h.request.request_id not in faulted:
                h.t_done = now
                h.finish_reason = "engine_fault"
                self._observe_phase(
                    "decode", max(now - (h.t_first_token or now), 0.0)
                )
                self._trace_span(
                    h, "server.decode", h.t_first_token or now, now,
                    ok=False, attrs={"finish_reason": "engine_fault"},
                )
                h.events.put(("done", {
                    "finish_reason": "engine_fault",
                    "tokens_out": len(h.tokens),
                    "error": reason,
                }))
            self.stats["requests_completed"] += 1
            # never retain this slot's KV: the wedged/errored sweep may
            # have written garbage into it
            self._retained[slot] = []
            self._release_slot(slot)
        with self._res_lock:
            self._engine_faults += 1
            self._degrade_level = min(self._degrade_level + 1, 4)
            level = self._degrade_level
        # degrade ladder: each trip gives up one optimization the fault
        # may have been hiding in; the queue keeps serving throughout
        if level == 1:
            self.ecfg.decode_pipeline = False
        elif level == 2:
            self.ecfg.decode_chunk = 1
        elif level == 3:
            self.ecfg.spec_tokens = 0
        elif level >= 4:
            # past the ladder: give up loudly — queued clients error out
            # through the crash path, never hang
            exc = RuntimeError(
                f"engine fault past the degrade ladder (trip {level}): {reason}"
            )
            print(f"engine: {exc}", file=sys.stderr)
            self._fail_all(exc)
            # scheduler-thread write, same as the _loop crash path
            self._running = False

    def estimate_wait_s(self) -> float:
        """Admission burn-rate estimate: seconds a request submitted NOW
        would take to COMPLETE, from queue depth and the rolling
        admit->done service EMA (waves of max_slots requests). 0.0 with
        no service history — the engine admits until it has data. The
        server's deadline-aware shed gate compares this against the
        request's deadline (docs/RESILIENCE.md)."""
        with self._res_lock:
            service = self._service_ema_s
            occupied = len(self._live_handles)
        if service <= 0.0:
            return 0.0
        depth = self._queue_depth()
        slots = max(self.ecfg.max_slots, 1)
        if depth == 0 and occupied < slots:
            # a free slot RIGHT NOW: admission is immediate. The queue
            # burn-rate model only gates work that must WAIT — an idle
            # engine must never shed on a stale (e.g. cold-compile-
            # inflated) service EMA.
            return 0.0
        # full waves ahead of it, plus its own
        waves = depth // slots + 1
        return (waves + 1) * service

    def count_shed(self) -> None:
        """Server-side admission shed accounting (the 429 path lives in
        runtime/server.py; the counter lives here so ONE stats key covers
        both shed sites)."""
        with self._res_lock:
            self._requests_shed += 1

    def arm_fault(self, name: str, **params: Any) -> dict[str, Any]:
        """Arm a named injection point at runtime (the /faults endpoint,
        docs/RESILIENCE.md). The registry is built once at construction
        and internally locked, so this is callable from any thread."""
        if name == "kv_alloc_fail" and not self.paged:
            # the point lives in the paged admission path: arming it on a
            # dense engine would let a chaos run stamp a green recovered
            # row for a fault that can never execute
            raise ValueError(
                "kv_alloc_fail needs kv_layout=paged; this engine is dense"
            )
        if name == "kv_handoff_drop" and self._disagg is None:
            # same honesty rule: the point lives on the prefill lane —
            # arming it on a colocated engine can never fire
            raise ValueError(
                "kv_handoff_drop needs a disaggregated engine (disagg "
                "/ --disagg); this engine prefills colocated"
            )
        return self._faults.arm(name, **params).to_dict()

    def clear_fault(self, name: Optional[str] = None) -> None:
        """Clear one armed point (None = all). An open kv_alloc_fail
        backpressure window expires by its armed duration (that state is
        scheduler-owned)."""
        self._faults.disarm(name)

    def active_faults(self) -> dict[str, Any]:
        return self._faults.active()

    def check_fault(self, name: str):
        """Hot-path fault check for NON-engine threads (the server's
        sse_disconnect point lives on the event loop): returns the fired
        FaultSpec or None. The registry is internally locked."""
        return self._faults.check(name)

    # -- introspection -----------------------------------------------------

    def snapshot_stats(self) -> dict[str, Any]:
        # Deliberately lock-free monitoring snapshot (single-writer engine:
        # only the scheduler thread mutates this state; list len/iteration
        # and dict copy are GIL-atomic). A snapshot taken mid-sweep is at
        # worst one sweep stale — adding a stats lock to the decode hot
        # path to fix that is the wrong trade. Each read below carries its
        # own thread-ok so a NEW cross-thread surface still gets flagged.
        s = dict(self.stats)
        wall = max(_now() - s["started_at"], 1e-9)
        s["duty_cycle"] = min(s["busy_s"] / wall, 1.0)
        # kvmini: thread-ok — benign racy snapshot (see above)
        s["active_slots"] = sum(1 for h in self._slot_req if h is not None)
        # kvmini: thread-ok — benign racy snapshot (see above)
        s["free_slots"] = len(self._free)
        # live recompute: the cached value goes stale between scheduler
        # iterations, and the deferred head-of-line handle must count
        s["queue_depth"] = self._queue_depth()
        # kvmini: thread-ok — benign racy snapshot (see above)
        s["inflight_sweeps"] = len(self._inflight)
        # the scheduler ledger (docs/TRACING.md): where this thread's time
        # went, by phase — written by the scheduler thread only, and at
        # worst one phase boundary stale here
        sched = self._sched.snapshot()
        s["sched_s"] = sched["seconds"]
        s["sched_exposed_s"] = sched["exposed"]
        s["sched_slow_s"] = sched["slow"]
        s["sched_spans"] = sched["spans"]
        # the decode gaps (docs/TRACING.md): what the slots that were
        # decoding lost between a drained readback and the next dispatch
        s["sched_gaps"] = sched["gaps"]
        s["sched_gap_s"] = sched["gap_seconds"]
        s["sched_gap_slot_s"] = sched["gap_slot_seconds"]
        s["sched_admit_gap_s"] = sched["admit_gap_seconds"]
        s["sched_admit_gap_admissions"] = sched["admit_gap_admissions"]
        # the older name of the same time: every gap, whatever caused it
        s["bubble_s"] = sum(sched["gap_seconds"].values())
        # Derived KV gauges (occupancy, fragmentation, retained fraction,
        # hit-depth percentiles) come from ONE consistent scheduler-thread
        # pass (_kv_admin_snapshot): a ratio built from independent
        # lock-free len() reads could tear between them, which the
        # single-writer annotations above never had to worry about.
        kv = self._kv_admin_snapshot()
        s["kv_prefix_hit_depth_p50"] = kv.get("kv_prefix_hit_depth_p50", 0)
        s["kv_prefix_hit_depth_p95"] = kv.get("kv_prefix_hit_depth_p95", 0)
        s["kv_bytes_per_token"] = self.kv_bytes_per_token()
        s["cache_bytes"] = dict(self._cache_bytes)
        s["linear_state_kernel"] = int(self._linear_state_kernel)
        s["ssm_state_kernel"] = int(self._ssm_state_kernel)
        s["block_mixers"] = self.cfg.block_mixers
        # physical bytes the reused prompt tokens did NOT re-write — the
        # byte-denominated view of prefix_tokens_reused_total
        s["kv_reused_bytes"] = s["prefix_tokens_reused"] * s["kv_bytes_per_token"]
        # per-device analytic footprint (computed once at build; see
        # __init__) — exported so headroom_error_pct can be derived from a
        # plain /metrics scrape next to the observed watermark
        s["hbm_headroom_estimate_bytes"] = self._headroom_estimate_bytes
        if self.paged:
            for key in ("kv_pool_blocks", "kv_free_blocks",
                        "kv_retained_blocks", "kv_used_blocks",
                        "kv_block_size", "kv_occupancy",
                        "kv_retained_fraction", "kv_fragmentation",
                        "kv_logical_bytes", "kv_physical_bytes",
                        "kv_tier_blocks", "kv_tier_bytes",
                        "kv_tier_capacity_bytes", "kv_tier_disabled"):
                if key in kv:
                    s[key] = kv[key]
        # HBM watermarks (docs/TROUBLESHOOTING.md): device memory_stats
        # when the backend reports them — gracefully absent (no keys, no
        # fabricated zeros) on CPU backends that don't
        from kserve_vllm_mini_tpu.profiling.headroom import hbm_watermarks

        hbm = hbm_watermarks()
        if hbm:
            s["hbm_bytes_in_use"] = hbm["bytes_in_use"]
            if "bytes_limit" in hbm:
                s["hbm_bytes_limit"] = hbm["bytes_limit"]
            with self._obs_lock:
                self._hbm_peak_seen = max(
                    self._hbm_peak_seen,
                    hbm.get("peak_bytes_in_use", 0),
                    hbm["bytes_in_use"],
                )
                s["hbm_peak_bytes"] = self._hbm_peak_seen
        if self._disagg is not None:
            # disaggregated-serving gauges (docs/DISAGGREGATION.md): lane
            # backlog (the handoff_stall monitor rule's input) and the
            # degrade-ladder position. queue_depth() is internally
            # locked; the degrade flag is a scheduler-owned bool.
            s["kv_handoff_queue_depth"] = self._disagg.queue_depth()
            # kvmini: thread-ok — GIL-atomic bool gauge, scheduler-owned
            s["disagg_degraded"] = 1 if self._disagg_degraded else 0
        s["spec_accept_ratio"] = (
            s["spec_accepted"] / s["spec_proposed"] if s["spec_proposed"] else 0.0
        )
        # resilience rail (docs/RESILIENCE.md): sheds, watchdog trips,
        # recovered engine faults, the degrade ladder position, and how
        # many injection points are currently armed — read in one pass
        # under the lock their writers hold
        with self._res_lock:
            s["requests_shed"] = self._requests_shed
            s["watchdog_trips"] = self._watchdog_trips
            s["engine_faults"] = self._engine_faults
            s["degrade_level"] = self._degrade_level
        s["faults_armed"] = self._faults.armed_count()
        # fleet-router placement input (docs/FLEET.md): the same
        # admission burn-rate estimate the deadline shed gate compares
        # deadlines against, exported so a router can score replicas
        # from one /metrics scrape (estimate_wait_s locks internally)
        s["estimated_wait_s"] = self.estimate_wait_s()
        # compile-stats totals (docs/PROFILING.md): the recorder is
        # internally locked, so this read is consistent by construction
        cs = self._compile_recorder.snapshot()
        s["compiles"] = cs["compiles"]
        s["compile_s"] = cs["compile_s"]
        s["compiled_flops"] = cs["compiled_flops"]
        s["compiled_bytes"] = cs["compiled_bytes"]
        s["compile_peak_bytes"] = cs["compile_peak_bytes"]
        # live economics rail (docs/ECONOMICS.md): one rolling-window
        # observation per snapshot, fed the busy/token values THIS
        # snapshot already read, under _obs_lock (scrapers from any
        # thread drive it). The $/hr accrual is a level gauge known from
        # construction; the per-token rates appear once the window holds
        # token progress — absent while warming up, never $0. No rail
        # object (CPU backend, no econ_accelerator) -> no keys at all.
        if self._econ is not None:
            with self._obs_lock:
                econ = self._econ.observe(
                    time.time(), s["busy_s"], s["decode_tokens"]
                )
            s["econ_usd_per_hour"] = self._econ.usd_per_hour
            if econ:
                s["econ_usd_per_1k_tokens"] = econ["usd_per_1k_tokens"]
                s["econ_wh_per_1k_tokens"] = econ["wh_per_1k_tokens"]
                s["econ_tokens_per_sec"] = econ["tokens_per_sec"]
        return s

    def _host_exposed(self) -> bool:
        """The scheduler ledger's ``exposed`` test: work is live (a slot
        occupied or a request waiting) and no decode sweep is in flight, so
        whatever the engine thread does now, the device waits for it.
        O(1): the ledger asks at every phase boundary — so from inside a
        phase, on the scheduler thread that owns both lists."""
        # kvmini: thread-ok — asked by the ledger, on the scheduler thread
        return not self._inflight and not self._sync_inflight and (
            len(self._free) < self.ecfg.max_slots
            or not self._pending.empty()
        )

    def kv_bytes_per_token(self) -> int:
        """Physical KV bytes one cached position costs, parameterized by
        the KV dtype — priced by the SAME kv_elem_bytes formula the
        admission estimate uses (profiling/headroom.py), so
        headroom_error_pct never compares two different models and the
        logical/physical byte gauges keep reading true when quantized
        KV lands on the paged path (ROADMAP item 3)."""
        from kserve_vllm_mini_tpu.profiling.headroom import kv_elem_bytes

        cfg = self.cfg
        if self.ecfg.kv_cache_dtype == "int8":
            elem = kv_elem_bytes(cfg.head_dim, 0.0, quantized=True)
        elif self.ecfg.kv_cache_dtype:
            elem = kv_elem_bytes(
                cfg.head_dim, jnp.dtype(self.ecfg.kv_cache_dtype).itemsize
            )
        else:
            elem = kv_elem_bytes(cfg.head_dim, cfg.jnp_dtype.itemsize)
        # K/V of the layers that hold them: all, or a hybrid's full-attention ones
        return int(2 * cfg.n_full_layers * cfg.n_kv_heads * cfg.head_dim * elem)

    def _kv_admin_snapshot(self, force: bool = False) -> dict[str, Any]:
        """Every DERIVED KV gauge — pool occupancy, fragmentation,
        retained fraction, logical/physical bytes, prefix-hit-depth
        percentiles — computed in ONE pass ON the scheduler thread.
        Ratios over ``_free_blocks``/``_retained_lru`` built from
        independent lock-free ``len()`` reads could tear between the
        reads: the single-writer discipline those attributes live under
        makes a lone stale length benign, but not a ratio of two lengths
        from different sweeps.

        While the scheduler runs, cross-thread callers (the aiohttp
        /metrics and /healthz handlers — which live ON the event loop, so
        they must never block on a sweep) read the cache the scheduler
        republishes every ~250 ms from its own loop; ``force=True``
        (the once-per-run results snapshot, called off the event loop)
        rendezvouses via ``_run_admin`` for a fully fresh pass, falling
        back to the cache on timeout/shutdown."""
        if (
            self._running
            and threading.current_thread() is not self._thread
            and not force
        ):
            with self._obs_lock:
                return dict(self._kv_gauges)
        fresh: dict[str, Any] = {}

        def _collect() -> None:
            depths = sorted(self._hit_depths)

            def pct(p: float) -> int:
                if not depths:
                    return 0
                k = max(int(round(p / 100.0 * len(depths) + 0.5)) - 1, 0)
                return depths[min(k, len(depths) - 1)]

            fresh["kv_prefix_hit_depth_p50"] = pct(50.0)
            fresh["kv_prefix_hit_depth_p95"] = pct(95.0)
            if not self.paged:
                return
            pool = self._scratch_block
            free = len(self._free_blocks)
            retained = len(self._retained_lru)
            used = pool - free - retained
            bpt = self.kv_bytes_per_token()
            live = sum(
                self._slot_len[i]
                for i in range(self.ecfg.max_slots)
                if self._slot_blocks[i]
            )
            # blocks allocated to a routed slot whose handoff/migration
            # is still in flight (_slot_len is 0 until activation): they
            # are BEING written, not fragmented — counting them would
            # false-fire the gauge on every disagg/migration run
            in_transit = sum(
                len(self._slot_blocks[i])
                for i in range(self.ecfg.max_slots)
                if self._slot_handoff[i] is not None
            )
            settled = used - in_transit
            fresh.update({
                "kv_pool_blocks": pool,
                "kv_free_blocks": free,
                "kv_retained_blocks": retained,
                "kv_used_blocks": used,
                "kv_block_size": self._blk,
                "kv_occupancy": used / pool,
                "kv_retained_fraction": retained / pool,
                # allocated-but-unwritten positions inside slot-owned
                # blocks (reservations are worst-case); shared prefixes
                # can push live-token totals past used*blk, so clamp
                "kv_fragmentation": (
                    min(max(1.0 - live / (settled * self._blk), 0.0), 1.0)
                    if settled > 0 else 0.0
                ),
                "kv_logical_bytes": live * bpt,
                "kv_physical_bytes": pool * self._blk * bpt,
                # host-RAM tier gauges (priced as HOST bytes — never in
                # the HBM headroom estimate)
                "kv_tier_blocks": len(self._tier),
                "kv_tier_bytes": self._tier_bytes,
                "kv_tier_capacity_bytes": self._tier_cap_bytes,
                "kv_tier_disabled": 1 if self._tier_disabled else 0,
            })

        err = self._run_admin(_collect, timeout_s=2.0)
        with self._obs_lock:
            if err is None and fresh:
                self._kv_gauges = dict(fresh)
                self._kv_gauges_t = time.time()
            return dict(self._kv_gauges)

    def kv_cache_snapshot(self) -> dict[str, Any]:
        """The results.json ``kv_cache`` block (core/schema.py
        validate_kv_cache): lifecycle counters plus the derived gauges,
        keyed the way the analyzer's /metrics scrape maps them
        (analysis/telemetry.py KV_METRIC_KEYS) — snapshotted directly in
        self-serve runs, where it is authoritative (it cannot race the
        server teardown the way a post-run scrape can). Called off the
        event loop once per run, so it can afford the forced scheduler
        rendezvous for a fully fresh gauge pass."""
        self._kv_admin_snapshot(force=True)
        s = self.snapshot_stats()
        block: dict[str, Any] = {
            "source": "engine:snapshot",
            "hit_depth_p50": s["kv_prefix_hit_depth_p50"],
            "hit_depth_p95": s["kv_prefix_hit_depth_p95"],
            "bytes_per_token": s["kv_bytes_per_token"],
            "reused_bytes": s["kv_reused_bytes"],
            "blocks_allocated": s["kv_blocks_allocated"],
            "retained_evictions": s["kv_retained_evictions"],
            "share_reclaims": s["kv_share_reclaims"],
            "prefix_hits": s["prefix_hits"],
            "prefix_lookups": s["prefix_lookups"],
            "headroom_estimate_bytes": s["hbm_headroom_estimate_bytes"],
        }
        for stats_key, sub in (
            ("kv_pool_blocks", "pool_blocks"),
            ("kv_free_blocks", "free_blocks"),
            ("kv_retained_blocks", "retained_blocks"),
            ("kv_used_blocks", "used_blocks"),
            ("kv_block_size", "block_size"),
            ("kv_occupancy", "occupancy"),
            ("kv_retained_fraction", "retained_fraction"),
            ("kv_fragmentation", "fragmentation"),
            ("kv_logical_bytes", "logical_bytes"),
            ("kv_physical_bytes", "physical_bytes"),
            ("kv_tier_demotions", "tier_demotions"),
            ("kv_tier_promotions", "tier_promotions"),
            ("kv_tier_hits", "tier_hits"),
            ("kv_tier_blocks", "tier_blocks"),
            ("kv_tier_bytes", "tier_bytes"),
            ("kv_tier_capacity_bytes", "tier_capacity_bytes"),
            ("kv_tier_disabled", "tier_disabled"),
            ("kv_migrated_blocks", "migrated_blocks"),
            ("kv_migrated_bytes", "migrated_bytes"),
            ("kv_export_blocks", "export_blocks"),
            ("hbm_bytes_in_use", "hbm_bytes_in_use"),
            ("hbm_peak_bytes", "hbm_peak_bytes"),
            ("hbm_bytes_limit", "hbm_bytes_limit"),
        ):
            if stats_key in s:
                block[sub] = s[stats_key]
        return block

    def disagg_snapshot(self) -> dict[str, Any]:
        """The results.json ``disagg`` block (docs/DISAGGREGATION.md):
        handoff counters keyed the way the analyzer's /metrics scrape
        maps them (analysis/telemetry.py DISAGG_METRIC_KEYS) —
        snapshotted directly in self-serve runs, where it is
        authoritative. Empty on colocated engines (no block, never
        fabricated zeros — the same absence contract as kv_cache)."""
        if self._disagg is None:
            return {}
        s = self.snapshot_stats()
        return {
            "source": "engine:snapshot",
            "handoffs": s["kv_handoffs"],
            "handoff_blocks": s["kv_handoff_blocks"],
            "handoff_wait_s": round(s["kv_handoff_wait_s"], 6),
            "handoff_drops": s["kv_handoff_drops"],
            "handoff_bytes_copied": s["kv_handoff_bytes_copied"],
            "lane_busy_s": round(s["prefill_lane_busy_s"], 6),
            "colocated_fallbacks": s["disagg_colocated_fallbacks"],
            "queue_depth": s["kv_handoff_queue_depth"],
            "degraded": bool(s["disagg_degraded"]),
        }

    def economics_snapshot(self) -> dict[str, Any]:
        """The results.json ``economics`` block (docs/ECONOMICS.md):
        live-rail gauges keyed the way the analyzer's /metrics scrape
        maps them (analysis/telemetry.py ECON_METRIC_KEYS) — snapshotted
        directly in self-serve runs, where it is authoritative. Empty on
        engines without the rail (CPU backends with no econ_accelerator:
        no block, never fabricated $0 — the same absence contract as
        kv_cache/disagg). The marginal-replica gauge never appears here:
        it is a fleet-router aggregate, not a single-engine fact."""
        if self._econ is None:
            return {}
        s = self.snapshot_stats()
        block: dict[str, Any] = {
            "source": "engine:snapshot",
            "usd_per_hour": s["econ_usd_per_hour"],
        }
        for stats_key, sub in (
            ("econ_usd_per_1k_tokens", "usd_per_1k_tokens"),
            ("econ_wh_per_1k_tokens", "wh_per_1k_tokens"),
            ("econ_tokens_per_sec", "tokens_per_sec"),
        ):
            if stats_key in s:
                block[sub] = s[stats_key]
        return block

    def compile_stats_snapshot(self) -> dict[str, Any]:
        """The results.json ``compile_stats`` block (docs/PROFILING.md):
        recorder totals keyed the way the analyzer's /metrics scrape maps
        them, plus the per-executable entries for run artifacts."""
        cs = self._compile_recorder.snapshot()
        return {
            "compiles": cs["compiles"],
            "compile_wall_s": round(cs["compile_s"], 4),
            "flops": cs["compiled_flops"],
            "bytes_accessed": cs["compiled_bytes"],
            "peak_bytes": cs["compile_peak_bytes"],
            "executables": [
                e.to_dict() for e in self._compile_recorder.entries()
            ],
        }

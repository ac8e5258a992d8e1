"""Disaggregated prefill/decode serving: the prefill lane and the
KV-block handoff protocol (docs/DISAGGREGATION.md).

The engine's scheduler thread is the DECODE lane: it retires decode
sweeps, and every millisecond it spends executing a prompt prefill is a
millisecond every streaming client's next token waits (chunked prefill —
PR 11 — bounds that stall; it does not remove it). This module moves
prompt prefills off that thread entirely:

- **PrefillLane** — a dedicated worker (its own thread, optionally its
  own mesh submesh via ``parallel/mesh.lane_meshes``) that owns a
  single-slot STAGING KV cache and its own compiled prefill executables
  (``disagg_prefill[bucket]`` / ``disagg_chunk_prefill[bucket]`` in the
  compile-stats rail). It consumes routed admissions from a bounded job
  queue, runs the prompt's prefill pieces against the staging cache, and
  emits one finished :class:`KVHandoff` per request.

- **KVHandoff** — the explicit, versioned handoff protocol: the staged
  KV payload (the slot stripe as the model's cache tree — int8 values +
  per-position f32 scales when the cache is quantized, bf16 otherwise),
  the last-position logits the first sampled token needs, block-count
  accounting (``n_blocks`` at the engine's ``kv_block_size``
  granularity), and prefix-attribution metadata
  (``reused_prefix_tokens``; always 0 in v1 — the lane has no prefix
  index). A payload computed under a different protocol version is
  REFUSED at consume (tombstoned, degrade-to-colocated) rather than
  injected: silently consuming a mismatched layout would corrupt the
  slot's cache.

- **Degrade ladder** — every failure mode ends in COLOCATED prefill,
  never a hung request: a dropped handoff (the ``kv_handoff_drop``
  injection point, a lane-side exception, a version mismatch) arrives
  as a TOMBSTONE and the engine re-prefills that prompt on the
  scheduler thread; ``DROPS_TO_DEGRADE`` consecutive tombstones (or a
  dead lane thread) flips the engine to colocated routing for the rest
  of the run (``disagg_degraded`` gauge). A handoff that never arrives
  at all (lane wedged without even a tombstone) hits the consume-side
  ``HANDOFF_TIMEOUT_S`` and takes the same colocated path.

Two payload formats, negotiated by KV layout (docs/DISAGGREGATION.md):

- **v1 (dense)** — the handoff unit is the SLOT STRIPE: the lane owns a
  1-slot staging cache, and consume injects the staged stripe verbatim
  (``update_cache_slots``) — one device-side copy per handoff.
- **v2 (paged, ``HANDOFF_VERSION``)** — the handoff unit is a BLOCK
  TABLE: the scheduler reserves blocks from the engine's shared pool at
  routing time and the lane prefills straight into them through the
  engine's own compiled paged executables (``Engine._lane_paged_prefill``
  — per-dispatch ``_cache_lock`` serializes the cache swap against the
  scheduler; device execution orders by buffer dependencies). The
  ``KVHandoff`` then carries NO KV bytes at all (``kv=None``): consume
  installs the slot's table row and the handoff tax is a host-side
  pointer write.

Byte-identity either way: both paths run the SAME forward, params,
bucket shapes, and piece schedule as colocated monolithic admission, so
greedy streams are byte-identical to the colocated engine's — pinned by
tests/test_disagg.py.
"""

from __future__ import annotations

import queue
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from kserve_vllm_mini_tpu.runtime.tracing import now_s  # the runtime's one clock

# Protocol versions stamped on every payload; bump whenever a payload's
# layout/semantics change. Consume accepts exactly the version its KV
# layout speaks (v2 block tables on paged engines, v1 dense stripes on
# dense ones) and refuses the rest (tombstone -> colocated re-prefill),
# so a rolling upgrade can never install a stale-layout payload.
HANDOFF_VERSION = 2        # paged block-table handoff (zero KV bytes)
DENSE_HANDOFF_VERSION = 1  # dense staged-stripe handoff

# consecutive tombstoned handoffs before the engine stops routing to the
# lane entirely (degrade-to-colocated for the rest of the run); one
# successful handoff resets the run
DROPS_TO_DEGRADE = 3

# consume-side last resort: a routed slot whose handoff has not arrived
# (payload OR tombstone) within this many seconds is re-prefilled
# colocated — a lane that dies without flushing can never hang a client.
# Generous on purpose: the lane tombstones every per-job failure and
# flushes its queue on crash, so this only fires when even that machinery
# is gone.
HANDOFF_TIMEOUT_S = 60.0


@dataclass
class KVHandoff:
    """One finished prefill crossing lanes (the wire unit of the
    protocol). ``kv`` is the staged slot stripe in the model's cache-tree
    layout — ``{"k","v"}`` leaves ``[L, 1, KVH, T, D]``, plus
    ``{"k_s","v_s"}`` ``[L, 1, KVH, T]`` f32 scales when the KV cache is
    int8-quantized — exactly what ``update_cache_slots`` writes back at
    the destination slot. ``dropped=True`` marks a tombstone: the
    payload was lost (injected drop, lane error, version mismatch) and
    the consumer must degrade to colocated prefill."""

    version: int
    request_id: str
    handle: Any                      # the engine RequestHandle (identity key)
    n_tokens: int = 0                # prompt tokens whose KV is staged
    n_blocks: int = 0                # ceil(n_tokens / kv_block_size)
    reused_prefix_tokens: int = 0    # prefix attribution (v1: lane has no index)
    chunks: int = 0                  # lane prefill pieces dispatched
    busy_s: float = 0.0              # lane compute wall for this prefill
    kv: Optional[dict[str, Any]] = None      # staged stripe (None on tombstone)
    logits: Optional[Any] = None     # [V] f32 last-position logits
    t_enqueued: float = 0.0          # handoff-queue entry (wait accounting)
    dropped: bool = False            # tombstone: degrade to colocated
    error: str = ""                  # why (tombstones only)


@dataclass
class _LaneStats:
    """Lane-internal counters, published under one lock (KVM05x: the
    lane thread writes, snapshot readers are server/scheduler threads)."""

    prefills: int = 0
    busy_s: float = 0.0
    drops: int = 0
    errors: int = 0
    lock: threading.Lock = field(default_factory=threading.Lock)


class PrefillLane:
    """The dedicated prefill worker of a disaggregated engine.

    Owns a 1-slot staging KV cache plus its own compiled prefill
    executables, consumes routed admissions from a bounded job queue,
    and pushes finished :class:`KVHandoff` payloads (or tombstones —
    NEVER nothing) onto the ready queue the engine's scheduler drains
    between sweeps. All cross-thread state is internally locked or
    thread-safe queues; the staging cache and compiled-fn dict are
    lane-thread-only.
    """

    def __init__(
        self,
        params: dict[str, Any],
        cfg: Any,                    # models/config.py ModelConfig
        ecfg: Any,                   # runtime/engine.py EngineConfig
        pad_id: int = 0,
        instrument: Optional[Callable[[Any, str], Any]] = None,
        faults: Optional[Any] = None,         # runtime/faults.py FaultRegistry
        prefill_mesh: Optional[Any] = None,   # parallel/mesh.lane_meshes submesh
        max_inflight: Optional[int] = None,
        paged_prefill: Optional[Callable[[Any, dict], Any]] = None,
        kernels: str = "auto",       # models/llama.py forward ``kernels``:
                                     # "off" when the lane's steps are
                                     # partitioned over a mesh
    ) -> None:
        self.cfg = cfg
        self._kernels = kernels
        self.ecfg = ecfg
        self.pad_id = pad_id
        self._instrument = instrument or (lambda fn, label: fn)
        self._faults = faults
        self.prefill_mesh = prefill_mesh
        # HANDOFF_VERSION=2 hook (paged engines): the engine's
        # _lane_paged_prefill bound method — (handle, meta) -> (logits,
        # chunks) — which writes the prompt's KV straight into the
        # shared-pool blocks meta["row"] names. None = v1 dense lane
        # with its own staging cache.
        self._paged_prefill = paged_prefill
        # backpressure bound: jobs routed but not yet handed off. Past it
        # the engine admits colocated (accepts() goes False) — the lane
        # sheds load back to the decode lane instead of queueing unbounded
        self.max_inflight = max_inflight or max(ecfg.max_slots, 1)
        if prefill_mesh is not None:
            # per-lane mesh (parallel/mesh.lane_meshes): the lane computes
            # on its own device subset with tp-sharded params; the staged
            # stripe crosses lanes through host memory (_to_host below)
            from kserve_vllm_mini_tpu.parallel.sharding import shard_params

            self.params = shard_params(params, cfg, prefill_mesh)
        else:
            # thread-only lanes share the engine's params by reference —
            # zero weight duplication, the handoff stays on-device
            self.params = params
        self._staging: Optional[dict[str, Any]] = None  # lazy (lane thread)
        self._prefill_fns: dict[Any, Any] = {}
        self._jobs: "queue.Queue[Any]" = queue.Queue()
        self._ready: "queue.Queue[KVHandoff]" = queue.Queue()
        self._inflight = 0               # routed-not-yet-ready, under _lock
        self._lock = threading.Lock()
        self.stats = _LaneStats()
        self._stop = threading.Event()
        self._dead = False               # lane loop crashed (under _lock)
        self._thread: Optional[threading.Thread] = None

    # -- engine-facing API (any thread) ------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="prefill-lane"
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)

    def accepts(self) -> bool:
        """Whether the engine should route the next admission here:
        lane alive and under the backpressure bound. False = admit
        colocated (the degrade ladder's zeroth step)."""
        with self._lock:
            return (
                not self._dead
                and not self._stop.is_set()
                and self._inflight < self.max_inflight
            )

    def queue_depth(self) -> int:
        """Routed prefills not yet consumed (jobs pending or computing
        plus finished handoffs awaiting the scheduler) — the
        ``kv_handoff_queue_depth`` gauge and the ``handoff_stall``
        monitor rule's input."""
        with self._lock:
            return self._inflight

    def submit(self, handle: Any, meta: Optional[dict] = None) -> None:
        """Route one admission to the lane (scheduler thread; the caller
        checked ``accepts()``). ``meta`` is the v2 block reservation —
        ``{"row", "off", "keys"}`` — for paged lanes; None on v1."""
        with self._lock:
            self._inflight += 1
        self._jobs.put((handle, meta))

    def pop_ready(self) -> Optional[KVHandoff]:
        """Next finished handoff (payload or tombstone), or None. The
        scheduler drains these between sweeps (_consume_handoffs)."""
        try:
            ho = self._ready.get_nowait()
        except queue.Empty:
            return None
        with self._lock:
            self._inflight -= 1
        return ho

    def snapshot(self) -> dict[str, Any]:
        with self.stats.lock:
            return {
                "lane_prefills": self.stats.prefills,
                "lane_busy_s": self.stats.busy_s,
                "lane_drops": self.stats.drops,
                "lane_errors": self.stats.errors,
            }

    # -- lane thread -------------------------------------------------------

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    handle, meta = self._jobs.get(timeout=0.05)
                except queue.Empty:
                    continue
                ho = self._one_job(handle, meta)
                ho.t_enqueued = now_s()
                self._ready.put(ho)
        finally:
            # the lane must NEVER exit with jobs unanswered: whatever is
            # still queued tombstones out so the consume path degrades
            # those requests to colocated prefill instead of hanging them
            with self._lock:
                self._dead = True
            while True:
                try:
                    h, _ = self._jobs.get_nowait()
                except queue.Empty:
                    break
                ho = self._tombstone(h, "prefill lane stopped")
                ho.t_enqueued = now_s()
                self._ready.put(ho)

    def _one_job(self, handle: Any, meta: Optional[dict] = None) -> KVHandoff:
        """One routed prefill -> exactly one KVHandoff (payload or
        tombstone — every exit path answers, the never-hang contract)."""
        if handle.cancelled is not None:
            # cancelled while queued in the lane: skip the compute; the
            # consume/cancel path already finishes the handle
            return self._tombstone(handle, "cancelled before lane prefill")
        try:
            if meta is not None and self._paged_prefill is not None:
                ho = self._paged_job(handle, meta)
            else:
                ho = self._prefill(handle)
        except Exception as e:  # noqa: BLE001 — a lane fault must become
            # a tombstone (degrade-to-colocated), never an unanswered job
            with self.stats.lock:
                self.stats.errors += 1
            return self._tombstone(handle, f"{type(e).__name__}: {e}")
        if self._faults is not None and self._faults.check("kv_handoff_drop"):
            # injected handoff loss (docs/RESILIENCE.md): the compute is
            # spent — exactly like a payload lost on a real transport —
            # and the tombstone makes the engine re-prefill colocated
            with self.stats.lock:
                self.stats.drops += 1
            return self._tombstone(
                handle, "injected kv_handoff_drop", busy_s=ho.busy_s,
            )
        return ho

    def _tombstone(self, handle: Any, error: str,
                   busy_s: float = 0.0) -> KVHandoff:
        return KVHandoff(
            version=(
                HANDOFF_VERSION if self._paged_prefill is not None
                else DENSE_HANDOFF_VERSION
            ),
            request_id=handle.request.request_id,
            handle=handle, busy_s=busy_s, dropped=True, error=error,
        )

    def _paged_job(self, handle: Any, meta: dict) -> KVHandoff:
        """HANDOFF_VERSION=2: delegate the compute to the engine's
        _lane_paged_prefill (same executables as colocated — the KV
        lands directly in the reserved shared-pool blocks) and hand back
        a table-only payload: zero KV bytes cross the lanes."""
        t0 = now_s()
        logits, chunks = self._paged_prefill(handle, meta)
        return self._payload(
            handle, HANDOFF_VERSION, now_s() - t0, chunks, logits,
            reused_prefix_tokens=int(meta.get("off", 0)),
        )

    def _payload(self, handle: Any, version: int, wall: float, chunks: int,
                 logits: Any, kv: Any = None,
                 reused_prefix_tokens: int = 0) -> KVHandoff:
        """Book one finished lane prefill and wrap it into its handoff."""
        n = len(handle.request.prompt_tokens)
        blk = max(getattr(self.ecfg, "kv_block_size", 64), 1)
        with self.stats.lock:
            self.stats.prefills += 1
            self.stats.busy_s += wall
        return KVHandoff(
            version=version,
            request_id=handle.request.request_id,
            handle=handle,
            n_tokens=n,
            n_blocks=-(-n // blk),
            reused_prefix_tokens=reused_prefix_tokens,
            chunks=chunks,
            busy_s=wall,
            kv=kv,
            logits=logits,
        )

    # -- compiled staging prefill (lane thread only) ------------------------

    def _make_staging(self) -> dict[str, Any]:
        import jax.numpy as jnp

        from kserve_vllm_mini_tpu.models.llama import init_kv_cache

        kv_quant = self.ecfg.kv_cache_dtype == "int8"
        kv_dt = (
            jnp.dtype(self.ecfg.kv_cache_dtype)
            if (self.ecfg.kv_cache_dtype and not kv_quant)
            else None
        )
        return init_kv_cache(
            self.cfg, 1, max_seq=self.ecfg.max_seq_len,
            dtype=kv_dt, quantized=kv_quant,
        )

    def _prefill_piece(self, piece: list[int], off: int):
        """One piece of a prompt into the staging cache, through
        runtime/steps.py's one prefill builder addressed at the whole cache
        — it IS the slot (B=1), so there is no slice/update pair. Returns
        the piece's last-position logits."""
        from kserve_vllm_mini_tpu.models.llama import forward
        from kserve_vllm_mini_tpu.runtime import steps

        bucket = steps.prefill_bucket(len(piece), self.ecfg)
        fresh = off == 0
        step = self._prefill_fns.get((bucket, fresh))
        if step is None:
            step = self._instrument(
                steps.build_prefill(
                    self.cfg, forward, self._kernels, "whole", fresh),
                steps.prefill_label("whole", fresh, bucket),
            )
            self._prefill_fns[bucket, fresh] = step
        self._staging, last_logits = step(
            self.params, self._staging,
            *steps.piece_operands("whole", piece, bucket, self.pad_id, off),
        )
        return last_logits

    def _get_slice_fn(self):
        """Jitted UNDONATED copy of the staging stripe: the payload must
        survive the next job's donated prefill over the same staging
        buffers."""
        fn = self._prefill_fns.get("slice")
        if fn is not None:
            return fn
        import jax

        from kserve_vllm_mini_tpu.models.llama import slice_cache_slots

        fn = jax.jit(lambda cache: slice_cache_slots(cache, 0))
        self._prefill_fns["slice"] = fn
        return fn

    def _prefill(self, handle: Any) -> KVHandoff:
        """Run one prompt's prefill against the staging cache: the same
        piece schedule as colocated monolithic admission (fresh piece at
        the prompt's bucket, continuation pieces at max_prefill_len), so
        the staged KV and last-position logits are byte-identical to
        what the engine would have computed in place."""
        import jax

        prompt = handle.request.prompt_tokens
        n = len(prompt)
        if self._staging is None:
            self._staging = self._make_staging()
        t0 = now_s()
        off, chunks = 0, 0
        last_logits = None
        budget = self.ecfg.max_prefill_len
        while off < n:
            piece = prompt[off : off + budget]
            last_logits = self._prefill_piece(piece, off)
            off += len(piece)
            chunks += 1
        payload = self._get_slice_fn()(self._staging)
        logits = last_logits
        if self.prefill_mesh is not None:
            # cross-mesh handoff travels through host memory: the decode
            # lane's inject re-uploads into its own layout. Same-device
            # lanes skip this (the payload stays on device, zero copies).
            payload = jax.device_get(payload)
            logits = jax.device_get(logits)
        else:
            jax.block_until_ready(logits)
        return self._payload(
            handle, DENSE_HANDOFF_VERSION, now_s() - t0, chunks, logits,
            kv=payload,
        )

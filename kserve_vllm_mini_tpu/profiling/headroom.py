"""Admission/headroom estimate: price a serving shape before building it.

The estimate pre-flights a serving config's memory footprint against
device capacity BEFORE any weights are materialized; ``plan_admission``
turns it into a labelled downshift (halve slots, then halve context) for
callers that prefer a smaller shape to a ``RESOURCE_EXHAUSTED``
(docs/PROFILING.md). Nothing on the serving path downshifts on its own:
the engine builds the shape it was asked for or fails.

The estimate is analytic (weights + KV + logits workspace + a fusion
margin), so it is deterministic, testable with mocked capacities, and
costs nothing; when a compiled executable exists its ``memory_analysis``
peak can be passed in to replace the workspace term with XLA's own
buffer-assignment number.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

# fraction of HBM the plan may fill: XLA needs slack for fusion scratch,
# infeed buffers, and the donation double-buffer window
DEFAULT_HEADROOM_FRACTION = 0.9


def hbm_watermarks(device: Any = None) -> dict[str, int]:
    """Live HBM watermarks from ``device.memory_stats()``:
    ``bytes_in_use`` always, ``peak_bytes_in_use``/``bytes_limit`` when
    the backend reports them. ABSENT — ``{}``, never fabricated zeros —
    on CPU backends (whose devices report None), so consumers can tell
    "no HBM telemetry" from "HBM empty" (docs/TROUBLESHOOTING.md)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    stats = device.memory_stats() or {}
    in_use = stats.get("bytes_in_use")
    if in_use is None:
        return {}
    out = {"bytes_in_use": int(in_use)}
    for key in ("peak_bytes_in_use", "bytes_limit"):
        v = stats.get(key)
        if v:
            out[key] = int(v)
    return out


def headroom_error_pct(
    estimate_bytes: Any, observed_peak_bytes: Any
) -> Optional[float]:
    """Headroom-model validation: signed % error of the analytic
    admission estimate vs the observed HBM peak. Positive = the model
    overestimates (safe but wasteful headroom); negative = it
    UNDERESTIMATES — the direction that RESOURCE_EXHAUSTs a run the
    guard admitted. None when either side is
    missing or non-positive (no peak observed = nothing to validate)."""
    try:
        est = float(estimate_bytes)
        peak = float(observed_peak_bytes)
    except (TypeError, ValueError):
        return None
    if est <= 0 or peak <= 0:
        return None
    return round((est - peak) / peak * 100.0, 2)


def kv_elem_bytes(head_dim: int, itemsize: float, quantized: bool = False) -> float:
    """Physical bytes one KV element costs: the raw element, or — for
    int8-quantized KV — 1 byte plus the per-head f32 scale amortized
    across head_dim. THE single copy of the quantized-KV price: the
    admission estimate (estimate_serving_bytes) and the engine's observed
    bytes gauges (Engine.kv_bytes_per_token) must price identically or
    headroom_error_pct compares two different models."""
    return (1.0 + 4.0 / head_dim) if quantized else float(itemsize)


def host_tier_block_bytes(cfg: Any, block_size: int,
                          kv_quant: bool = False) -> int:
    """Host-RAM bytes ONE demoted KV block occupies in the host tier
    (Engine._tier, EngineConfig.kv_host_tier_bytes) — the same
    kv_elem_bytes price the HBM estimate uses, applied to HOST memory.
    Deliberately a separate function from estimate_serving_bytes: the
    tier lives in host RAM and must NEVER inflate the HBM admission
    estimate (pinned in tests) — it only bounds how many evicted blocks
    the tier's byte budget can catch."""
    elem = kv_elem_bytes(cfg.head_dim, cfg.jnp_dtype.itemsize, kv_quant)
    return int(2 * cfg.n_layers * cfg.n_kv_heads * block_size
               * cfg.head_dim * elem)


def host_tier_capacity_blocks(cap_bytes: Optional[int], cfg: Any,
                              block_size: int,
                              kv_quant: bool = False) -> int:
    """How many demoted blocks a kv_host_tier_bytes budget can hold —
    the analytic sizing companion operators use to pick the knob (0 when
    the tier is off or the budget is under one block)."""
    if not cap_bytes:
        return 0
    per = host_tier_block_bytes(cfg, block_size, kv_quant)
    return max(int(cap_bytes) // per, 0) if per > 0 else 0


def _weight_bytes_per_param(quant: str) -> float:
    # int8: 1 byte + per-channel f32 scales (~1/256 of elements, rounded
    # up generously); int4: packed nibbles + scales; else dtype width
    if quant == "int8":
        return 1.02
    if quant == "int4":
        return 0.52
    if quant in ("bf16", "fp16", "float16", "bfloat16", ""):
        return 2.0
    return 4.0


def estimate_serving_bytes(
    cfg: Any,
    slots: int,
    max_seq: int,
    quant: str = "bf16",
    kv_quant: bool = False,
    quant_mode: str = "dequant",
    prefill_chunk: Optional[int] = None,
) -> dict[str, int]:
    """Analytic HBM footprint of the bench serving shape: weights + dense
    KV + the f32 logits/workspace the prefill and sampling steps need.
    ``cfg`` is a ``models.config.ModelConfig`` (only dims are read).

    ``quant_mode="w8a8"`` adds the activation-quant workspace: the int8
    copy of the widest activation a projection quantizes ([slots, T,
    max(d_ff, d_model)] for the w_down input) plus one f32 absmax scale
    per row — a transient XLA may or may not fuse away, priced so the
    guard can never admit a shape whose quantize step is the allocation
    that RESOURCE_EXHAUSTs (docs/PROFILING.md).

    ``prefill_chunk`` (EngineConfig.prefill_chunk) bounds the widest
    compiled prefill call: chunked prefill never materializes more than
    one chunk bucket of activations, so BOTH sequence-length workspace
    terms price the chunk instead of the monolithic bucket — chunking
    WIDENS the admissible configs rather than inheriting the monolithic
    estimate."""
    weights = int(cfg.param_count * _weight_bytes_per_param(quant))
    kv_elem = kv_elem_bytes(cfg.head_dim, cfg.jnp_dtype.itemsize, kv_quant)
    # a hybrid stack keeps K/V for its full-attention layers only, and a
    # float32 recurrent state and a convolution tail per slot for its
    # delta-rule layers (the plan has none of them in any other stack)
    kv = int(2 * cfg.n_full_layers * slots * cfg.n_kv_heads * max_seq
             * cfg.head_dim * kv_elem)
    h = cfg.linear_num_key_heads
    kv += int(cfg.n_linear_layers * slots * (
        4 * h * cfg.linear_key_head_dim * cfg.linear_value_head_dim
        + cfg.jnp_dtype.itemsize * (cfg.linear_conv_kernel_dim - 1)
        * cfg.linear_conv_channels))
    # widest live activation set tracks the widest compiled call: the
    # full prefill bucket monolithically, one chunk bucket when chunked
    prefill_len = (
        min(int(prefill_chunk), max_seq) if prefill_chunk else max_seq
    )
    # f32 last-position logits for the batch + one full-bucket activation
    # set; the 1.15 margin covers fusion scratch XLA actually allocates
    workspace = int(
        slots * cfg.vocab_size * 4 + slots * prefill_len * cfg.d_model * 2
    )
    if quant_mode == "w8a8":
        widest = max(getattr(cfg, "d_ff", cfg.d_model), cfg.d_model)
        workspace += int(slots * prefill_len * (widest + 4))
    total = int((weights + kv + workspace) * 1.15)
    return {"weight_bytes": weights, "kv_bytes": kv,
            "workspace_bytes": workspace, "total_bytes": total}


@dataclass
class HeadroomPlan:
    """The guard's decision for one config."""

    fits: bool                 # True even after downshifting succeeded
    slots: int                 # admitted slots (may be < requested)
    max_seq: int               # admitted context (may be < requested)
    estimate_bytes: int        # footprint of the ADMITTED shape
    capacity_bytes: int
    budget_bytes: int          # capacity * headroom fraction
    downshifted: Optional[str] = None   # "downshifted: ..." label, or None

    def to_dict(self) -> dict[str, Any]:
        out: dict[str, Any] = {
            "fits": self.fits,
            "slots": self.slots,
            "max_seq": self.max_seq,
            "estimate_bytes": self.estimate_bytes,
            "capacity_bytes": self.capacity_bytes,
            "budget_bytes": self.budget_bytes,
        }
        if self.downshifted:
            out["downshifted"] = self.downshifted
        return out


def plan_admission(
    estimate_fn: Callable[[int, int], int],
    capacity_bytes: int,
    slots: int,
    max_seq: int,
    min_slots: int = 8,
    min_seq: int = 256,
    fraction: float = DEFAULT_HEADROOM_FRACTION,
) -> HeadroomPlan:
    """Fit ``(slots, max_seq)`` under ``fraction * capacity``.

    Downshift order: halve slots to ``min_slots`` first (throughput knob —
    the measurement survives at lower batch), then halve context to
    ``min_seq`` (changes the workload more). The label records every hop
    so a downshifted round can never masquerade as the requested config.
    """
    budget = int(capacity_bytes * fraction)
    req_slots, req_seq = slots, max_seq
    est = estimate_fn(slots, max_seq)
    # clamp the last halving TO the floor rather than refusing it — from
    # the default 80 the sequence must be able to reach min_slots=8
    # (80->40->20->10->8), not stop at 10 and needlessly cut context
    while est > budget and slots > min_slots:
        slots = max(slots // 2, min_slots)
        est = estimate_fn(slots, max_seq)
    while est > budget and max_seq > min_seq:
        max_seq = max(max_seq // 2, min_seq)
        est = estimate_fn(slots, max_seq)
    label = None
    if (slots, max_seq) != (req_slots, req_seq):
        hops = []
        if slots != req_slots:
            hops.append(f"slots {req_slots}->{slots}")
        if max_seq != req_seq:
            hops.append(f"ctx {req_seq}->{max_seq}")
        label = (
            f"downshifted: {', '.join(hops)} "
            f"(est {estimate_fn(req_slots, req_seq) / 1e9:.1f} GB > "
            f"{fraction:.0%} of {capacity_bytes / 1e9:.1f} GB HBM)"
        )
    return HeadroomPlan(
        fits=est <= budget,
        slots=slots,
        max_seq=max_seq,
        estimate_bytes=est,
        capacity_bytes=capacity_bytes,
        budget_bytes=budget,
        downshifted=label,
    )


def serving_headroom_plan(
    model: str,
    slots: int,
    max_seq: int,
    quant: str,
    kv_quant: bool,
    capacity_bytes: int,
    quant_mode: str = "dequant",
    prefill_chunk: Optional[int] = None,
    **plan_kwargs: Any,
) -> HeadroomPlan:
    """``plan_admission`` over the analytic serving estimate for a named
    model config (context changes rebuild the config — the estimate must
    price the shape actually admitted). ``prefill_chunk`` prices the
    per-chunk prefill workspace instead of the monolithic one
    (estimate_serving_bytes)."""
    from kserve_vllm_mini_tpu.models.config import get_config

    def estimate(s: int, ctx: int) -> int:
        cfg = get_config(model, max_seq_len=ctx)
        return estimate_serving_bytes(cfg, s, ctx, quant=quant,
                                      kv_quant=kv_quant,
                                      quant_mode=quant_mode,
                                      prefill_chunk=prefill_chunk,
                                      )["total_bytes"]

    return plan_admission(estimate, capacity_bytes, slots, max_seq,
                          **plan_kwargs)

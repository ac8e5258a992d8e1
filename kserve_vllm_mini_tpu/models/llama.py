"""Llama-family decoder in pure functional JAX.

Design (TPU-first, not a port — the reference has no model code at all):

- parameters are a pytree of stacked per-layer arrays with a leading
  ``n_layers`` axis, walked with ``lax.scan`` so an 80-layer 70B compiles to
  one rolled loop instead of 80 unrolled blocks;
- one ``forward`` covers prefill and decode: the KV cache is a static-shape
  [L, B, KVH, S, D] pair written at per-slot offsets (decode-state slots are
  pre-allocated; XLA never sees a dynamic shape);
- attention masking is positional: query at absolute position p attends cache
  slot j iff j <= p, which subsumes causal prefill, chunked prefill, and
  decode against ragged slot fills in one formulation;
- bf16 params/activations feed the MXU; softmax/norm accumulate f32.

Weight layout matches HF Llama naming via models/loader.py so real
checkpoints (Llama-3.1-8B etc., BASELINE.json configs[1-4]) drop in.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from kserve_vllm_mini_tpu.models.config import ModelConfig
from kserve_vllm_mini_tpu.ops.attention import attention
from kserve_vllm_mini_tpu.ops.quant import linear
from kserve_vllm_mini_tpu.ops.rmsnorm import layer_norm, rms_norm
from kserve_vllm_mini_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]
# {"k": [L,B,KVH,S,D], "v": [L,B,KVH,S,D]} — plus, when int8-quantized,
# per-position scales {"k_s": [L,B,KVH,S], "v_s": [L,B,KVH,S]} (presence of
# "k_s" is the static flag that selects the quantized cache path)
KVCache = dict[str, jnp.ndarray]

def matmul_shapes(cfg: ModelConfig) -> dict[str, dict[str, tuple[int, ...]]]:
    """Per-layer shape of every matmul (the last two axes are [in, out];
    expert weights carry a leading expert axis), by kind of mixer and of
    feed-forward as ``cfg.layer_plan`` names them (``Layer.mixers``,
    ``Layer.ffn``). THE table: the initialisers stack a kind's rows for the
    layers that run it, in this order (the order fixes which rng key each
    weight draws from, so the bf16 and the quantized init draw the same
    values), and the int8 tree quantizes the rows ops/quant.py QUANTIZABLE
    names (all but the routers and the delta rule's ``wa``, ``wb``). A new
    kind of layer adds its row here and its names there."""
    from kserve_vllm_mini_tpu.models import linear_attention, mamba, short_conv

    d, hd, ff, n = cfg.d_model, cfg.head_dim, cfg.d_ff, cfg.n_experts
    mid, lat, held = cfg.moe_intermediate_size, cfg.moe_latent_size, cfg.experts_held
    shared = cfg.moe_shared_expert_intermediate_size
    mlp = {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    if cfg.block == "phi":
        del mlp["w_gate"]         # phi MLP is fc1/fc2 (up/down) with GELU: no gate
    return {
        "attn": {"wq": (d, cfg.n_heads * hd), "wk": (d, cfg.n_kv_heads * hd),
                 "wv": (d, cfg.n_kv_heads * hd), "wo": (cfg.n_heads * hd, d)},
        "ssm": mamba.mixer_weight_shapes(cfg),
        "conv": short_conv.mixer_weight_shapes(cfg),
        "linear": linear_attention.mixer_weight_shapes(cfg),
        "mlp": mlp,
        "moe": {"w_gate": (n, d, ff), "w_up": (n, d, ff), "w_down": (n, ff, d),
                "router": (d, n)},
        "glu": {"w_gate": (held, d, mid), "w_up": (held, d, mid), "w_down": (held, mid, d)},
        "latent": {"w_in": (d, lat), "w_out": (lat, d), "w1": (held, lat, mid),
                   "w2": (held, mid, lat), "s1": (d, shared), "s2": (shared, d)},
    }


def _stacked_weight_shapes(cfg: ModelConfig) -> dict[str, tuple[int, ...]]:
    """A uniform stack's matmuls: its one block's attention and feed-forward."""
    shapes = matmul_shapes(cfg)
    moe = cfg.is_moe and cfg.block != "phi"
    return {**shapes["attn"], **shapes["moe" if moe else "mlp"]}


def _init_keys(rng: jax.Array, cfg: ModelConfig) -> dict[str, jax.Array]:
    keys = jax.random.split(rng, 11)
    named = {"embed": keys[0], "lm_head": keys[8], "router": keys[9]}
    for i, name in enumerate(n for n in _stacked_weight_shapes(cfg) if n != "router"):
        named[name] = keys[1 + i]
    return named


def _nrm(key: jax.Array, shape: tuple, dt) -> jnp.ndarray:
    return (jax.random.normal(key, shape, dtype=jnp.float32) * 0.02).astype(dt)


def stack(key: jax.Array, n: int, shape: tuple, dt, fn) -> Any:
    """``n`` layers of one matmul, each drawn from its own key inside a scan
    and stored as ``fn`` of it: ``init_params_quantized`` never holds more
    than one layer in full precision."""
    def body(_, k):
        return None, fn(_nrm(k, shape, dt))

    return jax.lax.scan(body, None, jax.random.split(key, n))[1]


def gains(key: jax.Array, n: int, width: int, dt) -> jnp.ndarray:
    """Norm gains that show when they are left out (not 1)."""
    return (1.0 + 0.1 * jax.random.normal(key, (n, width), jnp.float32)).astype(dt)


def _init_impl(rng: jax.Array, cfg: ModelConfig, leaf_fn) -> Params:
    """Shared init skeleton. ``leaf_fn(w)`` maps each per-layer bf16 matmul
    weight to its stored leaf inside the per-layer scan — identity for the
    bf16 tree, quantize for the int8 tree. One implementation keeps the
    key-for-key RNG order identical between the two inits (the invariant
    the equivalence oracle in tests/test_quant.py rests on)."""
    dt = cfg.jnp_dtype
    keys = _init_keys(rng, cfg)
    if cfg.layer_period > 1:
        return _init_hybrid(keys, cfg, leaf_fn)
    if cfg.is_hybrid:
        return _init_stacks(keys, cfg, leaf_fn)
    L = cfg.n_layers

    layers: Params = {"attn_norm": jnp.ones((L, cfg.d_model), dtype=dt)}
    if cfg.block == "phi":
        # one LayerNorm (weight + bias) feeds both branches; biased o/fc
        layers["attn_norm_b"] = jnp.zeros((L, cfg.d_model), dtype=dt)
        layers["bo"] = jnp.zeros((L, cfg.d_model), dtype=dt)
        layers["b_up"] = jnp.zeros((L, cfg.d_ff), dtype=dt)
        layers["b_down"] = jnp.zeros((L, cfg.d_model), dtype=dt)
    elif cfg.block == "gemma2":
        # gemma norm weights are OFFSETS (applied as 1+w, the HF storage
        # convention), so identity init is zeros; four norms per layer
        # (sandwich: post-norms on both branches before their residuals)
        layers["attn_norm"] = jnp.zeros((L, cfg.d_model), dtype=dt)
        layers["post_attn_norm"] = jnp.zeros((L, cfg.d_model), dtype=dt)
        layers["mlp_norm"] = jnp.zeros((L, cfg.d_model), dtype=dt)
        layers["post_mlp_norm"] = jnp.zeros((L, cfg.d_model), dtype=dt)
    else:
        layers["mlp_norm"] = jnp.ones((L, cfg.d_model), dtype=dt)
    for name, shape in _stacked_weight_shapes(cfg).items():
        # the router is accuracy-critical and noise-level bytes — it stays
        # full precision even in the int8 tree (models/moe.py contract)
        layers[name] = stack(keys[name], L, shape, dt,
                              leaf_fn if name != "router" else (lambda w: w))
    if cfg.attn_bias:
        hd, kvd = cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        layers["bq"] = jnp.zeros((L, cfg.n_heads * hd), dtype=dt)
        layers["bk"] = jnp.zeros((L, kvd), dtype=dt)
        layers["bv"] = jnp.zeros((L, kvd), dtype=dt)

    params = _tree_around(keys, cfg, layers)
    if cfg.block == "gemma2":
        params["final_norm"] = jnp.zeros((cfg.d_model,), dtype=dt)
    if cfg.block == "phi":
        params["final_norm_b"] = jnp.zeros((cfg.d_model,), dtype=dt)
        params["lm_head_b"] = jnp.zeros((cfg.vocab_size,), dtype=dt)
    return params


def _tree_around(keys: dict[str, jax.Array], cfg: ModelConfig, layers: Params) -> Params:
    """The parameter tree around ``layers``: embedding, final gain, head."""
    dt = cfg.jnp_dtype
    params: Params = {
        "embed": _nrm(keys["embed"], (cfg.vocab_size, cfg.d_model), dt),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _nrm(keys["lm_head"], (cfg.vocab_size, cfg.d_model), dt)
    return params


def _init_hybrid(keys: dict[str, jax.Array], cfg: ModelConfig, leaf_fn) -> Params:
    """The delta-rule period's tree: ``layers`` holds each kind's whole block
    stacked on its own, ``{"full": [n_full, ...], "linear": [n_linear, ...]}``,
    in stack order within the kind. Its keys split as they did before the
    other stacks came (the MLP and its norms first, under one key), so it
    keeps its own body beside ``_init_stacks``. Gains, taps and gate
    parameters get values that show when they are left out (not 1, not 0)."""
    from kserve_vllm_mini_tpu.ops.quant import QUANTIZABLE

    dt = cfg.jnp_dtype
    shapes = matmul_shapes(cfg)

    def mlp_and_norms(key, n):
        ks = jax.random.split(key, 5)
        out = {name: stack(k, n, shape, dt, leaf_fn)
               for (name, shape), k in zip(shapes["mlp"].items(), ks)}
        out["post_attn_norm"] = gains(ks[3], n, cfg.d_model, dt)
        out["post_mlp_norm"] = gains(ks[4], n, cfg.d_model, dt)
        return out

    nf, nl, h = cfg.n_full_layers, cfg.n_linear_layers, cfg.linear_num_key_heads
    kf = jax.random.split(keys["wq"], 8)
    full: Params = mlp_and_norms(kf[0], nf)
    for (name, shape), k in zip(shapes["attn"].items(), kf[1:5]):
        full[name] = stack(k, nf, shape, dt, leaf_fn)
    # a hybrid stack is an olmo2 block (ModelConfig holds it to that): QK-norm
    full["q_norm"] = gains(kf[5], nf, cfg.n_heads * cfg.head_dim, dt)
    full["k_norm"] = gains(kf[6], nf, cfg.n_kv_heads * cfg.head_dim, dt)
    kl = jax.random.split(keys["wk"], 12)
    lin: Params = mlp_and_norms(kl[0], nl)
    for (name, shape), k in zip(shapes["linear"].items(), kl[1:8]):
        # wa, wb [d, heads] stay as they are in the int8 tree (ops/quant.py)
        lin[name] = stack(k, nl, shape, dt,
                           leaf_fn if name in QUANTIZABLE else (lambda w: w))
    lin["conv"] = (0.3 * jax.random.normal(
        kl[8], (nl, cfg.linear_conv_kernel_dim, cfg.linear_conv_channels), jnp.float32)
    ).astype(dt)
    # decays between ~0.5 and ~0.999 a step at a zero pre-activation
    lin["A_log"] = jnp.log(jax.random.uniform(kl[9], (nl, h), jnp.float32, 0.5, 4.0))
    lin["dt_bias"] = jax.random.uniform(kl[10], (nl, h), jnp.float32, -6.0, -1.0)
    lin["o_norm"] = gains(kl[11], nl, cfg.linear_value_head_dim, dt)
    return _tree_around(keys, cfg, {"full": full, "linear": lin})


def scaled(x: jnp.ndarray, by: float) -> jnp.ndarray:
    """``x`` times a fixed scalar of the configuration (a muP multiplier),
    formed in float32 and returned in ``x``'s type; ``x`` itself at 1.0, so a
    model without multipliers traces nothing."""
    return x if by == 1.0 else (x.astype(jnp.float32) * by).astype(x.dtype)


def _init_stacks(keys: dict[str, jax.Array], cfg: ModelConfig, leaf_fn) -> Params:
    """The tree of a stack whose kinds of layer lie each in a stack of its
    own (every ``cfg.layer_plan`` walked unrolled): ``layers`` holds
    ``{stack: {leaf: [n, ...]}}`` for the stacks the plan's layers name
    (``Layer.stack``, ``Layer.ffn_stack``), in stack order within each; a
    stack of no layers has no entry. The table below gives each stack its
    matmuls (``matmul_shapes`` rows) and its small leaves, each a
    ``(name, draw(key, n))``: a mixer's stack holds its layer's ``norm_in``,
    a feed-forward's its ``ffn_norm``. The stacks take the four matmul keys
    in the table's order; a stack splits its key into the matmuls' keys, then
    the small leaves' from ``first`` on. Small leaves get values that show
    when they are left out. A new kind of layer adds its stack here."""
    dt, d = cfg.jnp_dtype, cfg.d_model
    shapes = matmul_shapes(cfg)

    def gain(width=d):
        return lambda k, n: gains(k, n, width, dt)

    def noise(std, *shape):
        return lambda k, n: (std * jax.random.normal(k, (n, *shape), jnp.float32)).astype(dt)

    def uniform(lo, hi):
        return lambda k, n: jax.random.uniform(
            k, (n, cfg.mamba_num_heads), jnp.float32, lo, hi)

    def router(k, n):
        return stack(k, n, (d, cfg.n_routed_experts), dt, lambda w: w.astype(jnp.float32))

    biased = [("router", router),
              ("router_bias", lambda k, n: 0.05 * jax.random.normal(
                  k, (n, cfg.n_routed_experts), jnp.float32))]
    if cfg.is_pattern_stack:
        # one norm a block, whatever it runs; the small leaves' keys from the
        # ninth on, where the three single-mixer kinds have always had them
        least = 8
        ssm = [("conv", noise(0.3, cfg.conv_kernel, cfg.mamba_conv_channels)),
               ("conv_b", noise(0.1, cfg.mamba_conv_channels)),
               ("A_log", lambda k, n: jnp.log(uniform(1.0, 16.0)(k, n))),
               ("dt_bias", uniform(-5.0, -1.0)), ("D", uniform(0.5, 1.5)),
               ("norm", gain(cfg.mamba_d_inner))]
        table = {
            "mamba": (shapes["ssm"], [("norm_in", gain()), *ssm]),
            "attn": (shapes["attn"], [("norm_in", gain())]),
            "experts": (shapes["latent"], [("norm_in", gain()), *biased]),
            # both mixers and the MLP of a two-mixer block, in one stack
            "parallel": ({**shapes["ssm"], **shapes["attn"], **shapes["mlp"]},
                         [("norm_in", gain()), *ssm, ("mlp_norm", gain())]),
        }
    elif cfg.is_conv_stack:
        least = 4
        table = {
            "conv": (shapes["conv"], [("norm_in", gain()),
                                      ("conv", noise(0.5, cfg.conv_L_cache, d))]),
            # QK-norm: one gain a head dimension, shared by every head
            "attn": (shapes["attn"], [("norm_in", gain()), ("q_norm", gain(cfg.head_dim)),
                                      ("k_norm", gain(cfg.head_dim))]),
            "mlp": (shapes["mlp"], [("ffn_norm", gain())]),
            "experts": (shapes["glu"], [("ffn_norm", gain()), *biased]),
        }
    else:
        # window and global layers share one attention stack: the plan, not
        # the weights, tells them apart. The router (no selection bias) draws
        # from the key that is the router's in a uniform stack
        least = 4
        table = {
            "attn": (shapes["attn"], [("norm_in", gain())]),
            "experts": (shapes["glu"], [("ffn_norm", gain()),
                                        ("router", lambda _, n: router(keys["router"], n))]),
        }
    held = [e.stack for e in cfg.layer_plan if e.mixers]
    held += [e.ffn_stack for e in cfg.layer_plan if e.ffn and e.ffn_stack != e.stack]
    layers: Params = {}
    for (name, (matmuls, small)), key in zip(
            table.items(), (keys["wq"], keys["wk"], keys["wv"], keys["wo"])):
        n = held.count(name)
        if not n:
            continue
        first = max(len(matmuls), least)
        ks = jax.random.split(key, first + least)
        layers[name] = {leaf: stack(k, n, shape, dt, leaf_fn)
                        for (leaf, shape), k in zip(matmuls.items(), ks)}
        layers[name].update({leaf: draw(k, n) for (leaf, draw), k in zip(small, ks[first:])})
    return _tree_around(keys, cfg, layers)


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Random-normal init (0.02 std), bf16 — for tests, benches, and as the
    target pytree structure for checkpoint loading.

    Stacked weights are drawn layer-by-layer from per-layer keys (a
    ``lax.scan`` over ``jax.random.split(key, L)``) so
    ``init_params_quantized`` can draw the exact same values one layer at a
    time without ever materializing the full-precision stack."""
    return _init_impl(rng, cfg, lambda w: w)


def init_params_quantized(rng: jax.Array, cfg: ModelConfig, bits: int = 8) -> Params:
    """Random init straight into int8 (or int4) leaves, one layer at a time.

    Materializing the 8B bf16 tree first needs ~16 GB — the whole v5e HBM —
    before quantization can even start. Here each stacked matmul weight is drawn
    per layer inside a ``lax.scan`` and quantized immediately, so the peak
    transient is ONE layer's f32 weight (~1 GB for 8B) on top of the int8
    output. Equal to ``quantize_params(init_params(rng, cfg))`` to within
    one quantization LSB (same per-layer keys, same per-output-channel
    scale math — oracle-tested on llama-tiny in tests/test_quant.py)."""
    from kserve_vllm_mini_tpu.ops.quant import quantize_weight

    def leaf_fn(w):
        # the barrier materializes the layer's true bf16 values before
        # quantize_weight reads them back in f32 — without it XLA fuses
        # the bf16 cast into the quantize math and rounds at a different
        # boundary than quantize-after-init (±1 LSB drift)
        return quantize_weight(jax.lax.optimization_barrier(w), bits=bits)

    return _init_impl(rng, cfg, leaf_fn)


def init_kv_cache(
    cfg: ModelConfig,
    batch: int,
    max_seq: Optional[int] = None,
    dtype: Optional[Any] = None,
    quantized: bool = False,
) -> KVCache:
    """``quantized=True`` -> int8 cache with per-(position, head) f32
    scales: (D+4)/(2D) of the bf16 cache's HBM footprint (~52% at D=128)
    and the same factor off the bytes streamed per decode step (the KV
    read is the second-largest stream after the weights). Values are quantized on write with
    a per-position amax scale — reference analog: the kv-cache-dtype knob
    the quantization sweep measures (sweeps/quantization_sweep.py:40-234)."""
    from kserve_vllm_mini_tpu.models import linear_attention, mamba, short_conv

    s = max_seq or cfg.max_seq_len
    # a hybrid stack keeps K/V for its full-attention layers only, and beside
    # them (same tree, slot axis still dim 1) the other leaves its plan names
    shape = (cfg.n_full_layers, batch, cfg.n_kv_heads, s, cfg.head_dim)
    leaves = cfg.layer_plan.leaves
    extra: KVCache = {}
    if "k_win" in leaves:
        # two K/V leaves of different lengths in the one tree: ``k``/``v``
        # above are the global layers' stripes; the window layers keep a
        # ring of ring_len positions, addressed ``p mod R``
        if quantized:
            raise ValueError("a sliding_window_layout stack keeps its K/V in the model's "
                             "type: the ring has no int8 leaves")
        ring = (cfg.n_window_layers, batch, cfg.n_kv_heads, cfg.ring_len(s), cfg.head_dim)
        extra = {name: jnp.zeros(ring, dtype=dtype or cfg.jnp_dtype)
                 for name in ("k_win", "v_win")}
    for mixer in (mamba, short_conv, linear_attention):
        extra.update({name: jnp.zeros(shp, dtype=dt)
                      for name, (shp, dt) in mixer.state_shapes(cfg, batch).items()
                      if name in leaves})
    if quantized:
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "k_s": jnp.zeros(shape[:-1], dtype=jnp.float32),
            "v_s": jnp.zeros(shape[:-1], dtype=jnp.float32),
            **extra,
        }
    return {
        "k": jnp.zeros(shape, dtype=dtype or cfg.jnp_dtype),
        "v": jnp.zeros(shape, dtype=dtype or cfg.jnp_dtype),
        **extra,
    }


def init_paged_kv_cache(
    cfg: ModelConfig,
    n_blocks: int,
    block_size: int = 64,
    dtype: Optional[Any] = None,
    quantized: bool = False,
) -> KVCache:
    """Block-pool KV cache for paged attention (the TPU answer to vLLM's
    PagedAttention, the reference stack's namesake mechanism).

    Layout: ``k``/``v`` are [L, P, KVH, BLK, D] pools of P blocks of BLK
    token positions each; a request owns an ordered list of block ids (its
    block table) instead of a private [max_seq] stripe. Dense serving must
    reserve slots x max_seq positions up front — 64 slots x 4096 max_seq
    of 8B bf16 KV is 34 GB, unservable on a 16 GB v5e — while the pool is
    sized by TOKENS IN FLIGHT (admission reserves worst-case
    ceil((prompt+max_new)/BLK) blocks per request), so long max_model_len
    stops multiplying across slots.

    Same dict contract as ``init_kv_cache`` (`k`/`v` [+ `k_s`/`v_s` int8
    scales]); the rank-5 value layout moves the slot axis to a block axis.
    Consumed by ``forward(..., block_table=...)``.
    """
    if cfg.is_hybrid:
        raise ValueError("a hybrid stack's recurrent state has no positions "
                         "to page; use the dense cache")
    shape = (cfg.n_layers, n_blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
    if quantized:
        return {
            "k": jnp.zeros(shape, dtype=jnp.int8),
            "v": jnp.zeros(shape, dtype=jnp.int8),
            "k_s": jnp.zeros(shape[:-1], dtype=jnp.float32),
            "v_s": jnp.zeros(shape[:-1], dtype=jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, dtype=dtype or cfg.jnp_dtype),
        "v": jnp.zeros(shape, dtype=dtype or cfg.jnp_dtype),
    }


def slice_cache_slots(cache: KVCache, slot, n: int = 1) -> KVCache:
    """Sub-cache for slots [slot, slot+n) — slot axis is dim 1 on every
    leaf (value tensors are rank-5, scale tensors rank-4)."""
    out = {}
    for key, arr in cache.items():
        starts = (0, slot) + (0,) * (arr.ndim - 2)
        sizes = (arr.shape[0], n) + arr.shape[2:]
        out[key] = jax.lax.dynamic_slice(arr, starts, sizes)
    return out


def update_cache_slots(cache: KVCache, sub: KVCache, slot) -> KVCache:
    """Write a sub-cache back at ``slot`` (inverse of slice_cache_slots)."""
    return {
        key: jax.lax.dynamic_update_slice(
            arr, sub[key], (0, slot) + (0,) * (arr.ndim - 2)
        )
        for key, arr in cache.items()
    }


def _quantize_kv_block(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B,KVH,T,D] -> (int8 values, f32 per-position scales [B,KVH,T])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale




def qkv_proj(
    p: Params,
    cfg: ModelConfig,
    h: jnp.ndarray,              # [B, T, D] (already attn-normed)
    positions: jnp.ndarray,      # [B, T]
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    lora: Optional[Params] = None,       # one layer's adapter bank slices
    lora_ids: Optional[jnp.ndarray] = None,  # [B] adapter index per row
    rope: bool = True,           # static: False = this LAYER carries no position
                                 # (a rope_layout stack's 0 entries)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """QKV projections + RoPE -> (q [B,H,T,hd], k [B,KVH,T,hd], v). The one
    implementation every execution path (scan-rolled, cached, pipelined)
    shares. With ``lora``/``lora_ids`` each row adds its adapter's low-rank
    delta (ops/lora.py; index 0 = base)."""
    from kserve_vllm_mini_tpu.ops.lora import adapted_linear

    B, T, _ = h.shape
    qm = cfg.quant_mode
    h = scaled(h, cfg.attention_in_multiplier)
    q = adapted_linear(h, p["wq"], lora, "wq", lora_ids, mode=qm)
    k = scaled(adapted_linear(h, p["wk"], lora, "wk", lora_ids, mode=qm), cfg.key_multiplier)
    v = adapted_linear(h, p["wv"], lora, "wv", lora_ids, mode=qm)
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.block == "olmo2":
        # the family's QK-norm: over the whole projection, before the heads split
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    # The layout the head split wants stops here. Without the barrier the TPU
    # compiler carries it back through the dot onto the weight (heads become a
    # batch dimension of the dot), and feeds that dot by slicing the layer's
    # weight out of its stack and transposing all of it to [H, hd, d_in] every
    # step: 25 MB a layer on a 7B model, a fifth of a decode step. Behind the
    # barrier each projection is one fusion that reads the stack in place, as
    # wo and the MLP weights are read (profiling/compile_stats.py
    # weight_copy_bytes; tests/test_weight_copy_preflight.py).
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    if cfg.is_conv_stack:
        # LFM2's QK-norm: over each head's head_dim, one gain for every head
        # (olmo2's above is over the whole projection)
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    rd = cfg.rotary_dim if rope else 0
    if rd == 0:
        return q, k, v           # attention that carries no position
    if rd < cfg.head_dim:
        # phi-style partial rotary: RoPE on the first rotary_dim dims, the
        # rest pass through (cos/sin tables are built at rotary_dim width)
        q = jnp.concatenate(
            [apply_rope(q[..., :rd], positions, cos, sin), q[..., rd:]], axis=-1
        )
        k = jnp.concatenate(
            [apply_rope(k[..., :rd], positions, cos, sin), k[..., rd:]], axis=-1
        )
        return q, k, v
    return apply_rope(q, positions, cos, sin), apply_rope(k, positions, cos, sin), v


def embed_tokens(params: Params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """Embedding lookup + family-specific input transform. EVERY execution
    path (forward, pipeline trainer, serving-pp executor) must enter the
    layer stack through this helper — gemma scales embeddings by
    sqrt(d_model), and an executor that skips it produces silently-wrong
    activations ~sqrt(d_model)x too small."""
    x = scaled(params["embed"][tokens], cfg.embedding_multiplier)
    if cfg.block == "gemma2":
        # computed in the model dtype, matching the published
        # implementation's bf16 rounding
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype=cfg.jnp_dtype)
    return x


def final_logits(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Final norm + LM head + family epilogues (phi bias, gemma (1+w) norm
    and logit soft-capping), shared by every execution path — the exit
    twin of ``embed_tokens``. Returns f32 logits."""
    if cfg.block == "phi":
        x = layer_norm(x, params["final_norm"], params["final_norm_b"], cfg.rms_eps)
    elif cfg.block == "gemma2":
        x = rms_norm(x, 1.0 + params["final_norm"].astype(jnp.float32), cfg.rms_eps)
    else:
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = scaled((x @ head.T).astype(jnp.float32), cfg.lm_head_multiplier)
    if cfg.block == "phi":
        logits = logits + params["lm_head_b"].astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


def block_norm(p: Params, cfg: ModelConfig, x: jnp.ndarray, name: str) -> jnp.ndarray:
    """The block's norm: RMSNorm (llama family), biased LayerNorm (phi),
    or (1+w)-weighted RMSNorm (gemma — weights stored as offsets). The
    olmo2 block has no pre-norm: its mixers read the residual stream as it
    is, and ``branch_tail`` norms their outputs."""
    if cfg.block == "olmo2" and name == "attn_norm":
        return x
    if cfg.block == "phi":
        return layer_norm(x, p[name], p[name + "_b"], cfg.rms_eps)
    if cfg.block == "gemma2":
        return rms_norm(x, 1.0 + p[name].astype(jnp.float32), cfg.rms_eps)
    return rms_norm(x, p[name], cfg.rms_eps)


def attn_scale_softcap(cfg: ModelConfig) -> tuple[float, Optional[float]]:
    """(attention scale, attention-logit softcap) for every attention call
    site — gemma scales by query_pre_attn_scalar and tanh-caps the scores;
    everyone else uses the standard 1/sqrt(head_dim) with no cap."""
    denom = cfg.query_pre_attn_scalar or float(cfg.head_dim)
    return denom ** -0.5, cfg.attn_softcap


def attn_out_and_mlp(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,
    o: jnp.ndarray,
    h: Optional[jnp.ndarray] = None,
    lora: Optional[Params] = None,
    lora_ids: Optional[jnp.ndarray] = None,
    kernels: str = "off",
) -> jnp.ndarray:
    """Layer tail shared by every execution path.

    llama block: attention output projection + residual, then a fresh
    mlp_norm feeds the SwiGLU (or MoE) MLP + residual.
    phi block: ``h`` is the single LayerNorm output that already fed
    attention; the GELU MLP reads the same ``h``, and both branch outputs
    add to the residual in parallel.
    With ``lora``/``lora_ids``, every projection the bank covers adds its
    per-row adapter delta (ops/lora.py).
    """
    from functools import partial

    from kserve_vllm_mini_tpu.ops.lora import adapted_linear

    _al = partial(adapted_linear, mode=cfg.quant_mode)
    B, T, _ = x.shape
    dt = cfg.jnp_dtype
    o = o.transpose(0, 2, 1, 3).reshape(B, T, cfg.n_heads * cfg.head_dim)
    if cfg.block == "phi":
        attn_out = _al(o, p["wo"], lora, "wo", lora_ids) + p["bo"]
        up = _al(h, p["w_up"], lora, "w_up", lora_ids) + p["b_up"]
        act = jax.nn.gelu(up.astype(jnp.float32), approximate=True).astype(dt)
        mlp_out = _al(act, p["w_down"], lora, "w_down", lora_ids) + p["b_down"]
        return x + attn_out + mlp_out
    if cfg.block == "gemma2":
        # sandwich norms: each branch output is normed BEFORE its residual
        attn_out = _al(o, p["wo"], lora, "wo", lora_ids)
        x = x + block_norm(p, cfg, attn_out, "post_attn_norm")
        h2 = block_norm(p, cfg, x, "mlp_norm")
        gate = jax.nn.gelu(
            _al(h2, p["w_gate"], lora, "w_gate", lora_ids).astype(jnp.float32),
            approximate=True,
        ).astype(dt)
        mlp_out = _al(gate * _al(h2, p["w_up"], lora, "w_up", lora_ids),
                      p["w_down"], lora, "w_down", lora_ids)
        return x + block_norm(p, cfg, mlp_out, "post_mlp_norm")
    if cfg.block == "olmo2":
        return branch_tail(p, cfg, x, _al(o, p["wo"], lora, "wo", lora_ids))
    x = x + _al(o, p["wo"], lora, "wo", lora_ids)
    h = rms_norm(x, p["mlp_norm"], cfg.rms_eps)
    if cfg.is_moe:
        from kserve_vllm_mini_tpu.models.moe import moe_mlp

        return x + moe_mlp(p, cfg, h, kernels)
    return x + swiglu_mlp(p, cfg, h, lora, lora_ids)


def swiglu_mlp(
    p: Params,
    cfg: ModelConfig,
    h: jnp.ndarray,
    lora: Optional[Params] = None,
    lora_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """``W_down(silu(W_gate h) * W_up h)``: the dense MLP of the llama and
    olmo2 blocks and of a two-mixer block, residual and norms left to the
    caller. With ``cfg.mlp_multipliers`` (g, d):
    ``W_down(silu(g W_gate h) * W_up h) d``."""
    from functools import partial

    from kserve_vllm_mini_tpu.ops.lora import adapted_linear

    _al = partial(adapted_linear, mode=cfg.quant_mode)
    by_gate, by_down = cfg.mlp_multipliers or (1.0, 1.0)
    gated = jax.nn.silu(
        scaled(_al(h, p["w_gate"], lora, "w_gate", lora_ids).astype(jnp.float32), by_gate)
    ).astype(cfg.jnp_dtype) * _al(h, p["w_up"], lora, "w_up", lora_ids)
    return scaled(_al(gated, p["w_down"], lora, "w_down", lora_ids), by_down)


def branch_tail(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                mixer_out: jnp.ndarray) -> jnp.ndarray:
    """The olmo2 block after its mixer, whichever kind that was:
    ``h = x + RMSNorm(mixer_out)``, ``h + RMSNorm(MLP(h))``."""
    x = x + rms_norm(mixer_out, p["post_attn_norm"], cfg.rms_eps)
    return x + rms_norm(swiglu_mlp(p, cfg, x), p["post_mlp_norm"], cfg.rms_eps)


def layer_forward(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,              # [B, T, D]
    positions: jnp.ndarray,      # [B, T] int32 absolute positions
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    attention_fn=None,
    layer_idx: Optional[jnp.ndarray] = None,  # global layer index (scalar) —
                                 # only alt_sliding_window models need it
) -> jnp.ndarray:
    """One cache-free decoder layer (pre-norm attn + SwiGLU MLP, residuals).

    Shared by the scan-rolled forward below and the pipeline-parallel stage
    executor (parallel/pipeline.py), so every execution strategy runs the
    same layer math."""
    T = x.shape[1]
    h = block_norm(p, cfg, x, "attn_norm")
    q, k, v = qkv_proj(p, cfg, h, positions, cos, sin)
    if attention_fn is not None:
        o = attention_fn(q, k, v, positions)
    else:
        kj = jnp.arange(T)[None, None, :]
        qi = positions[:, :, None]
        mask = kj <= qi
        if cfg.sliding_window is not None:
            wmask = mask & (kj > qi - cfg.sliding_window)
            if cfg.alt_sliding_window:
                if layer_idx is None:
                    raise ValueError(
                        "alt_sliding_window models need layer_idx to pick "
                        "the local/global mask phase"
                    )
                mask = jnp.where(layer_idx % 2 == 0, wmask, mask)
            else:
                mask = wmask
        scale, softcap = attn_scale_softcap(cfg)
        o = attention(q, k, v, mask[:, None, :, :], scale=scale, softcap=softcap)
    return attn_out_and_mlp(p, cfg, x, o, h)


def dense_decode_kernel_block(
    cfg: ModelConfig, kernels: str, kv_cache: KVCache, paged: bool
) -> Optional[int]:
    """Key-block size with which a ``T == 1`` step over the full dense
    cache takes ``kvmini_dense_decode`` (ops/paged_attention.py), or None
    where it keeps the eager read. THE gate, read from what the call can
    see: the kernels mode and backend, the cache's layout, shape and dtype
    (``dense_decode_block``: a block that tiles the length and fits VMEM),
    and the model's attention — no softcap (the kernel has none) and one
    mask for every layer (``alt_sliding_window`` models alternate theirs
    inside the scan). The engine asks the same question once, to count the
    blocks a step fetches."""
    from kserve_vllm_mini_tpu.ops.flash_attention import pallas_interpret
    from kserve_vllm_mini_tpu.ops.paged_attention import dense_decode_block

    if (
        paged
        or pallas_interpret(kernels) is None
        or cfg.attn_softcap is not None
        or cfg.alt_sliding_window
    ):
        return None
    k = kv_cache["k"]                         # [L, B, KVH, S, D]
    return dense_decode_block(k.shape[3], k.shape[2], k.shape[4], k.dtype)


def window_decode_kernel_blocks(
    cfg: ModelConfig, kernels: str, kv_cache: KVCache
) -> Optional[tuple[int, int]]:
    """(global, ring) key-block sizes with which a ``sliding_window_layout``
    stack's decode step reads BOTH K/V leaves through
    ``kvmini_dense_decode``, or None where it keeps the eager reads: the
    gate of ``dense_decode_kernel_block`` asked of each leaf's own length.
    The engine asks it once, to count the blocks a step fetches by kind."""
    from kserve_vllm_mini_tpu.ops.flash_attention import pallas_interpret
    from kserve_vllm_mini_tpu.ops.paged_attention import dense_decode_block

    if pallas_interpret(kernels) is None or cfg.attn_softcap is not None:
        return None
    found = tuple(
        dense_decode_block(kv_cache[name].shape[3], kv_cache[name].shape[2],
                           kv_cache[name].shape[4], kv_cache[name].dtype)
        for name in ("k", "k_win"))
    return None if None in found else found


def run_cached_layers(
    layers: Params,              # stacked per-layer tree, leading axis = L (or a
                                 # local L/pp range under the pipeline executor)
    cfg: ModelConfig,
    x: jnp.ndarray,              # [B, T, D] embedded input
    positions: jnp.ndarray,      # [B, T] int32 absolute positions
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    kv_cache: KVCache,           # leading axis matches ``layers``
    cache_offsets: jnp.ndarray,  # [B]
    fresh_prefill: bool = False,
    write_gate: Optional[jnp.ndarray] = None,  # scalar bool: when False, the
                                 # cache write is a no-op (old values are
                                 # gathered and written back) — lets the
                                 # SPMD pipeline executor run every stage
                                 # every tick without corrupting inactive
                                 # stages' caches (parallel/serving_pp.py)
    slot_base: Optional[jnp.ndarray] = None,  # scalar int32: this block is
                                 # slots [slot_base, slot_base+B) of the
                                 # cache — the microbatched pipeline
                                 # executor walks slot groups while the
                                 # cache keeps the full slot axis
    layer_offset: int = 0,       # global index of this stack's first layer
                                 # (pipeline stages pass their range start;
                                 # alt_sliding_window's local/global phase
                                 # follows GLOBAL layer parity)
    block_table: Optional[jnp.ndarray] = None,  # [B, MAXB] int32 block ids:
                                 # paged-KV mode — ``kv_cache`` holds
                                 # [L, P, KVH, BLK, D] pools
                                 # (init_paged_kv_cache) and row b's K/V
                                 # live in blocks table[b, 0..], in order,
                                 # so the flattened MAXB*BLK axis is still
                                 # absolute-position order and every
                                 # positional mask below applies unchanged
    lora: Optional[Params] = None,  # ops/lora.py bank LAYER TREE (the
                                 # bank's "layers" entry — pure arrays, so
                                 # it can cross jit): {t_A: [L, N, in, r],
                                 # t_B: [L, N, r, out]}; leading axis L
                                 # rides the layer scan like the base
                                 # weights
    lora_ids: Optional[jnp.ndarray] = None,  # [B] adapter index per row
    kernels: str = "auto",       # which attention calls take a Pallas kernel
                                 # (ops/flash_attention.py pallas_interpret):
                                 # "auto" = compiled kernels on a TPU, jnp
                                 # elsewhere; "off" = jnp only (GSPMD-
                                 # partitioned jits — Mosaic calls cannot be
                                 # auto-partitioned); "interpret" = tests
    valid_len: Optional[jnp.ndarray] = None,  # [B] int32: leading positions
                                 # of this block that are real. Only a hybrid
                                 # stack reads it: positions past it leave
                                 # its recurrent state and convolution tail
                                 # untouched (models/linear_attention.py);
                                 # None = all T
    with_stats: bool = False,    # a pattern stack with expert blocks: also
                                 # return their routing counts, int32 [4]
                                 # summed over the blocks (models/moe.py
                                 # moe_latent_block), over the real positions
) -> tuple[jnp.ndarray, KVCache]:
    """The cached transformer stack: scan over stacked layers, writing this
    block's K/V at ``cache_offsets`` and attending with positional masking
    (or block-causal flash when ``fresh_prefill``). Shared by ``forward``
    and the serving pipeline executor so both run identical layer math.

    Cache-performance invariants (measured on llama-1b @ v5e; breaking
    either regresses decode by the full cache size in HBM traffic):
    1. The cache rides the scan CARRY — XLA aliases loop-carried buffers in
       place. Routing it through scan xs/ys stacks fresh outputs, i.e.
       copies the ENTIRE cache every forward call.
    2. New keys/values land via an indexed scatter (.at[...].set) that
       touches only [B, KVH, T, D] elements — extracting a layer, patching
       it, and writing the whole layer back rewrites the full layer per
       step instead.
    """
    B, T = positions.shape
    dt = cfg.jnp_dtype
    n_local = kv_cache["k"].shape[0]
    quantized_kv = "k_s" in kv_cache  # static: selects the int8 path
    paged = block_table is not None
    if paged and (write_gate is not None or slot_base is not None):
        raise ValueError(
            "paged KV is not supported under the serving pipeline executor "
            "(write_gate/slot_base); use the dense cache with pp"
        )
    if paged:
        blk = kv_cache["k"].shape[3]          # positions per block
        s = block_table.shape[1] * blk        # flattened key axis (abs order)
    else:
        s = kv_cache["k"].shape[3]
    from kserve_vllm_mini_tpu.ops.flash_attention import (
        cached_prefill_blocks,
        pallas_interpret,
    )
    # None = jnp paths; else the interpret flag every Pallas call below gets
    interpret = pallas_interpret(kernels)
    # The paged-decode and cached-prefill kernels are plain-causal only (no
    # window, no softcap); the dense kernels read the full-slot-axis cache
    # (the pp executor's write_gate/slot_base sub-views keep the eager
    # _read_layer path, which is also the oracle every parity test pins
    # against).
    plain_causal = (
        interpret is not None
        and cfg.attn_softcap is None
        and cfg.sliding_window is None
    )
    full_slots = write_gate is None and slot_base is None
    full_dense = (not paged) and full_slots
    # Pallas paged decode kernel: table-driven block DMA instead of the
    # gather copy; int8-KV pools dequantize in-kernel.
    use_paged_kernel = plain_causal and paged and T == 1
    # Dense decode kernel, bf16 and int8 KV alike: each slot fetches only
    # the key/value blocks its query position (and a uniform window) can
    # reach, instead of attending over the whole [B,KVH,S,D] stripe.
    # A cache with a ring beside its stripes reads both through the kernel or
    # neither, each by the block of its own length.
    use_dense_kernel = (
        full_slots
        and T == 1
        and (window_decode_kernel_blocks(cfg, kernels, kv_cache) if "k_win" in kv_cache
             else dense_decode_kernel_block(cfg, kernels, kv_cache, paged)) is not None
    )
    # Int8-KV cached-prefill kernel (ops/flash_attention.py
    # cached_prefill_attention): continuation chunks (T > 1 against the
    # cache, NOT fresh_prefill) stream the int8 stripes with in-kernel
    # dequant instead of materializing the eager read's bf16 KV tensor.
    use_chunk_kernel = (
        plain_causal
        and full_dense
        and quantized_kv
        and (not fresh_prefill)
        and T > 1
        and cached_prefill_blocks(T, s) is not None
    )
    kj = jnp.arange(s)[None, None, :]
    qi = positions[:, :, None]
    causal = kj <= qi
    if cfg.sliding_window is not None:
        # Mistral-style window: key j valid iff p - W < j <= p. Cache
        # slots are absolute positions, so the window is a second bound
        # on the same positional mask. Gemma-style alternation keeps BOTH
        # masks and selects per layer inside the scan.
        windowed = causal & (kj > qi - cfg.sliding_window)
        mask_global = causal[:, None, :, :] if cfg.alt_sliding_window else None
        mask = windowed[:, None, :, :]
    else:
        mask_global = None
        mask = causal[:, None, :, :]                         # [B, 1, T, S]
    attn_scale, attn_cap = attn_scale_softcap(cfg)
    base = slot_base if slot_base is not None else jnp.int32(0)
    h_idx = jnp.arange(cfg.n_kv_heads)[None, :, None]        # [1, KVH, 1]
    t_idx = cache_offsets[:, None, None] + jnp.arange(T)[None, None, :]  # [B, 1, T]
    if paged:
        # position p of row b lives at pool block table[b, p // blk],
        # offset p % blk — the scatter's slot axis becomes the block axis
        blk_of_t = jnp.take_along_axis(
            block_table, t_idx[:, 0, :] // blk, axis=1
        )                                                    # [B, T]
        b_idx = blk_of_t[:, None, :]                         # [B, 1, T]
        w_idx = t_idx % blk                                  # [B, 1, T]
    else:
        b_idx = base + jnp.arange(B)[:, None, None]          # [B, 1, 1]
        w_idx = t_idx
    if cfg.is_hybrid:
        if lora is not None or paged or write_gate is not None or slot_base is not None:
            raise ValueError(
                "a hybrid stack serves from the dense cache on one device, "
                "without adapters (no block table, pipeline stage or LoRA bank)"
            )
        valid = (jnp.full((B,), T, jnp.int32) if valid_len is None
                 else valid_len.astype(jnp.int32))
        real = jnp.arange(T)[None, :] < valid[:, None]
    if "k_win" in kv_cache:
        if quantized_kv or not (fresh_prefill or T == 1):
            raise ValueError(
                "a sliding_window_layout stack serves a fresh prefill piece or a decode "
                "step from bf16 K/V: a continuation piece (T > 1 against the cache) "
                "would attend ring + piece, which nothing here computes")
        R = kv_cache["k_win"].shape[3]
        qpos = cache_offsets.astype(jnp.int32)                    # [B], a decode step's
        if fresh_prefill and T > R:
            # The prompt's last R REAL positions stay behind: ring index r
            # gets the largest position p < valid with p = r (mod R). The
            # bucket's padding past ``valid`` must not push real keys out.
            last = valid[:, None] - 1                              # [B, 1]
            ring_src = last - jnp.mod(last - jnp.arange(R)[None, :], R)   # [B, R]
            ring_src = jnp.clip(ring_src, 0, T - 1)[:, None, :, None]     # [B, 1, R, 1]

    def _gate(cache, name, lidx, new):
        """Value actually scattered: ``new``, or — when write_gate is False —
        the existing values at the same indices (a same-size gather, so the
        no-op write stays O(B*KVH*T*D), never a full-cache select)."""
        if write_gate is None:
            return new
        # broadcasting yields [B,KVH,T,D] for values, [B,KVH,T] for scales
        old = cache[name][lidx, b_idx, h_idx, w_idx]
        return jnp.where(write_gate, new, old.astype(new.dtype))

    def _gather_blocks(arr):
        """Pool leaf -> this batch's blocks in table order, flattened to
        absolute-position order: [P, KVH, BLK, D] values -> [B, KVH, s, D],
        [P, KVH, BLK] scales -> [B, KVH, s]. ONE transpose/reshape for both
        layouts so the value and scale gathers can never drift apart."""
        g = arr[block_table]                     # [B, MAXB, KVH, BLK(, D)]
        g = g.transpose((0, 2, 1, 3) + ((4,) if g.ndim == 5 else ()))
        return g.reshape((B, cfg.n_kv_heads, s) + g.shape[4:])

    def _read_layer(cache, name, lidx):
        """Eager (non-kernel) cache read: gather/slice this layer's live
        view and dequantize on read. The path wherever the Pallas kernels
        don't apply (bf16 prefill-against-cache, pp sub-views,
        windowed/softcap models, every non-TPU backend)."""
        vals = jax.lax.dynamic_index_in_dim(cache[name], lidx, axis=0, keepdims=False)
        if paged:
            # the flattened axis is absolute position order, so downstream
            # masking is identical to dense
            vals = _gather_blocks(vals)
        elif slot_base is not None:
            # attention only needs this slot group's rows
            vals = jax.lax.dynamic_slice_in_dim(vals, base, B, axis=0)
        if quantized_kv:
            sc = jax.lax.dynamic_index_in_dim(
                cache[name + "_s"], lidx, axis=0, keepdims=False
            )
            if paged:
                sc = _gather_blocks(sc)
            elif slot_base is not None:
                sc = jax.lax.dynamic_slice_in_dim(sc, base, B, axis=0)
            # dequantize on read: halves the HBM stream vs bf16 and the
            # multiply fuses into the attention matmul's prologue
            return vals.astype(dt) * sc.astype(dt)[..., None]
        return vals.astype(dt)

    def scan_body(carry, layer_xs):
        y0, cache = carry
        if lora is not None:
            p, lora_p, lidx = layer_xs
        else:
            (p, lidx), lora_p = layer_xs, None
        return full_layer(y0, cache, p, lora_p, lidx), None

    def full_layer(y0, cache, p, lora_p, lidx):
        # a scan's attention layers are one entry of the plan, whichever the
        # layer (alt_sliding_window alternates its mask by the traced index)
        e = next(e for e in cfg.layer_plan if e.kv)
        h = block_norm(p, cfg, y0, "attn_norm")
        o, cache = attend(h, cache, p, lora_p, lidx, e)
        return (
            attn_out_and_mlp(p, cfg, y0, o, h, lora=lora_p, lora_ids=lora_ids, kernels=kernels),
            cache,
        )

    def ring_place(cache, e, lidx, k, v):
        """Where this block's K/V goes in layer ``lidx`` of a RING of R
        positions, position p at ``p mod R`` -> (k, v, their indices on the
        ring's axis, the index up to which a decode step reads the ring)."""
        kn, vn = e.kv
        if fresh_prefill and T > R:
            return (jnp.take_along_axis(k, ring_src, axis=2),
                    jnp.take_along_axis(v, ring_src, axis=2), jnp.arange(R)[None, None, :], None)
        if fresh_prefill:
            return k, v, t_idx, None
        # a row that is not decoding writes back what was there (its query
        # position is not its slot's next one: it would push a live key out)
        at = jnp.mod(t_idx, R)
        live = (valid > 0)[:, None, None, None]
        k = jnp.where(live, k.astype(cache[kn].dtype), cache[kn][lidx, b_idx, h_idx, at])
        v = jnp.where(live, v.astype(cache[vn].dtype), cache[vn][lidx, b_idx, h_idx, at])
        # Written before it is read, the ring holds exactly the positions
        # q - R < p <= q: with R = W every index that holds a position >= 0
        # is visible, the indices 0 .. min(q, R - 1); no position enters the
        # scores after the rotation, so their order in the ring does not
        # matter and the read is a causal one at min(q, R - 1)
        return k, v, at, jnp.minimum(qpos, R - 1)

    def attend(h, cache, p, lora_p, lidx, e):
        """The attention mixer on the normed ``h``: project, write this
        block's K/V into layer ``lidx`` of the cache, attend -> (o
        [B,H,T,hd], cache). ``e``, the layer's entry of ``cfg.layer_plan``,
        says which two leaves hold its K/V and whether they are a ring,
        how far back it attends, and whether q and k rotate."""
        kn, vn = e.kv
        q, k, v = qkv_proj(p, cfg, h, positions, cos, sin,
                           lora=lora_p, lora_ids=lora_ids, rope=e.rope)
        cache = dict(cache)
        # where the block lands on the leaves' own axis, and what a decode
        # step's kernel reads: keys up to the query's position, inside the
        # window; a ring holds its window and no more
        new, at, reach, window = {kn: k, vn: v}, w_idx, cache_offsets, e.window
        if e.ring:
            # a fresh piece attends its own projections below: what the ring
            # keeps of them is taken from them, not read back
            new[kn], new[vn], at, reach = ring_place(cache, e, lidx, k, v)
            window = None
        elif quantized_kv:
            (new[kn], new["k_s"]), (new[vn], new["v_s"]) = (
                _quantize_kv_block(k), _quantize_kv_block(v))
        for name, val in new.items():
            cache[name] = cache[name].at[lidx, b_idx, h_idx, at].set(
                _gate(cache, name, lidx, val.astype(cache[name].dtype)))
        glidx = layer_offset + lidx  # global layer index (mask phase)
        if fresh_prefill:
            # block-causal flash over the fresh block is exact for a
            # windowed model too as long as T <= window (every causal
            # key is inside the window), and a ring's layer hands the
            # kernel its window; longer prefills of the others take the masked
            # jnp path. T is static, so this is a trace-time branch. The
            # flash kernel has no softcap, so gemma's capped attention
            # always takes the masked path.
            needs_mask_path = (
                attn_cap is not None
                or attn_scale != float(cfg.head_dim) ** -0.5
                or (e.window is not None and T > e.window and not e.ring)
            )
            if needs_mask_path:
                fj = jnp.arange(T)[None, None, :]
                fcausal = fj <= qi
                if e.window is not None:
                    fwin = fcausal & (fj > qi - e.window)
                    if cfg.alt_sliding_window:
                        fmask = jnp.where(glidx % 2 == 0, fwin, fcausal)
                    else:
                        fmask = fwin
                else:
                    fmask = fcausal
                o = attention(q, k, v, fmask[:, None, :, :],
                              scale=attn_scale, softcap=attn_cap)
            else:
                from kserve_vllm_mini_tpu.ops.flash_attention import prefill_attention

                o = prefill_attention(q, k, v, kernels=kernels,
                                      window=e.window if e.ring else None)
        elif use_paged_kernel:
            # Pallas paged decode: the block table drives per-block DMA
            # straight from the LAYER-STACKED pool — no gathered KV copy,
            # and no per-layer pool slice either (a dynamic-slice operand
            # to the custom call would materialize the whole layer pool;
            # lidx rides the kernel's index map instead)
            from kserve_vllm_mini_tpu.ops.paged_attention import (
                paged_decode_attention,
            )

            G = cfg.n_heads // cfg.n_kv_heads
            qg = q[:, :, 0, :].reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
            og = paged_decode_attention(
                qg, cache["k"], cache["v"], block_table,
                cache_offsets, layer=lidx, scale=attn_scale,
                k_scale=cache.get("k_s"), v_scale=cache.get("v_s"),
                interpret=interpret,
            )
            o = og.reshape(B, cfg.n_heads, 1, cfg.head_dim)
        elif use_dense_kernel:
            # dense decode: only the blocks of the LAYER-STACKED cache
            # that ``reach`` (and the window) reach are DMA'd, int8
            # ones dequantized in-kernel — no full-stripe read, no
            # materialized bf16 KV tensor, and no per-layer cache slice
            # either (lidx rides the kernel's index map, same contract as
            # paged)
            from kserve_vllm_mini_tpu.ops.paged_attention import (
                dense_decode_attention,
            )

            G = cfg.n_heads // cfg.n_kv_heads
            qg = q[:, :, 0, :].reshape(B, cfg.n_kv_heads, G, cfg.head_dim)
            og = dense_decode_attention(
                qg, cache[kn], cache[vn], reach,
                layer=lidx, scale=attn_scale,
                k_scale=cache.get(kn + "_s"), v_scale=cache.get(vn + "_s"),
                window=window, interpret=interpret,
            )
            o = og.reshape(B, cfg.n_heads, 1, cfg.head_dim)
        elif use_chunk_kernel:
            # int8-KV cached prefill: the chunk's queries attend the whole
            # cache stripe — earlier chunks' KV plus the rows this scan
            # step just wrote — with the stripes DMA'd int8 and dequantized
            # in-kernel (lidx rides the index map, same contract as the
            # decode kernels)
            from kserve_vllm_mini_tpu.ops.flash_attention import (
                cached_prefill_attention,
            )

            o = cached_prefill_attention(
                q, cache["k"], cache["v"], cache_offsets,
                layer=lidx, scale=attn_scale,
                k_scale=cache.get("k_s"), v_scale=cache.get("v_s"),
                interpret=interpret,
            )
        else:
            k_layer = _read_layer(cache, kn, lidx)
            v_layer = _read_layer(cache, vn, lidx)
            if e.ring:
                m = (jnp.arange(R)[None, None, :] <= reach[:, None, None])[:, None]
            elif mask_global is not None:
                # gemma alternation: even global layers local, odd global
                m = jnp.where(glidx % 2 == 0, mask, mask_global)
            else:
                # every layer that keeps stripes shares the stack's one window
                m = mask
            o = attention(q, k_layer, v_layer, m,
                          scale=attn_scale, softcap=attn_cap)
        return o, cache

    if cfg.is_hybrid and cfg.layer_period == 1:
        # Each kind of layer lies in a weight stack of its own, in the plan's
        # (irregular) order. The walk is unrolled with static indices: every
        # layer reads its slice of its stack where it lies (a scan over a
        # [periods, k, ...] view, or a switch on the kind inside one, makes
        # XLA copy the slice out every step), and state, tail and both kinds
        # of K/V are updated in place in the donated cache.
        from kserve_vllm_mini_tpu.models.mamba import mamba_mixer, ssm_decode_kernel_block
        from kserve_vllm_mini_tpu.models.moe import moe_glu_block, moe_latent_block
        from kserve_vllm_mini_tpu.models.short_conv import short_conv_mixer

        cache = dict(kv_cache)
        stats = jnp.zeros((4,), jnp.int32)
        # a decode step on a TPU hands the Mamba-2 mixer the whole state
        # stack: the kernel updates the block's layer inside it (no slice
        # out, no write back); never a fresh prefill
        in_place = ("ssm_state" in kv_cache and not fresh_prefill and ssm_decode_kernel_block(
            kernels, kv_cache["ssm_state"], T) is not None)

        def at(name, i):
            return jax.tree_util.tree_map(lambda a: a[i], layers[name])

        # A mixer on the normed ``h`` -> (its delta of the residual stream,
        # cache): ``e`` says which layer of which cache leaves is its own,
        # ``p`` is its layer's slice of its weight stack.
        def ssm(e, p, h, cache):
            i = e.at("ssm_state")
            state, layer = (cache["ssm_state"], jnp.int32(i)) if in_place else (
                cache["ssm_state"][i], None)
            tail = cache["ssm_conv"][i]
            if fresh_prefill:
                # a new sequence starts from S_0 = 0 whatever the slot held
                state, tail = jnp.zeros_like(state), jnp.zeros_like(tail)
            out, state, tail = mamba_mixer(p, cfg, h, state, tail, valid, kernels, layer=layer)
            cache["ssm_state"] = state if in_place else cache["ssm_state"].at[i].set(state)
            cache["ssm_conv"] = cache["ssm_conv"].at[i].set(tail)
            return out, cache

        def conv(e, p, h, cache):
            i = e.at("conv_tail")
            tail = cache["conv_tail"][i]
            if fresh_prefill:
                # a new sequence starts from an empty tail whatever the slot held
                tail = jnp.zeros_like(tail)
            out, tail = short_conv_mixer(p, cfg, h, tail, valid)
            cache["conv_tail"] = cache["conv_tail"].at[i].set(tail)
            return out, cache

        def attn(e, p, h, cache):
            o, cache = attend(h, cache, p, None, jnp.int32(e.at(e.kv[0])), e)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, cfg.n_heads * cfg.head_dim)
            return linear(o, p["wo"], mode=cfg.quant_mode), cache

        mixers = {"ssm": ssm, "conv": conv, "attn": attn}
        out_by = {"ssm": cfg.ssm_out_multiplier, "attn": cfg.attention_out_multiplier}
        for e in cfg.layer_plan:
            x_in = x
            if e.mixers:
                p = at(e.stack, e.index)
                h = rms_norm(x, p["norm_in"], cfg.rms_eps)
                outs = []
                for kind in e.mixers:
                    out, cache = mixers[kind](e, p, h, cache)
                    outs.append(out)
                if len(outs) > 1:
                    # two mixers side by side on ONE normed input, each by its
                    # muP multiplier, one residual add for both
                    first, second = (scaled(o, out_by[kind]) for kind, o in zip(e.mixers, outs))
                    out = first + second
                x = x + out
            if e.ffn == "mlp":
                # a two-mixer block's MLP lies in its mixers' stack
                p = p if e.ffn_stack == e.stack else at(e.ffn_stack, e.ffn_index)
                out = swiglu_mlp(p, cfg, rms_norm(x, p[e.ffn_norm], cfg.rms_eps))
            elif e.ffn:
                # The stack and the index go down, not a slice: a long
                # piece's grouped dispatch and a decode step's sweep read the
                # expert stacks where they lie.
                experts, i = layers[e.ffn_stack], e.ffn_index
                h = rms_norm(x, experts[e.ffn_norm][i], cfg.rms_eps)
                if e.ffn == "glu":
                    out, counts = moe_glu_block(
                        experts, cfg, h, real, route_from=x_in if e.route_from_input else None,
                        kernels=kernels, layer=i)
                else:
                    out, counts = moe_latent_block(experts, cfg, h, real, kernels, layer=i)
                stats = stats + counts
            if e.ffn:
                x = x + out
        return (x, cache, stats) if with_stats else (x, cache)

    if cfg.layer_period > 1:
        # one period = k linear layers then one full layer; each kind's
        # weights are stacked on their own. The stack is ONE scan over the
        # periods with the period's linear layers unrolled in its body, each
        # indexing the linear stack by its own layer number: a nested scan
        # (or a [periods, k, ...] view as xs) makes XLA copy a period's
        # weights out of the stack every iteration, which doubles the
        # weight stream of a decode step. Both kinds of state ride the
        # carry (invariant 1 above holds for them too).
        from kserve_vllm_mini_tpu.models.linear_attention import (
            linear_decode_kernel_block,
            linear_mixer,
        )

        n_lin = cfg.layer_period - 1
        in_place = not fresh_prefill and linear_decode_kernel_block(
            kernels, kv_cache["lin_state"], T) is not None
        lin_layers = layers["linear"]

        def linear_layer(y0, cache, lidx):
            p = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, lidx, 0, keepdims=False),
                lin_layers)
            # a decode step on a TPU hands the mixer the whole stack: the
            # kernel updates this layer's state inside it (no slice out, no
            # write back); in_place is never a fresh prefill
            state, layer = (cache["lin_state"], lidx) if in_place else (
                jax.lax.dynamic_index_in_dim(cache["lin_state"], lidx, 0, keepdims=False), None)
            tail = jax.lax.dynamic_index_in_dim(cache["lin_conv"], lidx, 0, keepdims=False)
            if fresh_prefill:
                # a new sequence starts from S_0 = 0 whatever the slot held
                state, tail = jnp.zeros_like(state), jnp.zeros_like(tail)
            out, state, tail = linear_mixer(
                p, cfg, y0, state, tail, valid, kernels, layer=layer)
            cache = dict(cache)
            cache["lin_state"] = state if in_place else jax.lax.dynamic_update_index_in_dim(
                cache["lin_state"], state, lidx, 0)
            cache["lin_conv"] = jax.lax.dynamic_update_index_in_dim(
                cache["lin_conv"], tail, lidx, 0)
            return branch_tail(p, cfg, y0, out), cache

        def period(carry, period_xs):
            p_full, pidx = period_xs
            y, cache = carry
            for j in range(n_lin):
                y, cache = linear_layer(y, cache, pidx * n_lin + j)
            return full_layer(y, cache, p_full, None, pidx), None

        (x, new_cache), _ = jax.lax.scan(
            period, (x, dict(kv_cache)), (layers["full"], jnp.arange(n_local)),
        )
        return x, new_cache

    xs = (
        (layers, lora, jnp.arange(n_local))
        if lora is not None
        else (layers, jnp.arange(n_local))
    )
    (x, new_cache), _ = jax.lax.scan(
        scan_body,
        (x, dict(kv_cache)),
        xs,
        unroll=max(cfg.scan_unroll, 1),
    )
    return x, new_cache


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,         # [B, T] int32
    positions: jnp.ndarray,      # [B, T] int32 absolute positions
    kv_cache: Optional[KVCache] = None,
    cache_offsets: Optional[jnp.ndarray] = None,  # [B] slot where this block starts
    attention_fn=None,  # optional (q, k, v, positions) -> o override for the
                        # cache-free path (e.g. parallel.ring_attention for sp)
    fresh_prefill: bool = False,  # static: this cached call writes a new
                        # request's prompt at offset 0 (positions arange(T)),
                        # so attention runs block-causal over the fresh
                        # q/k/v via ops.flash_attention.prefill_attention
                        # (Pallas kernel on TPU) instead of reading back the
                        # whole max_seq cache buffer
    logit_index: Optional[jnp.ndarray] = None,  # [B] int32: compute logits
                        # at this one position per sequence ([B, 1, V])
                        # instead of all T positions. Prefill only samples
                        # the prompt's last position — a full [B, T, V] f32
                        # logits tensor at 128k vocab is GBs of HBM (and T×
                        # the lm_head matmul) the sampler never reads
    block_table: Optional[jnp.ndarray] = None,  # [B, MAXB] int32: paged-KV
                        # mode — kv_cache is an init_paged_kv_cache pool and
                        # row b's positions live in blocks table[b, :]
    lora: Optional[Params] = None,  # multi-LoRA bank layer tree (the
                        # ops/lora.py bank's "layers" entry); serving
                        # (cached) path only — the cache-free training path
                        # ignores it
    lora_ids: Optional[jnp.ndarray] = None,  # [B] adapter index per row
    kernels: str = "auto",  # Pallas kernel selection; the engine passes
                        # "off" under a GSPMD mesh (run_cached_layers)
    valid_len: Optional[jnp.ndarray] = None,  # [B] int32: how many leading
                        # positions of this block are real (a padded prefill
                        # bucket's prompt length; 0 for a decode row whose
                        # slot is parked). Padding is harmless in a KV stripe
                        # and corrupts a recurrent state, so a hybrid stack
                        # needs it; every other model ignores it
    with_stats: bool = False,  # static; cached calls of a pattern stack with
                        # expert blocks: a third result, the routing counts
                        # of run_cached_layers
) -> tuple[jnp.ndarray, Optional[KVCache]]:
    """Returns (logits [B, T, V] float32, updated cache).

    Without a cache this is a plain causal forward (training / compile
    checks). With a cache, keys/values of this block are written at
    ``cache_offsets`` and attention runs against the whole cache buffer with
    positional masking — or block-causal over the fresh projections when
    ``fresh_prefill`` (exact for offset-0 prefills; the engine's only
    prefill shape).
    """
    B, T = tokens.shape
    dt = cfg.jnp_dtype
    if attention_fn is not None and cfg.sliding_window is not None:
        raise ValueError(
            "attention_fn overrides (ring attention / sp) do not implement "
            "sliding-window masking; run windowed models with sp=1"
        )
    x = embed_tokens(params, cfg, tokens)  # [B, T, D]
    cos, sin = rope_frequencies(
        cfg.rotary_dim, cfg.max_seq_len, cfg.rope_theta, cfg.rope_scaling
    )

    use_cache = kv_cache is not None
    if use_cache and cache_offsets is None:
        cache_offsets = jnp.zeros((B,), dtype=jnp.int32)

    layers = params["layers"]
    if cfg.is_hybrid and not use_cache:
        # the cache-free forward of a hybrid is a fresh prefill into a cache
        # of exactly this block, thrown away
        if attention_fn is not None:
            raise ValueError("a hybrid stack takes no attention_fn override")
        x, _ = run_cached_layers(
            layers, cfg, x, positions, cos, sin,
            init_kv_cache(cfg, B, max_seq=T), jnp.zeros((B,), jnp.int32),
            fresh_prefill=True, kernels=kernels, valid_len=valid_len,
        )
        new_cache_dict = None
    elif use_cache:
        x, new_cache_dict, *stats = run_cached_layers(
            layers, cfg, x, positions, cos, sin, kv_cache, cache_offsets,
            fresh_prefill=fresh_prefill, block_table=block_table,
            lora=lora, lora_ids=lora_ids, kernels=kernels, valid_len=valid_len,
            with_stats=with_stats,
        )
    else:
        def scan_body_nocache(carry, xs):
            p, lidx = xs
            return layer_forward(
                p, cfg, carry, positions, cos, sin, attention_fn,
                layer_idx=lidx,
            ), None

        x, _ = jax.lax.scan(
            scan_body_nocache, x, (layers, jnp.arange(cfg.n_layers)),
            unroll=max(cfg.scan_unroll, 1),
        )
        new_cache_dict = None

    if logit_index is not None:
        x = x[jnp.arange(B)[:, None], logit_index[:, None]]  # [B, 1, D]
    logits = final_logits(params, cfg, x)
    if with_stats:
        return logits, new_cache_dict, stats[0]
    return logits, new_cache_dict

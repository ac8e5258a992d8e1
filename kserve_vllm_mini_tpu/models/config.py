"""Model architecture configs for the in-repo serving runtime.

The reference never touches model internals (models are opaque strings passed
to external engines, e.g. /root/reference/deploy.sh:25-39 --model-uri), but it
ships engine profiles for four model families
(/root/reference/profiles/tensorrt-llm/{llama-7b,codellama-7b,mistral-7b,
phi-2.7b}.yaml). The TPU build owns the runtime, so architecture configs are
first-class. The base family is the Llama-style decoder (RMSNorm, RoPE,
SwiGLU, GQA) covering BASELINE.json's Llama-3.x configs plus CodeLlama;
orthogonal architecture axes extend it to the other families:

- ``sliding_window`` — Mistral-style windowed attention;
- ``attn_bias`` — Qwen2-style q/k/v projection biases;
- ``n_experts`` / ``n_experts_per_tok`` — Mixtral-style sparse MoE MLP
  (models/moe.py), sharded over the mesh's ``ep`` axis;
- ``block="phi"`` — Phi-2-style parallel attention+MLP block: one
  LayerNorm (with bias) feeds both attention and a GELU MLP, partial
  rotary embedding, biases on every projection.
- ``block="gemma2"`` — Gemma-2-style block: sandwich RMSNorms (post-norms
  on both the attention and MLP branches before their residual adds),
  (1+w) norm weights, GeGLU MLP, sqrt(d_model)-scaled embeddings,
  attention/final logit soft-capping, explicit head_dim decoupled from
  d_model/n_heads, and sliding-window attention on alternating layers.
- ``layer_types`` — a hybrid stack (Olmo-Hybrid): ``linear_attention``
  layers (the gated delta rule, models/linear_attention.py, sized by the
  ``linear_*`` fields) beside ``full_attention`` layers in one repeating
  period; each kind's weights are stacked on their own, and the cache
  holds a recurrent state and a convolution tail beside K/V. Its block is
  ``block="olmo2"``, the OLMo-2/3 reordered norm: no pre-norms, an RMSNorm
  on each branch's OUTPUT before its residual add, and an RMSNorm over the
  whole q and k projections (QK-norm) before RoPE. ``olmo2`` is the hybrid
  stack's block and no axis of its own: only a hybrid's parameter tree
  holds its gains, so without ``layer_types`` it is refused.
- ``hybrid_override_pattern`` — a stack of single-mixer blocks (Nemotron-H):
  one character a block, ``M`` a Mamba-2 state-space mixer
  (models/mamba.py, sized by the ``mamba_*`` fields), ``*`` attention
  without rotary embedding, ``E`` routed experts in a latent beside a
  shared expert (models/moe.py, the ``moe_*`` fields), in any order. Each
  block is ``x + Mixer(RMSNorm(x))``: no attention+MLP pair. Each kind's
  weights are stacked on their own, and the cache holds K/V for the ``*``
  blocks and a state and a convolution tail for the ``M`` blocks.
  ``n_routed_experts`` is the router's width; ``n_experts_held`` and
  ``expert_offset`` say which of them this device holds (expert
  parallelism's share: it routes over all, computes its own).
  A fourth character, ``P``, is a block that holds TWO mixers and an MLP
  (Falcon-H1): one RMSNorm feeds a Mamba-2 mixer and rotary attention side
  by side, both outputs join the residual stream in one add, then a
  pre-normed SwiGLU MLP (``d_ff``). Such a block keeps K/V AND a state and a
  convolution tail, and its attention carries positions (``rotary_dim``
  follows the block kind). The ``*_multiplier(s)`` fields are the family's
  fixed muP scalars, applied to activations where the equations place them;
  1.0 / None leave a program as it is.
- ``layer_types`` with ``"conv"`` — a stack of operator + feed-forward pairs
  (LFM2-MoE): each layer's sequence operator is a gated short convolution
  (models/short_conv.py; ``conv_L_cache`` taps) or rotary attention with an
  RMSNorm over each head of q and k, in ANY order (the published one is
  irregular); the first ``num_dense_layers`` layers' feed-forward is a dense
  SwiGLU MLP (``d_ff``), every later layer's is ``n_routed_experts`` SwiGLU
  experts ``moe_intermediate_size`` wide behind a biased sigmoid router
  (models/moe.py moe_glu_block), no shared expert. Four weight stacks, one a
  kind; the cache holds K/V for the attention layers and a convolution tail
  of ``conv_L_cache - 1`` positions for the others, and no recurrent state.
- ``sliding_window_layout`` / ``rope_layout`` — a window and a rotary switch
  PER LAYER of a plain attention stack (SmallThinker), under the source's
  names: layer ``l`` attends keys ``i - sliding_window_size < j <= i`` where
  ``sliding_window_layout[l]`` is 1 and every ``j <= i`` where it is 0, and
  rotates q and k where ``rope_layout[l]`` is 1 (0: no position enters the
  layer at all). A stack of depth ``n`` reads the first ``n`` entries, so a
  cut stack keeps the published lists. The cache tree holds TWO K/V leaves
  of different lengths: ``k``/``v`` ``[n_global, B, KVH, max_seq, D]`` for the
  global layers and ``k_win``/``v_win`` ``[n_window, B, KVH, R, D]`` for the
  window layers, a ring addressed ``p mod R`` (``ring_len``). Every layer's
  feed-forward is ``n_routed_experts`` ReLU-gated GLU experts
  ``moe_intermediate_size`` wide behind a float32 softmax router that reads
  the layer's INPUT, before the attention norm (models/moe.py
  moe_glu_block). ``sliding_window`` (one window for every layer) and
  ``alt_sliding_window`` (even layers) stay what they are for the stacks
  that spell them so.

**One list of layers.** The three spellings above (a string of characters,
a tuple of kind names, two tuples of 0/1) and ``num_dense_layers`` are INPUTS,
kept under their published names. ``_plan`` reads them once into
``ModelConfig.layer_plan``: one ``Layer`` a layer, which says what the layer
runs (``mixers``, ``ffn``), where its weights lie (``stack``/``index``,
``ffn_stack``/``ffn_index``/``ffn_norm``), which cache leaves it owns
(``cache``, ``kv``) and how its attention reads them (``window``, ``rope``,
a ring or stripes), and what its router reads (``route_from_input``):

    M -> ssm in "mamba"        * -> attn in "attn"       E -> latent experts
    P -> ssm + attn side by side and an MLP, all three in "parallel"
    "conv" / "full_attention" -> conv in "conv" / attn in "attn", then the
        MLP in "mlp" (the first num_dense_layers) or GLU experts in "experts"
    sliding_window_layout[l], rope_layout[l] -> attn in "attn" over a ring
        ("k_win") or stripes ("k"), rotating or not, then GLU experts routed
        from the layer's input
    "linear_attention" / "full_attention" (the delta-rule period) -> the olmo2
        block's linear or attention mixer in "linear" / "full", with its MLP
    none of them -> cfg.block's attention + feed-forward in the flat tree

Every count (``n_full_layers`` ...), the initialiser's stacks, the cache
tree's leaves and the unrolled walk (models/llama.py) are read off the plan;
nothing else reads a spelling. The two stacks that REPEAT are scanned, not
walked: a uniform stack, and the delta-rule period (``layer_period``).
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, replace
from functools import cached_property
from typing import Optional

import jax.numpy as jnp


@dataclass(frozen=True)
class Layer:
    """One layer (or block) of ``ModelConfig.layer_plan``: plain static data,
    the same whichever spelling published the stack."""
    # sequence mixers on the layer's normed input, of "attn", "ssm" (Mamba-2),
    # "conv" (short convolution), "linear" (delta rule); two run side by side
    # and join the residual stream in one add; () = none (an expert block)
    mixers: tuple[str, ...] = ("attn",)
    # the weight stack of ``layers`` that holds the mixers and their norm, and
    # this layer's place in it; None = the flat tree of a uniform stack
    stack: Optional[str] = None
    index: int = 0
    # (leaf, layer index inside it) of every cache leaf the layer owns
    cache: tuple[tuple[str, int], ...] = ()
    # attention's K/V leaves among them: ("k", "v"), stripes as long as the
    # context, or ("k_win", "v_win"), a ring of ``window`` positions
    kv: Optional[tuple[str, str]] = None
    # attention reaches the last ``window`` positions; None = every earlier one
    window: Optional[int] = None
    # False: no position enters this layer's attention, whatever the stack's
    # ``rotary_dim`` (which is 0 for a stack that rotates nowhere)
    rope: bool = True
    # the feed-forward after the mixers: "mlp" (dense), "moe" (Mixtral's, in
    # the uniform block), "glu" (routed GLU experts), "latent" (routed experts
    # in a latent beside a shared one); None = none. Its weight stack, its
    # place there and the gain that norms its input (None: ``cfg.block`` says
    # how a uniform block norms)
    ffn: Optional[str] = None
    ffn_stack: Optional[str] = None
    ffn_index: int = 0
    ffn_norm: Optional[str] = None
    route_from_input: bool = False    # the router reads the LAYER's input, not the ffn's

    def at(self, leaf: str) -> int:
        """The layer's index inside the cache leaf ``leaf``."""
        return dict(self.cache)[leaf]

    @property
    def ring(self) -> bool:
        return self.kv == ("k_win", "v_win")


class LayerPlan(tuple):
    """``ModelConfig.layer_plan``: one ``Layer`` a layer, in stack order;
    ``leaves``, the cache tree's leaves (the spelling fixes them, so a leaf
    stays where the cut stack leaves it no layer: a ring of none); and
    ``n``, how many layers run each kind of mixer and of feed-forward and own
    a layer of each leaf, counted once (the engine asks every sweep)."""

    def __new__(cls, layers, leaves: tuple[str, ...]):
        plan = super().__new__(cls, layers)
        plan.leaves = leaves
        plan.n = Counter(name for e in plan for name in (
            *e.mixers, e.ffn, *(leaf for leaf, _ in e.cache)) if name)
        return plan

    def __getnewargs__(self):
        # a config that has built its plan still copies and pickles
        return tuple(self), self.leaves


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    max_seq_len: int = 4096
    rope_theta: float = 500_000.0
    # Llama-3.1-style RoPE frequency scaling:
    # (factor, low_freq_factor, high_freq_factor, original_max_position_embeddings)
    rope_scaling: Optional[tuple[float, float, float, int]] = None
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"          # parameter/activation dtype
    tie_embeddings: bool = False
    # Mistral-style sliding-window attention: a query at absolute position p
    # attends keys j with p - window < j <= p. None = full causal.
    sliding_window: Optional[int] = None
    # Qwen2-style biases on the q/k/v projections (o/mlp stay bias-free).
    attn_bias: bool = False
    # Mixtral-style sparse MoE: n_experts > 0 replaces the dense SwiGLU MLP
    # with a top-k routed expert MLP (models/moe.py; no token is dropped).
    n_experts: int = 0
    n_experts_per_tok: int = 2
    # Block style: "llama" (pre-norm attn -> pre-norm SwiGLU, RMSNorm) or
    # "phi" (parallel attn+MLP off one LayerNorm, GELU MLP, all-bias).
    block: str = "llama"
    # lax.scan unroll over the layer stack: >1 lets XLA software-pipeline
    # weight streaming across layer boundaries at the cost of code size.
    # A schedule knob: numerically equivalent, but XLA may reassociate bf16
    # fusions so the last bits can differ (oracle-tested within tolerance).
    scan_unroll: int = 1
    # Fraction of head_dim that receives rotary embedding (phi-2: 0.4).
    partial_rotary_factor: float = 1.0
    # Gemma-2-family axes --------------------------------------------------
    # head_dim decoupled from d_model/n_heads (gemma-2: 256 while
    # d_model/n_heads derives 288 for 2b, 224 for 9b); None = derived.
    explicit_head_dim: Optional[int] = None
    # tanh soft-capping: attention logits (gemma-2: 50.0) and final lm
    # logits (gemma-2: 30.0). None disables.
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    # attention scale denominator override (gemma-2 query_pre_attn_scalar);
    # None = head_dim (the standard 1/sqrt(head_dim)).
    query_pre_attn_scalar: Optional[float] = None
    # sliding_window applies only to EVEN layers (gemma-2's local/global
    # alternation); odd layers attend the full causal context.
    alt_sliding_window: bool = False
    # How quantized matmul leaves contract (ops/qmatmul.py QUANT_MODES):
    # "dequant" casts the int weight to the activation dtype before the dot
    # (W8A16/W4A16); "w8a8" quantizes activations per token and runs the
    # contraction int8 x int8 on the MXU with scales folded
    # post-accumulation. A no-op on unquantized params. Static — it
    # selects the traced program, so it lives on the config every
    # execution path already threads.
    quant_mode: str = "dequant"
    # Hybrid stacks (Olmo-Hybrid), named as the published config names them:
    # one mixer kind per layer, "linear_attention" or "full_attention", in a
    # repeating period of k linear layers then one full layer. None = every
    # layer is full attention. A list (from JSON) becomes a tuple: the
    # config is a static jit argument and must hash.
    # A stack whose kinds are "conv" and "full_attention" (LFM2-MoE) is the
    # other stack this key spells: any order, sized by the three keys below
    # and the expert block's (n_routed_experts is the source's num_experts).
    layer_types: Optional[tuple[str, ...]] = None
    # taps of the short convolution (its tail keeps conv_L_cache - 1 inputs)
    conv_L_cache: int = 3
    # leading layers whose feed-forward is the dense MLP; experts after them
    num_dense_layers: int = 0
    # the gated-delta-rule mixer's sizes (models/linear_attention.py)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # beta in (0, 2) instead of (0, 1): the state transition may have
    # negative eigenvalues
    linear_allow_neg_eigval: bool = False
    # A stack of single-mixer blocks (Nemotron-H), under the published names.
    # One character a block: "M" Mamba-2, "*" attention (no rotary
    # embedding), "E" routed experts. The pattern IS the depth: n_layers
    # follows it (the source's own rule), so a cut pattern cuts the stack.
    hybrid_override_pattern: Optional[str] = None
    # the Mamba-2 mixer's sizes (models/mamba.py)
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    # the expert block's (models/moe.py moe_latent_block): a sigmoid router
    # n_routed_experts wide with a selection bias, num_experts_per_tok
    # chosen, their weights normalised (norm_topk_prob) and scaled; un-gated
    # ReLU^2 experts moe_intermediate_size wide in a latent of
    # moe_latent_size; one shared expert at the model's width
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    moe_latent_size: int = 0
    moe_intermediate_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # which of the n_routed_experts this device holds: experts
    # [expert_offset, expert_offset + n_experts_held); None = all of them
    n_experts_held: Optional[int] = None
    expert_offset: int = 0
    # Fixed scalars of a muP-parametrised family (Falcon-H1), under the
    # published names: configuration, not weights. Each scales an activation
    # where the equations place it (models/llama.py, models/mamba.py); at
    # 1.0 / None nothing is traced, so every other model's program is as it was.
    embedding_multiplier: float = 1.0        # embed[tokens] * m
    lm_head_multiplier: float = 1.0          # lm_head(norm(x)) * m
    attention_in_multiplier: float = 1.0     # the normed input of wq, wk, wv
    attention_out_multiplier: float = 1.0    # wo's output
    key_multiplier: float = 1.0              # wk's output
    ssm_in_multiplier: float = 1.0           # the normed input of in_proj
    ssm_out_multiplier: float = 1.0          # out_proj's output
    # in_proj's output by segment: z | x | B | C | dt, in that order
    ssm_multipliers: Optional[tuple[float, ...]] = None
    # (on w_gate's output inside the SiLU, on w_down's output)
    mlp_multipliers: Optional[tuple[float, ...]] = None
    # A window and a rotary switch per layer (SmallThinker), under the
    # published names: 1 = the layer attends the last sliding_window_size
    # positions / rotates q and k; 0 = every earlier position / no position
    # enters. At least n_layers entries each; the stack reads the first
    # n_layers. None = the stack has no such axis.
    sliding_window_layout: Optional[tuple[int, ...]] = None
    rope_layout: Optional[tuple[int, ...]] = None
    sliding_window_size: Optional[int] = None

    def __post_init__(self) -> None:
        for name, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            given = getattr(self, name)
            if given is not None:
                # a list (from JSON) becomes a tuple: the config must hash
                object.__setattr__(self, name, tuple(float(v) for v in given))
                if len(given) != n:
                    raise ValueError(f"{name} takes {n} values, got {len(given)}")
        if self.sliding_window_layout is not None or self.rope_layout is not None:
            self._check_window_stack()
            return
        if self.hybrid_override_pattern is not None:
            self._check_pattern()
            return
        if self.layer_types is None:
            if self.block == "olmo2":
                raise ValueError("block='olmo2' is the hybrid stack's block: "
                                 "it needs layer_types")
            return
        types = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        if len(types) != self.n_layers:
            raise ValueError(
                f"layer_types names {len(types)} layers, n_layers={self.n_layers}"
            )
        if "conv" in types:
            self._check_conv_stack()
            return
        if "linear_attention" not in types:
            raise ValueError("layer_types without a linear_attention layer: "
                             "leave it None for a full-attention stack")
        period = types.index("full_attention") + 1 if "full_attention" in types else 0
        one = ("linear_attention",) * (period - 1) + ("full_attention",)
        if period < 2 or self.n_layers % period or types != one * (self.n_layers // period):
            raise ValueError(
                "layer_types must repeat one period of k >= 1 linear_attention "
                f"layers then one full_attention layer; got {types}"
            )
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError(
                "linear_num_key_heads != linear_num_value_heads (grouped "
                "linear-attention heads) is not implemented"
            )
        if min(self.linear_num_key_heads, self.linear_key_head_dim,
               self.linear_value_head_dim) < 1 or self.linear_conv_kernel_dim < 2:
            raise ValueError("a hybrid stack needs linear_num_key_heads, "
                             "linear_key_head_dim, linear_value_head_dim >= 1 "
                             "and linear_conv_kernel_dim >= 2")
        if self.block != "olmo2" or self.is_moe or self.alt_sliding_window:
            raise ValueError("a hybrid stack takes block='olmo2' with a dense MLP "
                             "and one attention mask for every full layer")

    def _check_conv_stack(self) -> None:
        if set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError("a stack with 'conv' layers takes 'conv' and 'full_attention' "
                             f"layers only; got {self.layer_types}")
        if self.block != "llama" or self.is_moe or self.sliding_window is not None:
            raise ValueError("a 'conv' stack takes no block, n_experts or sliding_window: "
                             "its layers are named by layer_types and num_dense_layers")
        if self.conv_L_cache < 2 or not 0 <= self.num_dense_layers <= self.n_layers:
            raise ValueError("a 'conv' stack needs conv_L_cache >= 2 and "
                             "0 <= num_dense_layers <= n_layers")
        if self.n_expert_layers:
            if min(self.n_routed_experts, self.num_experts_per_tok,
                   self.moe_intermediate_size) < 1:
                raise ValueError("the layers past num_dense_layers need n_routed_experts, "
                                 "num_experts_per_tok and moe_intermediate_size >= 1")
            self._check_experts_held()

    def _check_window_stack(self) -> None:
        if self.sliding_window_layout is None or self.rope_layout is None:
            raise ValueError("sliding_window_layout and rope_layout come together: "
                             "one entry a layer each")
        for name in ("sliding_window_layout", "rope_layout"):
            # a list (from JSON) becomes a tuple: the config must hash
            given = tuple(int(v) for v in getattr(self, name))
            object.__setattr__(self, name, given)
            if set(given) - {0, 1}:
                raise ValueError(f"{name} takes 0 or 1 a layer; got {given}")
        n_win, n_rope = len(self.sliding_window_layout), len(self.rope_layout)
        if n_win != n_rope or n_win < self.n_layers:
            raise ValueError(
                f"sliding_window_layout ({n_win}) and rope_layout ({n_rope}) must be "
                f"equally long and name at least n_layers={self.n_layers} layers")
        if self.n_window_layers and (self.sliding_window_size or 0) < 1:
            raise ValueError("a layer with sliding_window_layout 1 needs sliding_window_size >= 1")
        if (self.block != "llama" or self.layer_types is not None
                or self.hybrid_override_pattern is not None or self.is_moe
                or self.sliding_window is not None or self.alt_sliding_window):
            raise ValueError(
                "a sliding_window_layout stack takes block='llama' and no layer_types, "
                "hybrid_override_pattern, n_experts, sliding_window or alt_sliding_window: "
                "its layers are named by the two layouts")
        if min(self.n_routed_experts, self.num_experts_per_tok, self.moe_intermediate_size) < 1:
            raise ValueError("a sliding_window_layout stack's feed-forward is routed experts: "
                             "it needs n_routed_experts, num_experts_per_tok and "
                             "moe_intermediate_size >= 1")
        self._check_experts_held()

    def _check_experts_held(self) -> None:
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("num_experts_per_tok exceeds n_routed_experts")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held <= self.n_routed_experts
                and self.experts_held >= 1):
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + self.experts_held}) "
                f"are not among the router's {self.n_routed_experts}")

    def _check_pattern(self) -> None:
        pat = self.hybrid_override_pattern
        if not pat or set(pat) - set("M*EP"):
            raise ValueError(f"hybrid_override_pattern {pat!r}: one of 'M', '*', 'E', 'P' a block")
        if "P" in pat and "*" in pat:
            raise ValueError("a pattern takes 'P' (rotary attention beside a Mamba-2 mixer) "
                             "or '*' (attention without positions), not both: the stack "
                             "has one rotary_dim")
        object.__setattr__(self, "n_layers", len(pat))
        if self.layer_types is not None or self.is_moe or self.block != "llama":
            raise ValueError("a hybrid_override_pattern stack takes no layer_types, "
                             "n_experts or block: its blocks are named by the pattern")
        if self.n_ssm_layers and (min(self.mamba_num_heads, self.mamba_head_dim,
                                      self.ssm_state_size, self.n_groups) < 1
                                  or self.mamba_num_heads % self.n_groups
                                  or self.conv_kernel < 2 or self.chunk_size < 1):
            raise ValueError("'M' and 'P' blocks need mamba_num_heads (a multiple of n_groups), "
                             "mamba_head_dim, ssm_state_size >= 1 and conv_kernel >= 2")
        if "E" in pat:
            if min(self.n_routed_experts, self.num_experts_per_tok, self.moe_latent_size,
                   self.moe_intermediate_size, self.moe_shared_expert_intermediate_size) < 1:
                raise ValueError("'E' blocks need n_routed_experts, num_experts_per_tok, "
                                 "moe_latent_size, moe_intermediate_size and "
                                 "moe_shared_expert_intermediate_size >= 1")
            self._check_experts_held()

    @cached_property
    def layer_plan(self) -> LayerPlan:
        """One ``Layer`` a layer: what every count below, the initialiser, the
        cache and the walk read (``_plan``: the one reader of the spellings)."""
        return _plan(self)

    @property
    def is_hybrid(self) -> bool:
        """A stack that keeps, per slot and beside K/V stripes, something that
        positions do not address: a recurrent state, a convolution tail, a
        ring of K/V."""
        return self.layer_plan.leaves != ("k", "v")

    @property
    def is_window_stack(self) -> bool:
        """``sliding_window_layout`` / ``rope_layout``: window layers, whose
        K/V is a ring, beside global layers. The ring is what makes it a
        hybrid for the engine: positions do not address it, so it cannot be
        rewound, paged, shared or handed over as a stripe."""
        return "k_win" in self.layer_plan.leaves

    @property
    def window_layers(self) -> tuple[bool, ...]:
        """Per layer of the stack as it is cut: whether it is a window layer."""
        return tuple(e.ring for e in self.layer_plan) if self.is_window_stack else ()

    @property
    def n_window_layers(self) -> int:
        return self.layer_plan.n["k_win"]

    @property
    def n_global_layers(self) -> int:
        return self.n_full_layers if self.is_window_stack else 0

    def ring_len(self, max_seq: int) -> int:
        """Positions of a window layer's ring in a cache ``max_seq`` long.
        ``R = sliding_window_size``: the only cached read is a decode step
        (one query at ``q``, written at ``q mod R`` before it is read), whose
        window ``q - W < j <= q`` is exactly the R positions then in the ring;
        a fresh prefill attends its own projections and leaves its last R
        positions behind. A cache shorter than the window never wraps."""
        return min(self.sliding_window_size or max_seq, max_seq)

    @property
    def is_pattern_stack(self) -> bool:
        """``hybrid_override_pattern``: single-mixer and two-mixer blocks."""
        return "ssm_state" in self.layer_plan.leaves

    @property
    def is_conv_stack(self) -> bool:
        """``layer_types`` of short-convolution and attention operators."""
        return "conv_tail" in self.layer_plan.leaves

    @property
    def layer_period(self) -> int:
        """Layers in one period of a linear-attention stack (k linear + 1
        full): what its scan repeats. 1 for every other stack."""
        if not self.n_linear_layers:
            return 1
        return [e.mixers for e in self.layer_plan].index(("attn",)) + 1

    @property
    def n_full_layers(self) -> int:
        """Layers whose K/V is a stripe as long as the context: all of a
        uniform stack, a hybrid's full-attention ones, a pattern's '*' and
        'P' blocks, a window stack's global layers."""
        return self.layer_plan.n["k"]

    @property
    def n_conv_layers(self) -> int:
        """Layers that keep a short convolution's tail."""
        return self.layer_plan.n["conv"]

    @property
    def n_ssm_layers(self) -> int:
        """Blocks that keep a Mamba-2 state and tail: a pattern's 'M' and 'P'."""
        return self.layer_plan.n["ssm"]

    @property
    def n_parallel_layers(self) -> int:
        return sum(len(e.mixers) == 2 for e in self.layer_plan)

    @property
    def block_mixers(self) -> int:
        """Sequence mixers in one block: 2 where a Mamba-2 mixer and
        attention read one normed input side by side, else 1."""
        return 2 if self.n_parallel_layers else 1

    @property
    def n_expert_layers(self) -> int:
        """Layers with routed experts that count their routing: a pattern's
        'E' blocks, a 'conv' stack's layers past ``num_dense_layers``, every
        layer of a window stack."""
        return self.layer_plan.n["glu"] + self.layer_plan.n["latent"]

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.n_routed_experts if self.n_experts_held is None else self.n_experts_held

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_channels(self) -> int:
        """Channels of the Mamba-2 convolution: x, B and C side by side."""
        return self.mamba_d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def n_linear_layers(self) -> int:
        return self.layer_plan.n["linear"]

    @property
    def linear_conv_channels(self) -> int:
        """Channels the linear mixer's depthwise convolution runs over: q, k and v."""
        h = self.linear_num_key_heads
        return h * (2 * self.linear_key_head_dim + self.linear_value_head_dim)

    @property
    def head_dim(self) -> int:
        if self.explicit_head_dim is not None:
            return self.explicit_head_dim
        return self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def rotary_dim(self) -> int:
        """Even number of head dims receiving RoPE (phi uses a prefix). It
        follows the block kind: a pattern's '*' blocks carry no position (0),
        its 'P' blocks do."""
        if self.is_pattern_stack and not self.n_parallel_layers:
            return 0
        d = int(self.head_dim * self.partial_rotary_factor)
        return d - (d % 2)

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def param_count(self) -> int:
        """Approximate parameter count: embeddings, head, and every mixer,
        feed-forward and gain of the plan's layers, the experts held here."""
        d, hd, emb = self.d_model, self.head_dim, self.vocab_size * self.d_model
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        if self.attn_bias:
            attn += self.n_heads * hd + 2 * self.n_kv_heads * hd
        if self.block == "olmo2":
            attn += self.n_heads * hd + self.n_kv_heads * hd    # QK-norm over the projections
        elif self.is_conv_stack:
            attn += 2 * hd                                       # QK-norm over one head
        di, ch = self.mamba_d_inner, self.mamba_conv_channels
        h = self.linear_num_key_heads
        kd, vd = h * self.linear_key_head_dim, h * self.linear_value_head_dim
        lat, ff, sh = (self.moe_latent_size, self.moe_intermediate_size,
                       self.moe_shared_expert_intermediate_size)
        mlp = (2 if self.block == "phi" else 3) * d * self.d_ff
        router = d * self.n_routed_experts
        size = {
            "attn": attn,
            "ssm": (d * (di + ch + self.mamba_num_heads) + di * d      # in_proj, out_proj
                    + ch * (self.conv_kernel + 1)                       # taps and bias
                    + 3 * self.mamba_num_heads + di),                   # A_log, D, dt_bias, gain
            "conv": 4 * d * d + self.conv_L_cache * d,                  # in_proj (3d), out_proj, taps
            "linear": (d * (2 * kd + 2 * vd) + vd * d + 2 * d * h       # q k v gate, o, a b
                       + self.linear_conv_channels * self.linear_conv_kernel_dim
                       + 2 * h + self.linear_value_head_dim),           # A_log, dt_bias, norm gain
            "mlp": mlp,
            "moe": self.n_experts * mlp + d * self.n_experts,
            "glu": router + self.experts_held * 3 * d * ff,
            "latent": (router + self.n_routed_experts                   # router, selection bias
                       + 2 * d * lat + 2 * d * sh + self.experts_held * 2 * lat * ff),
        }
        gain = (2 if self.block == "gemma2" else 1) * d     # gemma2: a norm before and after
        layers = sum(
            sum(size[m] for m in e.mixers) + (size[e.ffn] if e.ffn else 0)
            + (bool(e.mixers) + bool(e.ffn)) * gain
            # a sigmoid router's selection bias; the softmax router that
            # reads the layer's input has none
            + (self.n_routed_experts if e.ffn == "glu" and not e.route_from_input else 0)
            for e in self.layer_plan)
        return emb + (0 if self.tie_embeddings else emb) + d + layers

    def scaled(self, **kwargs) -> "ModelConfig":
        return replace(self, **kwargs)


def _plan(cfg: ModelConfig) -> LayerPlan:
    """``cfg.layer_plan``, from whichever spelling names the stack's layers:
    the ONE place that reads ``sliding_window_layout`` / ``rope_layout``,
    ``hybrid_override_pattern``, ``layer_types`` and ``num_dense_layers``
    (``__post_init__`` has validated them). A new spelling is read here and
    nowhere else."""
    seen: dict[str, int] = {}

    def nth(name: str) -> int:
        """How many layers before this one took ``name``: its index there."""
        seen[name] = seen.get(name, 0) + 1
        return seen[name] - 1

    def owns(*leaves: str) -> tuple[tuple[str, int], ...]:
        i = nth(leaves[0])
        return tuple((leaf, i) for leaf in leaves)

    stripes, state = ("k", "v"), ("ssm_state", "ssm_conv")
    # every attention layer but a ring's shares the stack's one window
    attn = dict(kv=stripes, window=cfg.sliding_window)
    layers: list[Layer] = []
    if cfg.sliding_window_layout is not None:
        # attention then routed experts in every layer, two stacks n_layers long
        ring = ("k_win", "v_win")
        for l in range(cfg.n_layers):
            kv, window = ((ring, cfg.sliding_window_size) if cfg.sliding_window_layout[l]
                          else (stripes, None))
            layers.append(Layer(
                ("attn",), "attn", l, owns(*kv), kv, window, rope=bool(cfg.rope_layout[l]),
                ffn="glu", ffn_stack="experts", ffn_index=l, ffn_norm="ffn_norm",
                route_from_input=True))
        return LayerPlan(layers, stripes + ring)
    if cfg.hybrid_override_pattern is not None:
        # one stack a character; a block has one norm, whatever it runs
        for ch in cfg.hybrid_override_pattern:
            if ch == "M":
                layers.append(Layer(("ssm",), "mamba", nth("mamba"), owns(*state)))
            elif ch == "*":
                layers.append(Layer(("attn",), "attn", nth("attn"), owns(*stripes), **attn))
            elif ch == "P":
                i = nth("parallel")
                layers.append(Layer(("ssm", "attn"), "parallel", i, owns(*state) + owns(*stripes),
                                    ffn="mlp", ffn_stack="parallel", ffn_index=i,
                                    ffn_norm="mlp_norm", **attn))
            else:
                layers.append(Layer((), ffn="latent", ffn_stack="experts",
                                    ffn_index=nth("experts"), ffn_norm="norm_in"))
        return LayerPlan(layers, stripes + state)
    if cfg.layer_types is not None and "conv" in cfg.layer_types:
        # an operator's stack and a feed-forward's stack a layer, four in all
        for l, kind in enumerate(cfg.layer_types):
            ffn = (dict(ffn="mlp", ffn_stack="mlp", ffn_index=l) if l < cfg.num_dense_layers
                   else dict(ffn="glu", ffn_stack="experts", ffn_index=l - cfg.num_dense_layers))
            ffn["ffn_norm"] = "ffn_norm"
            if kind == "conv":
                layers.append(Layer(("conv",), "conv", nth("conv"), owns("conv_tail"), **ffn))
            else:
                layers.append(Layer(("attn",), "attn", nth("attn"), owns(*stripes), **attn, **ffn))
        return LayerPlan(layers, stripes + ("conv_tail",))
    if cfg.layer_types is not None:
        # the delta-rule period: each kind's whole block (cfg.block) in its stack
        for kind in cfg.layer_types:
            if kind == "linear_attention":
                i = nth("linear")
                layers.append(Layer(("linear",), "linear", i, owns("lin_state", "lin_conv"),
                                    ffn="mlp", ffn_stack="linear", ffn_index=i))
            else:
                i = nth("full")
                layers.append(Layer(("attn",), "full", i, owns(*stripes), ffn="mlp",
                                    ffn_stack="full", ffn_index=i, **attn))
        return LayerPlan(layers, stripes + ("lin_state", "lin_conv"))
    # a uniform stack: cfg.block's pair in every layer of the flat tree
    for l in range(cfg.n_layers):
        local = cfg.sliding_window if not (cfg.alt_sliding_window and l % 2) else None
        layers.append(Layer(("attn",), None, l, owns(*stripes), stripes, local,
                            ffn="moe" if cfg.is_moe else "mlp", ffn_index=l))
    return LayerPlan(layers, stripes)


# Presets. "llama-tiny" is the CI/test model (runs on CPU in <1s); the 8B and
# 70B configs match the published Llama-3.x architectures so real checkpoints
# load onto them; "smoke-125m" plays the role of the reference's
# facebook/opt-125m cpu-smoke config (BASELINE.json configs[0]).
PRESETS: dict[str, ModelConfig] = {
    "llama-tiny": ModelConfig(
        name="llama-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=256,
        rope_theta=10_000.0,
    ),
    "smoke-125m": ModelConfig(
        name="smoke-125m",
        vocab_size=32_000,
        d_model=768,
        n_layers=12,
        n_heads=12,
        n_kv_heads=12,
        d_ff=2048,
        max_seq_len=2048,
        rope_theta=10_000.0,
    ),
    "llama-1b": ModelConfig(
        name="llama-1b",
        vocab_size=128_256,
        d_model=2048,
        n_layers=16,
        n_heads=32,
        n_kv_heads=8,
        d_ff=8192,
        max_seq_len=8192,
        rope_scaling=(32.0, 1.0, 4.0, 8192),   # Llama-3.2-1B ships this
    ),
    "llama-3.1-8b": ModelConfig(
        name="llama-3.1-8b",
        vocab_size=128_256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14_336,
        max_seq_len=8192,
        rope_scaling=(8.0, 1.0, 4.0, 8192),    # Llama-3.1 config.json rope_scaling
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b",
        vocab_size=128_256,
        d_model=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        d_ff=28_672,
        max_seq_len=8192,
    ),
    # -- the reference's other engine-profile families ----------------------
    # (/root/reference/profiles/tensorrt-llm/codellama-7b.yaml, mistral-7b.yaml)
    "codellama-7b": ModelConfig(
        name="codellama-7b",
        vocab_size=32_016,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,               # Llama-2 7B is MHA
        d_ff=11_008,
        max_seq_len=8192,
        rope_theta=1_000_000.0,      # CodeLlama's long-context base
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b",
        vocab_size=32_000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14_336,
        max_seq_len=8192,
        rope_theta=10_000.0,
        sliding_window=4096,
    ),
    "qwen2-7b": ModelConfig(
        name="qwen2-7b",
        vocab_size=152_064,
        d_model=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        d_ff=18_944,
        max_seq_len=8192,
        rope_theta=1_000_000.0,
        attn_bias=True,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32_000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14_336,
        max_seq_len=8192,
        rope_theta=1_000_000.0,
        n_experts=8,
        n_experts_per_tok=2,
    ),
    "phi-2.7b": ModelConfig(
        name="phi-2.7b",
        vocab_size=51_200,
        d_model=2560,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,               # MHA
        d_ff=10_240,
        max_seq_len=2048,
        rope_theta=10_000.0,
        block="phi",
        partial_rotary_factor=0.4,
        attn_bias=True,
        rms_eps=1e-5,
    ),
    # Gemma-2 (published architecture): sandwich norms, GeGLU, soft-caps,
    # head_dim 256 decoupled from d_model/n_heads, alternating 4096-token
    # local / global attention, tied embeddings, 256k vocab.
    "gemma-2-9b": ModelConfig(
        name="gemma-2-9b",
        vocab_size=256_000,
        d_model=3584,
        n_layers=42,
        n_heads=16,
        n_kv_heads=8,
        d_ff=14_336,
        max_seq_len=8192,
        rope_theta=10_000.0,
        rms_eps=1e-6,
        block="gemma2",
        tie_embeddings=True,
        explicit_head_dim=256,
        query_pre_attn_scalar=256.0,
        attn_softcap=50.0,
        final_softcap=30.0,
        sliding_window=4096,
        alt_sliding_window=True,
    ),
    "gemma-2-2b": ModelConfig(
        name="gemma-2-2b",
        vocab_size=256_000,
        d_model=2304,
        n_layers=26,
        n_heads=8,
        n_kv_heads=4,
        d_ff=9216,
        max_seq_len=8192,
        rope_theta=10_000.0,
        rms_eps=1e-6,
        block="gemma2",
        tie_embeddings=True,
        explicit_head_dim=256,
        query_pre_attn_scalar=256.0,
        attn_softcap=50.0,
        final_softcap=30.0,
        sliding_window=4096,
        alt_sliding_window=True,
    ),
    # Olmo-Hybrid-7B (huggingface.co/allenai/Olmo-Hybrid-7B config.json):
    # three gated-delta-rule linear-attention layers to one full-attention
    # layer, eight times; OLMo-2/3's reordered norm and QK-norm. rope_theta
    # is OLMo-3's (the source's is null).
    "olmo-hybrid-7b": ModelConfig(
        name="olmo-hybrid-7b",
        vocab_size=100_352,
        d_model=3840,
        n_layers=32,
        n_heads=30,
        n_kv_heads=30,
        d_ff=11_008,
        max_seq_len=8192,
        rope_theta=500_000.0,
        rms_eps=1e-6,
        block="olmo2",
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 8,
        linear_num_key_heads=30,
        linear_num_value_heads=30,
        linear_key_head_dim=96,
        linear_value_head_dim=192,
        linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
    ),
    # Nemotron-3-Super-120B-A12B (huggingface.co/nvidia/NVIDIA-Nemotron-3-
    # Super-120B-A12B-BF16 config.json): 40 Mamba-2, 8 attention and 40
    # expert blocks in the published order; 512 latent experts, 22 a token,
    # one shared expert. The multi-token-prediction module is not built.
    "nemotron-3-super-120b": ModelConfig(
        name="nemotron-3-super-120b",
        vocab_size=131_072,
        d_model=4096,
        n_layers=88,
        n_heads=32,
        n_kv_heads=2,
        d_ff=2688,
        max_seq_len=8192,
        rms_eps=1e-5,
        hybrid_override_pattern=("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                                 "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        mamba_num_heads=128,
        mamba_head_dim=64,
        ssm_state_size=128,
        n_groups=8,
        conv_kernel=4,
        chunk_size=128,
        n_routed_experts=512,
        num_experts_per_tok=22,
        moe_latent_size=1024,
        moe_intermediate_size=2688,
        moe_shared_expert_intermediate_size=5376,
        routed_scaling_factor=5.0,
        norm_topk_prob=True,
    ),
    # Falcon-H1-34B-Instruct (huggingface.co/tiiuae/Falcon-H1-34B-Instruct
    # config.json): 72 blocks, each a Mamba-2 mixer (32 heads x 128, state
    # 256, 2 groups; d_inner 4096 is NOT expand x hidden) and rotary
    # attention (20 / 4 heads x 128, theta 1e11) side by side on one normed
    # input, then a SwiGLU MLP; the muP multipliers as published.
    "falcon-h1-34b": ModelConfig(
        name="falcon-h1-34b",
        vocab_size=261_120,
        d_model=5120,
        n_layers=72,
        n_heads=20,
        n_kv_heads=4,
        d_ff=21_504,
        max_seq_len=8192,
        rope_theta=1e11,
        rms_eps=1e-5,
        explicit_head_dim=128,
        hybrid_override_pattern="P" * 72,
        mamba_num_heads=32,
        mamba_head_dim=128,
        ssm_state_size=256,
        n_groups=2,
        conv_kernel=4,
        chunk_size=128,
        embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0078125,
        attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804,
        ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ),
    # LFM2-8B-A1B (huggingface.co/LiquidAI/LFM2-8B-A1B config.json): 18 gated
    # short convolutions and 6 attention layers (per-head QK-norm) in the
    # published irregular order; a dense MLP under the first two layers, 32
    # SwiGLU experts (4 a token, biased sigmoid router) under the other 22.
    "lfm2-8b-a1b": ModelConfig(
        name="lfm2-8b-a1b",
        vocab_size=65_536,
        d_model=2048,
        n_layers=24,
        n_heads=32,
        n_kv_heads=8,
        d_ff=7168,
        max_seq_len=8192,
        rope_theta=1_000_000.0,
        rms_eps=1e-5,
        tie_embeddings=True,
        layer_types=tuple("full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
                          for i in range(24)),
        conv_L_cache=3,
        num_dense_layers=2,
        n_routed_experts=32,
        num_experts_per_tok=4,
        moe_intermediate_size=1792,
        routed_scaling_factor=1.0,
        norm_topk_prob=True,
    ),
    # SmallThinker-21BA3B-Instruct (huggingface.co/PowerInfer/
    # SmallThinker-21BA3B-Instruct config.json): 52 layers in periods of one
    # global layer without positions and three window-4096 layers with RoPE;
    # 64 ReLU-gated GLU experts a layer, 6 a token, routed by a softmax router
    # from the layer's input. No dense feed-forward: d_ff multiplies nothing.
    "smallthinker-21b-a3b": ModelConfig(
        name="smallthinker-21b-a3b",
        vocab_size=151_936,
        d_model=2560,
        n_layers=52,
        n_heads=28,
        n_kv_heads=4,
        d_ff=0,
        explicit_head_dim=128,
        max_seq_len=16_384,
        rope_theta=1_500_000.0,
        rms_eps=1e-6,
        sliding_window_layout=(0, 1, 1, 1) * 13,
        rope_layout=(0, 1, 1, 1) * 13,
        sliding_window_size=4096,
        n_routed_experts=64,
        num_experts_per_tok=6,
        moe_intermediate_size=768,
        norm_topk_prob=True,
    ),
    # -- tiny CI variants (CPU in <1s) exercising each architecture axis ----
    "mistral-tiny": ModelConfig(
        name="mistral-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=256,
        rope_theta=10_000.0,
        sliding_window=16,           # small enough that tests hit the window
    ),
    "qwen-tiny": ModelConfig(
        name="qwen-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=256,
        rope_theta=10_000.0,
        attn_bias=True,
    ),
    "phi-tiny": ModelConfig(
        name="phi-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        d_ff=256,
        max_seq_len=256,
        rope_theta=10_000.0,
        block="phi",
        partial_rotary_factor=0.5,
        attn_bias=True,
    ),
    "gemma-tiny": ModelConfig(
        name="gemma-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=4,                  # even+odd layers: both mask phases run
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=256,
        rope_theta=10_000.0,
        rms_eps=1e-6,
        block="gemma2",
        tie_embeddings=True,
        explicit_head_dim=48,        # != d_model/n_heads: exercises the override
        query_pre_attn_scalar=48.0,
        attn_softcap=50.0,
        final_softcap=30.0,
        sliding_window=16,
        alt_sliding_window=True,
    ),
    # one whole period x 2 of the Olmo-Hybrid pattern (3 linear + 1 full)
    "olmo-hybrid-tiny": ModelConfig(
        name="olmo-hybrid-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=8,
        n_heads=4,
        n_kv_heads=4,
        d_ff=256,
        max_seq_len=256,
        rope_theta=500_000.0,
        rms_eps=1e-6,
        block="olmo2",
        layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2,
        linear_num_key_heads=4,
        linear_num_value_heads=4,
        linear_key_head_dim=24,
        linear_value_head_dim=48,
        linear_conv_kernel_dim=4,
        linear_allow_neg_eigval=True,
    ),
    # all three kinds of block in an irregular order; a quarter of the
    # experts held, as one of four devices that share each layer would
    "nemotron-h-tiny": ModelConfig(
        name="nemotron-h-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=7,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        max_seq_len=256,
        rms_eps=1e-5,
        hybrid_override_pattern="MEM*EME",
        mamba_num_heads=8,
        mamba_head_dim=16,
        ssm_state_size=16,
        n_groups=2,
        conv_kernel=4,
        chunk_size=32,
        n_routed_experts=32,
        num_experts_per_tok=4,
        moe_latent_size=64,
        moe_intermediate_size=96,
        moe_shared_expert_intermediate_size=192,
        routed_scaling_factor=2.5,
        norm_topk_prob=True,
        n_experts_held=8,
        expert_offset=0,
    ),
    # three two-mixer blocks: attention narrower than the hidden size, a
    # d_inner that is not expand x hidden, every multiplier away from 1
    "falcon-h1-tiny": ModelConfig(
        name="falcon-h1-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=3,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=256,
        rope_theta=1e11,
        rms_eps=1e-5,
        explicit_head_dim=16,
        hybrid_override_pattern="PPP",
        mamba_num_heads=8,
        mamba_head_dim=12,
        ssm_state_size=32,
        n_groups=2,
        conv_kernel=4,
        chunk_size=32,
        embedding_multiplier=5.656854249492381,
        lm_head_multiplier=0.0625,
        attention_in_multiplier=0.8,
        attention_out_multiplier=0.3,
        key_multiplier=0.25,
        ssm_in_multiplier=0.5,
        ssm_out_multiplier=0.35,
        ssm_multipliers=(0.7, 0.5, 0.35, 1.4, 0.7),
        mlp_multipliers=(0.35, 0.2),
    ),
    # a whole irregular order (gaps 2, 1 between the attention layers), one
    # dense layer then experts, every expert held
    "lfm2-moe-tiny": ModelConfig(
        name="lfm2-moe-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=6,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=256,
        rope_theta=1_000_000.0,
        rms_eps=1e-5,
        tie_embeddings=True,
        layer_types=("conv", "conv", "full_attention", "conv", "full_attention", "conv"),
        conv_L_cache=3,
        num_dense_layers=1,
        n_routed_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=96,
        routed_scaling_factor=1.0,
        norm_topk_prob=True,
    ),
    # two periods of global + three window layers; a window of 16 so that a
    # short prompt wraps the ring; every expert held
    "smallthinker-tiny": ModelConfig(
        name="smallthinker-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        d_ff=0,
        max_seq_len=256,
        rope_theta=1_500_000.0,
        rms_eps=1e-6,
        sliding_window_layout=(0, 1, 1, 1) * 2,
        rope_layout=(0, 1, 1, 1) * 2,
        sliding_window_size=16,
        n_routed_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=96,
        norm_topk_prob=True,
    ),
    "mixtral-tiny": ModelConfig(
        name="mixtral-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=256,
        max_seq_len=256,
        rope_theta=10_000.0,
        n_experts=4,
        n_experts_per_tok=2,
    ),
}


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in PRESETS:
        raise ValueError(f"unknown model preset {name!r}; known: {sorted(PRESETS)}")
    cfg = PRESETS[name]
    return cfg.scaled(**overrides) if overrides else cfg

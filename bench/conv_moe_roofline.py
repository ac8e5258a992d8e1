"""Bytes, operations and shape rules of a decoder whose layers pair a gated
short convolution or attention with a dense MLP or routed SwiGLU experts
(LFM2-MoE): what its per-layer metrics (``bench/readers/glu_moe_*.py``,
``short_conv_decode_ms.py``) compute from. Kept beside
``bench/ssm_moe_roofline.py`` (the latent experts' and the state-space
blocks'), whose reading of an operation's (cut) name it shares.

Nothing in a trace says which layer an operation belongs to, so the two kinds
of layer are found by their shapes, as the trace prints them
(``bench/trace_reduce.short_name``: layouts removed, cut to 96 characters):

- the **expert layers'** operations carry the experts' own width (1792, which
  nothing else in the model has: the dense MLP is 7168 wide, the hidden size
  2048, an operator's ``in_proj`` 6144, the K/V projections 512, a head 64):
  the int8 stacks ``s8[22,32,2048,1792]`` / ``s8[22,32,1792,2048]``, their
  scales ``f32[22,32,1792]`` and the ``[32, tokens, 1792]`` activations between
  them; or they are the router's: the float32 ``[.., 2048, 32]`` weights, the
  ``[tokens, 32]`` scores, sorts and choices, the ``[tokens, 4]`` weights of the
  four chosen. ``reads_expert_stack`` names the experts' matmuls alone (a
  shape with the experts held beside their width, or the weights' own);
  ``in_expert_layer`` all of them. 32 is also the number of query heads: a
  two-dimensional ``[tokens, 32]`` shape in an operation that shows a head
  (a last dimension of 64) is the per-head QK-norm's, and is not counted; the
  norm's two scalar fusions a layer that show nothing but ``f32[16,32]`` ARE
  counted (a microsecond each: 0.1% of the layers' time);
- the **short convolution's** operations read one of its stacks
  (``s8[18,2048,6144]``, ``s8[18,2048,2048]``: 18 deep, where the attention
  projections of the same width are 6 deep), carry ``in_proj``'s width (6144),
  the taps ``[.., 3, 2048]`` or the tail ``[18, slots, 2, 2048]`` /
  ``[slots, 2, 2048]``: ``in_short_conv``. In a decode step the compiler
  fetches the whole ``out_proj`` stack into fast memory once
  (``%copy-done = s8[18,2048,2048]``, counted: it is the stack's one read from
  HBM) and the 18 fusions that multiply it read it there; those also carry the
  residual add and the next norm's sum, ``(f32[16], bf16[16,2048]) fusion(``,
  and the 96 characters show neither the stack nor the gated operand, only
  what an attention layer's ``wo`` and the dense ``w_down`` fusions show too:
  they are left out (0.004 ms each, 0.07 ms a step of 0.49). Two dense
  layers' ``[2, 2048]`` gains and scales look like a tail and are counted
  (one conversion a program, microseconds).

Loops and calls are never counted (``hybrid_roofline.is_leaf``).
"""

from __future__ import annotations

from bench import hybrid_roofline, metrics, peaks

shapes_in = hybrid_roofline.shapes_in
is_leaf = hybrid_roofline.is_leaf


def sizes(m: dict) -> dict | None:
    """The layers' sizes from the configuration's (published) keys, or None
    for a model without such layers."""
    kinds = m.get("layer_types") or ()
    if "conv" not in kinds or "num_experts" not in m:
        return None
    share = m.get("share") or {}
    return {"hidden": m["hidden_size"], "ff": m["moe_intermediate_size"],
            "experts": m["num_experts"], "held": share.get("experts_held", m["num_experts"]),
            "top_k": m["num_experts_per_tok"], "taps": m["conv_L_cache"],
            "head_dim": m["head_dim"],
            "conv_layers": sum(k == "conv" for k in kinds),
            "expert_layers": m["num_hidden_layers"] - m["num_dense_layers"]}


def expert_bytes(m: dict) -> int:
    """One expert as the configuration stores it: W_gate, W_up and W_down in
    int8 with a float32 scale per output channel (11,010,048 + 22,528 B)."""
    x = sizes(m)
    return 3 * x["hidden"] * x["ff"] + 4 * (2 * x["ff"] + x["hidden"])


def expert_activation_bytes(m: dict, assignments: float) -> float:
    """What ``assignments`` token-expert pairs move beside the weights: the
    hidden input read and the hidden output written, bf16."""
    return assignments * 2 * 2 * sizes(m)["hidden"]


def expert_flops_per_token(m: dict) -> float:
    """Multiply-adds x 2 of the experts one token needs in one layer: its
    ``top_k`` chosen experts' three matmuls (4 x 22.02 MFLOP)."""
    x = sizes(m)
    return x["top_k"] * 3 * 2.0 * x["hidden"] * x["ff"]


def reads_expert_stack(op_name: str, m: dict) -> bool:
    """An operation that reads an expert weight stack: one of its shapes is
    the stack's own, or holds the experts beside their width (the activations
    between W_gate / W_up and W_down, which only those matmuls write and read)."""
    x = sizes(m)
    if not is_leaf(op_name):
        return False
    d, ff, held = x["hidden"], x["ff"], x["held"]
    for s in shapes_in(op_name):
        if len(s) >= 3 and s[-3:] in ((held, d, ff), (held, ff, d)):
            return True
        if len(s) == 3 and ff in s and held in s:
            return True
    return False


def in_expert_layer(op_name: str, m: dict) -> bool:
    """An operation of an expert layer's feed-forward: the router, the choice
    and its weights, the experts' matmuls and the combine."""
    if not is_leaf(op_name):
        return False
    x = sizes(m)
    shapes = shapes_in(op_name)
    if any(x["ff"] in s for s in shapes):
        return True
    if any(s and s[-1] == x["head_dim"] for s in shapes):
        return False                      # a head shows: the per-head QK-norm
    d, e, k = x["hidden"], x["experts"], x["top_k"]
    return any((len(s) >= 2 and s[-2:] == (d, e)) or (len(s) == 2 and s[-1] in (e, k))
               or (len(s) == 3 and s[-2:] == (k, 1)) for s in shapes)


def in_short_conv(op_name: str, m: dict) -> bool:
    """An operation of a short-convolution operator: its two projections, the
    gates, the taps and the tail."""
    if not is_leaf(op_name):
        return False
    x = sizes(m)
    d, n, t = x["hidden"], x["conv_layers"], x["taps"]
    for s in shapes_in(op_name):
        if s[-1] == 3 * d:
            return True
        if len(s) >= 3 and s[0] == n and s[-2:] in ((d, 3 * d), (d, d)):
            return True
        if len(s) >= 2 and s[-2:] in ((t, d), (t - 1, d)):
            return True
    return False


def seconds_and_runs(trace: dict | None, m: dict, program: str, rule) -> tuple[float, int] | None:
    """Seconds ``program`` (``jit_decode``, ``jit_prefill``) spent in the
    operations ``rule`` names over the traced window, and the times it ran;
    nothing without a trace, for a model without such layers or without such
    operations."""
    if sizes(m) is None:
        return None
    return hybrid_roofline.seconds_and_runs(trace, program, lambda name: rule(name, m))


def expert_hbm_share(ctx, stack) -> float | None:
    """What the routed experts' matmuls must read in a decode step over the
    memory bandwidth, as a share of their device time, count and time from the
    SAME seconds: the held experts a step touched are the traced slice's own
    (``moe_touched`` over ``moe_steps``, summed over the ``kvmini.emit`` spans
    that retired its sweeps; ``trace_reduce.span_counts``), the time a step that
    of the operations that read an expert stack in the slice's ``jit_decode``.
    ``stack`` is the roofline module of the cell's kind of expert (this one,
    ``window_moe_roofline``, ``ssm_moe_roofline``): its ``seconds_and_runs``,
    ``reads_expert_stack``, ``expert_bytes`` and ``expert_activation_bytes``.
    The assignments' activations (8 KB each beside 5.5-11 MB an expert, under
    0.2% of the bytes) keep the 45 s window's mean a step. Nothing for a trace
    whose spans carry no counts (an older program, a model without experts)."""
    m = ctx.memory["model"]
    found = stack.seconds_and_runs(ctx.trace, m, "jit_decode", stack.reads_expert_stack)
    emit = metrics.span_sums(ctx.trace, "kvmini.emit")
    if found is None or not emit.get("moe_steps") or not emit.get("moe_touched"):
        return None
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    landed = ctx.counters.get("kvmini_tpu_moe_local_assignments_total", 0.0) / max(steps, 1.0)
    need = (emit["moe_touched"] / emit["moe_steps"] * stack.expert_bytes(m)
            + stack.expert_activation_bytes(m, landed))
    least_s = need / peaks.for_kind(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (found[0] / found[1])

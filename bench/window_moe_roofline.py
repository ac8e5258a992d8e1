"""Bytes, operations and shape rules of a decoder whose every layer is attention
over a window or over the whole context (two K/V leaves of different lengths)
and routed ReLU-gated GLU experts behind a softmax router (SmallThinker): what
its per-layer metrics (``bench/readers/reglu_moe_*.py``, ``mixed_kv_decode_ms.py``,
``window_flash_prefill_roofline.py``) compute from. Kept beside
``bench/conv_moe_roofline.py`` (the SwiGLU experts' and the short convolution's),
whose reading of an operation's (cut) name it shares.

Nothing in a trace says which layer an operation belongs to, so the parts are
found by their shapes, as the trace prints them (``bench/trace_reduce.short_name``:
layouts removed, cut to 96 characters):

- the **expert layers'** operations carry the experts' own width (768, which
  nothing else in the model has: the hidden size is 2560, the query projection
  3584, the K/V projections 512, a head 128, the vocabulary 151936): the int8
  stacks ``s8[12,64,2560,768]`` / ``s8[12,64,768,2560]``, their scales and the
  ``[64, tokens, 768]`` activations between them; or they are the router's: the
  float32 ``[.., 2560, 64]`` weights, the ``[tokens, 64]`` logits, sorts and
  choices, the ``[tokens, 6]`` weights of the six chosen. ``reads_expert_stack``
  names the experts' matmuls alone, ``in_expert_layer`` all of them;
- the **K/V reads of a decode step** are the ``kvmini_dense_decode`` Mosaic
  calls, one a layer, whose result is the grouped query's shape ``[slots, 4, 7,
  128]``: ``reads_kv``. Nine of twelve read the window layers' ring, three the
  global layers' stripes; the shape does not say which, the count a step does;
- the **flash prefill** Mosaic calls' result is ``[1, 28, T, 128]``:
  ``flash_calls`` gives, for each piece length ``T`` the trace holds, their
  seconds and count. Of a program's twelve, nine run under the window's mask
  and three under the causal one; ``flash_min_s`` counts each kind's
  operations INSIDE its own mask.

Loops and calls are never counted (``hybrid_roofline.is_leaf``).
"""

from __future__ import annotations

from bench import hybrid_roofline, roofline

shapes_in = hybrid_roofline.shapes_in
is_leaf = hybrid_roofline.is_leaf


def sizes(m: dict) -> dict | None:
    """The layers' sizes from the configuration's (published) keys, or None
    for a model without such layers."""
    if "sliding_window_layout" not in m or "moe_num_primary_experts" not in m:
        return None
    n = m["num_hidden_layers"]
    window = sum(m["sliding_window_layout"][:n])
    return {"hidden": m["hidden_size"], "ff": m["moe_ffn_hidden_size"],
            "experts": m["moe_num_primary_experts"], "held": m["moe_num_primary_experts"],
            "top_k": m["moe_num_active_primary_experts"], "head_dim": m["head_dim"],
            "heads": m["num_attention_heads"], "kv_heads": m["num_key_value_heads"],
            "window": m["sliding_window_size"], "layers": n, "expert_layers": n,
            "window_layers": window, "global_layers": n - window}


def expert_bytes(m: dict) -> int:
    """One expert as the configuration stores it: W_gate, W_up and W_down in
    int8 with a float32 scale per output channel (5,898,240 + 16,384 B)."""
    x = sizes(m)
    return 3 * x["hidden"] * x["ff"] + 4 * (2 * x["ff"] + x["hidden"])


def expert_activation_bytes(m: dict, assignments: float) -> float:
    """What ``assignments`` token-expert pairs move beside the weights: the
    hidden input read and the hidden output written, bf16."""
    return assignments * 2 * 2 * sizes(m)["hidden"]


def expert_flops_per_token(m: dict) -> float:
    """Multiply-adds x 2 of the experts one token needs in one layer: its
    ``top_k`` chosen experts' three matmuls (6 x 11.80 MFLOP)."""
    x = sizes(m)
    return x["top_k"] * 3 * 2.0 * x["hidden"] * x["ff"]


def reads_expert_stack(op_name: str, m: dict) -> bool:
    """An operation that reads an expert weight stack: one of its shapes is
    the stack's own, or holds the experts beside their width (the activations
    between W_gate / W_up and W_down, which only those matmuls write and read)."""
    if not is_leaf(op_name):
        return False
    x = sizes(m)
    d, ff, held = x["hidden"], x["ff"], x["held"]
    for s in shapes_in(op_name):
        if len(s) >= 3 and s[-3:] in ((held, d, ff), (held, ff, d)):
            return True
        if len(s) == 3 and ff in s and held in s:
            return True
    return False


def in_expert_layer(op_name: str, m: dict) -> bool:
    """An operation of a layer's routed feed-forward: the router, the choice
    and its weights, the experts' matmuls and the combine."""
    if not is_leaf(op_name):
        return False
    x = sizes(m)
    shapes = shapes_in(op_name)
    if any(x["ff"] in s for s in shapes):
        return True
    if any(s and s[-1] == x["head_dim"] for s in shapes):
        return False                      # a head shows: attention's
    d, e, k = x["hidden"], x["experts"], x["top_k"]
    return any((len(s) >= 2 and s[-2:] == (d, e)) or (len(s) == 2 and s[-1] in (e, k))
               or (len(s) == 3 and s[-2:] == (k, 1)) for s in shapes)


def reads_kv(op_name: str, m: dict, slots: int) -> bool:
    """A decode step's Mosaic call over a K/V leaf: its result is the grouped
    query's, ``[slots, kv_heads, heads / kv_heads, head_dim]``."""
    x = sizes(m)
    return (" custom-call(" in op_name and roofline.output_shape(op_name)
            == (slots, x["kv_heads"], x["heads"] // x["kv_heads"], x["head_dim"]))


def seconds_and_runs(trace: dict | None, m: dict, program: str, rule) -> tuple[float, int] | None:
    """Seconds ``program`` (``jit_decode``, ``jit_prefill``) spent in the
    operations ``rule`` names over the traced window, and the times it ran;
    nothing without a trace, for a model without such layers or without such
    operations."""
    if sizes(m) is None:
        return None
    return hybrid_roofline.seconds_and_runs(trace, program, lambda name: rule(name, m))


def flash_calls(trace: dict | None, m: dict) -> dict[int, tuple[float, int]]:
    """``{T: (seconds, calls)}`` of the flash-prefill Mosaic calls in the
    traced window, by the piece length their result shows."""
    x, out = sizes(m), {}
    if x is None:
        return out
    for name, op in ((trace or {}).get("ops") or {}).items():
        shape = roofline.output_shape(name)
        if (name.startswith("jit_prefill/") and " custom-call(" in name and len(shape) == 4
                and shape[1] == x["heads"] and shape[3] == x["head_dim"]):
            took, calls = out.get(shape[2], (0.0, 0))
            out[shape[2]] = (took + op["seconds"], calls + op["calls"])
    return out


def visible_pairs(t: int, window: int | None) -> int:
    """(query, key) pairs inside the mask of a fresh piece of ``t`` positions:
    ``j <= i``, and ``i - j < window`` where there is one."""
    if window is None or t <= window:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def flash_min_s(m: dict, t: int, window: int | None, peaks: dict) -> float:
    """Least time of one attention call over a fresh piece of ``t`` positions:
    the larger of its operations over the bf16 peak and its bytes over the
    memory bandwidth. Operations: QK^T and PV for the pairs INSIDE the mask,
    ``4 * heads * pairs * head_dim``; bytes: q, k, v and the output, bf16."""
    x = sizes(m)
    flops = 4.0 * x["heads"] * visible_pairs(t, window) * x["head_dim"]
    nbytes = 2.0 * (2 * x["heads"] + 2 * x["kv_heads"]) * t * x["head_dim"]
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])

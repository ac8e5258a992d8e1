"""Bytes and shape rules of a decoder of single-mixer blocks (Mamba-2, routed
latent experts beside a shared expert, attention): what its per-layer metrics
(``bench/readers/moe_*.py``, ``ssm_*.py``) compute from. Kept beside
``bench/roofline.py``, whose ``decode_step_bytes`` counts a dense decoder's
bytes, and ``bench/hybrid_roofline.py`` (the delta rule's), whose reading of an
operation's (cut) name it shares.

Nothing in a trace says which block an operation belongs to, so the two kinds
of block are found by their shapes, as the trace prints them
(``bench/trace_reduce.short_name``: layouts removed, cut to 96 characters):

- the **expert block's** operations read one of its weights or carry one of
  its own widths: the int8 stacks ``s8[10,128,1024,2688]`` /
  ``s8[10,128,2688,1024]``, the router ``f32[10,4096,512]`` and its 512-wide
  scores, the 22 experts chosen a token, the latent projections
  ``s8[10,4096,1024]`` / ``s8[10,1024,4096]``, the shared expert
  ``s8[10,4096,5376]`` / ``s8[10,5376,4096]`` and its 5376-wide activations.
  ``in_expert_block`` names all of them; ``reads_expert_stack`` the routed
  experts' matmuls alone (an operand whose last three dimensions are held x
  latent x width or held x width x latent);
- the **state-space state** is the only tensor whose last three dimensions are
  heads x head_dim x state (128 x 64 x 128): ``touches_state``.

Loops and calls carry the whole cache in their tuples and span their bodies:
they are never counted (``hybrid_roofline.is_leaf``).
"""

from __future__ import annotations

from bench import hybrid_roofline

shapes_in = hybrid_roofline.shapes_in
is_leaf = hybrid_roofline.is_leaf


def sizes(m: dict) -> dict | None:
    """The blocks' sizes from the configuration's (published) keys, or None
    for a model without such blocks."""
    if "hybrid_override_pattern" not in m or "mamba_num_heads" not in m:
        return None
    pat = m["hybrid_override_pattern"]
    return {"heads": m["mamba_num_heads"], "head_dim": m["mamba_head_dim"],
            "state": m["ssm_state_size"], "hidden": m["hidden_size"],
            "held": m["n_routed_experts"], "router_width": m["share"]["router_width"],
            "latent": m["moe_latent_size"], "ff": m["moe_intermediate_size"],
            "shared_ff": m["moe_shared_expert_intermediate_size"],
            "top_k": m["num_experts_per_tok"],
            "ssm_layers": pat.count("M"), "expert_layers": pat.count("E")}


def state_bytes_per_slot(m: dict) -> int:
    """One pass (a read or a write) over one slot's float32 state, all Mamba-2 blocks."""
    x = sizes(m)
    return 4 * x["ssm_layers"] * x["heads"] * x["head_dim"] * x["state"]


def expert_bytes(m: dict) -> int:
    """One routed expert as the configuration stores it: W1 and W2 in int8
    with a float32 scale per output channel."""
    x = sizes(m)
    return 2 * x["latent"] * x["ff"] + 4 * (x["ff"] + x["latent"])


def expert_activation_bytes(m: dict, assignments: float) -> float:
    """What ``assignments`` token-expert pairs move beside the weights: the
    latent input read and the latent output written, bf16."""
    return assignments * 2 * 2 * sizes(m)["latent"]


def touches_state(op_name: str, m: dict) -> bool:
    """An operation that reads or writes the state-space state."""
    x = sizes(m)
    tail = (x["heads"], x["head_dim"], x["state"])
    return is_leaf(op_name) and any(len(s) >= 4 and s[-3:] == tail for s in shapes_in(op_name))


def reads_expert_stack(op_name: str, m: dict) -> bool:
    """An operation that reads a routed-expert weight stack."""
    x = sizes(m)
    tails = ((x["held"], x["latent"], x["ff"]), (x["held"], x["ff"], x["latent"]))
    return is_leaf(op_name) and any(len(s) >= 3 and s[-3:] in tails for s in shapes_in(op_name))


def in_expert_block(op_name: str, m: dict) -> bool:
    """An operation of an expert block: the router and the choice of experts,
    the latent projections, the experts' matmuls and combine, the shared
    expert. 128 is the experts held, the Mamba-2 heads and its state size at
    once, so a lone dimension of the experts held proves nothing: the rules
    are the weights' own shapes, the router's and the shared expert's widths,
    and the experts chosen a token (22)."""
    if not is_leaf(op_name) or touches_state(op_name, m):
        return False
    if reads_expert_stack(op_name, m):
        return True
    x = sizes(m)
    d = x["hidden"]
    pairs = {(d, x["router_width"]), (d, x["latent"]), (x["latent"], d),
             (d, x["shared_ff"]), (x["shared_ff"], d)}
    widths = {x["router_width"], x["shared_ff"], x["top_k"]}
    return any((len(s) >= 2 and s[-2:] in pairs) or (s and s[-1] in widths)
               or (len(s) >= 2 and s[-2] == x["top_k"]) for s in shapes_in(op_name))


def seconds_and_runs(trace: dict | None, m: dict, program: str, rule) -> tuple[float, int] | None:
    """Seconds ``program`` spent in the operations ``rule`` names over the
    traced window, and the times it ran; nothing without a trace, for a model
    without such blocks or without such operations."""
    if sizes(m) is None:
        return None
    return hybrid_roofline.seconds_and_runs(trace, program, lambda name: rule(name, m))


def decode_seconds_and_steps(trace: dict | None, m: dict, rule) -> tuple[float, int] | None:
    """``seconds_and_runs`` of ``jit_decode``: the seconds in the operations
    ``rule`` names, and the steps it ran."""
    return seconds_and_runs(trace, m, "jit_decode", rule)

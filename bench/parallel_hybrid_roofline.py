"""Bytes and shape rules of a decoder whose every block runs a Mamba-2 mixer
and rotary attention side by side: what its per-layer metrics
(``bench/readers/pmix_*.py``) compute from. Kept beside ``bench/roofline.py``
(a dense decoder's bytes), ``bench/hybrid_roofline.py`` (the delta rule's,
whose reading of an operation's cut name it shares) and
``bench/ssm_moe_roofline.py`` (the single-mixer blocks').

Nothing in a trace says which part of a block an operation belongs to, so the
mixers' operations are found by their shapes, as the trace prints them
(``bench/trace_reduce.short_name``: layouts removed, cut to 96 characters):

- the **state-space state** is the only tensor whose last three dimensions are
  heads x head width x state (32 x 128 x 256): ``touches_state``;
- the **mixers' matmuls** read one of six int8 weight stacks, each the only
  tensor of its shape ``[layers, in, out]``: ``in_proj`` ``s8[12,5120,9248]``,
  ``out_proj`` ``s8[12,4096,5120]``, ``wq`` ``s8[12,5120,2560]``, ``wk`` and
  ``wv`` ``s8[12,5120,512]``, ``wo`` ``s8[12,2560,5120]`` (the MLP's are
  ``[12,5120,21504]`` / ``[12,21504,5120]``, ``lm_head`` has no layer axis):
  ``reads_mixer_weight``. **Not found: ``wo``.** Its fusion also carries the
  residual add and the next norm's sum, ``%fusion.576 = (f32[16], bf16[16,5120])
  fusion(bf16[16,5120] .., bf16[5120] ..``, and the 96-character cut hides both its
  weight and its 2560-wide operand: what the name still shows is what the MLP's
  ``w_down`` fusion shows. Twelve calls of 0.019 ms (the slice of PR 35): the share
  reads 36.2% where it would read 37.7% with them;
- the **attention kernel** is the custom call named ``kvmini_dense_decode``.

``in_mixers`` is the three together: what a decode step spends in both mixers.
The MLP, ``lm_head``, the sampler, norms, the convolution and the rotation
are the rest (the convolution's 5120 channels are the hidden size too, so no
shape tells it apart).

Loops and calls carry the whole cache in their tuples and span their bodies:
they are never counted (``hybrid_roofline.is_leaf``).
"""

from __future__ import annotations

from bench import hybrid_roofline

shapes_in = hybrid_roofline.shapes_in
is_leaf = hybrid_roofline.is_leaf

ATTENTION_KERNEL = "kvmini_dense_decode"
# bench/run.py traces TRACE_SECONDS in the middle of the window
TRACE_SECONDS = 3.0


def sizes(m: dict) -> dict | None:
    """The two mixers' sizes from the configuration's (published) keys, or
    None for a model without such blocks."""
    if "mamba_d_ssm" not in m or "ssm_multipliers" not in m:
        return None
    hd = m["head_dim"]
    return {"heads": m["mamba_n_heads"], "head_dim": m["mamba_d_head"],
            "state": m["mamba_d_state"], "groups": m["mamba_n_groups"],
            "d_inner": m["mamba_d_ssm"], "hidden": m["hidden_size"],
            "layers": m["num_hidden_layers"],
            "q_width": m["num_attention_heads"] * hd, "kv_width": m["num_key_value_heads"] * hd}


def state_bytes_per_slot(m: dict) -> int:
    """One pass (a read or a write) over one slot's float32 state, all layers."""
    x = sizes(m)
    return 4 * x["layers"] * x["heads"] * x["head_dim"] * x["state"]


def mixer_weight_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """``[in, out]`` of the six matmuls of a block's two mixers."""
    x = sizes(m)
    d, di = x["hidden"], x["d_inner"]
    in_width = 2 * di + 2 * x["groups"] * x["state"] + x["heads"]
    return {"in_proj": (d, in_width), "out_proj": (di, d), "wq": (d, x["q_width"]),
            "wk": (d, x["kv_width"]), "wv": (d, x["kv_width"]), "wo": (x["q_width"], d)}


def mixer_weight_bytes_per_layer(m: dict) -> int:
    """Bytes of one layer's six mixer matmuls as stored: int8 with a float32
    scale per output channel."""
    return sum(i * o + 4 * o for i, o in mixer_weight_shapes(m).values())


def touches_state(op_name: str, m: dict) -> bool:
    """An operation that reads or writes the state-space state."""
    x = sizes(m)
    tail = (x["heads"], x["head_dim"], x["state"])
    return is_leaf(op_name) and any(len(s) >= 4 and s[-3:] == tail for s in shapes_in(op_name))


def reads_mixer_weight(op_name: str, m: dict) -> bool:
    """An operation that reads one of the two mixers' weight stacks."""
    stacks = {(sizes(m)["layers"], *shape) for shape in mixer_weight_shapes(m).values()}
    return is_leaf(op_name) and any(s in stacks for s in shapes_in(op_name))


def in_mixers(op_name: str, m: dict) -> bool:
    """An operation of either mixer: the state's, the six matmuls', the
    attention kernel."""
    return is_leaf(op_name) and (ATTENTION_KERNEL in op_name or touches_state(op_name, m)
                                 or reads_mixer_weight(op_name, m))


def decode_seconds_and_steps(trace: dict | None, m: dict, rule) -> tuple[float, int] | None:
    """Seconds ``jit_decode`` spent in the operations ``rule`` names over the
    traced slice, and the steps it ran; nothing without a trace, for a model
    without such blocks or without such operations."""
    steps = len((trace or {}).get("programs", {}).get("jit_decode", []))
    if sizes(m) is None or not steps:
        return None
    took = sum(op["seconds"] for name, op in trace["ops"].items()
               if name.startswith("jit_decode/") and rule(name, m))
    return (took, steps) if took else None


def live_slots_in_traced_slice(records: list[dict], window_s: float) -> float | None:
    """Slots that decoded, averaged over the slice of the window that was
    traced (``TRACE_SECONDS`` about its middle), from the client's books at 100
    Hz: a request holds a slot from its first token to its last. Taken over
    the SAME slice as the operations' time, so that a program that moves the
    state of live slots only cannot read over its bandwidth."""
    lo = max(window_s / 2.0 - TRACE_SECONDS / 2.0, 0.0)
    hi = min(lo + TRACE_SECONDS, window_s)
    spans = [(r["token_s"][0], r["done_s"] if r["done_s"] is not None else r["token_s"][-1])
             for r in records if r["token_s"]]
    n = int((hi - lo) * 100)
    if n <= 0 or not spans:
        return None
    at = [lo + (i + 0.5) / 100.0 for i in range(n)]
    return sum(sum(1 for a, b in spans if a <= t <= b) for t in at) / n

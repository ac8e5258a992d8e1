"""Bytes and shape rules of a hybrid decoder's linear mixer: what its
per-layer metrics (``bench/readers/linear_*.py``) compute from. Kept beside
``bench/roofline.py``, whose ``kv_bytes_per_token`` and ``decode_step_bytes``
count K/V for every layer and so hold for the dense decoders only.

The trace names an operation by its HLO text, ``%fusion.7 = f32[16,30,96,192]
fusion(f32[24,16,30,96,192] %get-tuple-element.4, ...)``, cut to 96 characters
(``bench/trace_reduce.short_name``). Nothing in it says which layer of the
model it belongs to, so the mixer's operations are found by their shapes:

- the recurrent state is the only tensor whose last two dimensions are
  ``linear_key_head_dim`` x ``linear_value_head_dim`` (96 x 192): an operation
  that reads or writes it has such a shape among its output and first operands;
- the convolution runs over ``heads x (2 dk + dv)`` channels (11520), a width no
  projection, MLP or attention tensor has;
- the chunk-parallel scan works, under a heads dimension, on ``[..., r, d]``
  blocks with ``r`` the chunk (64) or a half or quarter of it (the blocks the
  triangular inverse is merged from) and ``d`` one of those, ``dk``, ``dv`` or
  ``dk + dv``; and on ``[..., heads, dk | dv]`` rows before they are laid out so.

Loops and calls (``%while.13 = (s32[], bf16[16,1,3840], ...``) carry the whole
cache in their tuples and span their bodies: they are never counted. The cut
hides their `` while(``, so they are known by the name XLA gives them.
"""

from __future__ import annotations

import re

CHUNK = 64      # positions the program's chunk-parallel scan solves at once
_SHAPE = re.compile(r"\b[a-z]+\d+\[([\d,]+)\]")
_CONTROL = ("%while", "%conditional", "%call", "%tuple", "%get-tuple-element")


def shapes_in(op_name: str) -> list[tuple[int, ...]]:
    """Every shape the (cut) name shows: the output's, then the first operands'."""
    return [tuple(int(x) for x in dims.split(",")) for dims in _SHAPE.findall(op_name)]


def mixer(m: dict) -> dict:
    """The linear mixer's sizes from the configuration's (published) keys."""
    h, dk, dv = m["linear_num_key_heads"], m["linear_key_head_dim"], m["linear_value_head_dim"]
    return {"heads": h, "dk": dk, "dv": dv, "channels": h * (2 * dk + dv),
            "layers": sum(t == "linear_attention" for t in m["layer_types"])}


def state_bytes_per_slot(m: dict) -> int:
    """One pass (a read or a write) over one slot's float32 state, all linear layers."""
    x = mixer(m)
    return 4 * x["layers"] * x["heads"] * x["dk"] * x["dv"]


def is_leaf(op_name: str) -> bool:
    """``<program>/%fusion.7 = ...`` is; ``<program>/%while.13 = ...`` is not."""
    return not op_name.split("/", 1)[-1].startswith(_CONTROL)


def seconds_and_runs(trace: dict | None, program: str, named) -> tuple[float, int] | None:
    """Seconds ``program`` (``jit_decode``, ``jit_prefill``) spent over the
    traced window in the operations whose name ``named`` accepts, and the times
    it ran; nothing without a trace, without a run or without such operations."""
    runs = len((trace or {}).get("programs", {}).get(program, []))
    if not runs:
        return None
    took = sum(op["seconds"] for name, op in trace["ops"].items()
               if name.startswith(program + "/") and named(name))
    return (took, runs) if took else None


def touches_state(op_name: str, m: dict) -> bool:
    """An operation that reads or writes the recurrent state."""
    x = mixer(m)
    return is_leaf(op_name) and any(
        len(s) >= 3 and s[-2:] == (x["dk"], x["dv"]) for s in shapes_in(op_name))


def in_linear_scan(op_name: str, m: dict) -> bool:
    """An operation of the mixer between its projections: the convolution,
    the chunk products and solves, the state recurrence. Judged by the
    output's shape (the first in the name) and, for the state, any shape."""
    if not is_leaf(op_name):
        return False
    if touches_state(op_name, m):
        return True
    x = mixer(m)
    shapes = shapes_in(op_name)
    if not shapes:
        return False
    out = shapes[0]
    if out[-1] == x["channels"]:
        return True
    blocks = {CHUNK, CHUNK // 2, CHUNK // 4}
    widths = blocks | {x["dk"], x["dv"], x["dk"] + x["dv"]}
    if len(out) >= 3 and out[-2] in blocks and out[-1] in widths and x["heads"] in out[:-2]:
        return True
    return len(out) >= 3 and out[-2] == x["heads"] and out[-1] in (x["dk"], x["dv"])


def state_seconds_and_steps(trace: dict | None, m: dict) -> tuple[float, int] | None:
    """Seconds ``jit_decode`` spent in the operations that touch the state over
    the traced window, and the steps it ran; nothing without a trace, without
    linear layers or without such operations."""
    steps = len((trace or {}).get("programs", {}).get("jit_decode", []))
    if "layer_types" not in m or not steps:
        return None
    took = sum(op["seconds"] for name, op in trace["ops"].items()
               if name.startswith("jit_decode/") and touches_state(name, m))
    return (took, steps) if took else None

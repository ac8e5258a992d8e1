"""What a step or a kernel needs, from its shapes: operations and bytes, and
the least time the chip could take for them (``bench/peaks.py``)."""

from __future__ import annotations

import re

from bench import config


def kv_bytes_per_token(m: dict, kv_bytes: int = 2) -> int:
    """Keys and values of one position over all layers."""
    return 2 * m["num_hidden_layers"] * m["num_key_value_heads"] * m["head_dim"] * kv_bytes


def decode_step_bytes(m: dict, live_positions: float) -> float:
    """Bytes one decode step must read, whatever the batch: every decoder
    weight as the configuration stores it (int8 with float32 scales, or
    bf16), the bf16 ``lm_head``, and the keys and values of the positions
    that are live."""
    weights = config.load_module("references", m["reference"]).weights
    nbytes = weights.kind(m).nbytes
    per_layer = sum(nbytes(shape) for shape in weights.matmul_shapes(m).values())
    head = m["vocab_size"] * m["hidden_size"] * 2
    return m["num_hidden_layers"] * per_layer + head + live_positions * kv_bytes_per_token(m)


def flash_prefill_min_s(m: dict, t: int, peaks: dict) -> float:
    """Least time of one causal attention call over ``t`` positions of one
    sequence: the larger of its operations over the bf16 peak and its bytes
    over the memory bandwidth. Operations: QK^T and PV under the causal mask,
    ``2 * heads * t * t * head_dim``; bytes: q, k, v and the output, bf16."""
    h, kvh, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    flops = 2.0 * h * t * t * d
    nbytes = 2.0 * (2 * h + 2 * kvh) * t * d
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


_SHAPE = re.compile(r"= \(?\w+\[([\d,]+)\]")


def output_shape(op_name: str) -> tuple[int, ...]:
    """``%closed_call.10 = bf16[1,32,1024,128] custom-call(...)`` -> (1, 32, 1024, 128)."""
    hit = _SHAPE.search(op_name)
    return tuple(int(x) for x in hit.group(1).split(",")) if hit else ()

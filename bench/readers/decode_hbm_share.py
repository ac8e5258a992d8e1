"""Bytes one decode step must read (int8 weights, bf16 lm_head, live keys and values) over the memory bandwidth, as a share of the measured step: bound by HBM."""
from bench import metrics, peaks, roofline


def read(ctx):
    step = metrics.step_ms(ctx.trace, "jit_decode")
    live = metrics.mean_resident_tokens(ctx.records, ctx.window_s)
    if step is None or live is None:
        return None
    need = roofline.decode_step_bytes(ctx.memory["model"], live)
    least_ms = 1000.0 * need / peaks.for_kind(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_ms / step

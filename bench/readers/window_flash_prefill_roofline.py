"""The flash-prefill Mosaic calls of a model with window and global layers against their roofline: the least time their shapes allow (the operations INSIDE each call's own mask over the bf16 peak, or its bytes over HBM, the larger) over their measured device time. Calls and seconds come from the same traced seconds, by the piece length a call's result shows; of the calls of one length, the window layers' share of the layers run under the window's mask (``i - j < 4096``; the same as the causal one up to 4096 positions) and the rest under the causal one: the shape does not say which call is which, the layer counts do. Nothing for a model without such layers."""
from bench import peaks, window_moe_roofline


def read(ctx):
    m = ctx.memory["model"]
    x = window_moe_roofline.sizes(m)
    calls = window_moe_roofline.flash_calls(ctx.trace, m)
    if x is None or not calls:
        return None
    pk = peaks.for_kind(ctx.device_kind)
    took = least = 0.0
    for t, (seconds, n) in calls.items():
        took += seconds
        least += n / x["layers"] * (
            x["window_layers"] * window_moe_roofline.flash_min_s(m, t, x["window"], pk)
            + x["global_layers"] * window_moe_roofline.flash_min_s(m, t, None, pk))
    return 100.0 * least / took if took else None

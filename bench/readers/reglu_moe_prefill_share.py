"""Share of the prefill programs' device time spent in the layers' routed feed-forward (router, the 64 experts' matmuls over every token of the piece, the combine; found by shape, bench/window_moe_roofline.py). Nothing for a model without such layers."""
from bench import window_moe_roofline


def read(ctx):
    m = ctx.memory["model"]
    whole = sum((ctx.trace or {}).get("programs", {}).get("jit_prefill", []))
    found = window_moe_roofline.seconds_and_runs(
        ctx.trace, m, "jit_prefill", window_moe_roofline.in_expert_layer)
    return None if found is None or not whole else 100.0 * found[0] / whole

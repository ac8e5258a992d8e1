"""Share of the prefill programs' device time spent in the expert layers' feed-forward (router, the 32 experts' matmuls over every token of the piece, the combine; found by shape, bench/conv_moe_roofline.py). Nothing for a model without such layers."""
from bench import conv_moe_roofline


def read(ctx):
    m = ctx.memory["model"]
    whole = sum((ctx.trace or {}).get("programs", {}).get("jit_prefill", []))
    found = conv_moe_roofline.seconds_and_runs(
        ctx.trace, m, "jit_prefill", conv_moe_roofline.in_expert_layer)
    return None if found is None or not whole else 100.0 * found[0] / whole

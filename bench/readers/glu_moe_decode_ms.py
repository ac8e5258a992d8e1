"""Device milliseconds of one decode step spent in the expert layers' feed-forward (router and choice, the 32 experts' SwiGLU matmuls, the combine; found by their shapes, bench/conv_moe_roofline.py): their summed time in ``jit_decode`` over the traced window, over the steps it ran. Nothing for a model without such layers."""
from bench import conv_moe_roofline


def read(ctx):
    found = conv_moe_roofline.seconds_and_runs(
        ctx.trace, ctx.memory["model"], "jit_decode", conv_moe_roofline.in_expert_layer)
    return None if found is None else 1000.0 * found[0] / found[1]

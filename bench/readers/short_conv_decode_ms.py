"""Device milliseconds of one decode step spent in the short-convolution operators (in_proj, the two gates, the three taps over the two-position tail, out_proj; found by their shapes, bench/conv_moe_roofline.py): their summed time in ``jit_decode`` over the traced window, over the steps it ran. Nothing for a model without such operators."""
from bench import conv_moe_roofline


def read(ctx):
    found = conv_moe_roofline.seconds_and_runs(
        ctx.trace, ctx.memory["model"], "jit_decode", conv_moe_roofline.in_short_conv)
    return None if found is None else 1000.0 * found[0] / found[1]

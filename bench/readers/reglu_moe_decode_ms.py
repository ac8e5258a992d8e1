"""Device milliseconds of one decode step spent in the layers' routed feed-forward (the softmax router on the layer's input and the choice, the 64 experts' ReLU-gated matmuls, the combine; found by their shapes, bench/window_moe_roofline.py): their summed time in ``jit_decode`` over the traced window, over the steps it ran. Nothing for a model without such layers."""
from bench import window_moe_roofline


def read(ctx):
    found = window_moe_roofline.seconds_and_runs(
        ctx.trace, ctx.memory["model"], "jit_decode", window_moe_roofline.in_expert_layer)
    return None if found is None else 1000.0 * found[0] / found[1]

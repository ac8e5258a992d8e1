"""Device milliseconds of one decode step spent in the expert blocks (router, latent projections, the routed experts' matmuls and combine, the shared expert; found by their shapes, bench/ssm_moe_roofline.py): their summed time in ``jit_decode`` over the traced window, over the steps it ran. Nothing for a model without expert blocks."""
from bench import ssm_moe_roofline


def read(ctx):
    found = ssm_moe_roofline.decode_seconds_and_steps(
        ctx.trace, ctx.memory["model"], ssm_moe_roofline.in_expert_block)
    return None if found is None else 1000.0 * found[0] / found[1]

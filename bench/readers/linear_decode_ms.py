"""Device milliseconds of one decode step spent in the operations that read or write the hybrid's recurrent state (found by the state's shape, bench/hybrid_roofline.py): their summed time in ``jit_decode`` over the traced window, over the steps it ran. Nothing for a model without linear layers."""
from bench import hybrid_roofline


def read(ctx):
    found = hybrid_roofline.state_seconds_and_steps(ctx.trace, ctx.memory["model"])
    return None if found is None else 1000.0 * found[0] / found[1]

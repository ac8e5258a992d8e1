"""Device milliseconds of one decode step spent in the operations that read or write the Mamba-2 state of a two-mixer block (found by the state's shape, bench/parallel_hybrid_roofline.py): their summed time in ``jit_decode`` over the traced slice, over the steps it ran. Nothing for a model without such blocks or a program without such operations."""
from bench import parallel_hybrid_roofline as rl


def read(ctx):
    found = rl.decode_seconds_and_steps(ctx.trace, ctx.memory["model"], rl.touches_state)
    return None if found is None else 1000.0 * found[0] / found[1]

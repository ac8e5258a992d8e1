"""Device milliseconds of one decode step spent reading K/V where a model keeps two leaves of different lengths (window layers' rings beside global layers' stripes): the summed time of the ``kvmini_dense_decode`` Mosaic calls in ``jit_decode`` over the traced window (found by their result's shape, bench/window_moe_roofline.py: both leaves' calls), over the steps it ran. Nothing for a model without such layers, or where the decode step holds no such call (the eager reads are fusions no shape tells apart)."""
from bench import window_moe_roofline


def read(ctx):
    slots = ctx.memory["engine"]["max_slots"]
    found = window_moe_roofline.seconds_and_runs(
        ctx.trace, ctx.memory["model"], "jit_decode",
        lambda name, m: window_moe_roofline.reads_kv(name, m, slots))
    return None if found is None else 1000.0 * found[0] / found[1]

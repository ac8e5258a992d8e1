"""Experts that at least one live token chose, of the experts a decode step's expert layers hold: the program's ``expert_touches`` counter over decode steps times expert layers times experts held (12 x 64). What a dispatch that reads only the touched experts would stream, of what one that reads them all does. Nothing for a model without such layers or a program without the counter."""
from bench import window_moe_roofline


def read(ctx):
    x = window_moe_roofline.sizes(ctx.memory["model"])
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    touched = ctx.counters.get("kvmini_tpu_moe_expert_touches_total", 0.0)
    if x is None or steps <= 0 or touched <= 0:
        return None
    return 100.0 * touched / (steps * x["expert_layers"] * x["held"])

"""Held experts whose weights a decode step's expert dispatch multiplied, of the experts its expert layers hold: the program's ``expert_visits`` counter over decode steps times expert layers times experts held. 100 where every held expert runs every row of the step; the cell's ``*_expert_touch_share`` where the dispatch visits only the experts a live row chose. Any of the three stacks of routed experts (the sizes are their roofline modules'); nothing for a model without such layers or a program without the counter."""
from bench import conv_moe_roofline, ssm_moe_roofline, window_moe_roofline


def read(ctx):
    m = ctx.memory["model"]
    x = next((s for s in (r.sizes(m) for r in (conv_moe_roofline, window_moe_roofline,
                                                ssm_moe_roofline)) if s), None)
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    visited = ctx.counters.get("kvmini_tpu_moe_expert_visits_total", 0.0)
    if x is None or steps <= 0 or visited <= 0:
        return None
    return 100.0 * visited / (steps * x["expert_layers"] * x["held"])

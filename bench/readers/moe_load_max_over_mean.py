"""How uneven the routing is: the fullest held expert's tokens (summed over expert layers and decode steps) over the mean load of a held expert (the assignments that landed here, over the experts held). 1 is even; a grouped matmul pads every expert's rows to the fullest's or pays a tile an expert. From the program's counters over the window."""
from bench import ssm_moe_roofline


def read(ctx):
    x = ssm_moe_roofline.sizes(ctx.memory["model"])
    landed = ctx.counters.get("kvmini_tpu_moe_local_assignments_total", 0.0)
    fullest = ctx.counters.get("kvmini_tpu_moe_expert_load_max_total", 0.0)
    if x is None or landed <= 0:
        return None
    return fullest / (landed / x["held"])

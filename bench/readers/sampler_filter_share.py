"""Share of the window's decode steps in which the sampler's full-vocabulary sort ran: the steps dispatched while some occupied slot sampled with a top-k or top-p filter, over all decode steps, from the program's counters over the window. Nothing where the program has no such counter, or no decode step retired in the window."""

NAME = "kvmini_tpu_sampler_filter_steps_total"


def read(ctx):
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    if NAME not in ctx.counters or steps <= 0:
        return None
    return 100.0 * ctx.counters[NAME] / steps

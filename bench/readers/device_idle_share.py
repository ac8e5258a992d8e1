"""Share of the traced window in which no operation ran on the device."""
from bench import metrics


def read(ctx):
    return metrics.idle_share(ctx.trace)

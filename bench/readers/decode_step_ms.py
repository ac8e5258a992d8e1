"""Median device duration of the decode program's executions in the traced window."""
from bench import metrics


def read(ctx):
    return metrics.step_ms(ctx.trace, "jit_decode")

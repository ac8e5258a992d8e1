"""Median gap between streamed tokens, pooled over every gap that ended inside the window."""
from bench import metrics


def read(ctx):
    return metrics.median(ctx.gaps_ms)

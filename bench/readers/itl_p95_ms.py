"""95th percentile of the gaps between streamed tokens that ended inside the window."""
from bench import metrics


def read(ctx):
    return metrics.percentile(ctx.gaps_ms, 95.0) if ctx.gaps_ms else None

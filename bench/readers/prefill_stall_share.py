"""Share of the inter-token gaps of the window that are longer than three times the run's own median gap: what a prefill in the step does to everyone decoding."""
from bench import metrics


def read(ctx):
    if not ctx.gaps_ms:
        return None
    limit = 3.0 * metrics.median(ctx.gaps_ms)
    return 100.0 * sum(1 for g in ctx.gaps_ms if g > limit) / len(ctx.gaps_ms)

"""Wait for the first token from the instant the request was due, 95th percentile over every request due in the window."""
from bench import metrics


def read(ctx):
    return metrics.ttft_p95_ms(ctx.records, ctx.window_s, ctx.run_end_s)

"""Output tokens that arrived at the clients inside the window, per second of window."""
from bench import metrics


def read(ctx):
    return metrics.tokens_in_window(ctx.records, ctx.window_s) / ctx.window_s

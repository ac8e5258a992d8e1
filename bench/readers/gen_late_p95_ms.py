"""How late the load generator sent: actual send less due time, 95th percentile over the requests due in the window."""
from bench import metrics


def read(ctx):
    late = metrics.lateness_ms(ctx.records, ctx.window_s)
    return metrics.percentile(late, 95.0) if late else None

"""What the HTTP layer adds to the first token: the client's wait from send less the server's own ``server_ttft_ms``, median."""
from bench import metrics


def read(ctx):
    over = [metrics.ttft_ms(r, ctx.run_end_s, from_send=True) - r["server_ttft_ms"]
            for r in metrics.due_in_window(ctx.records, ctx.window_s)
            if r["token_s"] and r["server_ttft_ms"]]
    return metrics.median(over)

"""Positions whose keys and values are live, of the positions reserved (slots x context), sampled at 10 Hz over the window from the client's own books."""
from bench import metrics


def read(ctx):
    eng = ctx.memory["engine"]
    live = metrics.mean_resident_tokens(ctx.records, ctx.window_s)
    return None if live is None else 100.0 * live / (eng["max_slots"] * eng["max_seq_len"])

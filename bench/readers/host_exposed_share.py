"""Share of the whole window in which the engine thread did host work while requests were live and no decode sweep was in flight, so the device had nothing queued: the ledger's exposed self time of the host phases over the window's length."""

HOST_PHASES = ("loop", "admit", "first_token", "decode_prepare", "decode_dispatch", "emit")


def read(ctx):
    spent = [ctx.counters.get(f'kvmini_tpu_sched_exposed_seconds_total{{phase="{p}"}}')
             for p in HOST_PHASES]
    if None in spent or ctx.window_s <= 0:
        return None
    return 100.0 * sum(spent) / ctx.window_s

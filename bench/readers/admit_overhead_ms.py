"""Scheduler time an admission costs outside the prefill program: the self time of the ledger's ``admit`` and ``first_token`` phases over the window, per entry of ``first_token`` (an admission that reached its first token)."""

SERIES = 'kvmini_tpu_sched_%s_total{phase="%s"}'


def read(ctx):
    c = ctx.counters
    admitted = c.get(SERIES % ("spans", "first_token"), 0.0)
    spent = [c.get(SERIES % ("seconds", p)) for p in ("admit", "first_token")]
    if admitted <= 0 or None in spent:
        return None
    return 1000.0 * sum(spent) / admitted

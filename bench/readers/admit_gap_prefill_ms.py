"""The prefill's part of ``admit_gap_ms``: the self time of the ledger's ``prefill`` phase (the wait for the prompt's pieces, padding included) inside admission gaps, per first token sampled inside them. Nothing against a program without the ledger's gap counters or a window without such an admission."""


def read(ctx):
    c = ctx.counters
    admitted = c.get("kvmini_tpu_sched_admit_gap_admissions_total", 0.0)
    spent = c.get('kvmini_tpu_sched_admit_gap_seconds_total{phase="prefill"}')
    if admitted <= 0 or spent is None:
        return None
    return 1000.0 * spent / admitted

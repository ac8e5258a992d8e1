"""The rest of ``admit_gap_ms``: the self time of every phase but ``prefill`` inside admission gaps (emission of the drained sweep, loop, admit, first_token, which holds ``jit_first``'s and ``jit_reset``'s device time, the next sweep's decode_prepare), per first token sampled inside them; with ``admit_gap_prefill_ms`` it adds up to ``admit_gap_ms``. Nothing against a program without the ledger's gap counters or a window without such an admission."""

PREFIX = "kvmini_tpu_sched_admit_gap_seconds_total{phase="


def read(ctx):
    c = ctx.counters
    admitted = c.get("kvmini_tpu_sched_admit_gap_admissions_total", 0.0)
    spent = [v for k, v in c.items() if k.startswith(PREFIX) and k != PREFIX + '"prefill"}']
    if admitted <= 0 or not spent:
        return None
    return 1000.0 * sum(spent) / admitted

"""What one admission takes from the slots that were decoding: the wall time of the decode gaps of cause ``admission`` (drained readback -> the next dispatch: emission, the admission, the prefill, the first token, the next sweep's preparation) per first token sampled inside them. Nothing against a program without the ledger's gap counters or a window without such an admission."""


def read(ctx):
    c = ctx.counters
    admitted = c.get("kvmini_tpu_sched_admit_gap_admissions_total", 0.0)
    spent = c.get('kvmini_tpu_sched_gap_seconds_total{cause="admission"}')
    if admitted <= 0 or spent is None:
        return None
    return 1000.0 * spent / admitted

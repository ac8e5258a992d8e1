"""Share of the window the slots that were decoding spent in decode gaps: from the readback of the last sweep in flight (the pipeline drained for an admission, or a synchronous sweep) to the next dispatch, as the scheduler ledger books them, summed over the causes (admission, prefill_piece, sweep). Times the slots and the step rate it is the tokens the stream lost. Nothing against a program without the ledger's gap counters."""

PREFIX = "kvmini_tpu_sched_gap_seconds_total{cause="


def read(ctx):
    gaps = [v for k, v in ctx.counters.items() if k.startswith(PREFIX)]
    if not gaps or ctx.window_s <= 0:
        return None
    return 100.0 * sum(gaps) / ctx.window_s

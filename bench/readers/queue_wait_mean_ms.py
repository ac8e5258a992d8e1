"""Mean wait of a request between submit and the scheduler picking it up: the program's ``queue`` phase histogram (sum over count) over the window."""


def read(ctx):
    n = ctx.counters.get('kvmini_tpu_phase_seconds_count{phase="queue"}', 0.0)
    if n <= 0:
        return None
    return 1000.0 * ctx.counters['kvmini_tpu_phase_seconds_sum{phase="queue"}'] / n

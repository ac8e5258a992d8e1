"""Mean time from admission to the first sampled token (the prefill programs, ``jit_first`` and the host work between them): the program's ``prefill`` phase histogram (sum over count) over the window."""


def read(ctx):
    n = ctx.counters.get('kvmini_tpu_phase_seconds_count{phase="prefill"}', 0.0)
    if n <= 0:
        return None
    return 1000.0 * ctx.counters['kvmini_tpu_phase_seconds_sum{phase="prefill"}'] / n

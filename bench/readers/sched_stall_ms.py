"""Time the engine thread lost to stalls in the window: the self time of every phase entry longer than twice the rolling wall of a decode sweep (at least 50 ms), over all phases but ``prefill`` (a long prompt is work) and ``idle`` (a wait for work). Which phase holds it says whose stall it was."""

PREFIX = "kvmini_tpu_sched_slow_seconds_total{phase="


def read(ctx):
    slow = {k: v for k, v in ctx.counters.items() if k.startswith(PREFIX)}
    if not slow:
        return None
    return 1000.0 * sum(v for k, v in slow.items()
                        if k not in (PREFIX + '"prefill"}', PREFIX + '"idle"}'))

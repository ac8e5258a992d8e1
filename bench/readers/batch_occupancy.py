"""Slots that produced a token, of the slots every decode step carries: decode tokens over decode steps times slots, from the program's counters over the window."""


def read(ctx):
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    tokens = ctx.counters.get("kvmini_tpu_decode_tokens_total", 0.0)
    if steps <= 0:
        return None
    return 100.0 * tokens / (steps * ctx.memory["engine"]["max_slots"])

"""What the live slots' recurrent state must move in a decode step (one read and one write of each live slot's float32 state, all linear layers) over the memory bandwidth, as a share of the time the step spends in the operations that touch the state (``linear_decode_ms``): bound by HBM. Live slots only, so held slots' traffic lowers it and it cannot pass 100% by miscounting: the operations move all 16 slots' state whatever is live. The operations are the executor's (the jnp state update of models/linear_attention.py inside ``jit_decode``), no kernel's. The live count is the WINDOW's mean (the program's counters over the whole 45 s) while the time is the traced slice's (~3 s): where the slice holds more or fewer live slots than the window's mean, the share is off by that ratio (three traced runs read 14.8-17.6% over a linear_decode_ms of 4.95-5.00: the spread is the live count's; PERF.md section 6)."""
from bench import hybrid_roofline, peaks


def read(ctx):
    found = hybrid_roofline.state_seconds_and_steps(ctx.trace, ctx.memory["model"])
    moved = ctx.counters.get("kvmini_tpu_linear_state_slot_steps_total", 0.0)
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    if found is None or moved <= 0 or steps <= 0:
        return None
    live_per_step = (moved - ctx.counters.get(
        "kvmini_tpu_linear_state_held_slot_steps_total", 0.0)) / steps
    need = 2.0 * live_per_step * hybrid_roofline.state_bytes_per_slot(ctx.memory["model"])
    least_s = need / peaks.for_kind(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (found[0] / found[1])

"""What the live slots' Mamba-2 state must move in a decode step (one read and one write of each live slot's float32 state, all state-space blocks) over the memory bandwidth, as a share of the time the step spends in the operations that touch the state (``ssm_decode_ms``): bound by HBM. Live slots from the program's counters over the whole window (the recurrent-state counters count this state too), the time from the traced slice; the operations move every slot's state whatever is live, so it cannot pass 100% by miscounting. The operations are the executor's (the jnp state update of models/mamba.py inside ``jit_decode``), no kernel's."""
from bench import peaks, ssm_moe_roofline


def read(ctx):
    m = ctx.memory["model"]
    found = ssm_moe_roofline.decode_seconds_and_steps(ctx.trace, m, ssm_moe_roofline.touches_state)
    moved = ctx.counters.get("kvmini_tpu_linear_state_slot_steps_total", 0.0)
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    if found is None or moved <= 0 or steps <= 0:
        return None
    live_per_step = (moved - ctx.counters.get(
        "kvmini_tpu_linear_state_held_slot_steps_total", 0.0)) / steps
    need = 2.0 * live_per_step * ssm_moe_roofline.state_bytes_per_slot(m)
    least_s = need / peaks.for_kind(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (found[0] / found[1])

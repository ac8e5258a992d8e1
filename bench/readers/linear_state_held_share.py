"""Share of the slot-steps for which the decode sweeps moved a recurrent state whose slot was not live (parked, or between two pieces of its prefill), so the state was held: state traffic paid for slots that decode nothing. From the program's counters over the window; nothing where the program has none (a model without linear layers counts no slot-steps)."""


def read(ctx):
    moved = ctx.counters.get("kvmini_tpu_linear_state_slot_steps_total", 0.0)
    if moved <= 0:
        return None
    return 100.0 * ctx.counters.get("kvmini_tpu_linear_state_held_slot_steps_total", 0.0) / moved

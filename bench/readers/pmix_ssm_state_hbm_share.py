"""What the live slots' Mamba-2 state must move in a decode step (one read and one write of each live slot's float32 state, all layers) over the memory bandwidth, as a share of the time the step spends in the operations that touch the state (``pmix_ssm_decode_ms``): the state update's share of its roofline, bound by HBM. Live slots and time are of the SAME traced slice: the slots from the client's books over the slice's three seconds (a request holds a slot from its first token to its last), the time from the trace, so a later program that skips parked slots cannot read over 100% by a window that was fuller than the slice. The operations are the executor's (the jnp state update of models/mamba.py inside ``jit_decode``), no kernel's."""
from bench import parallel_hybrid_roofline as rl
from bench import peaks


def read(ctx):
    m = ctx.memory["model"]
    found = rl.decode_seconds_and_steps(ctx.trace, m, rl.touches_state)
    if found is None:
        return None
    live = rl.live_slots_in_traced_slice(ctx.records, ctx.window_s)
    if not live:
        return None
    need = 2.0 * live * rl.state_bytes_per_slot(m)
    least_s = need / peaks.for_kind(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (found[0] / found[1])

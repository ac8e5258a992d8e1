"""What the experts' matmuls must read in a decode step over the memory bandwidth, as a share of their device time: the experts that at least one live token chose (the program's ``expert_touches`` counter, a step's mean over the window) times one expert's int8 W_gate, W_up and W_down with their scales (5,898,240 + 16,384 B), plus the hidden activations of the assignments (read and written, bf16), over 819 GB/s, over the time of the operations that read an expert stack (found by shape, bench/window_moe_roofline.py) a step of the traced slice. Bound by HBM. The program multiplies every held expert, touched or not, so the time does not follow the count and the share cannot pass 100% by the count and the time coming from different seconds (PERF.md section 7); what it lacks to 100% is the untouched experts' stream and the matmuls' own shortfall."""
from bench import peaks, window_moe_roofline


def read(ctx):
    m = ctx.memory["model"]
    found = window_moe_roofline.seconds_and_runs(
        ctx.trace, m, "jit_decode", window_moe_roofline.reads_expert_stack)
    steps = ctx.counters.get("kvmini_tpu_decode_steps_total", 0.0)
    touched = ctx.counters.get("kvmini_tpu_moe_expert_touches_total", 0.0)
    if found is None or steps <= 0 or touched <= 0:
        return None
    landed = ctx.counters.get("kvmini_tpu_moe_local_assignments_total", 0.0)
    need = (touched * window_moe_roofline.expert_bytes(m)
            + window_moe_roofline.expert_activation_bytes(m, landed)) / steps
    least_s = need / peaks.for_kind(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (found[0] / found[1])

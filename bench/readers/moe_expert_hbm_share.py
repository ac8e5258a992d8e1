"""What the routed latent experts' matmuls must read in a decode step over the memory bandwidth, as a share of their device time: the held experts that at least one live token chose in a step OF THE TRACED SLICE (``moe_touched`` over ``moe_steps`` of the slice's ``kvmini.emit`` spans, the mean over its steps, all expert layers) times one expert's int8 W1 and W2 with their scales, plus the latent activations of the assignments that landed here (read and written, bf16; the 45 s window's mean, under 0.2% of the bytes), over 819 GB/s, over the time of the operations that read an expert stack (found by shape, bench/ssm_moe_roofline.py) a step of the same slice. Bound by HBM. Count and time come from the same seconds (``conv_moe_roofline.expert_hbm_share``); in a saturated cell slice and window agree, so this reads what it read from the window's counters. What it lacks to 100% is the call's fixed time a layer and the matmuls' own shortfall. Nothing for a model without such blocks or a trace whose spans carry no counts."""
from bench import conv_moe_roofline, ssm_moe_roofline


def read(ctx):
    return conv_moe_roofline.expert_hbm_share(ctx, ssm_moe_roofline)

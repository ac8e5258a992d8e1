"""The arithmetic the prompts' real tokens need of the CHOSEN experts over the bf16 peak, as a share of the device time of the operations that read an expert stack in the prefill programs. Tokens and time come from the same traced seconds: the real prompt tokens of the pieces the slice dispatched (``prompt_tokens`` summed over its ``kvmini.prefill`` spans, ``trace_reduce.span_counts``; not the 45 s window's mean length times the slice's prefills, which read 6.7-13.0% for one program), times 4 chosen experts x 22 layers x 22.02 MFLOP, over 197 TFLOP/s. Bound by compute. What it lacks to 100% is the bucket's padding, the short pieces' dense dispatch (every held expert on every token up to 128 tokens) and the grouped matmul's own shortfall. Nothing for a model without such layers or a trace whose spans carry no counts."""
from bench import conv_moe_roofline, metrics, peaks


def read(ctx):
    m = ctx.memory["model"]
    found = conv_moe_roofline.seconds_and_runs(
        ctx.trace, m, "jit_prefill", conv_moe_roofline.reads_expert_stack)
    tokens = metrics.span_sums(ctx.trace, "kvmini.prefill").get("prompt_tokens")
    if found is None or not tokens:
        return None
    x = conv_moe_roofline.sizes(m)
    flops = tokens * x["expert_layers"] * conv_moe_roofline.expert_flops_per_token(m)
    return 100.0 * flops / peaks.for_kind(ctx.device_kind)["bf16_flops_per_s"] / found[0]

"""The arithmetic the prompts' tokens need of the experts over the bf16 peak, as a share of the device time of the operations that read an expert stack in the prefill programs: real prompt tokens a prefill (the program's ``prefill_tokens`` counter less its padding, over its prefill pieces, the window's mean) times the traced slice's prefills, times 4 chosen experts x 22 layers x 22.02 MFLOP, over 197 TFLOP/s. Bound by compute. The program runs all 32 experts on every token of the padded bucket where a token chose 4, so the share stays under an eighth; what it lacks to 100% is that, the padding, and the matmuls' own shortfall. Nothing for a model without such layers."""
from bench import conv_moe_roofline, peaks


def read(ctx):
    m = ctx.memory["model"]
    found = conv_moe_roofline.seconds_and_runs(
        ctx.trace, m, "jit_prefill", conv_moe_roofline.reads_expert_stack)
    pieces = ctx.counters.get("kvmini_tpu_prefill_chunks_total", 0.0)
    tokens = ctx.counters.get("kvmini_tpu_prefill_tokens_total", 0.0)
    if found is None or pieces <= 0 or tokens <= 0:
        return None
    x = conv_moe_roofline.sizes(m)
    flops = (tokens / pieces) * found[1] * x["expert_layers"] * conv_moe_roofline.expert_flops_per_token(m)
    return 100.0 * flops / peaks.for_kind(ctx.device_kind)["bf16_flops_per_s"] / found[0]

"""The arithmetic the prompts' real tokens need of the CHOSEN experts over the bf16 peak, as a share of the device time of the operations that read an expert stack in the prefill programs. Tokens and time come from the same traced seconds: the pieces the slice ran are counted by the length their flash-prefill calls show (calls of a length over the 12 a program makes, times that length), and what of a bucket is real comes from the program's ``prefill_tokens`` over ``prefill_bucket_tokens`` counters: that ratio alone is the 45 s WINDOW's, and padding does not follow the live rows, so it is safe beside a slice's time; times 6 chosen experts x 12 layers x 11.80 MFLOP, over 197 TFLOP/s. Bound by compute. Every prompt here is longer than one row tile, so since PR 45 each piece takes the grouped dispatch (each expert multiplied by the rows that chose it): what the share lacks to 100% is the padding inside a group's tiles and the grouped matmul's own shortfall (47.6% at PR 46; under a tenth while every held expert ran on every token). Nothing for a model without such layers."""
from bench import peaks, window_moe_roofline


def read(ctx):
    m = ctx.memory["model"]
    found = window_moe_roofline.seconds_and_runs(
        ctx.trace, m, "jit_prefill", window_moe_roofline.reads_expert_stack)
    real = ctx.counters.get("kvmini_tpu_prefill_tokens_total", 0.0)
    padded = ctx.counters.get("kvmini_tpu_prefill_bucket_tokens_total", 0.0)
    if found is None or real <= 0 or padded <= 0:
        return None
    x = window_moe_roofline.sizes(m)
    bucket_tokens = sum(t * calls / x["layers"]
                        for t, (_, calls) in window_moe_roofline.flash_calls(ctx.trace, m).items())
    flops = (bucket_tokens * min(real / padded, 1.0) * x["expert_layers"]
             * window_moe_roofline.expert_flops_per_token(m))
    return 100.0 * flops / peaks.for_kind(ctx.device_kind)["bf16_flops_per_s"] / found[0]

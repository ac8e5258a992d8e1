"""Share of the positions the prefill programs computed that were padding: one less the real prompt tokens prefilled over the sum of the buckets their pieces were padded to."""


def read(ctx):
    padded = ctx.counters.get("kvmini_tpu_prefill_bucket_tokens_total", 0.0)
    if padded <= 0:
        return None
    return 100.0 * (1.0 - ctx.counters.get("kvmini_tpu_prefill_tokens_total", 0.0) / padded)

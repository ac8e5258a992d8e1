"""Of the bucket tokens of the window's prefill pieces (``kvmini_tpu_prefill_bucket_tokens_total``), the share whose program dispatched its tokens to the routed experts grouped by expert (``kvmini_tpu_prefill_grouped_bucket_tokens_total``: one grouped matmul over the rows sorted by expert, models/moe.py) and not every held expert on every token; a program is one or the other by its shape (a piece longer than one 128-row tile of the kernel, with the kernels on). Nothing against a program without the counter or a window without a prefill."""


def read(ctx):
    grouped = ctx.counters.get("kvmini_tpu_prefill_grouped_bucket_tokens_total")
    buckets = ctx.counters.get("kvmini_tpu_prefill_bucket_tokens_total", 0.0)
    if grouped is None or buckets <= 0:
        return None
    return 100.0 * grouped / buckets

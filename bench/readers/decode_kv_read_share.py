"""Share of the dense KV stripes' key/value blocks that the decode steps fetched from HBM: the blocks the decode kernel's rule reached, summed over slots and steps, over slots times blocks a stripe, from the program's counters over the window. Nothing where the steps take the eager full-stripe read (both counters stay 0) or the program has no such counters."""


def read(ctx):
    total = ctx.counters.get("kvmini_tpu_decode_kv_blocks_total", 0.0)
    if total <= 0:
        return None
    return 100.0 * ctx.counters.get("kvmini_tpu_decode_kv_blocks_fetched_total", 0.0) / total

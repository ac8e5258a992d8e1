"""The flash-prefill Mosaic call against its roofline: the least time its shapes allow (operations over the bf16 peak or bytes over HBM, the larger) over its measured device time."""
from bench import peaks, roofline


def read(ctx):
    if not (ctx.trace or {}).get("ops"):
        return None
    m = ctx.memory["model"]
    pk = peaks.for_kind(ctx.device_kind)
    took = least = 0.0
    for name, op in ctx.trace["ops"].items():
        shape = roofline.output_shape(name)
        if (name.startswith("jit_prefill/") and " custom-call(" in name and len(shape) == 4
                and shape[1] == m["num_attention_heads"] and shape[3] == m["head_dim"]):
            took += op["seconds"]
            least += op["calls"] * roofline.flash_prefill_min_s(m, shape[2], pk)
    return 100.0 * least / took if took else None

"""Share of the prefill programs' device time spent inside the linear mixers between their projections: the convolution, the chunk products and solves, the state recurrence (found by shape, bench/hybrid_roofline.py). Nothing for a model without linear layers."""
from bench import hybrid_roofline


def read(ctx):
    m = ctx.memory["model"]
    whole = sum((ctx.trace or {}).get("programs", {}).get("jit_prefill", []))
    if "layer_types" not in m or not whole:
        return None
    took = sum(op["seconds"] for name, op in ctx.trace["ops"].items()
               if name.startswith("jit_prefill/") and hybrid_roofline.in_linear_scan(name, m))
    return 100.0 * took / whole if took else None

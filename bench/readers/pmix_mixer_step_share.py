"""Share of ``jit_decode``'s device time that a two-mixer block's two mixers take: the operations that touch the Mamba-2 state, the fusions that read one of the six mixer weight stacks (in_proj, out_proj, wq, wk, wv, wo) and the attention kernel's calls (bench/parallel_hybrid_roofline.py ``in_mixers``), over the summed time of every leaf operation of ``jit_decode`` in the traced slice. The MLP, lm_head, the sampler, norms, convolution and rotation are the rest; so are wo's twelve fusions, whose cut names show nothing of theirs (1.5% of the step in the slice of PR 35). Nothing for a model without such blocks."""
from bench import parallel_hybrid_roofline as rl


def read(ctx):
    m = ctx.memory["model"]
    # a program without this state has no such block, whatever else its trace names
    if rl.decode_seconds_and_steps(ctx.trace, m, rl.touches_state) is None:
        return None
    found = rl.decode_seconds_and_steps(ctx.trace, m, rl.in_mixers)
    whole = sum(op["seconds"] for name, op in ctx.trace["ops"].items()
                if name.startswith("jit_decode/") and rl.is_leaf(name))
    return 100.0 * found[0] / whole if whole else None

"""Process start to ready for traffic: imports, weights, boot, warm-up (compile or cache load) and the lead-in."""


def read(ctx):
    return ctx.setup_s

"""Device milliseconds per call in the max-min fill (jit_fill)."""


def read(ctx):
    t = ctx.module_s_per_call("fill")
    return None if t is None else t * 1e3

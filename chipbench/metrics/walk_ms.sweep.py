"""Device milliseconds per call in the walk module (jit_walk)."""


def read(ctx):
    t = ctx.module_s_per_call("walk")
    return None if t is None else t * 1e3

"""Device milliseconds per call in link counts and FIM (jit_counts_fn + jit_fim_fn)."""


def read(ctx):
    t = ctx.module_s_per_call("counts_fn", "fim_fn")
    return None if t is None else t * 1e3

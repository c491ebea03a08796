"""Device milliseconds per fill round: ``fill_ms.sweep``'s device time
of jit_fill per call over the fill's rounds per call."""


def read(ctx):
    from chipbench.metrics.program_spans import fill_rounds
    rounds = fill_rounds(ctx)
    t = ctx.module_s_per_call("fill")
    return None if t is None or rounds is None else t * 1e3 / rounds

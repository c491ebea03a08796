"""Device milliseconds per walk hop: ``walk_ms.sweep``'s device time of
jit_walk per call over the walk's hops per call, the ``hops`` counter
of the program's ``walk.run`` span (the walk loop's trip count, summed
over the call's seed passes).  None where the program keeps no such
counter."""


def read(ctx):
    from chipbench.metrics.program_spans import table
    hops = table().get("walk.run", {}).get("hops")
    t = ctx.module_s_per_call("walk")
    if t is None or not hops or not ctx.calls:
        return None
    return t * 1e3 / (hops / ctx.calls)

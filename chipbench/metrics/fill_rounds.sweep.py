"""Freeze rounds of the max-min fill's while loop per call, summed over
the call's seed chunks (the program's ``rounds`` counter)."""


def read(ctx):
    from chipbench.metrics.program_spans import fill_rounds
    return fill_rounds(ctx)

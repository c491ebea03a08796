"""Walk: least bytes over HBM bandwidth over device time, in %."""


def read(ctx):
    from chipbench.metrics.least_bytes import roofline_pct
    return roofline_pct(ctx, "walk")

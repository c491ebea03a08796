"""Share of the traced window with no op on the device, in %."""


def read(ctx):
    r = ctx.reduced
    return 100.0 * (1.0 - r.busy_s / r.window_s)

"""peak_bytes_in_use of the chip after the traced calls, in GB."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes else None

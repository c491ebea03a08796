"""Host milliseconds per call in host<->device copies: the self time of
the program's ``*.to_host`` and ``*.to_device`` spans."""


def read(ctx):
    from chipbench.metrics.program_spans import copies
    rows = copies()
    if not rows or not ctx.calls:
        return None
    return 1e3 * sum(r["self_s"] for r in rows.values()) / ctx.calls

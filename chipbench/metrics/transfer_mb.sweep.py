"""Megabytes (1e6 B) per call copied between host and device: the
``bytes`` counts of the program's ``*.to_host`` and ``*.to_device``
spans, taken from the arrays' shapes."""


def read(ctx):
    from chipbench.metrics.program_spans import copies
    rows = copies()
    if not rows or not ctx.calls:
        return None
    return sum(r.get("bytes", 0) for r in rows.values()) / 1e6 / ctx.calls

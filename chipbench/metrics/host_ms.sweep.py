"""Host milliseconds per call in the front end and its glue: the self
time of the front end's own span, ``prep`` and ``assemble``."""


def read(ctx):
    from chipbench.frontends import FAMILIES
    from chipbench.metrics.program_spans import table
    rows = [r for k, r in table().items()
            if k in FAMILIES or k in ("prep", "assemble")]
    if not rows or not ctx.calls:
        return None
    return 1e3 * sum(r["self_s"] for r in rows) / ctx.calls

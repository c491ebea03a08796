"""The program's own span table over the traced calls
(``repro.core.spans.snapshot()``: per span name its count ``n``,
``total_s``, ``self_s`` and counters), for the readers that divide it
by the traced calls.  Empty where the program keeps no spans."""

from __future__ import annotations


def table() -> dict[str, dict]:
    try:
        from repro.core import spans
    except ImportError:
        return {}
    return spans.snapshot()


def copies() -> dict[str, dict]:
    """The host<->device copy spans: ``*.to_host`` and ``*.to_device``."""
    return {k: v for k, v in table().items()
            if k.endswith((".to_host", ".to_device"))}


def fill_rounds(ctx) -> float | None:
    """Freeze rounds of the max-min fill per call, summed over seed
    chunks (``rounds`` of the ``fill.to_host`` span)."""
    rounds = table().get("fill.to_host", {}).get("rounds")
    return rounds / ctx.calls if rounds and ctx.calls else None

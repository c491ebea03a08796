"""Least HBM bytes of a device kernel: its inputs and outputs at the
call's shapes, never the intermediates of a formulation.  A roofline
share built on them holds for any implementation of the kernel.

v5e publishes no float64 or int32 vector peak, so these shares are
against HBM bandwidth alone (``peaks.json``: ``hbm_bytes_per_s``)."""

from __future__ import annotations


def fill(H: int, N: int, S: int, L: int) -> int:
    """(H, N, S) int32 link ids, (N,) float64 weights, (L,) float64
    capacities in; (N, S) float64 rates out."""
    return H * N * S * 4 + N * 8 + L * 8 + N * S * 8


def walk(H: int, N: int, S: int, F: int, tables: int) -> int:
    """(N, F) 32-bit hash fields, (S,) uint64 seeds and the forwarding
    tables in; (H, N, S) int32 link ids out, ``H`` the real hop count."""
    return N * F * 4 + S * 8 + tables + H * N * S * 4


BYTES = {"fill": fill, "walk": walk}


def roofline_pct(ctx, stage: str):
    """Share of the HBM roofline, in %, of one stage's device time per
    call; ``None`` where the trace holds no such module."""
    t = ctx.module_s_per_call(stage)
    if not t:
        return None
    need = sum(BYTES[stage](**k.sizes) for k in ctx.kernels
               if k.stage == stage)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t

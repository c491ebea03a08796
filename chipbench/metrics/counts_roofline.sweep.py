"""Link counts: least bytes over HBM bandwidth over device time, in %.

The least bytes of one call's counts are its (H, N, S) int32 link ids
and (N,) float64 weights in and its (S, L) float64 counts out,
``H·N·S·4 + N·8 + S·L·8``, whatever computes them; the time is
jit_counts_fn's device time per call.  None where the trace holds no
such module."""


def bytes_of(H: int, N: int, S: int, L: int) -> int:
    return H * N * S * 4 + N * 8 + S * L * 8


def read(ctx):
    t = ctx.module_s_per_call("counts_fn")
    if not t:
        return None
    need = sum(bytes_of(**k.sizes) for k in ctx.kernels
               if k.stage == "counts_fn")
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t

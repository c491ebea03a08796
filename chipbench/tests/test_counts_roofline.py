"""``counts_roofline.sweep``: the counts' least bytes, ``H·N·S·4 + N·8 +
S·L·8`` summed over the call's ``counts_fn`` kernels, over HBM bandwidth
over jit_counts_fn's device time per call; nothing where the trace holds
no such module."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from chipbench.frontends import Kernel
from chipbench.metrics import program_spans

PATH = Path(program_spans.__file__).with_name("counts_roofline.sweep.py")
PEAKS = {"hbm_bytes_per_s": 819e9}


class Ctx:
    """The part of ``trace.Context`` the reader sees."""

    def __init__(self, counts_s_per_call, kernels):
        self.counts_s_per_call, self.kernels = counts_s_per_call, kernels
        self.peaks, self.calls = PEAKS, 3

    def module_s_per_call(self, *stages):
        assert stages == ("counts_fn",)
        return self.counts_s_per_call


def read(ctx):
    spec = importlib.util.spec_from_file_location("counts_roofline", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


@pytest.mark.parametrize("H, N, S, L", [
    (4, 256, 16384, 256),               # paper-ecmp-fim
    (6, 21504, 256, 5760),              # meta-3tier-2pod-fim
    (4, 86016, 128, 3264),              # dsv3-ep-dispatch-fim
])
def test_least_bytes_over_bandwidth_over_counts_time(H, N, S, L):
    kernels = [Kernel("walk", dict(H=H, N=N, S=S, F=5, tables=1 << 20)),
               Kernel("counts_fn", dict(H=H, N=N, S=S, L=L)),
               Kernel("fim_fn", dict(S=S, L=L))]
    need = H * N * S * 4 + N * 8 + S * L * 8
    t = 0.5e-3
    assert read(Ctx(t, kernels)) == pytest.approx(
        100.0 * need / 819e9 / t, rel=1e-12)


def test_none_without_a_counts_module():
    assert read(Ctx(None, [Kernel("counts_fn",
                                  dict(H=4, N=8, S=2, L=16))])) is None

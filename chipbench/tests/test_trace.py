"""The trace reduction, checked on a small window recorded on the chip."""

from __future__ import annotations

import gzip
import json
from pathlib import Path

import pytest

from chipbench import frontends
from chipbench import trace as tr
from chipbench.tests.profiles import xspace

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_clip_cuts_to_window():
    iv = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert iv == [(0, 3), (5, 9), (10, 11)]
    assert tr.clip(iv, 2, 10.5) == [(2, 3), (5, 9), (10, 10.5)]


def test_two_chip_trace_reduces_to_hand_worked_numbers():
    """Busy is the union of op intervals inside the annotated window,
    averaged over chips; modules lose their program id; each idle gap
    goes to the innermost host event open at its midpoint."""
    call = "call:monte_carlo_throughput"
    pd = xspace({
        "/host:CPU": {"python": [
            (call, 0, 100), (call, 110, 200),
            ("PjitFunction(walk)", 5, 10), ("host drain", 60, 90)]},
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 10, 30), ("fusion.2", 25, 50),
                        ("fusion.3", 120, 180), ("fusion.4", 250, 260)],
            "XLA Modules": [("jit_walk(1)", 10, 20), ("jit_fill(2)", 20, 50),
                            ("jit_fill(2)", 120, 180)]},
        "/device:TPU:1": {
            "XLA Ops": [("fusion.1", 10, 40), ("fusion.3", 130, 150)],
            "XLA Modules": [("jit_walk(1)", 10, 40), ("jit_fill(3)", 130, 150)]},
    })
    red = tr.reduce_profile(pd, call)
    ns = 1e-9
    assert red.chips == 2
    assert red.window_s == pytest.approx(200 * ns)
    assert red.busy_s == pytest.approx(75 * ns)
    assert red.modules == pytest.approx({"jit_walk": 20 * ns,
                                         "jit_fill": 55 * ns})
    assert red.gaps == pytest.approx({"PjitFunction(walk)": 10 * ns,
                                      "host drain": 80 * ns,
                                      call: 35 * ns})
    assert red.breakdown()["device_ops"][0][0] == "jit_fill"


def test_trace_without_device_plane_is_an_error():
    """Host events are never read as device time."""
    call = "call:monte_carlo_fim"
    pd = xspace({"/host:CPU": {"python": [(call, 0, 100)],
                               "worker": [("fusion.1", 10, 30)]}})
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce_profile(pd, call)


def _expected():
    return json.loads((DATA / "expected.json").read_text())


def _recorded(name: str, annotation: str) -> tr.Reduced:
    """The recorded trace, kept compressed, reduced as ``tr.reduce`` would
    reduce the ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes())
    return tr.reduce_profile(ProfileData.from_serialized_xspace(raw),
                             annotation)


@pytest.mark.parametrize("name", sorted(_expected()))
def test_recorded_chip_trace_reduces_to_fixed_numbers(name):
    want = _expected()[name]
    red = _recorded(name, want["annotation"])
    assert red.chips == 1
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert red.modules == pytest.approx(want["modules"], rel=1e-12)
    assert red.module_runs == want["module_runs"]
    assert red.gaps == pytest.approx(want["gaps"], rel=1e-12)
    # every second of the window is busy or in exactly one gap
    assert red.busy_s + sum(red.gaps.values()) == pytest.approx(
        red.window_s, rel=1e-9)
    assert 0 < red.busy_s <= red.window_s


@pytest.mark.parametrize("name", sorted(_expected()))
def test_readers_on_recorded_trace(name):
    """The per-layer readers of the recorded cell read the fixed numbers,
    and a roofline share stays under 100%."""
    import importlib.util

    want = _expected()[name]
    red = _recorded(name, want["annotation"])
    peaks = json.loads((tr.Path(tr.__file__).parent / "peaks.json")
                       .read_text())["TPU v5 lite"]
    ctx = tr.Context(reduced=red, calls=want["calls"], peaks=peaks,
                     peak_bytes=want["peak_bytes"],
                     kernels=[frontends.Kernel(k["stage"], k["sizes"])
                              for k in want["kernels"]])
    for metric, value in want["metrics"].items():
        path = DATA.parent.parent / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location("reader", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        got = mod.read(ctx)
        assert got == pytest.approx(value, rel=1e-12), metric
        if "roofline" in metric:
            assert 0 < got <= 100

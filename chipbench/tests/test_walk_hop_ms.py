"""``walk_hop_ms.sweep``: the walk's device time per call over the hops
its ``walk.run`` span counts per call, and nothing on a program that
keeps no such counter."""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from chipbench.metrics import program_spans

PATH = Path(program_spans.__file__).with_name("walk_hop_ms.sweep.py")


class Ctx:
    """The part of ``trace.Context`` the reader sees."""

    def __init__(self, walk_s_per_call, calls):
        self.walk_s_per_call, self.calls = walk_s_per_call, calls

    def module_s_per_call(self, *stages):
        assert stages == ("walk",)
        return self.walk_s_per_call


def read(ctx):
    spec = importlib.util.spec_from_file_location("walk_hop_ms", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_walk_ms_over_hops_per_call(monkeypatch):
    # three calls of two seed passes of six hops each: 12 hops a call
    monkeypatch.setattr(program_spans, "table", lambda: {
        "walk.run": {"n": 6, "total_s": 3.0, "self_s": 3.0, "hops": 36}})
    assert read(Ctx(0.9, 3)) == pytest.approx(900.0 / 12)


@pytest.mark.parametrize("table", [
    {},
    {"walk.run": {"n": 3, "total_s": 1.0, "self_s": 1.0}},
], ids=["no-spans", "no-hops-counter"])
def test_none_without_the_counter(monkeypatch, table):
    monkeypatch.setattr(program_spans, "table", lambda: table)
    assert read(Ctx(0.9, 3)) is None


def test_none_without_a_walk(monkeypatch):
    monkeypatch.setattr(program_spans, "table", lambda: {
        "walk.run": {"n": 3, "total_s": 1.0, "self_s": 1.0, "hops": 12}})
    assert read(Ctx(None, 3)) is None

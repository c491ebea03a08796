"""The comparison that decides ``correct``, driven through the harness
with its look for a chip stubbed out and the cells cut to sizes a CPU
test holds.

A sound run is correct.  The control (the reference in the program's
place, one precision below what the configuration states) is not, and
neither is a run whose timed path is broken underneath: an answer
altered where it is produced, or half of the seeds left out and the
rest repeated."""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

from chipbench import frontends
from chipbench import run as R

BENCH = json.loads((R.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
#: hash seeds per call at CPU size, by front end
SEEDS_PER_CALL = {"monte_carlo_throughput": 64, "monte_carlo_fim": 256}
#: float32 for float64 in the fill; the FIM of uniform flows is exact in
#: float32 (small integer counts, power-of-two layer sizes), so its
#: control is the next step down, bfloat16
CONTROL = {"monte_carlo_throughput": np.float32,
           "monte_carlo_fim": ml_dtypes.bfloat16}


@pytest.fixture
def small(monkeypatch):
    """Cells cut to CPU size, run on the CPU device against the v5e
    peaks."""
    def any_device(jax, chips):
        return jax.devices()[0], R.load_json(R.HERE / "peaks.json")[
            "TPU v5 lite"]

    monkeypatch.setattr(R, "check_device", any_device)

    class Small(R.Cell):
        def __init__(self, bench, name):
            super().__init__(bench, name)
            n = SEEDS_PER_CALL[self.traffic["front_end"]]
            check = dict(self.traffic["check"],
                         seeds=min(n, self.traffic["check"]["seeds"]))
            self.traffic = dict(self.traffic, seeds_per_call=n,
                                trace_calls=1, check=check)

    monkeypatch.setattr(R, "Cell", Small)


def run_cell(cell: str, trace: int = 0) -> dict:
    args = argparse.Namespace(workload=cell, seed=2**31 + 77, seconds=0.01,
                              trace=trace)
    result, _ = R.run(args)
    return result


def break_call(monkeypatch, cell: str, broken):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    traffic = json.loads(
        (R.HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    cls = frontends.FAMILIES[traffic["front_end"]]
    sound = cls.call
    monkeypatch.setattr(cls, "call", lambda self, seeds: broken(
        self, seeds, sound, traffic["front_end"]))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small, cell):
    result = run_cell(cell)
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "check"


def test_traced_run_reads_per_layer_metrics(small, monkeypatch):
    from chipbench import trace as tr
    from chipbench.tests.profiles import host_as_device

    monkeypatch.setattr(tr, "reduce", lambda path, annotation: (
        tr.reduce_profile(host_as_device(path), annotation)))
    result = run_cell("paper-ecmp-throughput", trace=1)
    assert result["correct"], result["check"]
    assert result["device"]["busy_s"] > 0
    assert {"fill_ms.sweep", "walk_ms.sweep"} <= set(result["metrics"])
    assert result["breakdown"]["device_ops"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(small, monkeypatch, cell):
    def control(self, seeds, sound, front):
        return self.reference(seeds, dtype=CONTROL[front])

    break_call(monkeypatch, cell, control)
    result = run_cell(cell)
    assert not result["correct"], result["check"]


def _altered(self, seeds, sound, front):
    out = {k: np.array(v) for k, v in sound(self, seeds).items()}
    for v in out.values():
        (v[0] if v.ndim == 2 else v)[...] *= 1 + 1e-6
    return out


def _half_seeds(self, seeds, sound, front):
    half = sound(self, seeds[: len(seeds) // 2])
    return {k: np.concatenate([v, v], axis=-1) for k, v in half.items()}


@pytest.mark.parametrize("fault", [_altered, _half_seeds],
                         ids=["answer-altered", "half-the-seeds"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(small, monkeypatch, cell, fault):
    break_call(monkeypatch, cell, fault)
    result = run_cell(cell)
    assert not result["correct"], result["check"]


def test_no_program_no_result(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    exits non-zero and prints no result."""
    import shutil
    import subprocess
    import sys

    shutil.copy(R.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(R.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""

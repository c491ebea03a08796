"""The readers of the program's spans and counters, on a traced CPU run
of each cell at test size: each metric appears in exactly the cells its
``BENCHMARK.json`` entry lists, and ``transfer_mb.sweep`` equals the
bytes the call's shapes give."""

from __future__ import annotations

import pytest

from chipbench import frontends
from chipbench import run as R
from chipbench.tests.test_check import (  # noqa: F401  (fixture)
    BENCH, CELLS, SEEDS_PER_CALL, run_cell, small,
)

SPAN_METRICS = ["transfer_ms.sweep", "transfer_mb.sweep", "host_ms.sweep",
                "fill_rounds.sweep", "fill_round_ms.sweep"]
FIELDS = 5                          # hash columns per flow (5-tuple)


def listed(metric: str) -> set[str]:
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    return set(entry["workloads"])


def exact_mb(cell: str) -> float:
    """Bytes copied per call at the test size, from the shapes: flow
    endpoints (int32), hash fields and seeds (uint64) in; the walk's
    (N, S) int32 state out; then the (N, S) float64 rates out, or the
    (S, L) float64 counts out and back in with the layer tables, and the
    FIM's (S,) float64 aggregate, per-layer (S,) live flags and (S,)
    float64 MAPE of every live layer out."""
    traffic = R.Cell(BENCH, cell).traffic
    fam = frontends.family(R.Cell(BENCH, cell).config_dir, traffic).build()
    comp = fam.comp
    N, S, L = len(fam.flows), SEEDS_PER_CALL[cell], comp.num_links
    walk = 3 * N * 4 + N * FIELDS * 8 + S * 8 + N * S * 4
    if traffic["front_end"] == "monte_carlo_throughput":
        return (walk + N * S * 8) / 1e6
    layers = sum((comp.link_layer == i).any()
                 for i in range(len(comp.layer_names)))
    fim = (S * L * 8 + layers * L + 2 * L * 4
           + S * 8 + layers * S + layers * S * 8)
    return (walk + S * L * 8 + fim) / 1e6


@pytest.mark.usefixtures("small")
@pytest.mark.parametrize("cell", CELLS)
def test_span_metrics_in_their_cells(monkeypatch, cell):
    from chipbench import trace as tr
    from chipbench.tests.profiles import host_as_device

    monkeypatch.setattr(tr, "reduce", lambda path, annotation: (
        tr.reduce_profile(host_as_device(path), annotation)))
    result = run_cell(cell, trace=1)
    assert result["correct"], result["check"]
    got = {m for m in SPAN_METRICS if m in result["metrics"]}
    assert got == {m for m in SPAN_METRICS if cell in listed(m)}
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["transfer_mb.sweep"] == pytest.approx(exact_mb(cell),
                                                         rel=1e-12)
    assert metrics["transfer_ms.sweep"] > 0 and metrics["host_ms.sweep"] > 0
    if "fill_rounds.sweep" in metrics:
        assert metrics["fill_rounds.sweep"] >= 1
        assert metrics["fill_round_ms.sweep"] == pytest.approx(
            metrics["fill_ms.sweep"] / metrics["fill_rounds.sweep"])

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
    """Bytes copied per traced call at the test size, from the shapes:
    the walk's source devices and source and destination NICs (int32),
    hash fields and seeds (uint64) and destination devices (int32) in,
    and its arrival check's one bool out; then the (N, S) float64 rates
    out, or the FIM's (1 + NL, S) float64 answers and (NL,) live flags
    out in one pull.  The FIM's layer tables go up on a fabric's first
    call only, the warm-up call."""
    traffic = R.Cell(BENCH, cell).traffic
    fam = frontends.family(R.Cell(BENCH, cell).config_dir, traffic).build()
    comp = fam.comp
    N, S = len(fam.flows), SEEDS_PER_CALL[traffic["front_end"]]
    walk = 3 * N * 4 + N * FIELDS * 8 + S * 8 + N * 4 + 1
    if traffic["front_end"] == "monte_carlo_throughput":
        return (walk + N * S * 8) / 1e6
    layers = sum((comp.link_layer == i).any()
                 for i in range(len(comp.layer_names)))
    return (walk + (1 + layers) * S * 8 + layers) / 1e6


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

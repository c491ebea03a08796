"""Reordering-cost model benchmark: exposure + efficiency on the hot path.

The flowlet reordering model (core/reordering.py) runs inside every
non-ideal ``throughput_from_result`` call, per strategy, per benchmark
row — segment reductions over the ``(Nf, S)`` flowlet tensors of a
sprayed result.  This module times that exposure/efficiency pass in
isolation (``goodput_exposure_model``, fed to the regression guard) and
emits the transport-profile comparison on the paper testbed: the same
sprayed allocation read through ``ideal`` / ``strack`` / ``roce-nack``
eyes, plus the headline ECMP-vs-spray goodput delta.
"""

from __future__ import annotations

import numpy as np

from repro.core import (
    FIELDS_5TUPLE, PrimeSpraying, SimSpec, compile_fabric, flow_fields_matrix,
    flowlet_exposure, max_min_rates, reordering_efficiency, simulate_paths,
    throughput_from_result,
)
from .common import bench_seeds, emit, paper_setup, timeit


def run() -> None:
    fab, wl, flows = paper_setup()
    comp = compile_fabric(fab)
    num_seeds = bench_seeds(256)
    seeds = np.arange(num_seeds)
    field_mat = flow_fields_matrix(flows, FIELDS_5TUPLE)

    res = simulate_paths(comp, flows, seeds,
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=8)),
                         field_matrix=field_mat)
    flowlet_rates = max_min_rates(res)

    state: dict = {}
    elapsed = timeit(
        lambda: state.update(exp=flowlet_exposure(res, flowlet_rates)))
    exposure = state["exp"]
    emit("goodput_exposure_model", elapsed / num_seeds * 1e6,
         f"mean={exposure.mean():.3f} p95={np.percentile(exposure, 95):.3f} "
         f"seeds={num_seeds} flows={len(flows)} "
         f"flowlets={res.num_flowlets // res.num_flows}")

    for profile in ("ideal", "strack", "roce-nack"):
        eff = reordering_efficiency(exposure, profile)
        emit(f"goodput_spray_eff_{profile.replace('-', '_')}", 0.0,
             f"mean={eff.mean():.3f} p5={np.percentile(eff, 5):.3f} "
             f"seeds={num_seeds}")

    base = simulate_paths(comp, flows, seeds, field_matrix=field_mat)
    tp_b = throughput_from_result(base, transport="roce-nack")
    tp_s = throughput_from_result(res, transport="roce-nack",
                                  flowlet_rates=flowlet_rates)
    emit("goodput_spray_vs_ecmp_gbps", 0.0,
         f"ecmp={tp_b.goodput.mean():.2f} spray={tp_s.goodput.mean():.2f} "
         f"spray_rate={tp_s.rates.mean():.2f} transport=roce-nack "
         f"seeds={num_seeds} flows={len(flows)}")

"""Monte-Carlo throughput: per-pair max-min rate distributions at scale.

The acceptance benchmark for the vectorized max-min engine
(``core/vector_throughput.py``): 1024 hash-seed realizations x 256 RoCE
flows on the paper testbed, reporting

* the per-pair throughput distribution ECMP produces (the paper's
  Fig. 3a throughput story, over three orders of magnitude more seeds),
* the measured speedup of the batched engine over the per-seed scalar
  loop (``paths_for_seed`` + dict ``per_pair_throughput`` — exactly what
  fig3a ran before the rewire; scalar timed on a seed sample and
  extrapolated linearly),
* the end-to-end speedup of the full vectorized pipeline (simulate +
  fill) over the hop-by-hop tracer + scalar fill toolchain.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (
    FIELDS_5TUPLE, EcmpRouting, FlowTracer, SimSpec, compile_fabric,
    flow_fields_matrix, per_pair_throughput, simulate_paths,
    throughput_from_result,
)
from .common import bench_seeds, emit, paper_setup, timeit

SCALAR_BATCH = 8     # seeds per scalar timing batch; the best batch
SCALAR_BATCHES = 3   # average extrapolates linearly over the full sweep
TRACER_SAMPLE = 4


def run() -> None:
    fab, wl, flows = paper_setup()
    comp = compile_fabric(fab)
    num_seeds = bench_seeds(1024)
    seeds = np.arange(num_seeds)
    fields = flow_fields_matrix(flows, FIELDS_5TUPLE)

    t0 = time.perf_counter()
    res = simulate_paths(comp, flows, seeds, field_matrix=fields)
    t_sim = time.perf_counter() - t0

    # Both sides are deterministic, so best-of-repeats compares steady-
    # state capability; the repeats interleave so scheduler noise hits
    # both sides alike.  The scalar loop is exactly what fig3a ran before
    # the rewire: per-seed paths_for_seed + dict per_pair_throughput.
    batch = min(SCALAR_BATCH, num_seeds)
    t_vec, per_seed = float("inf"), float("inf")
    for _ in range(SCALAR_BATCHES):
        t0 = time.perf_counter()
        tp = throughput_from_result(res)
        t_vec = min(t_vec, time.perf_counter() - t0)
        t0 = time.perf_counter()
        for i in range(batch):
            per_pair_throughput(flows, res.paths_for_seed(i))
        per_seed = min(per_seed, (time.perf_counter() - t0) / batch)
    t_scalar = per_seed * num_seeds

    # end-to-end: hop-by-hop tracer + scalar fill vs simulate + batched fill
    tsample = min(TRACER_SAMPLE, num_seeds)
    t0 = time.perf_counter()
    for s in range(tsample):
        tr = FlowTracer(fab, EcmpRouting(fab, seed=s), wl, flows).trace()
        per_pair_throughput(flows, tr.paths)
    t_tracer = (time.perf_counter() - t0) / tsample * num_seeds

    pair_min = tp.per_pair.min(axis=0)          # (S,) worst pair per seed
    pair_med = np.median(tp.per_pair, axis=0)
    emit("tp_sweep_pair_throughput_gbps", t_vec / num_seeds * 1e6,
         f"min={tp.per_pair.min():.0f} p5={np.percentile(pair_min, 5):.0f} "
         f"med={pair_med.mean():.0f} line_rate=400 "
         f"seeds={num_seeds} flows={len(flows)}")
    emit("tp_speedup_vs_scalar_loop", t_vec * 1e6,
         f"speedup={t_scalar / t_vec:.0f}x scalar_est_s={t_scalar:.2f} "
         f"vector_s={t_vec:.3f} seeds={num_seeds} flows={len(flows)}")
    emit("tp_speedup_end_to_end", (t_sim + t_vec) * 1e6,
         f"speedup={t_tracer / (t_sim + t_vec):.0f}x "
         f"tracer_est_s={t_tracer:.1f} sim_s={t_sim:.3f} fill_s={t_vec:.3f}")

    # sanity anchor: batched rates == scalar rates on one seed
    scalar = per_pair_throughput(flows, res.paths_for_seed(0))
    vec0 = tp.pair_throughput_for_seed(0)
    drift = max(abs(vec0[k] - v) / v for k, v in scalar.items())
    emit("tp_sweep_differential_drift", 0.0,
         f"max_rel={drift:.2e} tol=1e-9 "
         f"rates={tp.rates.shape[0]}x{tp.rates.shape[1]}")

    # congestion-aware route: the one remaining per-flow Python loop on
    # the hot path (greedy placement is inherently sequential over flows,
    # vectorized over seeds, hop tallies fused) — tracked here so the
    # regression guard catches it slipping back toward per-hop scatters.
    # Median-of-repeats: the loop is Python-overhead-bound and a single
    # shot swings >2x under scheduler noise at smoke shapes
    cong = SimSpec(strategy="congestion-aware")
    t_cong = timeit(lambda: simulate_paths(comp, flows, seeds, spec=cong,
                                           field_matrix=fields))
    emit("tp_congestion_route", t_cong / num_seeds * 1e6,
         f"total_s={t_cong:.3f} per_flow_us={t_cong / len(flows) * 1e6:.0f} "
         f"seeds={num_seeds} flows={len(flows)}")

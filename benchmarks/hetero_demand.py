"""Strategy matrix under uniform vs byte-weighted demand (beyond-paper).

The paper's workload description names flow *volumes* as well as pairs,
and real LLM training traffic is heavily non-uniform: the committed
scenarios (``core/llm_workload.py``) mix multi-GB DP all-reduce ring
edges with MB-scale MoE all-to-all and a bytes-scale barrier — ~9
orders of magnitude of volume spread.  This benchmark runs every
registered routing strategy over those flows twice, once with the
historical unit-demand model and once byte-weighted
(``demand_mode="bytes"``), on both the paper testbed (every cross-host
edge on the Clos) and the 2-pod DCN fabric (only pod-crossing edges).

Unweighted FIM says "how evenly are *flows* spread"; weighted FIM says
"how evenly are *bytes* spread" — when two elephants hash onto one
link, the second story is much worse than the first, which is exactly
the delta the ``*_fim_delta`` rows report.

The ``*_goodput_gbps`` rows add the other side of the spraying trade
(core/reordering.py): under a reordering-intolerant transport, full
spraying taxes every flow's goodput, while demand-aware elephant-only
spraying (``prime_spray_elephant``: split only >= 64 MiB flows,
volume-proportional K) keeps near-spray *byte*-FIM — the elephants
carry the bytes — and recovers most of the per-flow goodput, because
the mice never leave their ECMP paths.

Rows are emitted *derived-only* (``us_per_call=0``, median-of-repeats
timings inside the derived string as ``sim_ms``/``fill_ms``): these
composite-scenario timings swing ~2x under scheduler noise at smoke
shapes, too close to the regression guard's 2.5x threshold, and the
engines they exercise are already guarded by the stable fig3a /
monte_carlo / throughput rows at the same shapes.
"""

from __future__ import annotations

import numpy as np

from repro.core import (
    DEMAND_BYTES, DEMAND_UNIFORM, ELEPHANT_MIN_BYTES, FIELDS_5TUPLE,
    CongestionAware, EcmpStrategy, PrimeSpraying, SimSpec, WaveCongestionAware,
    build_multipod_fabric, build_paper_testbed, compile_fabric,
    fim_from_counts, flow_fields_matrix, multipod_llm_workload,
    paper_testbed_llm_workload, simulate_paths, throughput_from_result,
)
from .common import bench_seeds, emit, timeit

# reordering cost model for the goodput columns: the reordering-
# intolerant extreme, where the spray-vs-elephant contrast is starkest
TRANSPORT = "roce-nack"

STRATEGY_MATRIX = [
    ("ecmp", EcmpStrategy),
    ("prime_spray", lambda: PrimeSpraying(flowlets=8)),
    ("prime_spray_elephant",
     lambda: PrimeSpraying(flowlets=8, min_bytes=ELEPHANT_MIN_BYTES,
                           volume_k=True)),
    ("congestion", CongestionAware),
    # byte-weighted LLM volumes are heterogeneous, so the wave variant
    # delegates to the sequential chain here — these rows document the
    # delegation parity (identical FIM to "congestion") rather than a
    # wave-path speedup; benchmarks/wave_route.py times the wave proper
    ("wave_congestion", WaveCongestionAware),
]


def run() -> None:
    num_seeds = bench_seeds(256)
    seeds = np.arange(num_seeds)
    scenarios = [
        ("paper", build_paper_testbed(), paper_testbed_llm_workload),
        ("multipod",
         build_multipod_fabric(num_pods=2, hosts_per_pod=8,
                               leaves_per_pod=2, num_spines=4),
         multipod_llm_workload),
    ]
    for scen_tag, fab, generator in scenarios:
        comp = compile_fabric(fab)          # ONE compile per scenario
        wl, flows, stats = generator()
        field_mat = flow_fields_matrix(flows, FIELDS_5TUPLE)  # one CRC pass
        gb = wl.total_bytes / 1e9
        fim_means: dict[tuple[str, str], float] = {}
        for tag, factory in STRATEGY_MATRIX:
            for demand_mode in (DEMAND_UNIFORM, DEMAND_BYTES):
                # median-of-repeats like tp_congestion_route: these rows
                # feed the 2.5x regression guard and single shots swing
                # >2x under scheduler noise at smoke shapes
                state: dict = {}

                def sim():
                    res = simulate_paths(comp, flows, seeds,
                                         spec=SimSpec(strategy=factory(),
                                                      demand_mode=demand_mode),
                                         field_matrix=field_mat)
                    state["res"] = res
                    state["fims"] = fim_from_counts(
                        res.link_flow_counts(), comp)[0]

                sim_elapsed = timeit(sim)
                res, fims = state["res"], state["fims"]
                fim_means[(tag, demand_mode)] = fims.mean()
                emit(f"hetero_{scen_tag}_{tag}_{demand_mode}_fim_pct", 0.0,
                     f"mean={fims.mean():.1f} p95={np.percentile(fims, 95):.1f} "
                     f"sim_ms={sim_elapsed * 1e3:.1f} "
                     f"seeds={num_seeds} flows={len(flows)} gbytes={gb:.1f}")
                if demand_mode == DEMAND_UNIFORM:
                    # the goodput story runs on per-flow-fair rates (RoCE
                    # max-min is per-flow, volumes drive only the spray
                    # decision): full spray pays the reordering tax on
                    # every flow, elephant-only spraying leaves the mice
                    # at efficiency 1
                    tp = throughput_from_result(res, transport=TRANSPORT)
                    emit(f"hetero_{scen_tag}_{tag}_goodput_gbps", 0.0,
                         f"rate={tp.rates.mean():.2f} "
                         f"goodput={tp.goodput.mean():.2f} "
                         f"eff={tp.efficiency.mean():.3f} "
                         f"transport={TRANSPORT} "
                         f"seeds={num_seeds} flows={len(flows)}")
                if demand_mode == DEMAND_BYTES:
                    tp_elapsed = timeit(
                        lambda: state.update(
                            tp=throughput_from_result(state["res"])))
                    tp = state["tp"]
                    # a flow's step time is bytes / rate: the slowest flow
                    # gates the training step, so report the p99 transfer
                    # time alongside the weighted rate distribution
                    b = np.array([f.bytes for f in flows], np.float64)
                    xfer_ms = (8.0 * b[:, None] / 1e9
                               / np.maximum(tp.rates, 1e-30)) * 1e3
                    emit(f"hetero_{scen_tag}_{tag}_weighted_tp_gbps", 0.0,
                         f"mean={tp.rates.mean():.1f} "
                         f"p50_xfer_ms={np.percentile(xfer_ms, 50):.1f} "
                         f"p99_xfer_ms={np.percentile(xfer_ms, 99):.1f} "
                         f"fill_ms={tp_elapsed * 1e3:.1f} "
                         f"seeds={num_seeds} flows={len(flows)}")
            delta = (fim_means[(tag, DEMAND_BYTES)]
                     - fim_means[(tag, DEMAND_UNIFORM)])
            emit(f"hetero_{scen_tag}_{tag}_fim_delta_pct", 0.0,
                 f"value={delta:.1f} "
                 f"uniform={fim_means[(tag, DEMAND_UNIFORM)]:.1f} "
                 f"bytes={fim_means[(tag, DEMAND_BYTES)]:.1f}")

"""Monte-Carlo FIM: routing-scheme distributions over >=1024 hash seeds.

The acceptance benchmark for the vectorized engine: ECMP (5-tuple), VXLAN
outer-header, and broken-VTEP ip-pair hashing swept across 1024 per-switch
seed realizations on BOTH fabric families, vs the deterministic static
baseline — plus the measured speedup over the equivalent per-seed
``FlowTracer`` loop (tracer timed on a sample of seeds, extrapolated)."""

from __future__ import annotations

import time

import numpy as np

from repro.core import (
    FIELDS_5TUPLE, FIELDS_IP_PAIR, FIELDS_VXLAN, EcmpRouting, FlowTracer,
    SimSpec, bipartite_pairs, build_multipod_fabric, compile_fabric, fim,
    flow_fields_matrix, monte_carlo_fim, nic_ip, simulate_paths,
    static_route_assignment, synthesize_flows,
)
from .common import bench_seeds, emit, paper_setup

NUM_SEEDS = bench_seeds(1024)
MODES = {"ecmp_5tuple": FIELDS_5TUPLE, "vxlan": FIELDS_VXLAN,
         "ip_pair": FIELDS_IP_PAIR}


def _sweep(tag: str, fab, wl, flows) -> None:
    comp = compile_fabric(fab)
    seeds = np.arange(NUM_SEEDS)
    for name, mode in MODES.items():
        t0 = time.perf_counter()
        mc = monte_carlo_fim(comp, flows, seeds, spec=SimSpec(fields=mode))
        dt = time.perf_counter() - t0
        v = mc.aggregate
        emit(f"mc_{tag}_{name}", dt / NUM_SEEDS * 1e6,
             f"mean={v.mean():.1f} p5={np.percentile(v, 5):.1f} "
             f"p95={np.percentile(v, 95):.1f} seeds={NUM_SEEDS}")
    _, static_paths = static_route_assignment(fab, flows)
    emit(f"mc_{tag}_static", 0.0, f"value={fim(static_paths, fab):.2f}")


def _speedup() -> None:
    """1024-seed x 256-flow acceptance sweep vs the per-seed tracer loop."""
    fab, wl, flows = paper_setup()
    comp = compile_fabric(fab)
    fields = flow_fields_matrix(flows, FIELDS_5TUPLE)
    seeds = np.arange(NUM_SEEDS)

    t0 = time.perf_counter()
    res = simulate_paths(comp, flows, seeds, field_matrix=fields)
    res.link_flow_counts()
    t_vec = time.perf_counter() - t0

    sample = 8  # tracer seeds actually run; wall time extrapolates linearly
    t0 = time.perf_counter()
    for s in range(sample):
        tr = FlowTracer(fab, EcmpRouting(fab, seed=s), wl, flows).trace()
        fim(tr.paths, fab)
    t_loop = (time.perf_counter() - t0) / sample * NUM_SEEDS
    emit("mc_speedup_vs_tracer", t_vec * 1e6,
         f"speedup={t_loop / t_vec:.0f}x tracer_est_s={t_loop:.1f} "
         f"vector_s={t_vec:.3f} seeds={NUM_SEEDS} flows={len(flows)}")


def run() -> None:
    fab, wl, flows = paper_setup()
    _sweep("paper", fab, wl, flows)

    mp = build_multipod_fabric(num_pods=2, hosts_per_pod=16,
                               leaves_per_pod=4, num_spines=8)
    pod0 = [f"host-{i}" for i in range(16)]
    pod1 = [f"host-{16 + i}" for i in range(16)]
    wl2 = bipartite_pairs(pod0, pod1, flows_per_pair=8)
    flows2 = synthesize_flows(wl2, nic_ip=nic_ip, nics_per_server=1)
    _sweep("multipod", mp, wl2, flows2)

    _speedup()

"""Device-engine acceptance bench: numpy vs jax per pipeline stage.

Times the Monte-Carlo hot path at 4096 flows x ``bench_seeds(1024)``
seeds on the paper testbed, stage by stage — ECMP walk, max-min fill,
flowlet exposure (under prime-spraying, where flowlets actually exist),
and the fused end-to-end throughput front end — once per engine.  Every
row is tagged with its ``engine`` so the regression guard never compares
a numpy baseline against a jax timing (or vice versa), and the summary
row reports the measured end-to-end speedup/crossover on this host.

jax rows are timed after one warm-up call, so they measure steady-state
jit execution (including host<->device transfers), not compilation.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import (
    ELEPHANT_MIN_BYTES, PrimeSpraying, SimSpec, compile_fabric,
    flowlet_exposure, max_min_rates, monte_carlo_throughput, simulate_paths,
)
from .common import bench_seeds, emit, paper_setup, timeit

NUM_SEEDS = bench_seeds(1024)
FLOWS_PER_PAIR = 256         # 16 directed server pairs x 256 = 4096 flows


def run() -> None:
    fab, wl, flows = paper_setup(flows_per_pair=FLOWS_PER_PAIR)
    comp = compile_fabric(fab)
    seeds = np.arange(NUM_SEEDS)
    shape = f"seeds={NUM_SEEDS} flows={len(flows)}"
    # heterogeneous volumes (every 4th flow an elephant) so demand-aware
    # spraying produces a real flowlet structure for the exposure stage
    flows = [dataclasses.replace(
        f, bytes=(4 * ELEPHANT_MIN_BYTES if i % 4 == 0 else 1024 * 1024))
        for i, f in enumerate(flows)]
    spray = PrimeSpraying(flowlets=4, min_bytes=ELEPHANT_MIN_BYTES)
    # the exposure inputs are engine-independent (1e-9-identical rates);
    # prep once on the host engine so each engine's row times ONLY its
    # own exposure stage
    res_s = simulate_paths(comp, flows, seeds, spec=SimSpec(strategy=spray))
    rates_s = max_min_rates(res_s)
    e2e: dict[str, float] = {}

    for engine in ("numpy", "jax"):
        def walk():
            return simulate_paths(comp, flows, seeds,
                                  spec=SimSpec(engine=engine))

        walk()                                   # warm-up (jit compile)
        t = timeit(walk, repeats=1)
        emit(f"engine_walk_{engine}", t / NUM_SEEDS * 1e6, shape,
             engine=engine)

        res = walk()
        def fill():
            return max_min_rates(res, engine=engine)

        fill()
        t = timeit(fill, repeats=1)
        emit(f"engine_fill_{engine}", t / NUM_SEEDS * 1e6, shape,
             engine=engine)

        def exposure():
            return flowlet_exposure(res_s, rates_s, engine=engine)

        exposure()
        t = timeit(exposure, repeats=1)
        emit(f"engine_exposure_{engine}", t / NUM_SEEDS * 1e6, shape,
             engine=engine)

        def end_to_end():
            return monte_carlo_throughput(comp, flows, seeds,
                                          spec=SimSpec(transport="roce-nack",
                                                       engine=engine))

        end_to_end()
        t = timeit(end_to_end, repeats=1)
        e2e[engine] = t
        emit(f"engine_e2e_{engine}", t / NUM_SEEDS * 1e6, shape,
             engine=engine)

    # derived-only summary: the measured crossover on this host
    emit("engine_jax_vs_numpy", 0.0,
         f"speedup={e2e['numpy'] / e2e['jax']:.2f}x "
         f"numpy_s={e2e['numpy']:.3f} jax_s={e2e['jax']:.3f} {shape}")

"""Wave-parallel vs sequential congestion-aware placement (tentpole).

The sequential ``CongestionAware`` greedy loop places one flow at a
time — a Python-level chain over all flows x seeds that dominates the
routing cost well before the paper's bulk shapes.  The wave variant
routes the whole wave against a frozen load snapshot and repairs only
the conflicted subset per round, so its cost scales with rounds (a
small constant), not flows.

This bench times both at 10x the historical ``tp_congestion_route``
shape (2560 flows vs 256, same 8-seed default) on the paper testbed,
once per engine for the wave (the sequential chain is host-only), and
emits a derived speedup row plus both demand-weighted FIM means — the
wave must match or beat sequential balance while winning the wall
clock.  Uniform demand keeps the comparison on the wave path proper:
heterogeneous per-flow weights delegate to the sequential chain by
design (see ``WaveCongestionAware``), which would time the same code
twice.

jax rows are timed after one warm-up call, so they measure steady-state
jit execution, not compilation.
"""

from __future__ import annotations

import numpy as np

from repro.core import (
    CongestionAware, SimSpec, WaveCongestionAware, compile_fabric, fim_vector,
    simulate_paths,
)
from .common import bench_seeds, emit, paper_setup, timeit

NUM_SEEDS = bench_seeds(8)
FLOWS_PER_PAIR = 160         # 16 directed server pairs x 160 = 2560 flows


def run() -> None:
    fab, wl, flows = paper_setup(flows_per_pair=FLOWS_PER_PAIR)
    comp = compile_fabric(fab)
    seeds = np.arange(NUM_SEEDS)
    shape = f"seeds={NUM_SEEDS} flows={len(flows)}"

    def seq():
        return simulate_paths(comp, flows, seeds,
                              spec=SimSpec(strategy=CongestionAware()))

    t_seq = timeit(seq)
    fim_seq = fim_vector(seq()).mean()
    emit("wave_route_sequential", t_seq / NUM_SEEDS * 1e6,
         f"fim={fim_seq:.2f} {shape}", engine="numpy")

    t_wave: dict[str, float] = {}
    for engine in ("numpy", "jax"):
        def wave():
            return simulate_paths(comp, flows, seeds,
                                  spec=SimSpec(strategy=WaveCongestionAware(),
                                               engine=engine))

        wave()                                  # warm-up (jit compile)
        t_wave[engine] = timeit(wave)
        fim_wave = fim_vector(wave()).mean()
        emit(f"wave_route_wave_{engine}", t_wave[engine] / NUM_SEEDS * 1e6,
             f"fim={fim_wave:.2f} {shape}", engine=engine)

    # derived-only summary: the acceptance row (wave >= 5x sequential at
    # 10x the historical tp_congestion_route flow count)
    emit("wave_vs_sequential", 0.0,
         f"speedup={t_seq / t_wave['numpy']:.2f}x "
         f"jax_speedup={t_seq / t_wave['jax']:.2f}x "
         f"seq_s={t_seq:.3f} wave_s={t_wave['numpy']:.3f} {shape}")

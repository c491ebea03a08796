"""Time-expanded simulation benchmark: per-step engine cost + the two
headline directional results of the time axis.

The timed rows cover ``simulate_timeline`` over the paper-testbed LLM
sequential schedule — five ``simulate_paths`` + FIM + weighted-fill
passes over one compiled fabric — under both timing models, normalized
per seed, which is what the regression guard tracks.  The derived rows
pin the modeling claims: the merged snapshot *overstates* byte-FIM on
the committed multipod disjoint-elephant schedule (the bug the time
axis fixes); event-timed replay turns that same schedule into a
per-strategy job-completion-time ranking (the headline — ECMP's hash
collisions *lengthen* the elephant step, spray/wave placement shorten
it); and adaptive per-RTT re-spray beats static spraying's mean goodput
under the reordering-intolerant ``roce-nack`` transport even after
paying the re-spray reordering tax.
"""

from __future__ import annotations

import numpy as np

from repro.core import (
    AdaptiveSpraying, CH_GRAD_AR, CH_MOE_A2A, PrimeSpraying, SimSpec,
    TIMING_EVENT, TimelineStep, build_multipod_fabric, build_paper_testbed,
    compile_fabric, flow_channel, merged_step, multipod_llm_schedule,
    paper_testbed_llm_schedule, simulate_paths, simulate_timeline,
    throughput_from_result,
)
from .common import bench_seeds, emit, paper_setup, timeit


def run() -> None:
    num_seeds = bench_seeds(64)
    seeds = np.arange(num_seeds)

    # --- timed: the phased engine on the paper-testbed LLM schedule ----
    _, flows, _, schedule = paper_testbed_llm_schedule()
    comp = compile_fabric(build_paper_testbed())
    state: dict = {}
    spec = SimSpec(demand_mode="bytes", transport="roce-nack",
                   strategy="prime-spray-elephant")
    elapsed = timeit(lambda: state.update(tl=simulate_timeline(
        comp, flows, schedule, seeds, spec=spec)))
    tl = state["tl"]
    emit("timeline_phased_engine", elapsed / num_seeds * 1e6,
         f"fim={tl.fim.mean():.2f} goodput={tl.goodput.mean():.2f} "
         f"steps={tl.num_steps} seeds={num_seeds} flows={len(flows)}")

    # --- timed: the same schedule under event-timed replay -------------
    estate: dict = {}
    elapsed = timeit(lambda: estate.update(tl=simulate_timeline(
        comp, flows, schedule, seeds, spec=SimSpec(
            demand_mode="bytes", transport="roce-nack",
            strategy="prime-spray-elephant", timing=TIMING_EVENT))))
    etl = estate["tl"]
    emit("timeline_event_engine", elapsed / num_seeds * 1e6,
         f"jct={etl.job_completion.mean():.4f}s fim={etl.fim.mean():.2f} "
         f"steps={etl.num_steps} seeds={num_seeds} flows={len(flows)}")

    # --- derived: merged overstates the disjoint-elephant schedule -----
    mcomp = compile_fabric(build_multipod_fabric())
    _, mflows, _, _ = multipod_llm_schedule(param_bytes=20_000_000_000)
    sub = [f for f in mflows
           if flow_channel(f) in (CH_GRAD_AR, CH_MOE_A2A)]
    sched = [TimelineStep("grad-all-reduce", (CH_GRAD_AR,)),
             TimelineStep("moe-all-to-all", (CH_MOE_A2A,))]
    phased = simulate_timeline(mcomp, sub, sched, seeds,
                               spec=SimSpec(demand_mode="bytes"))
    merged = simulate_timeline(mcomp, sub, [merged_step(sched)], seeds,
                               spec=SimSpec(demand_mode="bytes"))
    emit("timeline_merged_vs_phased_fim", 0.0,
         f"merged={merged.fim.mean():.2f} phased={phased.fim.mean():.2f} "
         f"overstatement={merged.fim.mean() / phased.fim.mean():.3f}x "
         f"seeds={num_seeds}")

    # --- derived: per-strategy JCT on the disjoint-elephant schedule ---
    jct = {}
    for strategy in ("ecmp", "prime-spray", "wave-congestion-aware"):
        etl2 = simulate_timeline(mcomp, sub, sched, seeds, spec=SimSpec(
            demand_mode="bytes", strategy=strategy, timing=TIMING_EVENT))
        jct[strategy] = etl2.job_completion.mean()
    emit("timeline_event_jct", 0.0,
         f"ecmp={jct['ecmp']:.4f}s spray={jct['prime-spray']:.4f}s "
         f"wave={jct['wave-congestion-aware']:.4f}s "
         f"spray_speedup={jct['ecmp'] / jct['prime-spray']:.3f}x "
         f"wave_speedup={jct['ecmp'] / jct['wave-congestion-aware']:.3f}x "
         f"seeds={num_seeds}")

    # --- derived: adaptive re-spray vs static spray under roce-nack ----
    fab, _, bflows = paper_setup()
    bcomp = compile_fabric(fab)
    static = throughput_from_result(
        simulate_paths(bcomp, bflows, seeds,
                       spec=SimSpec(strategy=PrimeSpraying(8))),
        transport="roce-nack")
    adaptive = throughput_from_result(
        simulate_paths(bcomp, bflows, seeds,
                       spec=SimSpec(strategy=AdaptiveSpraying(8))),
        transport="roce-nack")
    emit("timeline_adaptive_vs_static_goodput", 0.0,
         f"static={static.goodput.mean():.2f} "
         f"adaptive={adaptive.goodput.mean():.2f} "
         f"gain={adaptive.goodput.mean() / static.goodput.mean():.3f}x "
         f"transport=roce-nack seeds={num_seeds} flows={len(bflows)}")

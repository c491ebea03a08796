"""Event-timed replay tests (timing="event"): departure fill + timeline.

Three layers of coverage, mirroring the module's anchors:

* ``departure_fill`` against hand-computed water-filling-with-departures
  scenarios (the two-flow one-link analytic case to 1e-9, weighted and
  efficiency-scaled variants, degenerate columns);
* the degenerate anchors of ``simulate_timeline(timing="event")``: a
  one-step schedule is bit-identical in rates/FIM to ``timing="static"``,
  and a tiny single-candidate fabric reproduces the analytic completion
  times under every strategy and both engines;
* the headline directional claim: on the committed multipod
  disjoint-elephant schedule, ECMP's collision-lengthened steps give a
  strictly worse per-seed job completion time than spray/wave placement
  — the metric the static FIM comparison provably cannot show.
"""

import dataclasses

import numpy as np
import pytest

import repro.core.timeline as timeline_mod
from repro.core import (
    AdaptiveSpraying, CH_GRAD_AR, CH_MOE_A2A, DEFAULT_RTT_SECONDS, Device,
    ENGINE_JAX, FiveTuple, Flow, HOST_TO_LEAF, IDEAL, LEAF_TO_HOST, Link,
    PrimeSpraying, ROCE_NACK, SimSpec, TIMING_EVENT, TIMING_STATIC,
    TimelineStep, TransportProfile, build_multipod_fabric, compile_fabric,
    departure_fill, flow_channel, known_channels, merged_step,
    multipod_llm_schedule, nic_ip, paper_testbed_llm_schedule,
    partition_flows, rtt_round_budget, simulate_timeline, step_byte_totals,
)
from repro.core.fabric import Fabric, LEAF, SERVER

# ---------------------------------------------------------------------------
# departure_fill: hand-computed water-filling with departures
# ---------------------------------------------------------------------------


def test_departure_fill_two_flows_one_link_analytic():
    """Two flows share one 100 Gb/s link, 8 and 24 Gbit: both drain at
    50 until the small one departs at t=0.16 s, then the big one runs
    alone at 100 — 16 Gbit left, so it completes at exactly 0.32 s."""
    ids = np.zeros((1, 2, 3), np.int64)
    dep = departure_fill(ids, np.array([100.0]), np.array([8.0, 24.0]))
    np.testing.assert_allclose(
        dep.completion, [[0.16] * 3, [0.32] * 3], rtol=0, atol=1e-9)
    np.testing.assert_allclose(dep.duration, 0.32, rtol=0, atol=1e-9)
    assert dep.rounds == 2


def test_departure_fill_efficiency_scales_time():
    ids = np.zeros((1, 2, 1), np.int64)
    dep = departure_fill(ids, np.array([100.0]), np.array([8.0, 24.0]),
                         efficiency=np.full((2, 1), 0.5))
    np.testing.assert_allclose(
        dep.completion[:, 0], [0.32, 0.64], rtol=0, atol=1e-9)


def test_departure_fill_weighted_simultaneous():
    """Weights proportional to bytes: rates 25/75 for 8/24 Gbit, so both
    cells complete at the same instant in a single round."""
    ids = np.zeros((1, 2, 1), np.int64)
    dep = departure_fill(ids, np.array([100.0]), np.array([8.0, 24.0]),
                         weights=np.array([1.0, 3.0]))
    np.testing.assert_allclose(
        dep.completion[:, 0], [0.32, 0.32], rtol=0, atol=1e-9)
    assert dep.rounds == 1


def test_departure_fill_degenerate_columns():
    # zero-gigabit columns finish at t=0 and never contend: the live
    # column gets the whole link from the start
    ids = np.zeros((1, 2, 1), np.int64)
    dep = departure_fill(ids, np.array([100.0]), np.array([0.0, 10.0]))
    np.testing.assert_allclose(dep.completion[:, 0], [0.0, 0.1],
                               rtol=0, atol=1e-12)
    # a link-free column drains at infinite rate: completes at t=0
    ids2 = np.stack([np.array([[0], [-1]])])
    dep2 = departure_fill(ids2, np.array([100.0]), np.array([10.0, 10.0]))
    np.testing.assert_allclose(dep2.completion[:, 0], [0.1, 0.0],
                               rtol=0, atol=1e-12)


def test_departure_fill_per_seed_independence():
    """Seeds depart independently: seed 0 shares the link, seed 1 puts
    the flows on disjoint links — different completion schedules."""
    ids = np.zeros((1, 2, 2), np.int64)
    ids[0, 1, 1] = 1                        # seed 1: second flow alone
    dep = departure_fill(ids, np.array([100.0, 100.0]),
                         np.array([8.0, 8.0]))
    np.testing.assert_allclose(dep.completion[:, 0], [0.16, 0.16],
                               rtol=0, atol=1e-12)
    np.testing.assert_allclose(dep.completion[:, 1], [0.08, 0.08],
                               rtol=0, atol=1e-12)


def test_departure_fill_validation():
    ids = np.zeros((1, 2, 1), np.int64)
    with pytest.raises(ValueError, match="col_gbits"):
        departure_fill(ids, np.array([100.0]), np.array([1.0]))
    with pytest.raises(ValueError, match="finite"):
        departure_fill(ids, np.array([100.0]), np.array([-1.0, 1.0]))
    with pytest.raises(ValueError, match="efficiency"):
        departure_fill(ids, np.array([100.0]), np.array([1.0, 1.0]),
                       efficiency=np.zeros((2, 1)))
    with pytest.raises(RuntimeError, match="zero goodput"):
        departure_fill(ids, np.array([0.0]), np.array([1.0, 1.0]))
    with pytest.raises(ValueError, match="initial_rates"):
        departure_fill(ids, np.array([100.0]), np.array([1.0, 1.0]),
                       initial_rates=np.ones((3, 1)))


def test_departure_fill_initial_rates_reuse_is_exact():
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 6, size=(3, 8, 5))
    gb = rng.uniform(0.5, 20.0, size=8)
    cap = rng.uniform(50.0, 200.0, size=6)
    base = departure_fill(ids, cap, gb, assume_unique=True)
    from repro.core import batched_max_min
    pre = batched_max_min(ids, cap, assume_unique=True)
    reused = departure_fill(ids, cap, gb, assume_unique=True,
                            initial_rates=pre)
    np.testing.assert_array_equal(base.completion, reused.completion)


# ---------------------------------------------------------------------------
# the analytic anchor fabric: one candidate per hop, every strategy equal
# ---------------------------------------------------------------------------


def _two_server_fabric() -> Fabric:
    """srv-0 -> leaf-0 -> srv-1 with exactly one candidate at every hop,
    so ECMP, spraying, and placement all route identically and the
    event-timed completion times are the analytic water-filling ones."""
    devices = [Device("srv-0", SERVER), Device("srv-1", SERVER),
               Device("leaf-0", LEAF)]
    links = []
    for i in (0, 1):
        links.append(Link(f"srv-{i}", "nic0p0", "leaf-0", f"swp{i}",
                          100.0, HOST_TO_LEAF))
        links.append(Link("leaf-0", f"dwn{i}", f"srv-{i}", "nic0p0",
                          100.0, LEAF_TO_HOST))
    return Fabric(devices, links)


def _xfer_flows(bytes_a: int, bytes_b: int) -> list[Flow]:
    flows = []
    for fid, b in enumerate((bytes_a, bytes_b)):
        flows.append(Flow(
            flow_id=fid, src="srv-0", dst="srv-1",
            tuple5=FiveTuple(nic_ip("srv-0", 0), nic_ip("srv-1", 0),
                             10000 + fid, 20000 + fid),
            bytes=b, label=f"xfer-{fid}#ch{CH_GRAD_AR}"))
    return flows


ANALYTIC_STRATEGIES = ["ecmp", "prime-spray", "adaptive-spray",
                       "congestion-aware", "wave-congestion-aware"]


@pytest.mark.parametrize("engine", ["numpy", ENGINE_JAX])
@pytest.mark.parametrize("strategy", ANALYTIC_STRATEGIES)
def test_event_analytic_completion_per_strategy(strategy, engine):
    """1 GB and 3 GB flows down one shared 100 Gb/s path: rates 50/50,
    the 8-Gbit flow departs at 0.16 s, the survivor finishes its
    remaining 16 Gbit at 100 Gb/s — job completion exactly 0.32 s,
    under every strategy and both engines (single candidate per hop)."""
    comp = compile_fabric(_two_server_fabric())
    flows = _xfer_flows(1_000_000_000, 3_000_000_000)
    sched = [TimelineStep("xfer", (CH_GRAD_AR,))]
    tl = simulate_timeline(
        comp, flows, sched, [0, 3], spec=SimSpec(
            strategy=strategy, timing=TIMING_EVENT, engine=engine))
    np.testing.assert_allclose(tl.job_completion, 0.32, rtol=1e-9)
    np.testing.assert_allclose(tl.steps[0].completion[:, 0], [0.16, 0.32],
                               rtol=1e-9)
    np.testing.assert_allclose(tl.steps[0].duration, 0.32, rtol=1e-9)
    # absolute time axis: one step starting at t=0
    np.testing.assert_array_equal(tl.step_starts, np.zeros((1, 2)))
    np.testing.assert_array_equal(tl.step_ends[0], tl.job_completion)
    np.testing.assert_array_equal(tl.flow_completion(0),
                                  tl.steps[0].completion)


# ---------------------------------------------------------------------------
# degenerate anchor: one-step schedule, event == static bit-identically
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["ecmp", "prime-spray-elephant"])
def test_one_step_uniform_bytes_event_matches_static(paper_compiled,
                                                     strategy):
    """The per-step FIM/rate/goodput snapshots are computed identically
    under both timings, so a one-step uniform-bytes schedule reproduces
    the static result bit for bit — event timing only *adds* the time
    axis on top."""
    _, flows, _, schedule = paper_testbed_llm_schedule()
    uniform = [dataclasses.replace(f, bytes=10_000_000) for f in flows]
    one = [merged_step(schedule)]
    seeds = [0, 7, 1234567]
    spec = SimSpec(demand_mode="bytes", transport="roce-nack",
                   strategy=strategy)
    static = simulate_timeline(
        paper_compiled, uniform, one, seeds,
        spec=dataclasses.replace(spec, timing=TIMING_STATIC))
    event = simulate_timeline(
        paper_compiled, uniform, one, seeds,
        spec=dataclasses.replace(spec, timing=TIMING_EVENT))
    np.testing.assert_array_equal(event.fim, static.fim)
    np.testing.assert_array_equal(event.rates, static.rates)
    np.testing.assert_array_equal(event.goodput, static.goodput)
    np.testing.assert_array_equal(event.steps[0].throughput.rates,
                                  static.steps[0].throughput.rates)
    np.testing.assert_array_equal(event.steps[0].throughput.goodput,
                                  static.steps[0].throughput.goodput)
    for layer, series in static.steps[0].fim.per_layer.items():
        np.testing.assert_array_equal(event.steps[0].fim.per_layer[layer],
                                      series)
    # and the event extras exist only on the event result
    assert static.job_completion is None and static.timing == TIMING_STATIC
    assert event.timing == TIMING_EVENT
    assert event.job_completion.shape == (len(seeds),)
    assert (event.job_completion > 0).all()
    np.testing.assert_array_equal(event.job_completion,
                                  event.steps[0].duration)


# ---------------------------------------------------------------------------
# the headline: per-strategy job completion time on disjoint elephants
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def multipod_elephants():
    comp = compile_fabric(build_multipod_fabric())
    _, flows, _, _ = multipod_llm_schedule(param_bytes=20_000_000_000)
    sub = [f for f in flows
           if flow_channel(f) in (CH_GRAD_AR, CH_MOE_A2A)]
    sched = [TimelineStep("grad-all-reduce", (CH_GRAD_AR,)),
             TimelineStep("moe-all-to-all", (CH_MOE_A2A,))]
    return comp, sub, sched


def test_event_jct_ecmp_strictly_worse_than_spray_and_wave(
        multipod_elephants):
    """The committed multipod disjoint-elephant schedule under event
    timing: ECMP's hash collisions halve elephant goodput, which now
    *lengthens* the gradient all-reduce step — so its per-seed job
    completion time is strictly worse than spraying (which splits the
    elephants across paths) and wave placement (which avoids the
    collisions outright), on every seed.  This is the degradation the
    static FIM comparison cannot show: FIM says "imbalanced", JCT says
    "slower"."""
    comp, sub, sched = multipod_elephants
    seeds = np.arange(16)
    jct = {}
    for strategy in ("ecmp", "prime-spray", "wave-congestion-aware"):
        tl = simulate_timeline(comp, sub, sched, seeds, spec=SimSpec(
            demand_mode="bytes", strategy=strategy, timing=TIMING_EVENT))
        assert tl.job_completion.shape == (16,)
        assert np.isfinite(tl.job_completion).all()
        # steps run back to back: ends - starts == durations, last end
        # is the job completion
        np.testing.assert_allclose(tl.step_ends - tl.step_starts,
                                   tl.step_durations)
        np.testing.assert_array_equal(tl.step_ends[-1], tl.job_completion)
        jct[strategy] = tl.job_completion
    assert (jct["ecmp"] > jct["prime-spray"]).all()
    assert (jct["ecmp"] > jct["wave-congestion-aware"]).all()
    # and the margin is the collision-halved elephant, not float noise
    assert jct["ecmp"].mean() > 1.2 * jct["prime-spray"].mean()
    assert jct["ecmp"].mean() > 1.5 * jct["wave-congestion-aware"].mean()


def test_event_multi_step_totals_are_per_seed_weighted(multipod_elephants):
    comp, sub, sched = multipod_elephants
    seeds = np.arange(4)
    tl = simulate_timeline(comp, sub, sched, seeds, spec=SimSpec(
        demand_mode="bytes", timing=TIMING_EVENT))
    wks = tl.step_durations / tl.step_durations.sum(axis=0)
    np.testing.assert_allclose(
        tl.fim, (wks * tl.step_fim()).sum(axis=0), rtol=0, atol=0)
    # display weights are the seed-mean duration shares, normalized
    w = tl.step_durations.mean(axis=1)
    np.testing.assert_allclose(tl.weights, w / w.sum())
    # byte totals attach through the flows' channel labels
    totals = step_byte_totals(sub, sched)
    assert totals.shape == (2,) and (totals > 0).all()
    assert totals[0] > totals[1]            # the all-reduce elephants


def test_event_timing_jax_matches_numpy(multipod_elephants):
    comp, sub, sched = multipod_elephants
    seeds = np.arange(3)
    a = simulate_timeline(comp, sub, sched, seeds, spec=SimSpec(
        demand_mode="bytes", timing=TIMING_EVENT))
    b = simulate_timeline(comp, sub, sched, seeds, spec=SimSpec(
        demand_mode="bytes", timing=TIMING_EVENT, engine=ENGINE_JAX))
    np.testing.assert_allclose(a.job_completion, b.job_completion,
                               rtol=1e-6)
    np.testing.assert_allclose(a.fim, b.fim, rtol=1e-6)


# ---------------------------------------------------------------------------
# RTT round budget: adaptation priced per unit time
# ---------------------------------------------------------------------------


def test_rtt_round_budget_math():
    assert rtt_round_budget(0.0, 25e-6, 4) == 1       # sub-RTT: no feedback
    assert rtt_round_budget(1e-9, 25e-6, 4) == 1
    assert rtt_round_budget(26e-6, 25e-6, 4) == 2
    assert rtt_round_budget(1.0, 25e-6, 4) == 4       # capped
    with pytest.raises(ValueError, match="rtt_s"):
        rtt_round_budget(1.0, 0.0, 4)
    with pytest.raises(ValueError, match="cap"):
        rtt_round_budget(1.0, 25e-6, 0)
    with pytest.raises(ValueError, match="duration_s"):
        rtt_round_budget(-1.0, 25e-6, 4)
    with pytest.raises(ValueError, match="rtt_seconds"):
        TransportProfile("bad-rtt", alpha=1.0, floor=0.5, rtt_seconds=0.0)
    assert IDEAL.rtt_seconds == DEFAULT_RTT_SECONDS


def test_with_rounds_copies_everything_else():
    s = AdaptiveSpraying(4, min_bytes=1e6, volume_k=True, rounds=4,
                         ecn_factor=1.5, respray_cost=0.1, move_prob=0.5)
    assert s.with_rounds(4) is s
    s2 = s.with_rounds(2)
    assert s2.rounds == 2
    for attr in ("flowlets", "parts", "min_bytes", "volume_k",
                 "ecn_factor", "respray_cost", "move_prob"):
        assert getattr(s2, attr) == getattr(s, attr)


def test_event_adaptive_sub_rtt_step_cannot_adapt(paper_compiled):
    """With a transport whose RTT exceeds every derived step duration,
    the budget clamps to 1 round — AdaptiveSpraying must reproduce the
    static spray result bit-identically (rounds=1 IS PrimeSpraying)."""
    _, flows, _, schedule = paper_testbed_llm_schedule()
    seeds = [0, 5]
    slow_feedback = TransportProfile(
        "slow-feedback", alpha=ROCE_NACK.alpha, floor=ROCE_NACK.floor,
        rtt_seconds=1e6)
    adaptive = simulate_timeline(
        paper_compiled, flows, schedule, seeds, spec=SimSpec(
            demand_mode="bytes", transport=slow_feedback,
            strategy=AdaptiveSpraying(8, rounds=4), timing=TIMING_EVENT))
    static = simulate_timeline(
        paper_compiled, flows, schedule, seeds, spec=SimSpec(
            demand_mode="bytes", transport=slow_feedback,
            strategy=PrimeSpraying(8), timing=TIMING_EVENT))
    np.testing.assert_array_equal(adaptive.job_completion,
                                  static.job_completion)
    np.testing.assert_array_equal(adaptive.fim, static.fim)
    np.testing.assert_array_equal(adaptive.goodput, static.goodput)


# ---------------------------------------------------------------------------
# strict channel validation
# ---------------------------------------------------------------------------


def test_unknown_channel_error_names_registered_vocabulary():
    _, flows, _, _ = paper_testbed_llm_schedule()
    with pytest.raises(ValueError) as ei:
        partition_flows(flows, [merged_step(
            [TimelineStep("all", (1, 2, 3, 4, 5))]),
            TimelineStep("ghost", (42,))])
    msg = str(ei.value)
    assert "42" in msg and "CH_MOE_A2A" in msg and "CH_GRAD_AR" in msg
    assert "1 (CH_GRAD_AR)" in known_channels()
    with pytest.raises(ValueError, match="empty"):
        partition_flows([], [TimelineStep("a", (1,))])


def test_register_channel_duplicate_raises():
    from repro.core import register_channel
    assert register_channel(1, "CH_GRAD_AR") == 1    # same pair: no-op
    with pytest.raises(ValueError, match="already registered"):
        register_channel(1, "CH_SOMETHING_ELSE")
    register_channel(93171, "CH_TEST_TMP")
    try:
        with pytest.raises(ValueError, match="replace=True"):
            register_channel(93171, "CH_TEST_TMP2")
        register_channel(93171, "CH_TEST_TMP2", replace=True)
    finally:
        timeline_mod._CHANNEL_NAMES.pop(93171, None)


# ---------------------------------------------------------------------------
# heavyweight sweep (excluded from the CI tier-1 run)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_event_timeline_sweep_slow():
    """Large event-timed sweep at benchmark scale, shape-scaled by
    FLOWTRACER_SWEEP_FLOWS / FLOWTRACER_SWEEP_SEEDS: JCT stays finite,
    reproducible, and ordered (ECMP never beats wave placement)."""
    import os
    flow_scale = int(os.environ.get("FLOWTRACER_SWEEP_FLOWS", 0))
    num_seeds = int(os.environ.get("FLOWTRACER_SWEEP_SEEDS", 64))
    param_bytes = max(20_000_000_000, flow_scale * 1_000_000)
    comp = compile_fabric(build_multipod_fabric())
    _, flows, _, sched = multipod_llm_schedule(param_bytes=param_bytes)
    seeds = np.arange(num_seeds)
    results = {}
    for strategy in ("ecmp", "prime-spray-elephant",
                     "wave-congestion-aware"):
        tl = simulate_timeline(comp, flows, sched, seeds, spec=SimSpec(
            demand_mode="bytes", transport="roce-nack", strategy=strategy,
            timing=TIMING_EVENT))
        assert np.isfinite(tl.job_completion).all()
        assert (tl.job_completion > 0).all()
        results[strategy] = tl
    again = simulate_timeline(comp, flows, sched, seeds, spec=SimSpec(
        demand_mode="bytes", transport="roce-nack", strategy="ecmp",
        timing=TIMING_EVENT))
    np.testing.assert_array_equal(results["ecmp"].job_completion,
                                  again.job_completion)
    assert (results["ecmp"].job_completion.mean()
            > results["wave-congestion-aware"].job_completion.mean())

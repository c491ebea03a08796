"""Time-expanded simulation tests (core/timeline.py).

Contract coverage: a one-step schedule containing every channel must
reproduce the merged-snapshot FIM/rates/goodput **bit-identically**
under every registered strategy (the degenerate anchor — same idiom as
``min_bytes=inf == ECMP``); the committed multipod two-elephant
scenario makes the flattening bug visible (merged byte-FIM strictly
exceeds the duration-weighted phased FIM on every seed, and the
fully-overlapped schedule matches merged exactly); and the schedule
emitters / partition plumbing validate their inputs instead of silently
dropping traffic."""

import numpy as np
import pytest

from repro.core import (
    CH_BARRIER, CH_FSDP_AG, CH_FSDP_RS, CH_GRAD_AR, CH_MOE_A2A, LlmJobSpec,
    SCHEDULE_DP_OVERLAP, SCHEDULE_SEQUENTIAL, SimSpec, TimelineStep,
    build_multipod_fabric, build_paper_testbed, channel_name, compile_fabric,
    flow_channel, known_channels, llm_collective_phases, merged_step,
    monte_carlo_fim, monte_carlo_throughput, multipod_llm_schedule,
    paper_testbed_llm_schedule, partition_flows, simulate_timeline,
)


@pytest.fixture(scope="module")
def testbed_llm_schedule(paper_compiled):
    """(compiled fabric, flows, sequential schedule) on the paper testbed."""
    _, flows, _, schedule = paper_testbed_llm_schedule()
    return paper_compiled, flows, schedule


# ---------------------------------------------------------------------------
# the degenerate anchor: one step == merged snapshot, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", [
    "ecmp", "prime-spray", "prime-spray-elephant", "adaptive-spray-elephant",
    "congestion-aware",
])
def test_one_step_schedule_is_merged_snapshot(testbed_llm_schedule, strategy):
    comp, flows, schedule = testbed_llm_schedule
    seeds = [0, 7, 1234567]
    one = [merged_step(schedule)]
    tl = simulate_timeline(comp, flows, one, seeds,
                           spec=SimSpec(demand_mode="bytes",
                                        transport="roce-nack",
                                        strategy=strategy))
    mf = monte_carlo_fim(comp, flows, seeds,
                         spec=SimSpec(demand_mode="bytes", strategy=strategy))
    mt = monte_carlo_throughput(comp, flows, seeds,
                                spec=SimSpec(demand_mode="bytes",
                                             transport="roce-nack",
                                             strategy=strategy))
    assert tl.num_steps == 1 and tl.weights[0] == 1.0
    np.testing.assert_array_equal(tl.fim, mf.aggregate)
    for layer, series in mf.per_layer.items():
        np.testing.assert_array_equal(tl.steps[0].fim.per_layer[layer],
                                      series)
    step = tl.steps[0].throughput
    np.testing.assert_array_equal(step.rates, mt.rates)
    np.testing.assert_array_equal(step.goodput, mt.goodput)
    np.testing.assert_array_equal(tl.goodput, mt.goodput.mean(axis=0))
    np.testing.assert_array_equal(tl.rates, mt.rates.mean(axis=0))


# ---------------------------------------------------------------------------
# the bug made visible: disjoint elephants, merged FIM > phased FIM
# ---------------------------------------------------------------------------


def test_merged_overstates_disjoint_elephants():
    """Two elephant collectives in disjoint steps: the grad all-reduce
    seam elephants carry ~15x the MoE shuffle's bytes, so the merged
    byte-FIM is essentially the all-reduce's own (high, few hot seams)
    while the duration-weighted phased FIM averages in the much flatter
    MoE step — the merged snapshot strictly overstates the imbalance a
    phase-sampling observer ever sees."""
    comp = compile_fabric(build_multipod_fabric())
    _, flows, _, _ = multipod_llm_schedule(param_bytes=20_000_000_000)
    sub = [f for f in flows
           if flow_channel(f) in (CH_GRAD_AR, CH_MOE_A2A)]
    sched = [TimelineStep("grad-all-reduce", (CH_GRAD_AR,)),
             TimelineStep("moe-all-to-all", (CH_MOE_A2A,))]
    seeds = np.arange(16)
    phased = simulate_timeline(comp, sub, sched, seeds,
                               spec=SimSpec(demand_mode="bytes"))
    merged = simulate_timeline(comp, sub, [merged_step(sched)], seeds,
                               spec=SimSpec(demand_mode="bytes"))
    assert phased.num_steps == 2
    assert (merged.fim > phased.fim).all()
    # and the gap is the elephant's FIM edge, not float noise
    assert merged.fim.mean() > phased.fim.mean() * 1.02

    # a fully-overlapped schedule (both collectives in one step) IS the
    # merged snapshot, bit for bit
    overlap = [TimelineStep("overlapped", (CH_GRAD_AR, CH_MOE_A2A))]
    tl = simulate_timeline(comp, sub, overlap, seeds,
                           spec=SimSpec(demand_mode="bytes"))
    np.testing.assert_array_equal(tl.fim, merged.fim)
    np.testing.assert_array_equal(tl.goodput, merged.goodput)


def test_phased_series_and_weights(testbed_llm_schedule):
    comp, flows, schedule = testbed_llm_schedule
    seeds = np.arange(4)
    tl = simulate_timeline(comp, flows, schedule, seeds,
                           spec=SimSpec(demand_mode="bytes"))
    assert tl.num_steps == len(schedule)
    np.testing.assert_allclose(tl.weights.sum(), 1.0)
    # equal default durations
    np.testing.assert_allclose(tl.weights, 1.0 / tl.num_steps)
    # the time-weighted total is exactly the weighted mean of the series
    np.testing.assert_allclose(
        tl.fim, np.einsum("k,ks->s", tl.weights, tl.step_fim()))
    # every step routed only its own channels
    for sr in tl.steps:
        assert {flow_channel(f) for f in sr.flows} <= set(sr.step.channels)
    assert sum(len(sr.flows) for sr in tl.steps) == len(flows)
    summary = tl.summary()
    assert {"fim", "goodput", "rate"} <= set(summary)


# ---------------------------------------------------------------------------
# schedule emitters
# ---------------------------------------------------------------------------


def test_llm_collective_phases_modes():
    spec = LlmJobSpec(num_hosts=8)
    ops, seq = llm_collective_phases(spec, SCHEDULE_SEQUENTIAL)
    assert [s.name for s in seq] == [
        "fwd-all-gather", "moe-all-to-all", "bwd-reduce-scatter",
        "grad-all-reduce", "barrier"]
    _, overlap = llm_collective_phases(spec, SCHEDULE_DP_OVERLAP)
    assert [s.name for s in overlap] == ["forward", "backward", "barrier"]
    # both modes cover exactly the emitted channels
    chans = {op.channel_id for op in ops}
    for sched in (seq, overlap):
        assert {c for s in sched for c in s.channels} >= chans
    with pytest.raises(ValueError, match="unknown schedule mode"):
        llm_collective_phases(spec, "pipelined")


def test_moe_free_spec_drops_moe_step():
    spec = LlmJobSpec(num_hosts=8, moe_layers=0)
    ops, seq = llm_collective_phases(spec)
    assert "moe-all-to-all" not in [s.name for s in seq]
    assert CH_MOE_A2A not in {op.channel_id for op in ops}


# ---------------------------------------------------------------------------
# validation: no traffic is ever silently dropped
# ---------------------------------------------------------------------------


def test_timeline_step_validation():
    with pytest.raises(ValueError, match="no channels"):
        TimelineStep("empty", ())
    with pytest.raises(ValueError, match="duration"):
        TimelineStep("bad", (1,), duration=0.0)


def test_channel_vocabulary_fully_registered():
    # every schedule channel resolves through the registry by name —
    # a CH_* constant no schedule exercise would otherwise rot unseen
    expected = {CH_GRAD_AR: "CH_GRAD_AR", CH_FSDP_AG: "CH_FSDP_AG",
                CH_FSDP_RS: "CH_FSDP_RS", CH_MOE_A2A: "CH_MOE_A2A",
                CH_BARRIER: "CH_BARRIER"}
    assert len(expected) == 5          # distinct channel ids
    known = known_channels()
    for cid, name in expected.items():
        assert channel_name(cid) == f"{cid} ({name})"
        assert f"{cid} ({name})" in known


def test_partition_rejects_stray_and_unlabeled(paper_setup_small):
    _, flows, _, schedule = paper_testbed_llm_schedule()
    with pytest.raises(ValueError, match=r"channels \[1"):
        partition_flows(flows, [TimelineStep("only-barrier", (5,))])
    _, _, plain_flows = paper_setup_small       # bipartite: no #ch labels
    with pytest.raises(ValueError, match="no '#ch<N>' label"):
        partition_flows(plain_flows, schedule)


def test_empty_schedule_empty_flows_and_idle_steps(testbed_llm_schedule):
    comp, flows, schedule = testbed_llm_schedule
    with pytest.raises(ValueError, match="at least one step"):
        simulate_timeline(comp, flows, [], [0])
    # a step whose channels no flow carries raises — unknown ids and
    # legitimately-empty collectives alike — naming the registered CH_*
    # vocabulary instead of silently simulating an idle step
    padded = list(schedule) + [TimelineStep("idle", (99,), duration=5.0)]
    with pytest.raises(ValueError, match=r"99.*known channels"):
        simulate_timeline(comp, flows, padded, [0, 1])
    with pytest.raises(ValueError, match="CH_GRAD_AR"):
        partition_flows(flows, padded)
    with pytest.raises(ValueError, match="empty"):
        simulate_timeline(comp, [], schedule, [0])


# ---------------------------------------------------------------------------
# heavyweight sweep (excluded from the CI tier-1 run)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_timeline_strategy_sweep_slow():
    """Multi-step x multi-strategy sweep at benchmark scale: the phased
    totals stay finite, ordered, and reproducible across repeat runs."""
    comp = compile_fabric(build_paper_testbed())
    _, flows, _, schedule = paper_testbed_llm_schedule(
        SCHEDULE_DP_OVERLAP)
    seeds = np.arange(64)
    results = {}
    for strategy in ("ecmp", "prime-spray-elephant",
                     "adaptive-spray-elephant"):
        tl = simulate_timeline(comp, flows, schedule, seeds,
                               spec=SimSpec(demand_mode="bytes",
                                            transport="roce-nack",
                                            strategy=strategy))
        assert np.isfinite(tl.fim).all() and np.isfinite(tl.goodput).all()
        results[strategy] = tl
    again = simulate_timeline(comp, flows, schedule, seeds,
                              spec=SimSpec(demand_mode="bytes",
                                           transport="roce-nack",
                                           strategy="adaptive-spray-elephant"))
    np.testing.assert_array_equal(
        results["adaptive-spray-elephant"].goodput, again.goodput)
    # spraying the elephants must cut the phased byte-FIM vs ECMP
    assert (results["prime-spray-elephant"].fim.mean()
            < results["ecmp"].fim.mean())

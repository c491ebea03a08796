"""Heterogeneous flow-demand plumbing: flows -> walk -> FIM -> max-min.

The silent-correctness contract this module pins down:

* ``demand_mode="bytes"`` with all-equal bytes is **bit-identical** to
  ``demand_mode="uniform"`` for every registered strategy (K=1 spray
  included) — weighting a homogeneous workload must change nothing;
* with heterogeneous bytes, FIM and max-min rates actually move — the
  regression half that fails on the historical unit-demand assumption;
* the weighted allocation matches a scalar weighted reference on
  randomized heterogeneous workloads, end-to-end through the demand
  pipeline (not just ``batched_max_min`` in isolation);
* flowlet demand composes multiplicatively with flow demand, and the
  flowlet->flow aggregation preserves the byte-weighted shares.
"""

import numpy as np
import pytest
from _propcheck import given, settings, strategies as st
from conftest import weighted_max_min_ref

from repro.core import (
    DEMAND_BYTES, DEMAND_UNIFORM, CongestionAware, Flow, LlmJobSpec, PairSpec,
    PrimeSpraying, SimSpec, WorkloadDescription, available_strategies,
    bipartite_pairs, build_paper_testbed, compile_fabric, fim_vector,
    flow_demand_weights, llm_collective_ops, monte_carlo_fim,
    monte_carlo_throughput, nic_ip, paper_testbed_llm_workload, server_name,
    simulate_paths, synthesize_flows, throughput_from_result,
    workload_from_flows,
)
from repro.core.vector_sim import resolve_flows


def _hetero_flows(paper_setup, volumes):
    """paper_setup flows with per-flow bytes cycling over ``volumes``."""
    _, _, flows = paper_setup
    return [
        Flow(flow_id=f.flow_id, src=f.src, dst=f.dst, tuple5=f.tuple5,
             bytes=int(volumes[i % len(volumes)]), label=f.label)
        for i, f in enumerate(flows)
    ]


# ---------------------------------------------------------------------------
# flow_demand_weights
# ---------------------------------------------------------------------------


def test_uniform_mode_is_ones(paper_setup):
    _, _, flows = paper_setup
    np.testing.assert_array_equal(
        flow_demand_weights(flows, DEMAND_UNIFORM), 1.0)


def test_bytes_mode_equal_bytes_is_exact_ones(paper_setup):
    for volume in (0, 1, 3, 1_000_000_007):
        flows = _hetero_flows(paper_setup, [volume])
        w = flow_demand_weights(flows, DEMAND_BYTES)
        assert (w == 1.0).all(), f"volume={volume} not exactly uniform"


def test_bytes_mode_proportional_and_mean_one(paper_setup):
    flows = _hetero_flows(paper_setup, [1 << 30, 1 << 10])
    w = flow_demand_weights(flows, DEMAND_BYTES)
    assert w.mean() == pytest.approx(1.0)
    assert w[0] / w[1] == pytest.approx((1 << 30) / (1 << 10))
    assert (w > 0).all()


def test_bytes_mode_zero_byte_flows_floored(paper_setup):
    # barriers (0 bytes) inside an elephant workload must stay strictly
    # positive: the max-min fill rejects zero weights
    flows = _hetero_flows(paper_setup, [0, 1 << 30])
    w = flow_demand_weights(flows, DEMAND_BYTES)
    assert (w > 0).all()
    assert w[0] < w[1]


def test_unknown_demand_mode_raises(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    with pytest.raises(ValueError, match="demand_mode"):
        flow_demand_weights(flows, "gigabytes")
    with pytest.raises(ValueError, match="demand_mode"):
        simulate_paths(paper_compiled, flows, [0],
                       spec=SimSpec(demand_mode="gigabytes"))


# ---------------------------------------------------------------------------
# bit-identity: bytes mode on a homogeneous workload == uniform mode
# ---------------------------------------------------------------------------


def _all_strategy_instances():
    for name in available_strategies():
        yield name, name
    yield "prime-spray-k1", PrimeSpraying(flowlets=1)


@pytest.mark.parametrize("tag,strategy", list(_all_strategy_instances()))
def test_equal_bytes_bit_identical_to_uniform(paper_compiled, paper_setup,
                                              tag, strategy):
    """The acceptance criterion: demand_mode="bytes" with uniform volumes
    must change *nothing* — same link ids, same weights, same FIM floats,
    same rates — for every registered strategy."""
    flows = _hetero_flows(paper_setup, [1 << 20])[:64]
    seeds = np.arange(6)
    base = simulate_paths(paper_compiled, flows, seeds,
                          spec=SimSpec(strategy=strategy))
    res = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=strategy,
                                      demand_mode=DEMAND_BYTES))
    np.testing.assert_array_equal(res.link_ids, base.link_ids)
    np.testing.assert_array_equal(res.flow_demand, 1.0)
    np.testing.assert_array_equal(res.column_weights(), base.column_weights())
    np.testing.assert_array_equal(res.link_flow_counts(),
                                  base.link_flow_counts())
    np.testing.assert_array_equal(
        throughput_from_result(res).rates, throughput_from_result(base).rates)


def test_legacy_strategy_without_demand_mode_kwarg(paper_compiled,
                                                   paper_setup):
    """Custom strategies registered against the pre-demand route()
    signature keep working under uniform demand; asking them for byte
    weighting fails loudly instead of silently dropping the weights."""
    from repro.core import RoutingStrategy

    class Legacy(RoutingStrategy):
        name = "legacy"

        def route(self, comp, flows, seeds_u64, *, fields, hash_backend,
                  max_hops, field_matrix):
            return simulate_paths(comp, flows, seeds_u64,
                                  spec=SimSpec(fields=fields,
                                               hash_backend=hash_backend,
                                               max_hops=max_hops),
                                  field_matrix=field_matrix)

    _, _, flows = paper_setup
    res = simulate_paths(paper_compiled, flows[:4], [0],
                         spec=SimSpec(strategy=Legacy()))
    assert res.num_flows == 4
    with pytest.raises(TypeError, match="demand_mode"):
        simulate_paths(paper_compiled, flows[:4], [0],
                       spec=SimSpec(strategy=Legacy(),
                                    demand_mode=DEMAND_BYTES))


def test_monte_carlo_fronts_bit_identical_on_equal_bytes(paper_compiled,
                                                         paper_setup):
    flows = _hetero_flows(paper_setup, [512])[:32]
    seeds = np.arange(4)
    for strategy in (None, "prime-spray", "congestion-aware"):
        a = monte_carlo_fim(paper_compiled, flows, seeds,
                            spec=SimSpec(strategy=strategy))
        b = monte_carlo_fim(paper_compiled, flows, seeds,
                            spec=SimSpec(strategy=strategy,
                                         demand_mode=DEMAND_BYTES))
        np.testing.assert_array_equal(a.aggregate, b.aggregate)
        ta = monte_carlo_throughput(paper_compiled, flows, seeds,
                                    spec=SimSpec(strategy=strategy))
        tb = monte_carlo_throughput(paper_compiled, flows, seeds,
                                    spec=SimSpec(strategy=strategy,
                                                 demand_mode=DEMAND_BYTES))
        np.testing.assert_array_equal(ta.rates, tb.rates)


# ---------------------------------------------------------------------------
# heterogeneous bytes actually move the answer (regression half)
# ---------------------------------------------------------------------------


def test_hetero_bytes_change_fim_and_rates(paper_compiled, paper_setup):
    """Fails on the historical unit-demand pipeline: a 1 GB elephant and
    a 1 KB mouse weighed identically in FIM and max-min."""
    flows = _hetero_flows(paper_setup, [1 << 30, 1 << 10])
    seeds = np.arange(8)
    uni = simulate_paths(paper_compiled, flows, seeds)
    wtd = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(demand_mode=DEMAND_BYTES))
    # identical paths (ECMP ignores demand) ...
    np.testing.assert_array_equal(uni.link_ids, wtd.link_ids)
    # ... but weighted FIM and weighted rates tell a different story
    assert not np.allclose(fim_vector(uni), fim_vector(wtd))
    ru = throughput_from_result(uni).rates
    rw = throughput_from_result(wtd).rates
    assert not np.allclose(ru, rw)
    # elephants claim more than mice under weighted max-min (exact
    # proportional sharing is pinned by the forced-bottleneck test and
    # the scalar-reference differential below)
    assert rw[0::2].mean() > rw[1::2].mean()


def test_throughput_aggregation_is_demand_weighted(paper_compiled,
                                                   paper_setup):
    """S1 regression: two flows sharing one bottleneck with 3:1 byte
    demand must split it 3:1 (a plain unit-demand fill gives 1:1)."""
    _, _, flows = paper_setup
    f0, f1 = flows[0], flows[1]
    pair = [
        Flow(0, f0.src, f0.dst, f0.tuple5, bytes=3 * (1 << 20)),
        Flow(1, f1.src, f1.dst, f1.tuple5, bytes=1 << 20),
    ]
    res = simulate_paths(paper_compiled, pair, [0],
                         spec=SimSpec(demand_mode=DEMAND_BYTES))
    # force a shared single-link contention: replace walked paths with one
    # common link so the split ratio is exactly the demand ratio
    res.link_ids = np.zeros((1, 2, 1), np.int32)
    tp = throughput_from_result(res)
    assert tp.rates[0, 0] == pytest.approx(3.0 * tp.rates[1, 0])
    cap = float(res.compiled.link_gbps[0])
    assert tp.rates[:, 0].sum() == pytest.approx(cap)


def test_spray_composes_flow_demand_with_flowlet_fractions(paper_compiled,
                                                           paper_setup):
    flows = _hetero_flows(paper_setup, [1 << 28, 1 << 12])[:32]
    res = simulate_paths(paper_compiled, flows, [3],
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=4),
                                      demand_mode=DEMAND_BYTES))
    w = res.column_weights()
    # each column = parent weight / K; per-flow sums recover flow_demand
    per_flow = np.bincount(res.flow_index, weights=w, minlength=len(flows))
    np.testing.assert_allclose(per_flow, res.flow_demand, rtol=1e-12)
    # total per-layer load comparable with single-path: sum of weights
    np.testing.assert_allclose(w.sum(), res.flow_demand.sum(), rtol=1e-12)


def test_congestion_aware_places_largest_first(paper_compiled, paper_setup):
    """The heaviest flow must see an empty fabric: its path load is laid
    down before any lighter flow's, so under byte demand its first-hop
    choice equals the choice on an unloaded fabric."""
    flows = _hetero_flows(paper_setup, [1, 2, 4, 1 << 30])[:64]
    heavy = max(range(len(flows)), key=lambda i: flows[i].bytes)
    res = simulate_paths(paper_compiled, flows, [0],
                         spec=SimSpec(strategy=CongestionAware(),
                                      demand_mode=DEMAND_BYTES))
    alone = simulate_paths(paper_compiled, [flows[heavy]], [0],
                           spec=SimSpec(strategy=CongestionAware()))
    np.testing.assert_array_equal(
        res.link_ids[:, heavy, :], alone.link_ids[:, 0, :])


def test_congestion_aware_weighted_loads_change_placement(paper_compiled,
                                                          paper_setup):
    """With weighted tallies a placed elephant repels later flows; unit
    tallies would let them pile onto its links."""
    flows = _hetero_flows(paper_setup, [1 << 30, 1 << 10])
    seeds = np.arange(4)
    uni = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=CongestionAware()))
    wtd = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=CongestionAware(),
                                      demand_mode=DEMAND_BYTES))
    assert not np.array_equal(uni.link_ids, wtd.link_ids)
    # and the weighted placement spreads bytes better than hashing does
    ecmp = simulate_paths(paper_compiled, flows, seeds,
                          spec=SimSpec(demand_mode=DEMAND_BYTES))
    assert fim_vector(wtd).mean() < fim_vector(ecmp).mean()


# ---------------------------------------------------------------------------
# differential: weighted pipeline vs scalar weighted reference
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**31))
@settings(max_examples=8, deadline=None)
def test_bytes_pipeline_matches_scalar_weighted_reference(rngseed):
    """End-to-end differential: random heterogeneous volumes through
    simulate_paths(demand_mode="bytes") + throughput_from_result equal a
    readable scalar weighted progressive fill on the walked paths."""
    rng = np.random.default_rng(rngseed)
    fab = compile_fabric(build_paper_testbed(servers_per_rack=2))
    wl = bipartite_pairs([server_name(0), server_name(1)],
                         [server_name(2), server_name(3)], flows_per_pair=4)
    flows = [
        Flow(f.flow_id, f.src, f.dst, f.tuple5,
             bytes=int(rng.integers(1, 1 << 32)))
        for f in synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
    ]
    seeds = [int(rng.integers(0, 2**63)) for _ in range(2)]
    res = simulate_paths(fab, flows, seeds,
                         spec=SimSpec(demand_mode=DEMAND_BYTES))
    tp = throughput_from_result(res)
    w = res.flow_demand
    link_index = {link: i for i, link in enumerate(fab.links)}
    for s in range(len(seeds)):
        paths = {
            j: [link_index[link] for link in path]
            for j, (fid, path) in enumerate(
                sorted(res.paths_for_seed(s).items()))
        }
        ref = weighted_max_min_ref(paths, list(fab.link_gbps),
                                   {j: w[j] for j in paths})
        for j in paths:
            assert tp.rates[j, s] == pytest.approx(ref[j], rel=1e-9)


# ---------------------------------------------------------------------------
# PairSpec byte specs + workload accounting (S2)
# ---------------------------------------------------------------------------


def test_pairspec_bytes_override_and_total_bytes():
    wl = WorkloadDescription(pairs=[
        PairSpec("srv-0", "srv-1", 2, bytes_per_flow=100),
        PairSpec("srv-1", "srv-0", 3),
    ])
    assert wl.total_flows == 5
    assert wl.total_bytes == 200          # unspecified pairs count 0
    flows = synthesize_flows(wl, nic_ip=nic_ip, bytes_per_flow=7)
    assert [f.bytes for f in flows] == [100, 100, 7, 7, 7]


def test_bipartite_pairs_per_pair_volumes():
    a = [server_name(i) for i in range(2)]
    b = [server_name(2 + i) for i in range(2)]
    wl = bipartite_pairs(a, b, 3, bytes_per_flow=[10, 20])
    assert [p.bytes_per_flow for p in wl.pairs] == [10, 10, 20, 20]
    assert wl.total_bytes == 3 * (10 + 10 + 20 + 20)
    scalar = bipartite_pairs(a, b, 3, bytes_per_flow=5)
    assert {p.bytes_per_flow for p in scalar.pairs} == {5}
    with pytest.raises(ValueError, match="bytes_per_flow"):
        bipartite_pairs(a, b, 3, bytes_per_flow=[10])
    with pytest.raises(TypeError, match="bytes_per_flow"):
        bipartite_pairs(a, b, 3, bytes_per_flow="12")  # not char-by-char


def test_workload_description_bytes_reach_demand(paper_compiled):
    """A byte-weighted WorkloadDescription drives weighted FIM through
    the Monte-Carlo front end without an explicit flow list."""
    a = [server_name(i) for i in range(8)]
    b = [server_name(8 + i) for i in range(8)]
    wl = bipartite_pairs(a, b, 4,
                         bytes_per_flow=[1 << 30] * 2 + [1 << 10] * 6)
    flows = resolve_flows(paper_compiled, wl)
    assert sum(f.bytes for f in flows) == wl.total_bytes
    seeds = np.arange(4)
    u = monte_carlo_fim(paper_compiled, wl, seeds)
    w = monte_carlo_fim(paper_compiled, wl, seeds,
                        spec=SimSpec(demand_mode=DEMAND_BYTES))
    assert not np.allclose(u.aggregate, w.aggregate)


def test_workload_from_flows_roundtrip(paper_setup):
    flows = _hetero_flows(paper_setup, [1000])[:48]
    wl = workload_from_flows(flows)
    assert wl.total_flows == len(flows)
    assert wl.total_bytes == sum(f.bytes for f in flows)
    assert all(p.bytes_per_flow == 1000 for p in wl.pairs)
    # an all-zero pair must pin 0 explicitly, not fall back to the
    # synthesize-time default volume
    zeros = workload_from_flows(_hetero_flows(paper_setup, [0])[:8])
    assert all(p.bytes_per_flow == 0 for p in zeros.pairs)
    resyn = synthesize_flows(zeros, nic_ip=nic_ip, bytes_per_flow=999)
    assert all(f.bytes == 0 for f in resyn)


def test_bipartite_pairs_numpy_scalar_volume():
    a, b = [server_name(0)], [server_name(1)]
    wl = bipartite_pairs(a, b, 2, bytes_per_flow=np.int64(1 << 20))
    assert [p.bytes_per_flow for p in wl.pairs] == [1 << 20, 1 << 20]


# ---------------------------------------------------------------------------
# LLM workload generator
# ---------------------------------------------------------------------------


def test_llm_collective_ops_mix():
    spec = LlmJobSpec(num_hosts=16)
    ops = llm_collective_ops(spec)
    kinds = [op.kind for op in ops]
    assert kinds == ["all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "all-reduce"]
    ar, ag, rs, a2a, barrier = ops
    assert ar.wire_bytes > a2a.wire_bytes > barrier.wire_bytes
    assert ag.multiplier == spec.num_layers
    # FSDP traffic (gather + scatter) totals ~ the gradient all-reduce
    assert (ag.total_wire_bytes + rs.total_wire_bytes
            == pytest.approx(ar.total_wire_bytes, rel=0.1))


def test_paper_testbed_llm_scenario(paper_compiled):
    wl, flows, stats = paper_testbed_llm_workload()
    assert stats.inter_pod_dcn == len(flows) > 250
    assert stats.intra_host == stats.intra_pod_ici == 0
    assert {f.src for f in flows} <= {server_name(i) for i in range(16)}
    volumes = sorted({f.bytes for f in flows})
    assert volumes[-1] / volumes[0] > 1e6      # elephants and mice
    # per-pair mean rounding: the description is pair-granular
    assert wl.total_bytes == pytest.approx(sum(f.bytes for f in flows),
                                           rel=1e-6)
    # the committed acceptance scenario: weighted FIM != unweighted FIM
    seeds = np.arange(8)
    for strategy in (None, "prime-spray", "congestion-aware"):
        u = monte_carlo_fim(paper_compiled, flows, seeds,
                            spec=SimSpec(strategy=strategy))
        w = monte_carlo_fim(paper_compiled, flows, seeds,
                            spec=SimSpec(strategy=strategy,
                                         demand_mode=DEMAND_BYTES))
        assert not np.allclose(u.aggregate, w.aggregate), strategy


def test_multipod_llm_scenario_splits_ici_and_dcn(multipod_small):
    fab, _, _ = multipod_small
    from repro.core import multipod_llm_workload
    wl, flows, stats = multipod_llm_workload()
    assert stats.intra_pod_ici > 0          # FSDP rings mostly stay on ICI
    assert stats.inter_pod_dcn == len(flows) > 0
    assert stats.ici_bytes > stats.dcn_bytes
    res = simulate_paths(compile_fabric(fab), flows, [0, 1],
                         spec=SimSpec(demand_mode=DEMAND_BYTES))
    assert res.link_ids.shape[1] == len(flows)
    assert (res.flow_demand > 0).all()

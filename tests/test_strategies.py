"""Routing-strategy subsystem tests.

Contract coverage: the ECMP strategy must reproduce ``simulate_paths``
bit-identically (it IS the baseline every comparison is anchored to);
PRIME spraying must degenerate to ECMP at K=1 and carry demand
fractions that sum to 1 per flow; the weighted max-min fill must match
a scalar weighted progressive-filling reference; and the congestion-
aware strategy must emit topologically valid paths with lower imbalance
than hashed ECMP."""

import numpy as np
import pytest
from _propcheck import given, settings, strategies as st
from conftest import weighted_max_min_ref

from repro.core import (
    CongestionAware, EcmpStrategy, PrimeSpraying, RoutingStrategy, SimSpec,
    available_strategies, batched_max_min, fim_vector,
    flow_rates_from_flowlets, monte_carlo_fim, monte_carlo_throughput,
    register_strategy, resolve_strategy, simulate_paths,
    throughput_from_result,
)
from repro.core.strategies import _balanced_parts

LINE_RATE = 400.0


# ---------------------------------------------------------------------------
# ECMP strategy: bit-identical to the default walk
# ---------------------------------------------------------------------------


def test_ecmp_strategy_bit_identical(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    seeds = [0, 7, 1234567, 2**40 + 17]
    base = simulate_paths(paper_compiled, flows, seeds)
    for strategy in ("ecmp", EcmpStrategy()):
        res = simulate_paths(paper_compiled, flows, seeds,
                             spec=SimSpec(strategy=strategy))
        np.testing.assert_array_equal(res.link_ids, base.link_ids)
        np.testing.assert_array_equal(res.flow_index, np.arange(len(flows)))
        assert (res.demand == 1.0).all()
        assert not res.is_multipath


def test_strategy_kwarg_threads_fields_and_backend(paper_compiled,
                                                   paper_setup):
    _, _, flows = paper_setup
    base = simulate_paths(paper_compiled, flows, [3, 9],
                          spec=SimSpec(fields="ip-pair"))
    res = simulate_paths(paper_compiled, flows, [3, 9],
                         spec=SimSpec(fields="ip-pair", strategy="ecmp"))
    np.testing.assert_array_equal(res.link_ids, base.link_ids)


# ---------------------------------------------------------------------------
# PRIME spraying
# ---------------------------------------------------------------------------


def test_prime_k1_degenerates_to_ecmp(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    seeds = [0, 42, 2**33]
    base = simulate_paths(paper_compiled, flows, seeds)
    res = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=1)))
    np.testing.assert_array_equal(res.link_ids, base.link_ids)
    assert (res.demand == 1.0).all()
    assert not res.is_multipath


@given(st.integers(1, 9))
@settings(max_examples=9, deadline=None)
def test_prime_demand_fractions_sum_to_one(k):
    from repro.core import (
        bipartite_pairs, build_paper_testbed, compile_fabric, nic_ip,
        server_name, synthesize_flows,
    )
    fab = compile_fabric(build_paper_testbed(servers_per_rack=2))
    wl = bipartite_pairs([server_name(0), server_name(1)],
                         [server_name(2), server_name(3)], flows_per_pair=3)
    flows = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
    res = simulate_paths(fab, flows, [5],
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=k)))
    assert res.num_flowlets == len(flows) * k
    np.testing.assert_allclose(res.demand, 1.0 / k)
    per_flow = np.bincount(res.flow_index, weights=res.demand,
                           minlength=len(flows))
    np.testing.assert_allclose(per_flow, 1.0)
    # flowlets of one flow are contiguous and parent-ordered
    np.testing.assert_array_equal(
        res.flow_index, np.repeat(np.arange(len(flows)), k))


def test_prime_flowlet_paths_topologically_valid(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    res = simulate_paths(paper_compiled, flows[:16], [0, 11],
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=4)))
    by_id = {f.flow_id: f for f in flows[:16]}
    for seed_index in range(2):
        flowlet_paths = res.flowlet_paths_for_seed(seed_index)
        assert set(flowlet_paths) == set(by_id)
        for fid, paths in flowlet_paths.items():
            assert len(paths) == 4
            for path in paths:
                assert path[0].src == by_id[fid].src
                assert path[-1].dst == by_id[fid].dst
                for a, b in zip(path, path[1:]):
                    assert a.dst == b.src


def test_prime_lower_fim_than_ecmp(paper_compiled, paper_setup):
    """The acceptance-criterion regime at test scale: multi-part entropy
    spraying spreads each flow over K paths, so the demand-weighted link
    loads even out and FIM drops well below per-flow ECMP."""
    _, _, flows = paper_setup
    seeds = np.arange(64)
    ecmp = fim_vector(simulate_paths(paper_compiled, flows, seeds))
    spray = fim_vector(simulate_paths(
        paper_compiled, flows, seeds,
        spec=SimSpec(strategy=PrimeSpraying(flowlets=8))))
    assert spray.mean() < ecmp.mean() - 10.0
    assert (spray >= 0).all()


def test_prime_parts_validation():
    assert _balanced_parts(8) == (2, 4)
    assert _balanced_parts(7) == (7,)
    assert _balanced_parts(1) == (1,)
    assert PrimeSpraying(flowlets=6, parts=(2, 3)).parts == (2, 3)
    labels = PrimeSpraying(flowlets=8).entropy_labels()
    assert labels.shape == (8, 2)
    assert len({tuple(r) for r in labels.tolist()}) == 8  # distinct per flowlet
    with pytest.raises(ValueError):
        PrimeSpraying(flowlets=0)
    with pytest.raises(ValueError):
        PrimeSpraying(flowlets=8, parts=(3, 3))
    with pytest.raises(ValueError):
        PrimeSpraying(flowlets=4, parts=(4, 0))


def test_multipath_result_guards_paths_for_seed(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    res = simulate_paths(paper_compiled, flows[:4], [0],
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=2)))
    with pytest.raises(ValueError):
        res.paths_for_seed(0)


# ---------------------------------------------------------------------------
# congestion-aware selection
# ---------------------------------------------------------------------------


def test_congestion_aware_valid_paths(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    res = simulate_paths(paper_compiled, flows, [0, 3],
                         spec=SimSpec(strategy=CongestionAware()))
    by_id = {f.flow_id: f for f in flows}
    for fid, path in res.paths_for_seed(0).items():
        assert path[0].src == by_id[fid].src
        assert path[-1].dst == by_id[fid].dst
        for a, b in zip(path, path[1:]):
            assert a.dst == b.src


def test_congestion_aware_lower_fim_than_ecmp(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    seeds = np.arange(16)
    ecmp = fim_vector(simulate_paths(paper_compiled, flows, seeds))
    cong = fim_vector(simulate_paths(
        paper_compiled, flows, seeds,
        spec=SimSpec(strategy="congestion-aware")))
    assert cong.mean() < ecmp.mean() - 10.0


def test_congestion_aware_throughput_sane(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    res = simulate_paths(paper_compiled, flows, np.arange(4),
                         spec=SimSpec(strategy=CongestionAware()))
    tp = throughput_from_result(res)
    assert tp.rates.shape == (len(flows), 4)
    assert (tp.rates > 0).all()
    assert tp.per_pair.max() <= LINE_RATE + 1e-6
    # greedy balancing beats hashed ECMP on the worst pair
    base = throughput_from_result(simulate_paths(paper_compiled, flows,
                                                 np.arange(4)))
    assert tp.per_pair.min() >= base.per_pair.min()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_resolution():
    assert {"ecmp", "prime-spray", "congestion-aware"} <= set(
        available_strategies())
    assert isinstance(resolve_strategy("prime-spray"), PrimeSpraying)
    inst = CongestionAware()
    assert resolve_strategy(inst) is inst
    with pytest.raises(ValueError, match="unknown routing strategy"):
        resolve_strategy("no-such-scheme")
    with pytest.raises(TypeError):
        resolve_strategy(42)


def test_register_custom_strategy():
    class Probe(RoutingStrategy):
        name = "probe"

    register_strategy("probe-test", Probe)
    try:
        assert isinstance(resolve_strategy("probe-test"), Probe)
    finally:
        from repro.core.strategies import _REGISTRY
        _REGISTRY.pop("probe-test", None)


def test_unknown_strategy_error_lists_registry():
    with pytest.raises(ValueError) as exc:
        resolve_strategy("no-such-scheme")
    msg = str(exc.value)
    for name in available_strategies():
        assert name in msg
    assert str(available_strategies()) in msg      # sorted listing


def test_duplicate_registration_raises():
    class Probe(RoutingStrategy):
        name = "probe"

    register_strategy("dup-test", Probe)
    try:
        with pytest.raises(ValueError, match="already registered"):
            register_strategy("dup-test", Probe)
        # the baseline is protected too — and the error lists the registry
        with pytest.raises(ValueError, match="'ecmp'.*registered"):
            register_strategy("ecmp", Probe)
        register_strategy("dup-test", Probe, replace=True)   # explicit wins
    finally:
        from repro.core.strategies import _REGISTRY
        _REGISTRY.pop("dup-test", None)


# ---------------------------------------------------------------------------
# adaptive re-spray
# ---------------------------------------------------------------------------


def test_adaptive_validation():
    from repro.core import AdaptiveSpraying
    with pytest.raises(ValueError, match="rounds"):
        AdaptiveSpraying(rounds=0)
    with pytest.raises(ValueError, match="ecn_factor"):
        AdaptiveSpraying(ecn_factor=0.0)
    with pytest.raises(ValueError, match="respray_cost"):
        AdaptiveSpraying(respray_cost=-0.1)
    with pytest.raises(ValueError, match="move_prob"):
        AdaptiveSpraying(move_prob=0.0)


def test_adaptive_rounds1_is_static_spray(paper_compiled, paper_setup_small):
    """``rounds=1`` is PrimeSpraying wholesale — same tensor, no extra
    exposure — and ``min_bytes=inf`` still degenerates to ECMP."""
    from repro.core import AdaptiveSpraying
    _, _, flows = paper_setup_small
    seeds = [0, 7, 1234567]
    static = simulate_paths(paper_compiled, flows, seeds,
                            spec=SimSpec(strategy=PrimeSpraying(8)))
    deg = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=AdaptiveSpraying(8, rounds=1)))
    np.testing.assert_array_equal(static.link_ids, deg.link_ids)
    assert deg.extra_exposure is None
    ecmp = simulate_paths(paper_compiled, flows, seeds)
    off = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=AdaptiveSpraying(
                             8, min_bytes=np.inf, rounds=4)))
    np.testing.assert_array_equal(ecmp.link_ids, off.link_ids)


def test_adaptive_beats_static_spray_goodput(paper_compiled, paper_setup):
    """The acceptance criterion: per-RTT re-spray under congestion
    feedback must beat static spraying's mean goodput under the
    reordering-intolerant roce-nack transport on the committed
    saturating scenario — the balance win has to outweigh the
    re-spray reordering tax it is charged."""
    from repro.core import AdaptiveSpraying
    _, _, flows = paper_setup
    seeds = np.arange(8)
    static = throughput_from_result(
        simulate_paths(paper_compiled, flows, seeds,
                       spec=SimSpec(strategy=PrimeSpraying(8))),
        transport="roce-nack")
    adaptive = throughput_from_result(
        simulate_paths(paper_compiled, flows, seeds,
                       spec=SimSpec(strategy=AdaptiveSpraying(8))),
        transport="roce-nack")
    assert adaptive.goodput.mean() > static.goodput.mean()
    # the adaptation really moved flowlets and really paid for it
    res = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=AdaptiveSpraying(8)))
    assert res.extra_exposure is not None and res.extra_exposure.max() > 0


def test_adaptive_charges_respray_exposure(paper_compiled,
                                           paper_setup_small):
    """Each accepted move costs ``respray_cost`` x flowlet demand: the
    same routed tensor under a doubled cost parameter reports exactly
    doubled extra exposure, and goodput can only go down."""
    from repro.core import AdaptiveSpraying
    _, _, flows = paper_setup_small
    seeds = np.arange(4)
    cheap = simulate_paths(paper_compiled, flows, seeds,
                           spec=SimSpec(strategy=AdaptiveSpraying(
                               8, respray_cost=0.05)))
    dear = simulate_paths(paper_compiled, flows, seeds,
                          spec=SimSpec(strategy=AdaptiveSpraying(
                              8, respray_cost=0.10)))
    np.testing.assert_array_equal(cheap.link_ids, dear.link_ids)
    np.testing.assert_allclose(dear.extra_exposure,
                               2.0 * cheap.extra_exposure)
    g_cheap = throughput_from_result(cheap, transport="roce-nack")
    g_dear = throughput_from_result(dear, transport="roce-nack")
    assert g_dear.goodput.mean() <= g_cheap.goodput.mean()


# ---------------------------------------------------------------------------
# weighted max-min: differential vs a scalar weighted reference
# ---------------------------------------------------------------------------


@given(st.integers(2, 6), st.integers(2, 8), st.integers(0, 2**31))
@settings(max_examples=15, deadline=None)
def test_weighted_fill_matches_scalar_reference(n_links, n_flows, rngseed):
    rng = np.random.default_rng(rngseed)
    caps = rng.uniform(10.0, 1000.0, n_links)
    n_hops = min(3, n_links)
    ids = rng.integers(0, n_links, (n_hops, n_flows, 2)).astype(np.int32)
    ids[n_hops - 1, rng.integers(0, n_flows, 2), 0] = -1   # short paths
    # weights from exact and inexact binary fractions alike
    weights = rng.choice([0.125, 0.25, 1 / 3, 0.5, 1.0, 2.0], n_flows)
    rates = batched_max_min(ids, caps, weights=weights)
    for s in range(2):
        paths = {}
        for j in range(n_flows):
            hop_ids = [int(i) for i in ids[:, j, s] if i >= 0]
            paths[j] = list(dict.fromkeys(hop_ids))
        ref = weighted_max_min_ref(paths, list(caps),
                                   {j: weights[j] for j in range(n_flows)})
        for j in range(n_flows):
            if np.isinf(ref[j]):
                assert np.isinf(rates[j, s])
            else:
                assert rates[j, s] == pytest.approx(ref[j], rel=1e-9), (
                    f"flow {j} seed {s}")


@given(st.integers(2, 8), st.integers(0, 2**31))
@settings(max_examples=10, deadline=None)
def test_coincident_flowlets_share_like_parent(k, rngseed):
    """K flowlets of one flow on the *same* path with demand 1/K must
    aggregate to exactly the unweighted single-flow allocation."""
    rng = np.random.default_rng(rngseed)
    n_links, n_flows, n_hops = 5, 6, 3
    caps = rng.uniform(50.0, 500.0, n_links)
    ids = rng.integers(0, n_links, (n_hops, n_flows, 1)).astype(np.int32)
    base = batched_max_min(ids, caps)
    split = np.repeat(ids, k, axis=1)
    weights = np.full(n_flows * k, 1.0 / k)
    flowlet = batched_max_min(split, caps, weights=weights)
    parent = flowlet.reshape(n_flows, k).sum(axis=1)
    np.testing.assert_allclose(parent, base[:, 0], rtol=1e-9)


def test_weighted_fill_validation():
    ids = np.zeros((1, 2, 1), np.int32)
    caps = np.array([100.0])
    with pytest.raises(ValueError, match="weights"):
        batched_max_min(ids, caps, weights=np.ones(3))
    with pytest.raises(ValueError, match="positive"):
        batched_max_min(ids, caps, weights=np.array([1.0, 0.0]))
    # all-ones weights take the exact unweighted path
    np.testing.assert_array_equal(
        batched_max_min(ids, caps, weights=np.ones(2)),
        batched_max_min(ids, caps))


def test_weighted_zero_link_flowlet_inf():
    ids = np.array([[[0], [-1]]], np.int32)
    rates = batched_max_min(ids, np.array([100.0]),
                            weights=np.array([0.5, 0.5]))
    # alone on the link: weighted max-min still grants the full capacity
    assert rates[0, 0] == pytest.approx(100.0)
    assert np.isinf(rates[1, 0])


def test_weighted_contention_splits_proportionally():
    ids = np.zeros((1, 2, 1), np.int32)           # both flows on link 0
    rates = batched_max_min(ids, np.array([100.0]),
                            weights=np.array([0.25, 0.75]))
    assert rates[0, 0] == pytest.approx(25.0)
    assert rates[1, 0] == pytest.approx(75.0)


# ---------------------------------------------------------------------------
# flowlet -> parent aggregation + Monte-Carlo front ends
# ---------------------------------------------------------------------------


def test_flow_rates_from_flowlets_unsorted_fallback(paper_compiled,
                                                    paper_setup):
    _, _, flows = paper_setup
    res = simulate_paths(paper_compiled, flows[:8], [0, 1],
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=2)))
    rates = np.arange(res.num_flowlets * 2, dtype=np.float64).reshape(
        res.num_flowlets, 2)
    sorted_sum = flow_rates_from_flowlets(res, rates)      # reduceat path
    perm = np.random.default_rng(0).permutation(res.num_flowlets)
    res.flow_index = res.flow_index[perm]
    got = flow_rates_from_flowlets(res, rates[perm])       # scatter path
    np.testing.assert_allclose(got, sorted_sum)


def test_throughput_from_result_multipath(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    res = simulate_paths(paper_compiled, flows, np.arange(8),
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=4)))
    tp = throughput_from_result(res)
    assert tp.rates.shape == (len(flows), 8)
    assert tp.per_pair.shape == (16, 8)
    assert (tp.rates > 0).all()
    assert tp.per_pair.max() <= LINE_RATE + 1e-6


def test_monte_carlo_front_ends_accept_strategy(paper_compiled, paper_setup):
    _, wl, _ = paper_setup
    mc = monte_carlo_fim(paper_compiled, wl, np.arange(8),
                         spec=SimSpec(strategy="prime-spray"))
    assert mc.aggregate.shape == (8,)
    assert (mc.aggregate >= 0).all()
    tp = monte_carlo_throughput(paper_compiled, wl, np.arange(4),
                                spec=SimSpec(strategy="congestion-aware"))
    assert tp.rates.shape == (256, 4)


def test_weighted_fim_counts_comparable_across_strategies(paper_compiled,
                                                          paper_setup):
    """Demand weighting keeps total per-layer load equal across
    strategies, so FIM differences are imbalance, not volume."""
    _, _, flows = paper_setup
    seeds = [0, 1]
    a = simulate_paths(paper_compiled, flows, seeds)
    b = simulate_paths(paper_compiled, flows, seeds,
                       spec=SimSpec(strategy=PrimeSpraying(flowlets=8)))
    ca, cb = a.link_flow_counts(), b.link_flow_counts()
    lid = paper_compiled.link_layer
    for layer in range(len(paper_compiled.layer_names)):
        sel = np.flatnonzero(lid == layer)
        np.testing.assert_allclose(ca[:, sel].sum(axis=1),
                                   cb[:, sel].sum(axis=1), rtol=1e-9)

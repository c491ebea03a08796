"""Reordering-cost subsystem tests.

Contract coverage: single-path strategies have exactly zero out-of-order
exposure (so their goodput is bit-identical to their max-min rates under
ANY transport); ``K=1`` spraying and ``min_bytes=inf`` demand-aware
spraying are bit-identical to ECMP end-to-end *including*
``effective_goodput``; the efficiency model is monotone (more skew or
more rate dispersion can never raise efficiency; the ideal profile is
exactly 1.0); and on the committed LLM scenario the acceptance-criterion
regime holds directionally — full spraying keeps its byte-FIM win but
pays a measurable goodput penalty under a reordering-intolerant
transport, and elephant-only spraying recovers most of it at near-spray
byte-FIM."""

import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import (
    ELEPHANT_MIN_BYTES, IDEAL, ROCE_NACK, STRACK, PrimeSpraying, SimSpec,
    TransportProfile, available_transports, fim_vector, flowlet_exposure,
    monte_carlo_throughput, paper_testbed_llm_workload, reordering_efficiency,
    resolve_strategy, resolve_transport, simulate_paths,
    throughput_from_result,
)
from repro.core.vector_sim import VectorTraceResult


# ---------------------------------------------------------------------------
# transport profile registry
# ---------------------------------------------------------------------------


def test_transport_registry():
    assert {"ideal", "roce-nack", "strack"} <= set(available_transports())
    assert resolve_transport(None) is IDEAL
    assert resolve_transport("roce-nack") is ROCE_NACK
    assert resolve_transport(STRACK) is STRACK
    with pytest.raises(ValueError, match="unknown transport"):
        resolve_transport("tcp-reno")
    with pytest.raises(TypeError):
        resolve_transport(3.5)


def test_transport_profile_validation():
    with pytest.raises(ValueError, match="alpha"):
        TransportProfile("bad", alpha=-1.0, floor=0.5)
    with pytest.raises(ValueError, match="floor"):
        TransportProfile("bad", alpha=1.0, floor=0.0)
    with pytest.raises(ValueError, match="floor"):
        TransportProfile("bad", alpha=1.0, floor=1.5)


def test_unknown_transport_error_lists_registry():
    with pytest.raises(ValueError) as exc:
        resolve_transport("tcp-reno")
    msg = str(exc.value)
    assert str(available_transports()) in msg      # sorted listing


def test_duplicate_transport_registration_raises():
    from repro.core import register_transport
    probe = TransportProfile("dup-transport", alpha=1.0, floor=0.5)
    register_transport(probe)
    try:
        with pytest.raises(ValueError, match="already registered"):
            register_transport(
                TransportProfile("dup-transport", alpha=2.0, floor=0.5))
        # the published anchors are protected, and replace=True is explicit
        with pytest.raises(ValueError, match="'roce-nack'"):
            register_transport(
                TransportProfile("roce-nack", alpha=9.0, floor=0.5))
        register_transport(probe, replace=True)
    finally:
        from repro.core.reordering import _TRANSPORTS
        _TRANSPORTS.pop("dup-transport", None)


# ---------------------------------------------------------------------------
# transport calibration against published anchor curves
# ---------------------------------------------------------------------------


def test_calibrated_profiles_reproduce_anchors():
    """``roce-nack`` / ``strack`` are no longer stylized constants: the
    committed profiles must pass through their documented anchor points
    (STrack's goodput-vs-reordering curve; IRN's go-back-N collapse)
    within a tolerance commensurate with a 2-parameter model."""
    from repro.core import ROCE_NACK_ANCHORS, STRACK_ANCHORS
    for profile, anchors, tol in ((ROCE_NACK, ROCE_NACK_ANCHORS, 0.08),
                                  (STRACK, STRACK_ANCHORS, 0.02)):
        for x, y in anchors:
            eff = float(reordering_efficiency(np.array([x]), profile)[0])
            assert abs(eff - y) <= tol, (profile.name, x, eff, y)
    # the qualitative ordering the suite's directional tests rely on
    assert ROCE_NACK.floor < STRACK.floor
    assert ROCE_NACK.alpha > STRACK.alpha


def test_calibrate_transport_exact_recovery():
    """Anchors sampled from a model instance are recovered (alpha on the
    grid, floor in closed form) — the fit is deterministic and exact up
    to grid resolution."""
    from repro.core import calibrate_transport
    truth = TransportProfile("truth", alpha=2.0, floor=0.4)
    xs = (0.3, 0.7, 1.5, 3.0)
    anchors = [(x, float(reordering_efficiency(np.array([x]), truth)[0]))
               for x in xs]
    fit = calibrate_transport("refit", anchors)
    assert abs(fit.alpha - truth.alpha) / truth.alpha < 0.01
    assert abs(fit.floor - truth.floor) < 0.01
    # identical inputs -> identical constants (no RNG anywhere)
    again = calibrate_transport("refit", anchors)
    assert (fit.alpha, fit.floor) == (again.alpha, again.floor)


def test_calibrate_transport_validation():
    from repro.core import calibrate_transport
    with pytest.raises(ValueError, match=">= 2 anchor"):
        calibrate_transport("x", [(1.0, 0.5)])
    with pytest.raises(ValueError, match="exposure must be > 0"):
        calibrate_transport("x", [(0.0, 0.5), (1.0, 0.4)])
    with pytest.raises(ValueError, match="efficiency must be in"):
        calibrate_transport("x", [(0.5, 1.0), (1.0, 0.4)])


# ---------------------------------------------------------------------------
# efficiency model: bounds + monotonicity
# ---------------------------------------------------------------------------


@given(st.floats(0.0, 50.0), st.floats(0.0, 50.0))
@settings(max_examples=50, deadline=None)
def test_efficiency_monotone_in_exposure(a, b):
    """More exposure can never mean higher efficiency, for any profile."""
    lo, hi = sorted((a, b))
    for profile in (IDEAL, ROCE_NACK, STRACK,
                    TransportProfile("custom", alpha=1.7, floor=0.4)):
        e_lo, e_hi = reordering_efficiency(
            np.array([lo, hi]), profile)
        assert e_hi <= e_lo
        assert profile.floor <= e_hi <= e_lo <= 1.0


def test_ideal_profile_is_exactly_one():
    exposure = np.array([0.0, 0.3, 2.0, 50.0])
    np.testing.assert_array_equal(
        reordering_efficiency(exposure, "ideal"), 1.0)


def test_zero_exposure_is_exactly_one_for_all_profiles():
    """expm1(-0) == 0, so unexposed flows keep bitwise-identical goodput
    under every profile — the keystone of the K=1 == ECMP guarantee."""
    z = np.zeros(4)
    for name in available_transports():
        np.testing.assert_array_equal(reordering_efficiency(z, name), 1.0)


def test_efficiency_rejects_negative_exposure():
    with pytest.raises(ValueError, match="non-negative"):
        reordering_efficiency(np.array([-0.1]), "strack")


# ---------------------------------------------------------------------------
# exposure: zero for single-path, monotone in skew and dispersion
# ---------------------------------------------------------------------------


def test_single_path_strategies_zero_exposure(paper_compiled,
                                              paper_setup_small):
    _, _, flows = paper_setup_small
    for strategy in (None, "ecmp", "congestion-aware"):
        res = simulate_paths(paper_compiled, flows, [0, 5],
                             spec=SimSpec(strategy=strategy))
        np.testing.assert_array_equal(flowlet_exposure(res), 0.0)


_SMALL = {}


def _small_compiled_and_flows():
    """Tiny compiled testbed + a one-flow table for synthetic tensors
    (module-cached; property tests can't take session fixtures)."""
    if not _SMALL:
        from repro.core import (
            bipartite_pairs, build_paper_testbed, compile_fabric, nic_ip,
            server_name, synthesize_flows,
        )
        comp = compile_fabric(build_paper_testbed(servers_per_rack=2))
        wl = bipartite_pairs([server_name(0)], [server_name(2)],
                             flows_per_pair=1)
        flows = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
        _SMALL["comp"], _SMALL["flows"] = comp, flows
    return _SMALL["comp"], _SMALL["flows"]


def _synthetic_two_flowlets(hops_a, hops_b):
    """One flow, two flowlets of the given path lengths; link ids are
    arbitrary — exposure reads only the -1 structure when rates are
    supplied."""
    comp, flows = _small_compiled_and_flows()
    h = max(hops_a, hops_b, 1)
    ids = np.full((h, 2, 1), -1, np.int32)
    ids[:hops_a, 0, 0] = np.arange(hops_a)
    ids[:hops_b, 1, 0] = np.arange(hops_b)
    return VectorTraceResult(
        compiled=comp, flows=flows[:1], seeds=np.zeros(1, np.uint64),
        link_ids=ids, flow_index=np.zeros(2, np.int32),
        demand=np.full(2, 0.5), strategy="prime-spray")


@given(st.integers(1, 6), st.integers(0, 6), st.integers(0, 6))
@settings(max_examples=20, deadline=None)
def test_exposure_monotone_in_path_skew(base, da, db):
    """Longer relative path-length spread between a flow's flowlets must
    never lower exposure (equal-rate flowlets isolate the skew term)."""
    lo, hi = sorted((da, db))
    rates = np.full((2, 1), 10.0)
    x_lo = flowlet_exposure(_synthetic_two_flowlets(base, base + lo),
                            rates)[0, 0]
    x_hi = flowlet_exposure(_synthetic_two_flowlets(base, base + hi),
                            rates)[0, 0]
    assert x_hi >= x_lo
    eff_lo = reordering_efficiency(np.array([x_lo]), "roce-nack")[0]
    eff_hi = reordering_efficiency(np.array([x_hi]), "roce-nack")[0]
    assert eff_hi <= eff_lo
    if hi == 0:
        assert x_hi == 0.0


@given(st.floats(0.0, 1.0), st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_exposure_monotone_in_rate_dispersion(a, b):
    """A slower slowest-flowlet (relative to the fastest) must never
    lower exposure (equal-hop flowlets isolate the dispersion term)."""
    res = _synthetic_two_flowlets(3, 3)
    lo, hi = sorted((a, b))
    x_lo = flowlet_exposure(
        res, np.array([[10.0], [10.0 * (1.0 - lo * 0.99)]]))[0, 0]
    x_hi = flowlet_exposure(
        res, np.array([[10.0], [10.0 * (1.0 - hi * 0.99)]]))[0, 0]
    assert x_hi >= x_lo


def test_exposure_ignores_infinite_rate_flowlets():
    res = _synthetic_two_flowlets(3, 3)
    # one link-free flowlet (inf rate): dispersion must not blow up
    x = flowlet_exposure(res, np.array([[10.0], [np.inf]]))[0, 0]
    assert np.isfinite(x)
    # all flowlets link-free: nothing disperses at all
    x2 = flowlet_exposure(res, np.array([[np.inf], [np.inf]]))[0, 0]
    assert x2 == 0.0


# ---------------------------------------------------------------------------
# end-to-end bit-identity: K=1 spray / min_bytes=inf == ECMP incl. goodput
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", [
    PrimeSpraying(flowlets=1),
    PrimeSpraying(flowlets=8, min_bytes=float("inf")),
    PrimeSpraying(flowlets=8, min_bytes=float("inf"), volume_k=True),
])
def test_degenerate_spray_bit_identical_to_ecmp(paper_compiled, paper_setup,
                                                strategy):
    _, _, flows = paper_setup
    seeds = [0, 42, 2**33]
    base = simulate_paths(paper_compiled, flows, seeds)
    res = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=strategy))
    np.testing.assert_array_equal(res.link_ids, base.link_ids)
    assert not res.is_multipath
    assert (res.demand == 1.0).all()
    for transport in (None, "roce-nack", "strack"):
        tp_b = throughput_from_result(base, transport=transport)
        tp_r = throughput_from_result(res, transport=transport)
        np.testing.assert_array_equal(tp_r.rates, tp_b.rates)
        np.testing.assert_array_equal(tp_r.goodput, tp_b.goodput)
        np.testing.assert_array_equal(tp_r.goodput, tp_r.rates)
        np.testing.assert_array_equal(tp_r.efficiency, 1.0)
        np.testing.assert_array_equal(tp_r.exposure, 0.0)


def test_ideal_transport_goodput_is_rates_even_when_sprayed(paper_compiled,
                                                            paper_setup_small):
    _, _, flows = paper_setup_small
    res = simulate_paths(paper_compiled, flows, [0, 3],
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=4)))
    tp = throughput_from_result(res)            # default: ideal
    assert tp.transport == "ideal"
    np.testing.assert_array_equal(tp.goodput, tp.rates)
    assert tp.goodput is not tp.rates           # never an alias
    np.testing.assert_array_equal(tp.efficiency, 1.0)
    # the exposure pass is skipped under a free transport (pre-reordering
    # cost for pre-reordering callers); a lossy profile reports it
    np.testing.assert_array_equal(tp.exposure, 0.0)
    lossy = throughput_from_result(res, transport="strack")
    assert (lossy.exposure > 0).any()
    np.testing.assert_array_equal(lossy.rates, tp.rates)


# ---------------------------------------------------------------------------
# demand-aware (elephant-only) spraying
# ---------------------------------------------------------------------------


def test_flowlet_counts_policies():
    from repro.core.flows import FiveTuple, Flow

    def f(b):
        return Flow(0, "a", "b",
                    FiveTuple("10.0.0.0", "10.1.0.0", 1, 2, 17), bytes=b)

    flows = [f(0), f(10), f(100), f(1000)]
    np.testing.assert_array_equal(
        PrimeSpraying(flowlets=8).flowlet_counts(flows), 8)
    np.testing.assert_array_equal(
        PrimeSpraying(flowlets=8, min_bytes=100).flowlet_counts(flows),
        [1, 1, 8, 8])
    np.testing.assert_array_equal(
        PrimeSpraying(flowlets=8, min_bytes=100,
                      volume_k=True).flowlet_counts(flows),
        [1, 1, 1, 8])
    # ceil semantics: anything over one min_bytes chunk splits
    np.testing.assert_array_equal(
        PrimeSpraying(flowlets=8, min_bytes=300,
                      volume_k=True).flowlet_counts(flows),
        [1, 1, 1, 4])
    np.testing.assert_array_equal(
        PrimeSpraying(flowlets=8, min_bytes=99,
                      volume_k=True).flowlet_counts(flows),
        [1, 1, 2, 8])
    np.testing.assert_array_equal(
        PrimeSpraying(flowlets=8,
                      min_bytes=float("inf")).flowlet_counts(flows), 1)


def test_prime_spray_param_validation():
    with pytest.raises(ValueError, match="min_bytes"):
        PrimeSpraying(flowlets=8, min_bytes=0)
    with pytest.raises(ValueError, match="volume_k"):
        PrimeSpraying(flowlets=8, volume_k=True)


def test_elephant_spray_warns_on_volume_less_workload(paper_compiled,
                                                      paper_setup_small):
    """A finite min_bytes against a workload that never set Flow.bytes
    sprays nothing — that silent ECMP degenerate must be called out."""
    _, _, flows = paper_setup_small        # bipartite flows: bytes == 0
    with pytest.warns(UserWarning, match="no flow\\s+sprays"):
        res = simulate_paths(paper_compiled, flows[:8], [0],
                             spec=SimSpec(strategy="prime-spray-elephant"))
    assert not res.is_multipath
    # min_bytes=inf is the *intentional* ECMP degenerate: no warning
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        simulate_paths(paper_compiled, flows[:8], [0],
                       spec=SimSpec(strategy=PrimeSpraying(
                           flowlets=8, min_bytes=float("inf"))))


def test_elephant_registry_entry():
    s = resolve_strategy("prime-spray-elephant")
    assert isinstance(s, PrimeSpraying)
    assert s.min_bytes == ELEPHANT_MIN_BYTES
    assert s.volume_k


def test_mixed_spray_demand_fractions_and_layout(paper_compiled):
    wl, flows, _ = paper_testbed_llm_workload()
    strat = PrimeSpraying(flowlets=8, min_bytes=ELEPHANT_MIN_BYTES,
                          volume_k=True)
    res = simulate_paths(paper_compiled, flows, [3],
                         spec=SimSpec(strategy=strat))
    k_f = strat.flowlet_counts(flows)
    assert res.num_flowlets == int(k_f.sum())
    assert (k_f == 1).any() and (k_f > 1).any()   # genuinely mixed
    np.testing.assert_array_equal(
        res.flow_index, np.repeat(np.arange(len(flows)), k_f))
    per_flow = np.bincount(res.flow_index, weights=res.demand,
                           minlength=len(flows))
    np.testing.assert_allclose(per_flow, 1.0)


def test_mixed_spray_mice_keep_exact_ecmp_paths(paper_compiled):
    """Unsprayed flows of a demand-aware spray walk without entropy
    columns, so they stay bit-identical to ECMP flow by flow."""
    wl, flows, _ = paper_testbed_llm_workload()
    seeds = [0, 17]
    strat = PrimeSpraying(flowlets=8, min_bytes=ELEPHANT_MIN_BYTES)
    res = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(strategy=strat))
    base = simulate_paths(paper_compiled, flows, seeds)
    k_f = strat.flowlet_counts(flows)
    mice = np.flatnonzero(k_f == 1)
    assert mice.size                               # scenario has mice
    cols = np.flatnonzero(np.isin(res.flow_index, mice))
    h = base.link_ids.shape[0]
    got = res.link_ids[:, cols]
    np.testing.assert_array_equal(got[:h], base.link_ids[:, mice])
    assert (got[h:] == -1).all()


# ---------------------------------------------------------------------------
# the acceptance-criterion regime, directionally, at test scale
# ---------------------------------------------------------------------------


def test_spray_tax_and_elephant_recovery(paper_compiled):
    """Full spraying keeps its byte-FIM win but pays a measurable goodput
    penalty under roce-nack; elephant-only spraying holds near-spray
    byte-FIM while recovering most of the penalty (its mice never leave
    their ECMP paths)."""
    wl, flows, _ = paper_testbed_llm_workload()
    seeds = np.arange(8)
    elephant = PrimeSpraying(flowlets=8, min_bytes=ELEPHANT_MIN_BYTES,
                             volume_k=True)
    byte_fim = {}
    tp = {}
    for tag, strat in (("ecmp", None), ("spray", PrimeSpraying(flowlets=8)),
                       ("elephant", elephant)):
        byte_fim[tag] = fim_vector(
            simulate_paths(paper_compiled, flows, seeds,
                           spec=SimSpec(strategy=strat,
                                        demand_mode="bytes"))).mean()
        tp[tag] = throughput_from_result(
            simulate_paths(paper_compiled, flows, seeds,
                           spec=SimSpec(strategy=strat)),
            transport="roce-nack")
    g = {tag: t.goodput.mean() for tag, t in tp.items()}
    # ECMP pays nothing; spraying keeps its byte-FIM win...
    np.testing.assert_array_equal(tp["ecmp"].goodput, tp["ecmp"].rates)
    assert byte_fim["spray"] < byte_fim["ecmp"] - 10.0
    # ...but pays a measurable goodput tax (>10% of ECMP's goodput)
    assert g["spray"] < 0.9 * g["ecmp"]
    assert tp["spray"].rates.mean() > g["spray"]
    # elephant-only: near-spray byte-FIM (well below ECMP), most of the
    # goodput recovered
    assert byte_fim["elephant"] < byte_fim["ecmp"] - 10.0
    assert byte_fim["elephant"] < byte_fim["spray"] + 10.0
    assert g["elephant"] > g["spray"] + 0.3 * (g["ecmp"] - g["spray"])


def test_monte_carlo_front_end_threads_transport(paper_compiled):
    wl, flows, _ = paper_testbed_llm_workload()
    mc = monte_carlo_throughput(paper_compiled, flows, np.arange(4),
                                spec=SimSpec(strategy="prime-spray-elephant",
                                             transport="strack"))
    assert mc.transport == "strack"
    assert mc.goodput.shape == mc.rates.shape == (len(flows), 4)
    assert (mc.goodput <= mc.rates + 1e-12).all()
    assert "flow_goodput" in mc.summary()

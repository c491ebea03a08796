"""Differential tests: the vectorized engine must reproduce the hop-by-hop
``FlowTracer`` + ``EcmpRouting`` **exactly** — same paths, same link
loads, same FIM — across fabric shapes, hash-field modes, and seeds.
This is the contract that makes Monte-Carlo results from ``vector_sim``
statements about the real (traced) routing behaviour."""

import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import (
    FIELDS_5TUPLE, FIELDS_IP_PAIR, FIELDS_VXLAN, EcmpRouting, FlowTracer,
    SimSpec, bipartite_pairs, build_multipod_fabric, build_paper_testbed,
    compile_fabric, ecmp_hash, fim, fim_from_counts, fim_vector,
    flow_fields_matrix, flow_hash_fields, link_flow_counts, monte_carlo_fim,
    nic_ip, per_layer_fim, server_name, simulate_paths, synthesize_flows,
)
from repro.core.vector_sim import ecmp_hash_vec

MODES = [FIELDS_5TUPLE, FIELDS_VXLAN, FIELDS_IP_PAIR]


def _tracer_paths(fab, wl, flows, seed, mode):
    res = FlowTracer(fab, EcmpRouting(fab, seed=seed, fields=mode),
                     wl, flows).trace()
    return {k: [l.name for l in v] for k, v in res.paths.items()}


def _vector_paths(result, seed_index):
    return {k: [l.name for l in v]
            for k, v in result.paths_for_seed(seed_index).items()}


# ---------------------------------------------------------------------------
# hash primitives
# ---------------------------------------------------------------------------


@given(st.integers(0, 2**63 - 1), st.integers(0, 2**32 - 1),
       st.integers(0, 2**32 - 1))
@settings(max_examples=50, deadline=None)
def test_ecmp_hash_vec_matches_scalar(seed, f0, f1):
    fields = np.array([[f0, f1]], np.uint64)
    seeds = np.array([seed], np.uint64)
    got = int(ecmp_hash_vec(fields, seeds[None, :])[0, 0])
    assert got == ecmp_hash([f0, f1], seed)


def test_flow_fields_matrix_matches_scalar(paper_setup):
    _, _, flows = paper_setup
    for mode in MODES:
        mat = flow_fields_matrix(flows, mode)
        for j, f in enumerate(flows):
            assert mat[j].tolist() == flow_hash_fields(f, mode)


# ---------------------------------------------------------------------------
# path / load / FIM identity on the paper testbed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_paths_identical_paper_testbed(paper_setup, paper_compiled, mode):
    fab, wl, flows = paper_setup
    seeds = [0, 7, 1234567, 2**40 + 17]
    res = simulate_paths(paper_compiled, flows, seeds,
                         spec=SimSpec(fields=mode))
    for i, seed in enumerate(seeds):
        assert _vector_paths(res, i) == _tracer_paths(fab, wl, flows, seed, mode)


def test_link_counts_and_fim_identical(paper_setup, paper_compiled):
    fab, wl, flows = paper_setup
    seeds = [3, 99]
    res = simulate_paths(paper_compiled, flows, seeds)
    counts = res.link_flow_counts()
    agg, per_layer = fim_from_counts(counts, paper_compiled)
    for i, seed in enumerate(seeds):
        tr = FlowTracer(fab, EcmpRouting(fab, seed=seed), wl, flows).trace()
        dict_counts = link_flow_counts(tr.paths)
        for lid, link in enumerate(paper_compiled.links):
            assert counts[i, lid] == dict_counts.get(link.name, 0)
        assert agg[i] == pytest.approx(fim(tr.paths, fab), rel=1e-12)
        for layer, (val, _n) in per_layer_fim(tr.paths, fab).items():
            assert per_layer[layer][i] == pytest.approx(val, rel=1e-12)


def test_only_used_leaves_identical(multipod_small):
    """Partial workloads leave idle leaves; the per-seed used-device
    restriction must match the dict implementation."""
    fab, wl, flows = multipod_small
    flows = flows[: len(flows) // 2]
    comp = compile_fabric(fab)
    seeds = [0, 11]
    res = simulate_paths(comp, flows, seeds)
    agg, per_layer = fim_from_counts(res.link_flow_counts(), comp,
                                     only_used_leaves=True)
    for i, seed in enumerate(seeds):
        wl_half = wl
        tr = FlowTracer(fab, EcmpRouting(fab, seed=seed), wl_half, flows).trace()
        assert agg[i] == pytest.approx(
            fim(tr.paths, fab, only_used_leaves=True), rel=1e-12)
        for layer, (val, _n) in per_layer_fim(
                tr.paths, fab, only_used_leaves=True).items():
            assert per_layer[layer][i] == pytest.approx(val, rel=1e-12)


# ---------------------------------------------------------------------------
# randomized fabric shapes (property test)
# ---------------------------------------------------------------------------


@given(st.integers(1, 3), st.integers(2, 6), st.integers(1, 3),
       st.integers(0, 2**31), st.sampled_from(MODES))
@settings(max_examples=8, deadline=None)
def test_random_shapes_identical(spines, links_per, flows_per_pair, seed, mode):
    fab = build_paper_testbed(num_spines=spines,
                              links_per_leaf_spine=links_per,
                              servers_per_rack=4)
    rack0 = [server_name(i) for i in range(4)]
    rack1 = [server_name(4 + i) for i in range(4)]
    wl = bipartite_pairs(rack0, rack1, flows_per_pair=flows_per_pair)
    flows = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
    res = simulate_paths(fab, flows, [seed], spec=SimSpec(fields=mode))
    assert _vector_paths(res, 0) == _tracer_paths(fab, wl, flows, seed, mode)


@given(st.integers(2, 3), st.integers(2, 4), st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_multipod_shapes_identical(pods, leaves_per_pod, seed):
    fab = build_multipod_fabric(num_pods=pods, hosts_per_pod=4,
                                leaves_per_pod=leaves_per_pod, num_spines=4)
    pod0 = [f"host-{i}" for i in range(4)]
    pod1 = [f"host-{4 + i}" for i in range(4)]
    wl = bipartite_pairs(pod0, pod1, flows_per_pair=2)
    flows = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=1)
    res = simulate_paths(fab, flows, [seed])
    assert _vector_paths(res, 0) == _tracer_paths(fab, wl, flows, seed,
                                                  FIELDS_5TUPLE)


# ---------------------------------------------------------------------------
# monte_carlo front end + murmur backend
# ---------------------------------------------------------------------------


def test_monte_carlo_fim_from_workload(paper_compiled, paper_setup):
    _, wl, flows = paper_setup
    mc = monte_carlo_fim(paper_compiled, wl, np.arange(64))
    assert mc.aggregate.shape == (64,)
    assert set(mc.per_layer) == {"host-to-leaf", "leaf-to-spine",
                                 "spine-to-leaf", "leaf-to-host"}
    # the paper's regime: substantial expected imbalance, strictly positive
    assert 15.0 < mc.aggregate.mean() < 60.0
    assert (mc.aggregate >= 0).all()
    s = mc.summary()
    assert s["aggregate"]["min"] <= s["aggregate"]["p50"] <= s["aggregate"]["max"]
    # workload synthesis inside monte_carlo_fim == explicit flow list
    mc2 = monte_carlo_fim(paper_compiled, flows, np.arange(64))
    np.testing.assert_allclose(mc.aggregate, mc2.aggregate)


def test_murmur_backend_valid_and_statistically_similar(paper_compiled,
                                                        paper_setup):
    fab, wl, flows = paper_setup
    res = simulate_paths(paper_compiled, flows, np.arange(16),
                         spec=SimSpec(hash_backend="murmur"))
    # topologically valid chains ending at the right host
    paths = res.paths_for_seed(0)
    by_id = {f.flow_id: f for f in flows}
    for fid, path in paths.items():
        assert path[0].src == by_id[fid].src
        assert path[-1].dst == by_id[fid].dst
        for a, b in zip(path, path[1:]):
            assert a.dst == b.src
    # same imbalance regime as the exact hash (both uniform avalanches)
    exact = fim_vector(simulate_paths(paper_compiled, flows, np.arange(16)))
    murmur = fim_vector(res)
    assert abs(exact.mean() - murmur.mean()) < 12.0


def test_unknown_backend_raises(paper_compiled, paper_setup):
    _, _, flows = paper_setup
    with pytest.raises(ValueError):
        simulate_paths(paper_compiled, flows[:4], [0],
                       spec=SimSpec(hash_backend="xxh3"))


def test_sparse_nic_numbering_resolves_flows():
    """A fabric whose servers expose non-contiguous NIC indices (here 0
    and 4, as on a half-populated host) must synthesize workload traffic
    on exactly the recorded NICs — inferring ``range(max + 1)`` would
    invent link-less NICs 1-3 and either crash the walk or route ghost
    traffic."""
    import dataclasses as _dc

    from repro.core import monte_carlo_fim, resolve_flows
    from repro.core.fabric import build_paper_testbed as _build

    fab = _build()
    links = [
        _dc.replace(ln,
                    src_port=ln.src_port.replace("nic1p", "nic4p"),
                    dst_port=ln.dst_port.replace("nic1p", "nic4p"))
        for ln in fab.links
    ]
    from repro.core.fabric import Fabric
    sparse = Fabric(list(fab.devices.values()), links)
    comp = compile_fabric(sparse)
    assert comp.nic_indices == (0, 4)

    rack0 = [server_name(i) for i in range(8)]
    rack1 = [server_name(8 + i) for i in range(8)]
    wl = bipartite_pairs(rack0, rack1, flows_per_pair=4)
    flows = resolve_flows(comp, wl)
    used = {int(f.tuple5.src_ip.split(".")[1]) for f in flows}
    assert used == {0, 4}
    mc = monte_carlo_fim(comp, wl, [0, 1, 2])
    assert mc.aggregate.shape == (3,)


# ---------------------------------------------------------------------------
# SimSpec: the unified front-end contract
# ---------------------------------------------------------------------------


LEGACY_KWARGS = ("fields", "hash_backend", "strategy", "demand_mode",
                 "engine", "transport", "timing", "max_hops")


def _front_end(name, comp, flows, seeds):
    """(front-end function, call taking only keyword arguments) for one
    of the four front ends on the testbed; the timeline runs the LLM
    schedule's labelled flows."""
    from repro.core import (
        monte_carlo_throughput, paper_testbed_llm_schedule,
        simulate_timeline,
    )
    if name == "simulate_timeline":
        _, lflows, _, sched = paper_testbed_llm_schedule()
        return simulate_timeline, lambda **kw: simulate_timeline(
            comp, lflows, sched, seeds, **kw)
    fn = {"simulate_paths": simulate_paths,
          "monte_carlo_fim": monte_carlo_fim,
          "monte_carlo_throughput": monte_carlo_throughput}[name]
    return fn, lambda **kw: fn(comp, flows, seeds, **kw)


def _answers(out) -> dict[str, np.ndarray]:
    """Every array a front end's result carries, per-layer dicts
    flattened, by name."""
    got = {}
    for k, v in vars(out).items():
        if isinstance(v, np.ndarray):
            got[k] = v
        elif isinstance(v, dict):
            got.update({f"{k}:{n}": a for n, a in v.items()
                        if isinstance(a, np.ndarray)})
    return got


@pytest.mark.parametrize("front", ["simulate_paths", "monte_carlo_fim",
                                   "monte_carlo_throughput",
                                   "simulate_timeline"])
def test_front_end_takes_only_a_spec(paper_compiled, paper_setup, front):
    import inspect
    _, _, flows = paper_setup
    fn, call = _front_end(front, paper_compiled, flows, [0, 7])
    assert not set(inspect.signature(fn).parameters) & set(LEGACY_KWARGS)
    with pytest.raises(TypeError, match="engine"):
        call(engine="jax")
    with pytest.raises(TypeError, match="SimSpec"):
        call(spec="jax")
    default = _answers(call(spec=None))
    explicit = _answers(call(spec=SimSpec()))
    assert default and sorted(default) == sorted(explicit)
    for k in default:
        np.testing.assert_array_equal(explicit[k], default[k], err_msg=k)


def test_simspec_resolve_validates_and_is_idempotent():
    from repro.core import WaveCongestionAware
    from repro.core.reordering import TransportProfile
    s = SimSpec(strategy="wave-congestion-aware", transport="roce-nack",
                engine="jax").resolve()
    assert isinstance(s.strategy, WaveCongestionAware)
    assert isinstance(s.transport, TransportProfile)
    assert s.hash_backend is not None          # engine-coupled concrete
    s2 = s.resolve()
    assert s2.strategy is s.strategy and s2.transport is s.transport
    for bad in (SimSpec(engine="cuda"), SimSpec(demand_mode="packets"),
                SimSpec(fields="l4"), SimSpec(max_hops=0)):
        with pytest.raises(ValueError):
            bad.resolve()


@pytest.mark.parametrize("front", ["monte_carlo_throughput",
                                   "simulate_timeline"])
def test_simspec_spans_all_front_ends(paper_compiled, paper_setup, front):
    # a strategy named in the spec resolves through the registry to the
    # same strategy an instance gives, in every front end
    from repro.core import PrimeSpraying
    _, _, flows = paper_setup
    _, call = _front_end(front, paper_compiled, flows, [0, 3])
    by_name = _answers(call(spec=SimSpec(strategy="prime-spray",
                                         transport="roce-nack")))
    by_instance = _answers(call(spec=SimSpec(strategy=PrimeSpraying(),
                                             transport="roce-nack")))
    assert by_name and sorted(by_name) == sorted(by_instance)
    for k in by_name:
        np.testing.assert_array_equal(by_instance[k], by_name[k], err_msg=k)


@pytest.mark.parametrize("field", [{"max_hops": 1},
                                   {"fields": FIELDS_IP_PAIR},
                                   {"demand_mode": "bytes"},
                                   {"hash_backend": "murmur"}],
                         ids=lambda f: next(iter(f)))
def test_fused_path_honours_each_spec_field(paper_compiled, paper_setup,
                                            field):
    # the fused device pipelines read the resolved spec whole: each field
    # moves their answers as it moves the numpy engine's, to float64
    # rounding
    import dataclasses
    from repro.core import (
        monte_carlo_throughput, paper_testbed_llm_schedule, simulate_timeline,
    )
    _, _, flows = paper_setup
    # heterogeneous bytes, so demand_mode="bytes" weighs flows unequally
    flows = [dataclasses.replace(f, bytes=(1 + f.flow_id % 5) * 1_000_000)
             for f in flows]
    seeds = [0, 7]
    if "max_hops" in field:
        # testbed paths take >1 hop: an insufficient budget fails loudly
        # on both engines
        _, lflows, _, sched = paper_testbed_llm_schedule()
        with pytest.raises(RuntimeError, match="did not terminate"):
            simulate_timeline(paper_compiled, lflows, sched, seeds,
                              spec=SimSpec(**field))
        for front in (monte_carlo_fim, monte_carlo_throughput):
            for engine in ("numpy", "jax"):
                with pytest.raises(RuntimeError, match="did not terminate"):
                    front(paper_compiled, flows, seeds,
                          spec=SimSpec(engine=engine, **field))
        return
    for front, answer in ((monte_carlo_fim, "aggregate"),
                          (monte_carlo_throughput, "rates")):
        want = _answers(front(paper_compiled, flows, seeds,
                              spec=SimSpec(**field)))
        got = _answers(front(paper_compiled, flows, seeds,
                             spec=SimSpec(engine="jax", **field)))
        plain = _answers(front(paper_compiled, flows, seeds))
        # the field changes the answer, so a fused path that dropped it
        # could not match
        assert not np.allclose(want[answer], plain[answer])
        assert sorted(got) == sorted(want)
        for k in want:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-12,
                                       err_msg=k)

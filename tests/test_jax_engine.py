"""Differential tests for the device-resident jax engine.

The numpy engine is the reference: under the exact splitmix64 backend
the jax walk must be **bit-identical** (same uint64 arithmetic, just
jitted), and every downstream stage — max-min fill, flowlet exposure,
transport goodput, FIM — must agree within 1e-6 (the device's per-cell
reductions sum in a different order than numpy's bincount, nothing
more).  The sweep crosses randomized fabric shapes, all three routing
strategies, both demand modes, and the fused front-end fast paths; the
large-scale sweep rides behind the ``slow`` marker and scales via
``FLOWTRACER_SWEEP_FLOWS`` / ``FLOWTRACER_SWEEP_SEEDS`` toward the
100k-flow x 10k-seed acceptance shape on device hosts."""

import dataclasses
import os

import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import (
    AdaptiveSpraying, PrimeSpraying, RoutingStrategy, SimSpec, TimelineStep,
    batched_max_min, bipartite_pairs, build_multipod_fabric,
    build_paper_testbed, build_three_tier_clos, compile_fabric,
    flowlet_exposure, max_min_rates, monte_carlo_fim, monte_carlo_throughput,
    nic_ip, resolve_flows, server_name, simulate_paths, simulate_timeline,
    synthesize_flows, throughput_from_result,
)
from repro.core.ecmp import FIELDS_5TUPLE, flow_fields_matrix
from repro.core.fabric import SERVER
from repro.compile_cache import (
    CACHE_ENV, DEFAULT_CACHE_DIR, configure_compile_cache,
)
from repro.core import jax_engine
from repro.core.jax_engine import (
    default_hash_backend, resolve_engine, seed_chunk,
)
from repro.core.vector_sim import (
    ENGINE_JAX, ENGINE_NUMPY, EXACT, MURMUR, ecmp_walk, normalize_seeds,
    resolve_hash_backend,
)

STRATEGIES = {
    "ecmp": None,
    "prime-spray": PrimeSpraying(flowlets=4),
    "adaptive-spray": AdaptiveSpraying(flowlets=4, rounds=2),
    "congestion-aware": "congestion-aware",
}


def _workload(fab, flows_per_pair=4, servers=8, hetero=True):
    half = servers // 2
    rack0 = [server_name(i) for i in range(half)]
    rack1 = [server_name(half + i) for i in range(half)]
    wl = bipartite_pairs(rack0, rack1, flows_per_pair=flows_per_pair)
    flows = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
    if hetero:
        flows = [dataclasses.replace(
            f, bytes=(256 * 1024 * 1024 if i % 3 == 0 else 1024 * 1024))
            for i, f in enumerate(flows)]
    return flows


@pytest.fixture(scope="module")
def paper8():
    fab = build_paper_testbed()
    return compile_fabric(fab), _workload(fab)


# ---------------------------------------------------------------------------
# engine selection plumbing
# ---------------------------------------------------------------------------


def test_resolve_engine_rejects_unknown():
    assert resolve_engine("jax") == "jax"
    with pytest.raises(ValueError, match="engine"):
        resolve_engine("cuda")


def test_resolve_hash_backend_defaults():
    # numpy always defaults to the tracer-identical exact hash; jax
    # defaults to the engine's natural backend (exact on CPU, where the
    # differential CI runs); an explicit choice always wins
    assert resolve_hash_backend(None, ENGINE_NUMPY) == EXACT
    assert resolve_hash_backend(None, ENGINE_JAX) == default_hash_backend()
    assert resolve_hash_backend(MURMUR, ENGINE_NUMPY) == MURMUR
    assert resolve_hash_backend(EXACT, ENGINE_JAX) == EXACT
    with pytest.raises(ValueError):
        resolve_hash_backend("sha1", ENGINE_NUMPY)


def test_legacy_strategy_rejects_engine_loudly(paper8):
    """A pre-engine custom strategy keeps working under the defaults but
    a non-default engine request against it must fail, not silently run
    on numpy."""

    class Legacy(RoutingStrategy):
        name = "legacy"

        def route(self, comp, flows, seeds, *, fields, hash_backend,
                  max_hops, field_matrix):
            return simulate_paths(comp, flows, seeds,
                                  spec=SimSpec(fields=fields,
                                               hash_backend=hash_backend,
                                               max_hops=max_hops),
                                  field_matrix=field_matrix)

    comp, flows = paper8
    res = simulate_paths(comp, flows, [0, 1], spec=SimSpec(strategy=Legacy()))
    assert res.num_seeds == 2
    with pytest.raises(TypeError):
        simulate_paths(comp, flows, [0, 1],
                       spec=SimSpec(strategy=Legacy(), engine=ENGINE_JAX))


# ---------------------------------------------------------------------------
# walk + downstream parity across strategies and demand modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_engine_parity_per_strategy(paper8, strategy):
    comp, flows = paper8
    seeds = [0, 7, 1234567, 2**40 + 17]
    for demand_mode in ("uniform", "bytes"):
        r_np = simulate_paths(comp, flows, seeds,
                              spec=SimSpec(strategy=STRATEGIES[strategy],
                                           demand_mode=demand_mode))
        r_jx = simulate_paths(comp, flows, seeds,
                              spec=SimSpec(strategy=STRATEGIES[strategy],
                                           demand_mode=demand_mode,
                                           engine=ENGINE_JAX))
        # exact backend on both engines: the walk is bit-identical
        assert np.array_equal(r_np.link_ids, r_jx.link_ids)
        assert np.array_equal(r_np.flow_demand, r_jx.flow_demand)
        tp_np = throughput_from_result(r_np, transport="roce-nack")
        tp_jx = throughput_from_result(r_jx, transport="roce-nack",
                                       engine=ENGINE_JAX)
        for attr in ("rates", "exposure", "goodput", "per_pair"):
            a, b = getattr(tp_np, attr), getattr(tp_jx, attr)
            assert np.abs(a - b).max() < 1e-6, (strategy, demand_mode, attr)


def test_murmur_walk_bit_identical(paper8):
    """Both engines evaluate the ONE murmur definition (seed-as-init,
    fold, fmix) — same uint32 formulas, so bit-identical too."""
    comp, flows = paper8
    r_np = simulate_paths(comp, flows, [0, 3, 99],
                          spec=SimSpec(hash_backend=MURMUR))
    r_jx = simulate_paths(comp, flows, [0, 3, 99],
                          spec=SimSpec(hash_backend=MURMUR, engine=ENGINE_JAX))
    assert np.array_equal(r_np.link_ids, r_jx.link_ids)
    # and murmur actually routes differently than exact (distinct hash)
    r_ex = simulate_paths(comp, flows, [0, 3, 99])
    assert not np.array_equal(r_np.link_ids, r_ex.link_ids)


@given(st.integers(1, 3), st.integers(2, 4), st.integers(0, 2**31))
@settings(max_examples=3, deadline=None)
def test_randomized_fabric_walk_parity(spines, links_per, seed):
    fab = build_paper_testbed(num_spines=spines,
                              links_per_leaf_spine=links_per,
                              servers_per_rack=4)
    comp = compile_fabric(fab)
    flows = _workload(fab, flows_per_pair=2, servers=8, hetero=False)
    seeds = [seed, seed + 1]
    r_np = simulate_paths(comp, flows, seeds)
    r_jx = simulate_paths(comp, flows, seeds, spec=SimSpec(engine=ENGINE_JAX))
    assert np.array_equal(r_np.link_ids, r_jx.link_ids)
    a = max_min_rates(r_np)
    b = max_min_rates(r_jx, engine=ENGINE_JAX)
    assert np.abs(a - b).max() < 1e-6
    # the murmur walk under a cell salt too, with its final devices
    _assert_walks_equal(comp, flows, normalize_seeds(seeds), MURMUR,
                        salted=True)


# ---------------------------------------------------------------------------
# the walk's per-flow route tables against the numpy walk
# ---------------------------------------------------------------------------


WALK_FABRICS = {
    "testbed": build_paper_testbed,
    "three-tier": lambda: build_three_tier_clos(
        num_pods=2, racks_per_pod=3, servers_per_rack=2, nics_per_server=2,
        cluster_switches=4, aggs_per_plane=2, uplinks=2),
    "multipod": build_multipod_fabric,
}


@pytest.fixture(scope="module", params=sorted(WALK_FABRICS))
def walk_fabric(request):
    """A fabric and flows of every path length it has: each server to
    the server across the fabric and to its neighbour."""
    fab = WALK_FABRICS[request.param]()
    comp = compile_fabric(fab)
    srv = sorted(d for d in fab.devices if fab.kind(d) == SERVER)
    wl = bipartite_pairs(srv[: len(srv) // 2], srv[::-1][: len(srv) // 2],
                         flows_per_pair=1)
    near = bipartite_pairs(srv[0::2], srv[1::2], flows_per_pair=1)
    flows = resolve_flows(comp, wl) + resolve_flows(comp, near)
    return comp, [dataclasses.replace(f, flow_id=i)
                  for i, f in enumerate(flows)]


def _assert_walks_equal(comp, flows, seeds, backend, *, salted=False,
                        flowlets=1):
    """The jax walk's link ids, hop count and final devices equal the
    numpy walk's; ``flowlets`` columns a flow, each with its own entropy
    label column, as the spray strategies walk them."""
    import jax

    ends = comp.flow_endpoint_ids(flows)
    fm = flow_fields_matrix(flows, FIELDS_5TUPLE)
    if flowlets > 1:
        rep = np.repeat(np.arange(len(flows)), flowlets)
        ends = tuple(a[rep] for a in ends)
        labels = np.tile(np.arange(flowlets, dtype=np.uint64), len(flows))
        fm = np.concatenate([fm[rep], labels[:, None]], axis=1)
    src, dst, skey, dkey = ends
    salt = None
    if salted:                        # half the cells keep the plain walk
        salt = np.random.default_rng(len(src)).integers(
            0, 2**63, (len(src), len(seeds))).astype(np.uint64)
        salt[::2] = 0
    want = ecmp_walk(comp, src, dst, skey, dkey, fm, seeds,
                     hash_backend=backend, cell_salt=salt)
    got = jax_engine.jax_ecmp_walk(comp, src, dst, skey, dkey, fm, seeds,
                                   hash_backend=backend, cell_salt=salt)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    with jax.enable_x64(True):
        routes = jax_engine.route_tables(comp, src, skey, dkey, 16)
        ids, state, done, t = jax.device_get(jax_engine._jax_walk_device(
            routes, fm, seeds, hash_backend=backend, cell_salt=salt))
    assert done.all() and int(t) == want.shape[0]
    assert np.array_equal(ids[: int(t)], want) and (ids[int(t):] == -1).all()
    # a walk ends on the far device of its last link
    last = np.take_along_axis(
        want, (want >= 0).sum(axis=0, keepdims=True) - 1, axis=0)[0]
    assert np.array_equal(state, comp.link_dst[last])
    assert np.array_equal(state, np.broadcast_to(dst[:, None], state.shape))


@pytest.mark.parametrize("variant", ["plain", "salted", "flowlets"])
@pytest.mark.parametrize("backend", [EXACT, MURMUR])
def test_walk_matches_the_numpy_walk(walk_fabric, backend, variant):
    comp, flows = walk_fabric
    seeds = normalize_seeds([0, 3, 2**40 + 17, 2**63 + 5])
    _assert_walks_equal(comp, flows, seeds, backend,
                        salted=variant != "plain",
                        flowlets=3 if variant == "flowlets" else 1)


def _frozen_testbed_flows():
    """The paper-ecmp cells' 256 flows, from the configuration's frozen
    ``flows.json``."""
    import json
    from pathlib import Path

    from repro.core import FiveTuple, Flow
    path = (Path(__file__).resolve().parents[1] / "chipbench" / "configs"
            / "paper-testbed" / "flows.json")
    with open(path) as fh:
        rows = json.load(fh)["flows"]
    return [Flow(flow_id=f["flow_id"], src=f["src"], dst=f["dst"],
                 tuple5=FiveTuple(f["src_ip"], f["dst_ip"], f["src_port"],
                                  f["dst_port"], f["protocol"]))
            for f in rows]


def test_route_tables_of_the_testbed():
    """Frontier widths (1, 1, 4, 1) and fan-outs (2, 16, 4, 2) a hop:
    the source NIC's two ports to its leaf, the leaf's 16 spine links,
    each spine's 4 links to the far leaf, the far leaf's two ports; one
    exit, the destination server."""
    comp = compile_fabric(build_paper_testbed())
    flows = _frozen_testbed_flows()
    src, dst, skey, dkey = comp.flow_endpoint_ids(flows)
    hops, exits, widths, shift, base, inverse = \
        jax_engine._route_tables_host(comp, src, skey, dkey, 16)
    assert widths == ((1, 2), (1, 16), (4, 4), (1, 2))
    assert sum(w * c for w, c in widths) == 36
    assert len(exits) == 64 and exits.shape[1] == 1
    assert np.array_equal(exits[inverse, 0], dst)
    assert base == 4 and shift == 3
    # a depth cut short of the fabric's leaves the walk unfinished
    _, _, short, *_ = jax_engine._route_tables_host(comp, src, skey, dkey, 3)
    assert short == widths[:3]
    with pytest.raises(RuntimeError, match="did not terminate in 3 hops"):
        jax_engine.jax_ecmp_walk(
            comp, src, dst, skey, dkey,
            flow_fields_matrix(flows, FIELDS_5TUPLE), normalize_seeds([1]),
            max_hops=3)


# ---------------------------------------------------------------------------
# fill + exposure stage twins
# ---------------------------------------------------------------------------


@given(st.integers(1, 4), st.integers(1, 40), st.integers(1, 8),
       st.integers(2, 12), st.integers(0, 2**31))
@settings(max_examples=8, deadline=None)
def test_weighted_fill_parity_random(H, N, S, L, seed):
    rng = np.random.default_rng(seed)
    ids = rng.integers(-1, L, (H, N, S)).astype(np.int32)
    gbps = rng.uniform(1.0, 400.0, L)
    w = rng.uniform(0.05, 8.0, N)
    a = batched_max_min(ids, gbps, weights=w)
    b = batched_max_min(ids, gbps, weights=w, engine=ENGINE_JAX)
    assert np.allclose(a, b, rtol=1e-9, atol=1e-9)


def test_fill_edge_cases_match_numpy():
    gbps = np.array([100.0, 40.0])
    # H == 0: no hops at all -> unconstrained
    a = batched_max_min(np.empty((0, 3, 2), np.int32), gbps)
    b = batched_max_min(np.empty((0, 3, 2), np.int32), gbps,
                        engine=ENGINE_JAX)
    assert np.isinf(a).all() and np.isinf(b).all()
    # all-sentinel column (flow crossing no link) -> inf, others finite
    ids = np.array([[[0], [-1]]], np.int32)          # (1, 2, 1)
    a = batched_max_min(ids, gbps)
    b = batched_max_min(ids, gbps, engine=ENGINE_JAX)
    assert np.array_equal(np.isinf(a), np.isinf(b))
    assert np.allclose(a[np.isfinite(a)], b[np.isfinite(b)])


def test_exposure_parity_under_spray(paper8):
    comp, flows = paper8
    res = simulate_paths(comp, flows, [0, 5],
                         spec=SimSpec(strategy=PrimeSpraying(flowlets=4)))
    rates = max_min_rates(res)
    a = flowlet_exposure(res, rates)
    b = flowlet_exposure(res, rates, engine=ENGINE_JAX)
    assert np.abs(a - b).max() < 1e-6
    # single-path result: exposure is identically zero on both engines
    res1 = simulate_paths(comp, flows, [0, 5])
    assert (flowlet_exposure(res1, engine=ENGINE_JAX) == 0).all()


# ---------------------------------------------------------------------------
# fused front ends + timeline
# ---------------------------------------------------------------------------


def test_fused_fim_parity(paper8):
    comp, flows = paper8
    seeds = np.arange(16)
    for demand_mode, used in (("uniform", False), ("bytes", True)):
        a = monte_carlo_fim(comp, flows, seeds,
                            spec=SimSpec(demand_mode=demand_mode),
                            only_used_leaves=used)
        b = monte_carlo_fim(comp, flows, seeds,
                            spec=SimSpec(demand_mode=demand_mode,
                                         engine=ENGINE_JAX),
                            only_used_leaves=used)
        assert np.abs(a.aggregate - b.aggregate).max() < 1e-6
        assert sorted(a.per_layer) == sorted(b.per_layer)
        for layer in a.per_layer:
            assert np.abs(a.per_layer[layer]
                          - b.per_layer[layer]).max() < 1e-6


def test_fused_throughput_parity(paper8):
    comp, flows = paper8
    seeds = np.arange(16)
    a = monte_carlo_throughput(comp, flows, seeds,
                               spec=SimSpec(demand_mode="bytes",
                                            transport="strack"))
    b = monte_carlo_throughput(comp, flows, seeds,
                               spec=SimSpec(demand_mode="bytes",
                                            transport="strack",
                                            engine=ENGINE_JAX))
    assert np.abs(a.rates - b.rates).max() < 1e-6
    assert np.abs(a.goodput - b.goodput).max() < 1e-6
    assert np.abs(a.per_pair - b.per_pair).max() < 1e-6


def test_seed_chunk_sizes():
    # one pass while the chunk fits the byte budget ...
    assert seed_chunk(4096, 16, 1024) == 1024
    # ... then equal lane-aligned chunks: 3000 seeds in three passes
    per = seed_chunk(4096, 16, 3000)
    assert per % jax_engine._SEED_LANES == 0 and -(-3000 // per) == 3
    # 100k flows: one lane tile per pass, 1024 seeds in equal passes
    per = seed_chunk(100_000, 16, 1024)
    assert per == jax_engine._SEED_LANES and 1024 % per == 0


def test_fused_padded_last_chunk_parity(paper8, monkeypatch):
    """A seed count that is not a multiple of the chunk pads the last
    pass to the chunk size: the numbers match numpy and the walk and
    fill compile one chunk shape, not two."""
    comp, flows = paper8
    per_seed = len(flows) * 16 * (jax_engine._WALK_BYTES_PER_HOP
                                  + jax_engine._FILL_BYTES_PER_CELL)
    monkeypatch.setattr(jax_engine, "_CHUNK_BYTES", per_seed * 128)
    seeds = np.arange(200)                  # chunks of 128 + 72 (padded)
    assert seed_chunk(len(flows), 16, len(seeds)) == 128
    walk0 = jax_engine._walk_fn()._cache_size()
    fill0 = jax_engine._fill_fn()._cache_size()
    a = monte_carlo_throughput(comp, flows, seeds,
                               spec=SimSpec(demand_mode="bytes"))
    b = monte_carlo_throughput(comp, flows, seeds,
                               spec=SimSpec(demand_mode="bytes",
                                            engine=ENGINE_JAX))
    assert np.abs(a.rates - b.rates).max() < 1e-6
    assert jax_engine._walk_fn()._cache_size() - walk0 <= 1
    assert jax_engine._fill_fn()._cache_size() - fill0 <= 1
    for demand_mode, used in (("uniform", False), ("bytes", True)):
        fim0 = jax_engine._fim_fn()._cache_size()
        fa = monte_carlo_fim(comp, flows, seeds,
                             spec=SimSpec(demand_mode=demand_mode),
                             only_used_leaves=used)
        fb = monte_carlo_fim(comp, flows, seeds,
                             spec=SimSpec(demand_mode=demand_mode,
                                          engine=ENGINE_JAX),
                             only_used_leaves=used)
        assert jax_engine._fim_fn()._cache_size() - fim0 <= 1
        assert np.abs(fa.aggregate - fb.aggregate).max() < 1e-6
        assert sorted(fa.per_layer) == sorted(fb.per_layer)
        for layer in fa.per_layer:
            assert np.abs(fa.per_layer[layer]
                          - fb.per_layer[layer]).max() < 1e-6


def test_fim_keeps_layers_by_real_seeds_only(paper8):
    """A layer live only in a chunk's padding rows is dropped, and the
    padding's answers are sliced off; a host count matrix and a device
    one give the numpy engine's answers."""
    import jax
    import jax.numpy as jnp
    from repro.core.vector_sim import fim_from_counts

    comp, _ = paper8
    rng = np.random.default_rng(5)
    counts = rng.integers(0, 4, (8, comp.num_links)).astype(np.float64)
    dead = comp.layer_names[0]
    counts[:5, comp.link_layer == 0] = 0.0     # dead in the 5 real seeds
    with jax.enable_x64(True):
        agg, per_layer = jax_engine._fim_chunks(
            comp, [(5, jnp.asarray(counts))], layers=None,
            only_used_leaves=False)
    ref_agg, ref = fim_from_counts(counts[:5], comp)
    assert dead not in ref and sorted(per_layer) == sorted(ref)
    assert np.abs(agg - ref_agg).max() < 1e-12
    for layer in ref:
        assert np.abs(per_layer[layer] - ref[layer]).max() < 1e-12

    ref_agg, ref = fim_from_counts(counts, comp, only_used_leaves=True)
    for given in (counts, jnp.asarray(counts)):
        agg, per_layer = jax_engine.jax_fim_from_counts(
            given, comp, only_used_leaves=True)
        assert isinstance(agg, np.ndarray) and sorted(per_layer) == sorted(ref)
        assert np.abs(agg - ref_agg).max() < 1e-12


def _bincount_counts(ids, L, weights=None):
    """The numpy twin's rule (``VectorTraceResult.link_flow_counts``)."""
    S = ids.shape[2]
    keep = ids >= 0
    flat = (ids.astype(np.int64) + np.arange(S) * L)[keep]
    w = (None if weights is None else
         np.broadcast_to(weights[None, :, None], ids.shape)[keep])
    return np.bincount(flat, weights=w, minlength=S * L).reshape(S, L)


@pytest.mark.parametrize("H, N, S, L, block_bytes", [
    (4, 256, 64, 256, None),          # the testbed's links, one block
    (6, 2048, 9, 5760, 9 << 20),      # the three-tier's, blocks of 2
    (3, 700, 13, 300, 1 << 20),       # L not a multiple of B, of 5
    (2, 3000, 5, 40, 1 << 19),        # L below B's cap, of 3
    (5, 120, 3, 1, None),             # one link
], ids=["testbed", "three-tier", "ragged-L", "small-L", "one-link"])
def test_link_flow_counts_match_bincount(monkeypatch, H, N, S, L,
                                         block_bytes):
    """Unit weights count on the factored one-hots, bit-identical to the
    numpy bincount, also when -1 tails end paths early and the seed
    blocks do not divide S; unequal weights take the weighted path."""
    import jax

    A, B = jax_engine.onehot_split(L)
    assert B & (B - 1) == 0 and B <= 128 and (A - 1) * B < L <= A * B
    if block_bytes is not None:
        per_seed = H * N * (A + B) * 2
        assert 1 < block_bytes // per_seed < S
        assert S % (block_bytes // per_seed) != 0
        monkeypatch.setattr(jax_engine, "_ONEHOT_BYTES", block_bytes)
    rng = np.random.default_rng(L)
    ids = rng.integers(0, L, (H, N, S)).astype(np.int32)
    ends = rng.integers(1, H + 1, (N, S))        # hops each cell walked
    ids[np.arange(H)[:, None, None] >= ends[None]] = -1
    w = rng.uniform(0.05, 8.0, N)
    with jax.enable_x64(True):
        dev = jax.numpy.asarray(ids)
        unit = np.asarray(jax_engine.jax_link_flow_counts(dev, np.ones(N), L))
        weighted = np.asarray(jax_engine.jax_link_flow_counts(dev, w, L))
    assert unit.dtype == np.float64 and unit.shape == (S, L)
    np.testing.assert_array_equal(unit, _bincount_counts(ids, L))
    np.testing.assert_allclose(weighted, _bincount_counts(ids, L, w),
                               rtol=1e-12, atol=0)


@pytest.mark.parametrize("demand_mode, exact_f32, unit", [
    ("uniform", None, True),
    ("bytes", None, False),           # unequal bytes: weighted sums
    ("uniform", 1, False),            # H * N past float32's exact range
], ids=["uniform", "bytes", "uniform-past-float32"])
def test_fused_fim_counts_by_demand(paper8, tmp_path, monkeypatch,
                                    demand_mode, exact_f32, unit):
    """The fused FIM counts unit demand on the one-hots (counters
    ``unit_counts`` and ``onehot_width`` of ``counts.run``) and anything
    else with the weighted sums; both give the numpy engine's FIM."""
    import jax

    from repro.core import spans

    comp, flows = paper8
    if exact_f32 is not None:
        monkeypatch.setattr(jax_engine, "_EXACT_F32", exact_f32)
    seeds = np.arange(24)
    spec = SimSpec(demand_mode=demand_mode)
    a = monte_carlo_fim(comp, flows, seeds, spec=spec)
    with jax.profiler.trace(str(tmp_path)):
        b = monte_carlo_fim(comp, flows, seeds,
                            spec=dataclasses.replace(spec, engine=ENGINE_JAX))
    row = spans.snapshot()["counts.run"]
    if unit:
        assert row["unit_counts"] == row["n"] == 1
        assert row["onehot_width"] == sum(
            jax_engine.onehot_split(comp.num_links))
    else:
        assert "unit_counts" not in row and "onehot_width" not in row
    assert np.abs(a.aggregate - b.aggregate).max() < 1e-9
    assert sorted(a.per_layer) == sorted(b.per_layer)
    for layer in a.per_layer:
        assert np.abs(a.per_layer[layer] - b.per_layer[layer]).max() < 1e-9


def test_fused_walk_names_the_flow_that_did_not_arrive(monkeypatch):
    """With spine-0's candidate sets toward every NIC on leaf-2 emptied
    in the compiled table, a flow into leaf-2 that hashes onto spine-0
    ends there.  The fused front ends check arrival on the device and
    raise the numpy engine's error: flow id, the seed's index in the
    whole sweep (here in the padded second chunk) and the device the
    flow ended at."""
    from repro.core.fabric import port_nic

    comp = compile_fabric(build_paper_testbed())
    on_leaf2 = [comp.key_of_ip[nic_ip(ln.dst, port_nic(ln.dst_port))]
                for ln in comp.links
                if ln.src == "leaf-2" and ln.dst.startswith("srv-")]
    cand_n = comp.cand_n.copy()
    cand_n[comp.device_id["spine-0"], on_leaf2] = 0
    comp = dataclasses.replace(comp, cand_n=cand_n)
    flows = bipartite_pairs([server_name(0)], [server_name(8)],
                            flows_per_pair=1)
    stranded = [s for s in range(64)
                if _strands(comp, flows, np.array([s]))]
    assert stranded and len(stranded) < 64
    ok = next(s for s in range(64) if s not in stranded)
    seeds = np.full(200, ok)
    seeds[150] = stranded[0]
    per_seed = 2 * 16 * (jax_engine._WALK_BYTES_PER_HOP
                         + jax_engine._FILL_BYTES_PER_CELL)
    monkeypatch.setattr(jax_engine, "_CHUNK_BYTES", per_seed * 128)
    want = "flow 0 (seed index 150) terminated at spine-0"
    assert _strands(comp, flows, seeds) == want
    for fn in (monte_carlo_fim, monte_carlo_throughput):
        with pytest.raises(RuntimeError) as err:
            fn(comp, flows, seeds, spec=SimSpec(engine=ENGINE_JAX))
        assert str(err.value) == want


def _strands(comp, flows, seeds):
    """The numpy engine's arrival error for ``seeds``, or None."""
    try:
        monte_carlo_fim(comp, flows, seeds)
    except RuntimeError as e:
        return str(e)
    return None


def test_compile_cache_placement(monkeypatch):
    import jax
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CACHE_ENV, "/elsewhere/cache")
        assert configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was   # untouched
        monkeypatch.delenv(CACHE_ENV)
        assert configure_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        assert DEFAULT_CACHE_DIR.name == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_fused_path_only_for_plain_ecmp(paper8):
    """A *configured* EcmpStrategy subclass must not be silently routed
    through the fused plain-ECMP fast path."""
    comp, flows = paper8
    seeds = np.arange(4)
    spray = PrimeSpraying(flowlets=4)
    spec = SimSpec(strategy=spray, transport="roce-nack")
    a = monte_carlo_throughput(comp, flows, seeds, spec=spec)
    b = monte_carlo_throughput(
        comp, flows, seeds, spec=dataclasses.replace(spec, engine=ENGINE_JAX))
    assert np.abs(a.goodput - b.goodput).max() < 1e-6


def test_timeline_engine_parity(paper8):
    comp, flows = paper8
    labeled = [dataclasses.replace(f, label=f"x#ch{i % 2}")
               for i, f in enumerate(flows)]
    sched = [TimelineStep("a", (0,)), TimelineStep("b", (1,), duration=2.0)]
    a = simulate_timeline(comp, labeled, sched, [0, 1, 2],
                          spec=SimSpec(demand_mode="bytes",
                                       transport="roce-nack"))
    b = simulate_timeline(comp, labeled, sched, [0, 1, 2],
                          spec=SimSpec(demand_mode="bytes",
                                       transport="roce-nack",
                                       engine=ENGINE_JAX))
    assert np.abs(a.fim - b.fim).max() < 1e-6
    assert np.abs(a.goodput - b.goodput).max() < 1e-6
    for sa, sb in zip(a.steps, b.steps):
        assert np.abs(sa.throughput.rates - sb.throughput.rates).max() < 1e-6


# ---------------------------------------------------------------------------
# large-scale acceptance sweep (slow; env-scalable toward 100k x 10k)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_large_scale_sweep_parity():
    n_flows = int(os.environ.get("FLOWTRACER_SWEEP_FLOWS", 16384))
    n_seeds = int(os.environ.get("FLOWTRACER_SWEEP_SEEDS", 1024))
    fab = build_paper_testbed()
    rack0 = [server_name(i) for i in range(8)]
    rack1 = [server_name(8 + i) for i in range(8)]
    wl = bipartite_pairs(rack0, rack1,
                         flows_per_pair=max(1, n_flows // 16))
    comp = compile_fabric(fab)
    seeds = np.arange(n_seeds)
    jx = monte_carlo_throughput(comp, wl, seeds,
                                spec=SimSpec(transport="roce-nack",
                                             engine=ENGINE_JAX))
    assert jx.rates.shape[1] == n_seeds
    # numpy reference on a seed subsample keeps the differential check
    # affordable at acceptance scale
    sub = np.arange(min(n_seeds, 64))
    ref = monte_carlo_throughput(comp, wl, sub,
                                 spec=SimSpec(transport="roce-nack"))
    assert np.abs(ref.goodput - jx.goodput[:, :len(sub)]).max() < 1e-6

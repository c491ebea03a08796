"""Wave-parallel congestion-aware placement: differential + regression.

The sequential ``CongestionAware`` greedy loop is the reference.  The
divergence contract under test (the class docstring's): wherever the
cutover delegates — below the ``min_wave_load`` depth, or
heterogeneous per-flow weights at any depth — the wave is
**bit-identical** to sequential greedy; on the wave path itself
(homogeneous weights above the cutover) it converges to a different
member of the same local-optimum family whose demand-weighted FIM is
no worse than sequential's.  Both engines must agree bit-for-bit on
the wave path itself, and the symmetric-conflict repair dynamics must
converge (no livelock) under the documented tie-break.
"""

import dataclasses
import os

import numpy as np
import pytest

from repro.core import (
    CongestionAware, LEAF_TO_SPINE, SimSpec, WaveCongestionAware,
    bipartite_pairs, build_paper_testbed, compile_fabric, fim_vector, nic_ip,
    server_name, simulate_paths, synthesize_flows,
)
from repro.core.vector_sim import ENGINE_JAX, ENGINE_NUMPY

SEEDS = [0, 7, 1234567, 2**40 + 17]


def _flows(fab_kw=None, flows_per_pair=4, servers=16, hetero=False,
           rngseed=0):
    half = servers // 2
    rack0 = [server_name(i) for i in range(half)]
    rack1 = [server_name(half + i) for i in range(half)]
    wl = bipartite_pairs(rack0, rack1, flows_per_pair=flows_per_pair)
    flows = synthesize_flows(wl, nic_ip=nic_ip, nics_per_server=2)
    if hetero:
        rng = np.random.default_rng(rngseed)
        sizes = rng.choice([1 << 20, 64 << 20, 1 << 30], len(flows))
        flows = [dataclasses.replace(f, bytes=int(b))
                 for f, b in zip(flows, sizes)]
    return flows


@pytest.fixture(scope="module")
def paper_comp():
    return compile_fabric(build_paper_testbed())


# ---------------------------------------------------------------------------
# below the cutover: delegation, bit-identical to sequential greedy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("demand_mode", ["uniform", "bytes"])
@pytest.mark.parametrize("engine", [ENGINE_NUMPY, ENGINE_JAX])
def test_wave_below_cutover_bit_identical(paper_comp, demand_mode, engine):
    flows = _flows(flows_per_pair=16, hetero=True)      # 256 < 7 * 256 links
    seq = simulate_paths(paper_comp, flows, SEEDS,
                         spec=SimSpec(strategy=CongestionAware(),
                                      demand_mode=demand_mode, engine=engine))
    wav = simulate_paths(paper_comp, flows, SEEDS,
                         spec=SimSpec(strategy=WaveCongestionAware(),
                                      demand_mode=demand_mode, engine=engine))
    np.testing.assert_array_equal(wav.link_ids, seq.link_ids)
    # the delegated result still reports the wave strategy's name
    assert wav.strategy == "wave-congestion-aware"


@pytest.mark.parametrize("shape", [
    dict(num_spines=2, links_per_leaf_spine=2),
    dict(num_spines=4, links_per_leaf_spine=2),
    dict(servers_per_rack=4, num_spines=3, links_per_leaf_spine=3),
])
@pytest.mark.parametrize("demand_mode", ["uniform", "bytes"])
def test_wave_randomized_fabrics_match_sequential(shape, demand_mode):
    """Randomized fabric shapes, both demand modes, both engines: small
    waves delegate, so the match with sequential greedy is exact."""
    fab = build_paper_testbed(**shape)
    comp = compile_fabric(fab)
    servers = 2 * shape.get("servers_per_rack", 8)
    flows = _flows(flows_per_pair=2, servers=servers, hetero=True,
                   rngseed=sum(shape.values()))
    seq = simulate_paths(comp, flows, SEEDS,
                         spec=SimSpec(strategy=CongestionAware(),
                                      demand_mode=demand_mode))
    for engine in (ENGINE_NUMPY, ENGINE_JAX):
        wav = simulate_paths(comp, flows, SEEDS,
                             spec=SimSpec(strategy=WaveCongestionAware(),
                                          demand_mode=demand_mode,
                                          engine=engine))
        np.testing.assert_array_equal(wav.link_ids, seq.link_ids)


# ---------------------------------------------------------------------------
# above the cutover: documented divergence, FIM no worse than sequential
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("demand_mode", ["uniform", "bytes"])
def test_wave_above_cutover_fim_no_worse(paper_comp, demand_mode):
    """Homogeneous-weight waves above the depth cutover take the wave
    path (``demand_mode="bytes"`` on equal volumes normalizes to the
    same unit weights) and must land at or below sequential greedy's
    imbalance; the jax wave must be the numpy wave bit for bit."""
    flows = _flows(flows_per_pair=120)
    assert len(flows) / paper_comp.num_links >= 7.0   # wave path engaged
    seeds = np.arange(4)
    seq = simulate_paths(paper_comp, flows, seeds,
                         spec=SimSpec(strategy=CongestionAware(),
                                      demand_mode=demand_mode))
    wav = simulate_paths(paper_comp, flows, seeds,
                         spec=SimSpec(strategy=WaveCongestionAware(),
                                      demand_mode=demand_mode))
    assert fim_vector(wav).mean() <= fim_vector(seq).mean() + 1e-9
    # the jax wave is the same wave, bit for bit
    jx = simulate_paths(paper_comp, flows, seeds,
                        spec=SimSpec(strategy=WaveCongestionAware(),
                                     demand_mode=demand_mode,
                                     engine=ENGINE_JAX))
    np.testing.assert_array_equal(jx.link_ids, wav.link_ids)


@pytest.mark.parametrize("engine", [ENGINE_NUMPY, ENGINE_JAX])
def test_wave_hetero_demand_delegates_exactly(paper_comp, engine):
    """Genuinely unequal per-flow volumes delegate to the sequential
    chain even above the depth cutover (quantized repair cannot
    reproduce its heaviest-first ordering advantage — the documented
    interchangeability cutover), so byte-weighted placements stay
    bit-identical to ``CongestionAware`` at every scale."""
    flows = _flows(flows_per_pair=120, hetero=True)
    assert len(flows) / paper_comp.num_links >= 7.0
    seeds = np.arange(4)
    seq = simulate_paths(paper_comp, flows, seeds,
                         spec=SimSpec(strategy=CongestionAware(),
                                      demand_mode="bytes", engine=engine))
    wav = simulate_paths(paper_comp, flows, seeds,
                         spec=SimSpec(strategy=WaveCongestionAware(),
                                      demand_mode="bytes", engine=engine))
    np.testing.assert_array_equal(wav.link_ids, seq.link_ids)


# ---------------------------------------------------------------------------
# symmetric-conflict convergence (the atomic-commit regression)
# ---------------------------------------------------------------------------


def _spine_loads(comp, res, seed_idx):
    ids = res.link_ids[:, :, seed_idx]
    sel = ids[(ids >= 0)]
    counts = np.bincount(sel, minlength=comp.num_links)
    lid = comp.layer_names.index(LEAF_TO_SPINE)
    return counts[comp.link_layer == lid]


def test_wave_two_flow_symmetric_conflict_converges(paper_comp):
    """Two flows between distinct server pairs, forced onto the wave
    path: whenever hashed ECMP collides them onto one leaf->spine link
    the repair must separate them — and never flip-flop, because under
    the accept rule "equally good elsewhere" is not a move.  The
    sequential round-cap fallback makes separation deterministic even
    if the damped repair itself dawdles."""
    flows = _flows(flows_per_pair=1, servers=2)        # 2 flows, one pair
    assert len(flows) == 2
    strategy = WaveCongestionAware(tolerance=1.0, min_wave_load=0.0)
    seeds = list(range(64))
    res = simulate_paths(paper_comp, flows, seeds,
                         spec=SimSpec(strategy=strategy))
    ecmp = simulate_paths(paper_comp, flows, seeds)
    for k in range(len(seeds)):
        assert _spine_loads(paper_comp, res, k).max() <= 1, (
            f"seed {seeds[k]}: symmetric conflict did not separate")
        # where ECMP already balanced the pair there was no conflict to
        # repair, so the wave placement IS the ECMP placement
        if _spine_loads(paper_comp, ecmp, k).max() <= 1:
            np.testing.assert_array_equal(res.link_ids[:, :, k],
                                          ecmp.link_ids[:, :, k])


def test_wave_round_cap_residue_falls_back_sequential(paper_comp):
    """A 1-round cap leaves conflicted residue on a dense wave; the
    fallback must place it sequentially — valid paths, every flow
    present, and imbalance still clearly below hashed ECMP."""
    flows = _flows(flows_per_pair=16)
    strategy = WaveCongestionAware(max_rounds=1, min_wave_load=0.0)
    seeds = np.arange(4)
    res = simulate_paths(paper_comp, flows, seeds,
                         spec=SimSpec(strategy=strategy))
    by_id = {f.flow_id: f for f in flows}
    paths = res.paths_for_seed(0)
    assert set(paths) == set(by_id)
    for fid, path in paths.items():
        assert path[0].src == by_id[fid].src
        assert path[-1].dst == by_id[fid].dst
        for a, b in zip(path, path[1:]):
            assert a.dst == b.src
    ecmp = fim_vector(simulate_paths(paper_comp, flows, seeds))
    assert fim_vector(res).mean() < ecmp.mean() - 10.0


def test_wave_validation():
    with pytest.raises(ValueError, match="max_rounds"):
        WaveCongestionAware(max_rounds=0)
    with pytest.raises(ValueError, match="quantum"):
        WaveCongestionAware(quantum=0.0)
    with pytest.raises(ValueError, match="move_prob"):
        WaveCongestionAware(move_prob=0.0)
    with pytest.raises(ValueError, match="tolerance"):
        WaveCongestionAware(tolerance=0.5)
    with pytest.raises(ValueError, match="min_wave_load"):
        WaveCongestionAware(min_wave_load=-1.0)


# ---------------------------------------------------------------------------
# large-scale sweep (slow; env-scalable like the jax-engine sweep)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_wave_flow_sweep_no_worse_than_sequential(paper_comp):
    n_flows = int(os.environ.get("FLOWTRACER_SWEEP_FLOWS", 2560))
    flows = _flows(flows_per_pair=max(1, n_flows // 16))
    seeds = np.arange(8)
    seq = simulate_paths(paper_comp, flows, seeds,
                         spec=SimSpec(strategy=CongestionAware()))
    for engine in (ENGINE_NUMPY, ENGINE_JAX):
        wav = simulate_paths(paper_comp, flows, seeds,
                             spec=SimSpec(strategy=WaveCongestionAware(),
                                          engine=engine))
        assert fim_vector(wav).mean() <= fim_vector(seq).mean() + 1e-9

"""Shortest-path ECMP in the program: ``Forwarder`` and ``compile_fabric``
against the benchmark's independent reference (``chipbench/reference.py``,
loaded by its file path: it imports only numpy) on the paper testbed,
the multipod default and a small three-tier Clos; the three-tier
builder's shape; and every engine's FIM across the pod boundary."""

from __future__ import annotations

import hashlib
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    AGG_TO_SPINE, SPINE_TO_AGG, EcmpRouting, FiveTuple, Flow, FlowTracer,
    Forwarder, SimSpec, build_multipod_fabric, build_paper_testbed,
    build_three_tier_clos, compile_fabric, fim, monte_carlo_fim, nic_ip,
    per_layer_fim, server_name, simulate_paths, workload_from_flows,
)
from repro.core.fabric import AGG, LEAF, SPINE


def _load_reference():
    path = Path(__file__).resolve().parents[1] / "chipbench" / "reference.py"
    spec = importlib.util.spec_from_file_location("flowtracer_reference",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()

#: 2 pods x 3 racks x 2 servers x 2 single-port NICs, 4 cluster switches
#: a pod, 4 planes of 2 aggregation switches, 2 uplinks (3 down : 2 up)
SMALL = dict(num_pods=2, racks_per_pod=3, servers_per_rack=2,
             nics_per_server=2, cluster_switches=4, aggs_per_plane=2,
             uplinks=2, link_gbps=400.0)
#: the benchmark's meta-roce-3tier-2pod configuration
META = dict(num_pods=2, racks_per_pod=42, servers_per_rack=2,
            nics_per_server=8, cluster_switches=16, aggs_per_plane=2,
            uplinks=6, link_gbps=400.0)

FABRICS = {
    "testbed": build_paper_testbed,
    "multipod": build_multipod_fabric,
    "three-tier": lambda: build_three_tier_clos(**SMALL),
}


def pod_boundary_flows(shape: dict, seed: int, per_pair: int = 2):
    """NIC g of server i in pod 0 <-> NIC g of server i in pod 1, both
    directions, ``per_pair`` flows each with seeded UDP source ports."""
    rng = np.random.default_rng(seed)
    per_pod = shape["racks_per_pod"] * shape["servers_per_rack"]
    out = []
    for i in range(per_pod):
        a, b = server_name(i), server_name(per_pod + i)
        for g in range(shape["nics_per_server"]):
            for src, dst in ((a, b), (b, a)):
                for _ in range(per_pair):
                    out.append(Flow(
                        flow_id=len(out), src=src, dst=dst,
                        tuple5=FiveTuple(nic_ip(src, g), nic_ip(dst, g),
                                         int(rng.integers(1024, 65536)),
                                         4791, 17)))
    return out


def _flow_dicts(flows):
    return [{"flow_id": f.flow_id, "src": f.src, "dst": f.dst,
             "src_ip": f.tuple5.src_ip, "dst_ip": f.tuple5.dst_ip,
             "src_port": f.tuple5.src_port, "dst_port": f.tuple5.dst_port,
             "protocol": f.tuple5.protocol, "bytes": f.bytes}
            for f in flows]


def _cells(fab):
    """Every (device, NIC ip) whose candidate set the tables hold: each
    switch toward each NIC, and each host for its own NICs."""
    for srv, nic in sorted(Forwarder(fab).nic_links):
        ip = nic_ip(srv, nic)
        for dev, d in fab.devices.items():
            if d.kind != "server" or dev == srv:
                yield dev, srv, ip


@pytest.fixture(scope="module", params=sorted(FABRICS))
def fabric(request):
    fab = FABRICS[request.param]()
    return fab, ref.Topology(fab.to_json())


def test_forwarder_is_the_references_shortest_path_rule(fabric):
    """Same candidate sets in the same order on every (device, NIC)."""
    fab, topo = fabric
    fwd = Forwarder(fab)
    link_id = {ln.name: i for i, ln in enumerate(fab.links)}
    n = 0
    for dev, srv, ip in _cells(fab):
        probe = Flow(flow_id=-1, src=srv, dst=srv,
                     tuple5=FiveTuple(ip, ip, 0, 0))
        got = [link_id[ln.name] for ln in fwd.candidates(dev, probe)]
        assert got == topo.candidates(dev, ip, ip), (dev, ip)
        n += bool(got)
    assert n > 0


def test_compiled_tables_are_the_references_sets(fabric):
    fab, topo = fabric
    comp = compile_fabric(fab)
    for dev, _, ip in _cells(fab):
        v, k = comp.device_id[dev], comp.key_of_ip[ip]
        want = topo.candidates(dev, ip, ip)
        assert comp.cand_n[v, k] == len(want), (dev, ip)
        assert comp.cand[v, k, :len(want)].tolist() == want, (dev, ip)
        assert (comp.cand[v, k, len(want):] == -1).all()
    assert comp.cand.shape[2] == topo.largest_fanout()


def test_testbed_tables_keep_their_digests():
    """The testbed's (V, K, C) table is element for element what the
    two-tier rule compiled to."""
    comp = compile_fabric(build_paper_testbed())
    assert comp.cand.shape == (24, 32, 16)
    assert comp.cand.dtype == comp.cand_n.dtype == np.int32
    assert hashlib.sha256(comp.cand.tobytes()).hexdigest() == (
        "60dab894906c8ade2d0315e53442d873e0dbd465eb2883fb1ce1d01edac42133")
    assert hashlib.sha256(comp.cand_n.tobytes()).hexdigest() == (
        "f3d30b61b91f8e37f16fc7a51f7fc01e15ad9dbe61972e270eec44196acd0b28")


@pytest.mark.parametrize("shape", [SMALL, META], ids=["small", "meta"])
def test_three_tier_builder_shape(shape):
    fab = build_three_tier_clos(**shape)
    pods, racks = shape["num_pods"], shape["racks_per_pod"]
    csw, per_plane = shape["cluster_switches"], shape["aggs_per_plane"]
    servers = pods * racks * shape["servers_per_rack"]
    nics = servers * shape["nics_per_server"]
    kinds = [d.kind for d in fab.devices.values()]
    assert kinds.count("server") == servers
    assert kinds.count(LEAF) == pods * racks
    assert kinds.count(SPINE) == pods * csw
    assert kinds.count(AGG) == csw * per_plane
    assert len(fab.links) == 2 * (nics + pods * racks * csw
                                  + pods * csw * shape["uplinks"])
    assert fab.layers[-2:] == [SPINE_TO_AGG, AGG_TO_SPINE]
    for name, d in fab.devices.items():
        if d.kind == SPINE:           # racks_per_pod down : uplinks up
            out = [fab.kind(ln.dst) for ln in fab.egress_links(name)]
            assert out.count(LEAF) == racks
            assert out.count(AGG) == shape["uplinks"]
        if d.kind == AGG:             # one plane: cluster switch j of each pod
            j = int(name.split("-")[1])
            assert {ln.dst for ln in fab.egress_links(name)} == {
                f"ctsw-{p}-{j}" for p in range(pods)}


def test_meta_configuration_is_one_to_seven_with_six_hops_across_pods():
    fab = build_three_tier_clos(**META)
    assert (len(fab.devices), len(fab.links)) == (316, 5760)
    up = sum(fab.kind(ln.dst) == AGG for ln in fab.egress_links("ctsw-0-0"))
    down = sum(fab.kind(ln.dst) == LEAF
               for ln in fab.egress_links("ctsw-0-0"))
    assert down == 7 * up == 42
    per_pod = 42 * 2
    flows = [Flow(i, server_name(a), server_name(b),
                  FiveTuple(nic_ip(server_name(a), 3),
                            nic_ip(server_name(b), 3), 49152 + i, 4791))
             for i, (a, b) in enumerate([(0, per_pod), (0, 2), (0, 1)])]
    res = simulate_paths(compile_fabric(fab), flows, [0, 1, 2])
    hops = (res.link_ids >= 0).sum(axis=0)
    assert hops.tolist() == [[6] * 3, [4] * 3, [2] * 3]


def test_meta_walk_selects_among_226_route_table_entries():
    """Across the pod boundary of the meta shape a flow can be at 1, 1,
    16, 32, 16 and 1 devices at its six hops (host, ToR, cluster
    switches, aggregation planes, far cluster switches, far ToR), with
    1, 16, 6, 3, 1 and 1 candidates: 226 entries to select among."""
    from repro.core import jax_engine

    comp = compile_fabric(build_three_tier_clos(**META))
    flows = pod_boundary_flows(META, 0, per_pair=1)
    src, dst, skey, dkey = comp.flow_endpoint_ids(flows)
    hops, exits, widths, shift, base, inverse = \
        jax_engine._route_tables_host(comp, src, skey, dkey, 16)
    assert widths == ((1, 1), (1, 16), (16, 6), (32, 3), (16, 1), (1, 1))
    assert sum(w * c for w, c in widths) == 226
    assert len(exits) == len(flows) == 1344
    assert np.array_equal(exits[inverse, 0], dst)


def test_uneven_uplinks_are_refused():
    with pytest.raises(ValueError, match="evenly"):
        build_three_tier_clos(**{**SMALL, "uplinks": 3})


@pytest.mark.parametrize("seed", [0, 1])
def test_fim_across_pods_matches_tracer_and_reference(seed):
    """The jax engine equals the numpy engine and the hop-by-hop tracer
    under the exact hash, and the reference under murmur, at 1e-12."""
    fab = build_three_tier_clos(**SMALL)
    comp = compile_fabric(fab)
    flows = pod_boundary_flows(SMALL, seed)
    seeds = np.random.default_rng(seed).integers(
        0, 2**62, 6, dtype=np.int64).astype(np.uint64)

    exact = {e: monte_carlo_fim(comp, flows, seeds,
                                spec=SimSpec(engine=e, hash_backend="exact"))
             for e in ("numpy", "jax")}
    wl = workload_from_flows(flows)
    for i, s in enumerate(seeds):
        paths = FlowTracer(fab, EcmpRouting(fab, seed=int(s)), wl,
                           flows).trace().paths
        for mc in exact.values():
            assert mc.aggregate[i] == pytest.approx(fim(paths, fab),
                                                    rel=1e-12)
            for layer, (value, _) in per_layer_fim(paths, fab).items():
                assert mc.per_layer[layer][i] == pytest.approx(value,
                                                               rel=1e-12)

    agg, per_layer = ref.fim_sweep(ref.Topology(fab.to_json()),
                                   _flow_dicts(flows), seeds)
    for e in ("numpy", "jax"):
        mc = monte_carlo_fim(comp, flows, seeds,
                             spec=SimSpec(engine=e, hash_backend="murmur"))
        np.testing.assert_allclose(mc.aggregate, agg, rtol=1e-12)
        assert set(mc.per_layer) == set(per_layer)
        for layer, want in per_layer.items():
            np.testing.assert_allclose(mc.per_layer[layer], want,
                                       rtol=1e-12)
        assert agg.min() > 0

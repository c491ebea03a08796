"""Shortest-path ECMP in the reference, on small fabrics built by hand
with their candidate sets written out, and on the paper testbed against
the two-tier rule it states."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import frontends
from chipbench import reference as ref

HERE = Path(ref.__file__).resolve().parent
TESTBED = HERE / "configs" / "paper-testbed"
SEEDS = np.random.default_rng(16).integers(
    0, 2**62, 1024, dtype=np.int64).astype(np.uint64)


def fabric(kinds: dict[str, str], cables) -> dict:
    """A topology file from {device: kind} and two-way cables
    ``(a, port at a, b, port at b)``."""
    links = []
    for a, pa, b, pb in cables:
        for src, sp, dst, dp in ((a, pa, b, pb), (b, pb, a, pa)):
            links.append({"src": src, "src_port": sp, "dst": dst,
                          "dst_port": dp, "gbps": 100.0,
                          "layer": f"{kinds[src]}-to-{kinds[dst]}"})
    return {"devices": [{"name": n, "kind": k} for n, k in kinds.items()],
            "links": links}


def flow(i: int, src: int, dst: int, nic: int = 0) -> dict:
    return {"flow_id": i, "src": f"srv-{src}", "dst": f"srv-{dst}",
            "src_ip": f"10.{nic}.0.{src}", "dst_ip": f"10.{nic}.0.{dst}",
            "src_port": 49152 + i, "dst_port": 4791, "protocol": 17,
            "bytes": 0, "label": ""}


def named(topo: ref.Topology, f: dict, device: str) -> list[tuple[str, str]]:
    return [(topo.links[i]["src"], topo.links[i]["src_port"])
            for i in topo.candidates(device, f["src_ip"], f["dst_ip"])]


def assert_sets(topo: ref.Topology, f: dict, want: dict) -> None:
    for device, ports in want.items():
        assert named(topo, f, device) == [(device, p) for p in ports], device


def used(topo: ref.Topology, paths: np.ndarray) -> set[tuple[str, str]]:
    return {(topo.links[i]["src"], topo.links[i]["src_port"])
            for i in np.unique(paths[paths >= 0])}


def three_tier() -> dict:
    """Two pods of two leaves and two aggregation switches, two cores;
    one server per leaf.  Each aggregation switch's uplink ports are
    numbered against the cores' names, so hash order (far device, then
    port) differs from port order.  A peer link joins agg-0 and agg-1,
    which no shortest path takes."""
    kinds = {f"srv-{i}": "server" for i in range(4)}
    kinds |= {f"leaf-{i}": "leaf" for i in range(4)}
    kinds |= {f"agg-{i}": "agg" for i in range(4)}
    kinds |= {"core-0": "core", "core-1": "core"}
    cables = [(f"srv-{i}", "nic0p0", f"leaf-{i}", f"dn-srv-{i}")
              for i in range(4)]
    for pod in (0, 1):
        for leaf in (2 * pod, 2 * pod + 1):
            for agg in (2 * pod, 2 * pod + 1):
                cables.append((f"leaf-{leaf}", f"up-agg-{agg}",
                               f"agg-{agg}", f"dn-leaf-{leaf}"))
    for agg in range(4):
        for core in (0, 1):
            cables.append((f"agg-{agg}", f"up-{1 - core}", f"core-{core}",
                           f"dn-agg-{agg}"))
    cables.append(("agg-0", "peer", "agg-1", "peer"))
    return fabric(kinds, cables)


def test_three_tier_clos_of_two_pods():
    topo = ref.Topology(three_tier())
    across, within = flow(0, 0, 3), flow(1, 0, 1)
    assert_sets(topo, across, {
        "srv-0": ["nic0p0"],
        "leaf-0": ["up-agg-0", "up-agg-1"],
        "leaf-1": ["up-agg-0", "up-agg-1"],
        "agg-0": ["up-1", "up-0"],             # core-0, then core-1
        "agg-1": ["up-1", "up-0"],
        "core-0": ["dn-agg-2", "dn-agg-3"],
        "core-1": ["dn-agg-2", "dn-agg-3"],
        "agg-2": ["dn-leaf-3"],
        "agg-3": ["dn-leaf-3"],
        "leaf-2": ["up-agg-2", "up-agg-3"],
        "leaf-3": ["dn-srv-3"],
    })
    assert_sets(topo, within, {
        "leaf-0": ["up-agg-0", "up-agg-1"],
        "agg-0": ["dn-leaf-1"],
        "agg-1": ["dn-leaf-1"],
        "core-0": ["dn-agg-0", "dn-agg-1"],
        "leaf-1": ["dn-srv-1"],
    })
    paths = ref.route(topo, [across, within], SEEDS)
    assert (paths[:, 0] >= 0).sum(axis=0).tolist() == [6] * len(SEEDS)
    assert (paths[:, 1] >= 0).sum(axis=0).tolist() == [4] * len(SEEDS)
    assert {("agg-0", "up-0"), ("agg-1", "up-1"), ("core-1", "dn-agg-2"),
            ("agg-3", "dn-leaf-3")} <= used(topo, paths[:, :1])


def test_nic_dual_homed_into_two_planes():
    """Each NIC's two ports sit on leaves of two planes.  A spine of the
    second plane goes down to the destination NIC's leaf in its own
    plane, where the two-tier rule would look only for the leaf of the
    NIC's first port."""
    kinds = {"srv-0": "server", "srv-1": "server"}
    kinds |= {f"leaf-{p}{i}": "leaf" for p in "ab" for i in (0, 1)}
    kinds |= {"spine-a0": "spine", "spine-a1": "spine", "spine-b0": "spine"}
    cables = []
    for i in (0, 1):
        for port, plane in enumerate("ab"):
            cables.append((f"srv-{i}", f"nic0p{port}", f"leaf-{plane}{i}",
                           f"dn-srv-{i}"))
    for spine in ("a0", "a1", "b0"):
        for i in (0, 1):
            cables.append((f"leaf-{spine[0]}{i}", f"up-spine-{spine}",
                           f"spine-{spine}", f"dn-leaf-{spine[0]}{i}"))
    topo = ref.Topology(fabric(kinds, cables))
    f = flow(0, 0, 1)
    assert_sets(topo, f, {
        "srv-0": ["nic0p0", "nic0p1"],
        "leaf-a0": ["up-spine-a0", "up-spine-a1"],
        "leaf-b0": ["up-spine-b0"],
        "spine-a0": ["dn-leaf-a1"],
        "spine-a1": ["dn-leaf-a1"],
        "spine-b0": ["dn-leaf-b1"],
        "leaf-a1": ["dn-srv-1"],
        "leaf-b1": ["dn-srv-1"],
    })
    paths = ref.route(topo, [f], SEEDS)
    assert (paths >= 0).sum(axis=0).tolist() == [[4] * len(SEEDS)]
    assert used(topo, paths) == {
        ("srv-0", "nic0p0"), ("srv-0", "nic0p1"),
        ("leaf-a0", "up-spine-a0"), ("leaf-a0", "up-spine-a1"),
        ("leaf-b0", "up-spine-b0"), ("spine-a0", "dn-leaf-a1"),
        ("spine-a1", "dn-leaf-a1"), ("spine-b0", "dn-leaf-b1"),
        ("leaf-a1", "dn-srv-1"), ("leaf-b1", "dn-srv-1")}


def _testbed() -> tuple[dict, list[dict]]:
    return (json.loads((TESTBED / "fabric.json").read_text()),
            json.loads((TESTBED / "flows.json").read_text())["flows"])


def test_testbed_without_spine0_to_leaf2():
    """All four links from spine-0 down to leaf-2 have failed: toward a
    NIC on leaf-2 no leaf chooses an uplink to spine-0."""
    fab, flows = _testbed()
    fab["links"] = [ln for ln in fab["links"]
                    if (ln["src"], ln["dst"]) != ("spine-0", "leaf-2")]
    topo = ref.Topology(fab)
    to_leaf2 = next(f for f in flows if f["dst_ip"].startswith("10.0.")
                    and f["dst"] == "srv-8")
    ups = [f"up-spine-{s}-{k}" for s in (1, 2, 3) for k in range(4)]
    assert_sets(topo, to_leaf2, {
        "leaf-0": ups,
        "leaf-1": ups,
        "leaf-3": ups,
        "spine-1": [f"down-leaf-2-{k}" for k in range(4)],
        "leaf-2": ["down-srv-8-0-0", "down-srv-8-0-1"],
    })
    paths = ref.route(topo, flows, SEEDS)
    mine = [topo.nic(f["dst_ip"]) in {(f"srv-{i}", 0) for i in range(8, 16)}
            for f in flows]
    hit = used(topo, paths[:, np.flatnonzero(mine)])
    assert not any(p.startswith("up-spine-0-") for _, p in hit)
    assert any(p.startswith("up-spine-0-")
               for _, p in used(topo, paths[:, ~np.array(mine)]))


def test_unreachable_nic_is_a_flow_that_did_not_arrive():
    fab, flows = _testbed()
    fab["links"] = [ln for ln in fab["links"] if ln["dst"] != "leaf-2"
                    or not ln["src"].startswith("spine-")]
    with pytest.raises(RuntimeError, match="did not arrive"):
        ref.route(ref.Topology(fab), flows, SEEDS[:8])


def two_tier_candidates(topo: ref.Topology, device: str, f: dict) -> list[int]:
    """The testbed's stated rule: a host hashes over its source NIC's
    ports; a leaf goes down to the destination NIC's ports when attached
    and otherwise hashes over all its spine uplinks; a spine hashes over
    its links to the leaf of the destination NIC's first port."""
    L = topo.links

    def nic_ports(ip):
        server, nic = topo.nic(ip)
        return server, nic, [i for i, ln in enumerate(L)
                             if ln["src"] == server
                             and ln["src_port"].startswith(f"nic{nic}p")]

    if topo.kind[device] == "server":
        return sorted(nic_ports(f["src_ip"])[2],
                      key=lambda i: L[i]["src_port"])
    dserver, dnic, ports = nic_ports(f["dst_ip"])
    dleaf = L[ports[0]]["dst"]
    if device == dleaf:
        return sorted((i for i, ln in enumerate(L) if ln["src"] == device
                       and ln["dst"] == dserver
                       and ln["dst_port"].startswith(f"nic{dnic}p")),
                      key=lambda i: L[i]["src_port"])
    if topo.kind[device] == "leaf":
        return sorted((i for i, ln in enumerate(L) if ln["src"] == device
                       and topo.kind[ln["dst"]] == "spine"),
                      key=lambda i: (L[i]["dst"], L[i]["src_port"]))
    return sorted((i for i, ln in enumerate(L)
                   if ln["src"] == device and ln["dst"] == dleaf),
                  key=lambda i: L[i]["src_port"])


def two_tier_walk(topo: ref.Topology, f: dict, seeds: np.ndarray):
    """(16, S) link ids of one flow under the two-tier rule, -1 after
    arrival: hop by hop, one device at a time."""
    fields = ref.flow_fields(f)
    seed_lo = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    at = np.full(len(seeds), topo.index[f["src"]])
    done = np.zeros(len(seeds), bool)
    out = np.full((16, len(seeds)), -1, np.int64)
    for h in range(16):
        for v in np.unique(at[~done]):
            sel = np.flatnonzero((at == v) & ~done)
            device = topo.names[v]
            cands = np.array(two_tier_candidates(topo, device, f))
            pick = np.zeros(sel.size, np.int64)
            if len(cands) > 1:
                hsh = ref.murmur3(fields, seed_lo[sel]
                                  ^ np.uint32(ref.crc32(device)))
                pick = (hsh % np.uint32(len(cands))).astype(np.int64)
            out[h, sel] = cands[pick]
        moved = out[h] >= 0
        at = np.where(moved, topo.link_dst[np.maximum(out[h], 0)], at)
        done |= moved & topo.is_server[at]
    assert done.all() and (at == topo.index[f["dst"]]).all()
    return out


def test_testbed_routes_as_its_two_tier_rule():
    """Same candidate sets in the same order at every (flow, device),
    and the same link ids on all 256 flows x 1024 seeds."""
    fab, flows = _testbed()
    topo = ref.Topology(fab)
    switches = [n for n, k in topo.kind.items() if k != "server"]
    for f in flows:
        for device in [f["src"], *switches]:
            assert topo.candidates(device, f["src_ip"], f["dst_ip"]) == (
                two_tier_candidates(topo, device, f)), (f["flow_id"], device)
    paths = ref.route(topo, flows, SEEDS)
    H = paths.shape[0]
    for n, f in enumerate(flows):
        want = two_tier_walk(topo, f, SEEDS)
        assert (want[H:] == -1).all()
        np.testing.assert_array_equal(paths[:, n], want[:H])


def test_testbed_walk_tables_keep_their_bytes():
    """The walk roofline's least bytes read the reference's tables:
    K = 32 NICs, C = 16 candidates, 4 hops on the testbed."""
    traffic = json.loads(
        (HERE / "traffic" / "ecmp-throughput-1024.json").read_text())
    fam = frontends.family(TESTBED, traffic)
    assert len(fam.topo.nics) == 32
    assert fam.topo.largest_fanout() == 16
    assert fam.table_bytes() == 53_464
    assert fam.hops(SEEDS) == 4

"""Routing policies: ECMP hashing (with VXLAN entropy reduction) and
preprogrammed static routing.

Every forwarding decision in the fabric is a choice among a set of
equal-cost egress links.  ``EcmpRouting`` picks by hashing flow headers —
per switch, with a per-switch seed, exactly how real fabrics behave (and
why collisions differ hop to hop).  ``StaticRouting`` consults a
preprogrammed table (the paper's second configuration).

The hash is a deterministic integer mix (splitmix64 finalizer) over CRC32s
of the header fields — stable across runs and processes, unlike Python's
salted ``hash``.
"""

from __future__ import annotations

import dataclasses
import zlib
from collections import defaultdict
from collections.abc import Sequence

from .fabric import Fabric, Link, SERVER, host_of_nic_ip, port_nic
from .flows import Flow

_MASK = (1 << 64) - 1

# Hash-field presets.  VXLAN encapsulation hides the inner 5-tuple from
# transit switches; entropy survives only via the outer UDP source port
# (derived from an inner-header hash) — fewer effective fields, more
# collisions (paper Section II).
FIELDS_5TUPLE = "5tuple"
FIELDS_VXLAN = "vxlan"
FIELDS_IP_PAIR = "ip-pair"


def _mix64(x: int) -> int:
    x &= _MASK
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _MASK
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _MASK
    return (x ^ (x >> 31)) & _MASK


def _crc(s: str) -> int:
    return zlib.crc32(s.encode())


HASH_INIT = 0x9E3779B97F4A7C15


def ecmp_hash(fields: Sequence[int], seed: int) -> int:
    h = _mix64(seed ^ HASH_INIT)
    for f in fields:
        h = _mix64(h ^ (f & _MASK))
    return h


def device_seed(device: str, seed: int) -> int:
    """The effective per-switch hash seed: every device salts the shared
    run seed with a stable digest of its own name (real switches differ in
    per-ASIC seeds the same way — that is why collisions differ hop to
    hop)."""
    return _crc(device) ^ seed


def flow_hash_fields(flow: Flow, mode: str) -> list[int]:
    t = flow.tuple5
    if mode == FIELDS_5TUPLE:
        return [_crc(t.src_ip), _crc(t.dst_ip), t.src_port, t.dst_port, t.protocol]
    if mode == FIELDS_VXLAN:
        # Outer header: (outer src ip, outer dst ip, outer UDP sport).  The
        # sport is the VTEP's hash of the inner 5-tuple folded to 14 bits.
        inner = ecmp_hash(
            [_crc(t.src_ip), _crc(t.dst_ip), t.src_port, t.dst_port, t.protocol],
            seed=0x564C414E,  # "VLAN"
        )
        return [_crc(t.src_ip), _crc(t.dst_ip), inner % 16384]
    if mode == FIELDS_IP_PAIR:
        return [_crc(t.src_ip), _crc(t.dst_ip)]
    raise ValueError(f"unknown hash-field mode: {mode}")


def flow_fields_matrix(flows: Sequence[Flow], mode: str):
    """Integer hash fields for many flows as a dense ``(N, F)`` uint64
    array — the batched twin of ``flow_hash_fields`` (identical values),
    consumed by ``vector_sim``.  Imported lazily so the tracer stays
    numpy-free."""
    import numpy as np

    return np.array(
        [flow_hash_fields(f, mode) for f in flows], np.uint64
    ).reshape(len(flows), -1)


# ---------------------------------------------------------------------------
# Candidate-set computation (the "equal cost" part of ECMP)
# ---------------------------------------------------------------------------


class Forwarder:
    """Computes the equal-cost candidate egress set at each device:
    shortest-path ECMP over the topology file, for any number of tiers.

      * a host (kind ``server``) hashes over the ports of the NIC that
        owns the flow's source address;
      * any other device hashes over its egress links that lie on a
        shortest path to the destination NIC: a path ends on one of that
        NIC's ports (``nic<k>p<m>``) and never passes through a host.

    Every set is ordered by (far device name, egress port name), the
    order the hash indexes into.  On a two-tier Clos this is the familiar
    rule (a leaf goes down to an attached destination NIC and otherwise
    hashes over its spine uplinks; a spine takes its links to the
    destination's leaf); tiers, planes and failed links follow from it.
    The shortest paths toward a NIC come from one backward breadth-first
    search per set of switches the NIC is attached to, made when first
    needed and kept.
    """

    def __init__(self, fabric: Fabric):
        self.fabric = fabric
        self._server_of_index: dict[int, str] = {}
        nic_links: dict[tuple[str, int], list[Link]] = {}
        self._ingress: dict[str, list[Link]] = defaultdict(list)
        for ln in fabric.links:
            self._ingress[ln.dst].append(ln)
            nic = port_nic(ln.src_port)
            if fabric.kind(ln.src) == SERVER and nic is not None:
                nic_links.setdefault((ln.src, nic), []).append(ln)
        for name, dev in fabric.devices.items():
            suffix = name.rsplit("-", 1)[-1]
            if dev.kind == SERVER and suffix.isdigit():
                self._server_of_index[int(suffix)] = name
        #: {(server, nic index): the NIC's egress links, in hash order}
        self.nic_links = {k: _hash_order(v) for k, v in nic_links.items()}
        self._toward: dict[tuple[str, int], dict[str, list[Link]]] = {}
        self._up: dict[frozenset[str], dict[str, list[Link]]] = {}

    def _nic_of_ip(self, ip: str) -> tuple[str, int]:
        """(server, nic index) owning ``ip`` (``fabric.nic_ip``'s plan)."""
        idx, nic = host_of_nic_ip(ip)
        if idx not in self._server_of_index:
            raise KeyError(f"no server for ip {ip}")
        return self._server_of_index[idx], nic

    def attachment(self, nic: tuple[str, int]) -> dict[str, list[Link]]:
        """{switch: its links onto the ports of ``nic``, in hash order}."""
        server, k = nic
        onto: dict[str, list[Link]] = {}
        for ln in self._ingress[server]:
            if (port_nic(ln.dst_port) == k
                    and self.fabric.kind(ln.src) != SERVER):
                onto.setdefault(ln.src, []).append(ln)
        return {v: _hash_order(links) for v, links in onto.items()}

    def upstream(self, first: frozenset[str]) -> dict[str, list[Link]]:
        """{switch: its egress links on a shortest path to any of the
        switches ``first``, in hash order}, for the switches outside
        ``first``: a backward breadth-first search over the links into
        each device that never passes through a host.  Every NIC behind
        the same switches shares it."""
        hit = self._up.get(first)
        if hit is not None:
            return hit
        fab = self.fabric
        dist = dict.fromkeys(first, 0)
        cands: dict[str, list[Link]] = {}
        frontier, d = sorted(first), 0
        while frontier:
            nxt = []
            for u in frontier:
                for ln in self._ingress[u]:
                    w = ln.src
                    if fab.kind(w) == SERVER:
                        continue
                    if w not in dist:
                        dist[w] = d + 1
                        nxt.append(w)
                    if dist[w] == d + 1:
                        cands.setdefault(w, []).append(ln)
            frontier, d = nxt, d + 1
        out = {v: _hash_order(links) for v, links in cands.items()}
        self._up[first] = out
        return out

    def toward(self, nic: tuple[str, int]) -> dict[str, list[Link]]:
        """{switch: its egress links on a shortest path to ``nic``, in
        hash order}."""
        hit = self._toward.get(nic)
        if hit is None:
            onto = self.attachment(nic)
            hit = {**self.upstream(frozenset(onto)), **onto}
            self._toward[nic] = hit
        return hit

    def candidates(self, device: str, flow: Flow) -> list[Link]:
        if self.fabric.kind(device) == SERVER:
            server, nic = self._nic_of_ip(flow.tuple5.src_ip)
            assert server == device, (server, device, "flow must start at src")
            return self.nic_links.get((device, nic), [])
        return self.toward(self._nic_of_ip(flow.tuple5.dst_ip)).get(device, [])


def _hash_order(links: list[Link]) -> list[Link]:
    return sorted(links, key=lambda ln: (ln.dst, ln.src_port))


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------


class RoutingPolicy:
    """Interface: the forwarding decision a device would reveal via its
    hash-visibility CLI (switches) or driver/route table (servers)."""

    def egress(self, device: str, flow: Flow, ingress_port: str | None) -> Link:
        raise NotImplementedError


@dataclasses.dataclass
class EcmpRouting(RoutingPolicy):
    fabric: Fabric
    seed: int = 0
    fields: str = FIELDS_5TUPLE

    def __post_init__(self):
        self.forwarder = Forwarder(self.fabric)

    def egress(self, device: str, flow: Flow, ingress_port: str | None) -> Link:
        cands = self.forwarder.candidates(device, flow)
        if len(cands) == 1:
            return cands[0]
        h = ecmp_hash(flow_hash_fields(flow, self.fields),
                      device_seed(device, self.seed))
        return cands[h % len(cands)]


class StaticRouting(RoutingPolicy):
    """Preprogrammed routing: an explicit (device, flow) -> egress-port map,
    as produced by placement.static_route_assignment.  Falls back to the
    single candidate when no choice exists."""

    def __init__(self, fabric: Fabric, table: dict[tuple[str, int], str]):
        self.fabric = fabric
        self.forwarder = Forwarder(fabric)
        self.table = table  # (device, flow_id) -> src_port

    def egress(self, device: str, flow: Flow, ingress_port: str | None) -> Link:
        port = self.table.get((device, flow.flow_id))
        if port is not None:
            return self.fabric.link_from_port(device, port)
        cands = self.forwarder.candidates(device, flow)
        if len(cands) != 1:
            raise KeyError(
                f"static table has no entry for ({device}, flow {flow.flow_id}) "
                f"and {len(cands)} candidates exist"
            )
        return cands[0]

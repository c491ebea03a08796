"""Compile a ``Fabric`` + forwarding logic into dense arrays.

The hop-by-hop tracer asks ``Forwarder.candidates(device, flow)`` at every
hop — a Python dict walk per flow per hop.  For Monte-Carlo sweeps over
thousands of hash seeds that is the bottleneck, so we compile the fabric
once into integer tables the vectorized engine (``vector_sim``) can index
with whole arrays:

* every device gets an id, a ``crc32(name)`` (the per-switch hash-seed
  component of ``EcmpRouting``), and a server/switch flag;
* every link gets an id plus dst-device / layer / capacity columns;
* the equal-cost candidate set at ``(device, flow)`` depends only on the
  device and one *NIC key* — the flow's **src** (server, nic) while the
  packet is on the source host, its **dst** (server, nic) everywhere else
  (forwarding is destination-routed).  So all candidate sets live in
  one padded ``(V, K, C_max)`` table of link ids, filled from the real
  ``Forwarder``'s shortest-path sets so candidate *order* — which the
  hash indexes into — is identical to the Python path by construction.

The rows above a NIC's own switches are one backward search per set of
attachment switches, shared by every NIC behind them and written into
the table for all of those NICs at once; compilation runs in the
``compile_fabric`` span.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .ecmp import Forwarder, _crc
from .fabric import Fabric, Link, SERVER, nic_ip
from .spans import count, span


@dataclasses.dataclass(frozen=True)
class CompiledFabric:
    """Dense-array view of a fabric, consumed by ``vector_sim``."""

    fabric: Fabric
    # devices
    device_names: list[str]         # device id -> name
    device_id: dict[str, int]       # name -> device id
    dev_crc: np.ndarray             # (V,) uint64  crc32(name)
    is_server: np.ndarray           # (V,) bool
    # links
    links: list[Link]               # link id -> Link
    link_src: np.ndarray            # (L,) int32  src device id
    link_dst: np.ndarray            # (L,) int32  dst device id
    link_layer: np.ndarray          # (L,) int32  layer id
    layer_names: list[str]          # layer id -> name (fabric.layers order)
    link_gbps: np.ndarray           # (L,) float64
    # NIC keys: one per (server, nic index), i.e. one per NIC IP
    key_of_ip: dict[str, int]       # nic ip -> key id
    key_server: np.ndarray          # (K,) int32  device id owning the key
    #: distinct NIC indices present on the fabric's servers, sorted — the
    #: authoritative record of the NIC plan (``resolve_flows`` synthesizes
    #: against it; sparse numbering like (0, 4) survives, where re-parsing
    #: IP strings for a max would invent NICs that do not exist)
    nic_indices: tuple[int, ...]
    # candidate tables
    cand: np.ndarray                # (V, K, C_max) int32 link ids, -1 padded
    cand_n: np.ndarray              # (V, K) int32  candidate count

    @property
    def num_devices(self) -> int:
        return len(self.device_names)

    @property
    def num_links(self) -> int:
        return len(self.links)

    def flow_endpoint_ids(
        self, flows,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Per-flow (src device id, dst device id, src key, dst key)."""
        src_dev = np.array([self.device_id[f.src] for f in flows], np.int32)
        dst_dev = np.array([self.device_id[f.dst] for f in flows], np.int32)
        src_key = np.array(
            [self.key_of_ip[f.tuple5.src_ip] for f in flows], np.int32)
        dst_key = np.array(
            [self.key_of_ip[f.tuple5.dst_ip] for f in flows], np.int32)
        return src_dev, dst_dev, src_key, dst_key


def compile_fabric(fabric: Fabric) -> CompiledFabric:
    with span("compile_fabric"):
        comp = _compile(fabric)
        count("devices", comp.num_devices)
        count("links", comp.num_links)
        count("keys", len(comp.key_of_ip))
        count("table_bytes", comp.cand.nbytes + comp.cand_n.nbytes)
    return comp


def _compile(fabric: Fabric) -> CompiledFabric:
    fwd = Forwarder(fabric)
    device_names = list(fabric.devices)
    device_id = {name: i for i, name in enumerate(device_names)}
    dev_crc = np.array([_crc(n) for n in device_names], np.uint64)
    is_server = np.array(
        [fabric.kind(n) == SERVER for n in device_names], bool)

    links = list(fabric.links)
    link_id = {ln.name: i for i, ln in enumerate(links)}
    layer_names = fabric.layers
    layer_id = {name: i for i, name in enumerate(layer_names)}
    link_src = np.array([device_id[ln.src] for ln in links], np.int32)
    link_dst = np.array([device_id[ln.dst] for ln in links], np.int32)
    link_layer = np.array([layer_id[ln.layer] for ln in links], np.int32)
    link_gbps = np.array([ln.gbps for ln in links], np.float64)

    # NIC keys, in deterministic (server name, nic index) order.
    nic_keys = sorted(fwd.nic_links)
    key_of_ip = {nic_ip(srv, nic): k for k, (srv, nic) in enumerate(nic_keys)}
    key_server = np.array(
        [device_id[srv] for srv, _ in nic_keys], np.int32)

    # Candidate table: the source host's row of a key is its NIC's ports;
    # a switch's row is its shortest-path set toward the key's NIC, taken
    # from one search per set of attachment switches.
    def ids(cands: list[Link]) -> list[int]:
        return [link_id[c.name] for c in cands]

    attach = [fwd.attachment(key) for key in nic_keys]
    groups: dict[frozenset[str], list[int]] = {}
    for k, onto in enumerate(attach):
        groups.setdefault(frozenset(onto), []).append(k)
    upstream = {first: fwd.upstream(first) for first in groups}
    c_max = max([1, *map(len, fwd.nic_links.values()), *(
        len(c) for rows in [*upstream.values(), *attach]
        for c in rows.values())])

    V, K = len(device_names), len(nic_keys)
    cand = np.full((V, K, c_max), -1, np.int32)
    cand_n = np.zeros((V, K), np.int32)
    for first, keys in groups.items():        # shared by the group's keys
        rows = np.full((V, c_max), -1, np.int32)
        n = np.zeros(V, np.int32)
        for v, c in upstream[first].items():
            rows[device_id[v], : len(c)] = ids(c)
            n[device_id[v]] = len(c)
        cand[:, keys] = rows[:, None]
        cand_n[:, keys] = n[:, None]
    for k, key in enumerate(nic_keys):
        for v, c in [*attach[k].items(), (key[0], fwd.nic_links[key])]:
            cand[device_id[v], k, : len(c)] = ids(c)
            cand_n[device_id[v], k] = len(c)

    return CompiledFabric(
        fabric=fabric,
        device_names=device_names,
        device_id=device_id,
        dev_crc=dev_crc,
        is_server=is_server,
        links=links,
        link_src=link_src,
        link_dst=link_dst,
        link_layer=link_layer,
        layer_names=layer_names,
        link_gbps=link_gbps,
        key_of_ip=key_of_ip,
        key_server=key_server,
        nic_indices=tuple(sorted({nic for _, nic in nic_keys})),
        cand=cand,
        cand_n=cand_n,
    )

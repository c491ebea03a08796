"""Plain reference of the FlowTracer Monte-Carlo semantics.

Written from the deployment's own statement (the topology file, the flow
table, the forwarding rules and hash of ``configs/<name>/config.json``,
the demand mode of ``traffic/<mix>.json``), and independent of
the program under test: it imports nothing of ``repro`` and takes nothing
the program made.  Everything is plain numpy:

* routing: shortest-path ECMP over the topology file.  A host (a device
  of kind ``server``) hashes over the ports of the NIC that owns the
  flow's source address.  At any other device the candidates are the
  egress links that lie on a shortest path to the destination NIC: a
  path ends on one of that NIC's ports (``nic<k>p<m>``) and never passes
  through a host.  Any number of tiers, planes or device kinds follows
  from this, and so do failed links (a link that is not in the file).
  Candidates are ordered by (far device name, egress port name), as
  strings; the choice is ``murmur3(fields, crc32(device) ^ seed) %
  candidates`` over the flow's 5-tuple fields, and a single candidate
  takes no hash.  A flow that meets a device with no candidate did not
  arrive, which is an error.  The two-tier wording of the paper
  testbed's ``config.json`` (a leaf goes down to the destination NIC's
  ports when attached and otherwise hashes over its spine uplinks; a
  spine hashes over its links to the destination's leaf) is this rule
  on a fabric in which every spine reaches every leaf;
* link counts and FIM: the mean absolute percentage error of each
  layer's link loads against that layer's ideal, link-weighted;
* max-min rates: classic water filling, one global bottleneck level per
  round, weighted by demand, seed by seed.

The shortest paths toward each NIC come from a backward breadth-first
search over the links into each device, made when a flow first needs
them and kept; NICs behind the same switches share one search.  ``route`` walks all flows and seeds at once, hop by hop,
one device at a time.

``dtype`` sets the precision of every rate, share, time and FIM
computation.  ``float64`` is the reference; one precision below it is
the control that a sound comparison has to reject.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np

HOST = "server"

_C1, _C2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)
_F1, _F2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)


def crc32(text: str) -> int:
    return zlib.crc32(text.encode())


def _rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def murmur3(fields, init: np.ndarray) -> np.ndarray:
    """murmur3_32 of the 32-bit words ``fields`` (each an int or a uint32
    array of ``init``'s shape) with ``init`` (uint32, any shape) as the
    seed, one hash per element of ``init``."""
    h = init.astype(np.uint32)
    with np.errstate(over="ignore"):
        for f in fields:
            k = np.asarray(f, np.uint32) * _C1
            k = _rotl(k, 15) * _C2
            h = _rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)
        h = h ^ (h >> np.uint32(16))
        h = h * _F1
        h = h ^ (h >> np.uint32(13))
        h = h * _F2
        return h ^ (h >> np.uint32(16))


# ---------------------------------------------------------------------------
# topology and forwarding
# ---------------------------------------------------------------------------


def _port_nic(port: str) -> int | None:
    """``k`` of a host port ``nic<k>p<m>``, else None."""
    if not port.startswith("nic") or "p" not in port[3:]:
        return None
    return int(port[3:port.index("p", 3)])


class Topology:
    """The topology file: devices, unidirectional links, the NIC plan
    ``10.<nic>.<index // 256>.<index % 256>`` and shortest-path ECMP."""

    def __init__(self, fabric: dict):
        self.kind = {d["name"]: d["kind"] for d in fabric["devices"]}
        self.links = fabric["links"]
        self.num_links = len(self.links)
        self.gbps = np.array([ln["gbps"] for ln in self.links], np.float64)
        self.layers: list[str] = []
        for ln in self.links:
            if ln["layer"] not in self.layers:
                self.layers.append(ln["layer"])
        self.link_layer = np.array(
            [self.layers.index(ln["layer"]) for ln in self.links])
        self.names = list(self.kind)
        self.index = {n: i for i, n in enumerate(self.names)}
        self.link_src = np.array([self.index[ln["src"]] for ln in self.links])
        self.link_dst = np.array([self.index[ln["dst"]] for ln in self.links])
        self.is_server = np.array([self.kind[n] == HOST for n in self.names])
        self.server_of_index = {int(n.split("-")[-1]): n
                                for n, k in self.kind.items() if k == HOST}
        self._toward: dict[tuple[str, int], dict[int, list[int]]] = {}
        self._up: dict[frozenset[int], dict[int, list[int]]] = {}

    def nic(self, ip: str) -> tuple[str, int]:
        a = [int(p) for p in ip.split(".")]
        return self.server_of_index[a[2] * 256 + a[3]], a[1]

    def _hash_order(self, links) -> list[int]:
        L = self.links
        return sorted(links, key=lambda i: (L[i]["dst"], L[i]["src_port"]))

    @functools.cached_property
    def nics(self) -> dict[tuple[str, int], list[int]]:
        """{(host, nic): the NIC's egress links, in hash order}."""
        out: dict[tuple[str, int], list[int]] = {}
        for i, ln in enumerate(self.links):
            k = _port_nic(ln["src_port"])
            if self.kind[ln["src"]] == HOST and k is not None:
                out.setdefault((ln["src"], k), []).append(i)
        return {key: self._hash_order(v) for key, v in out.items()}

    @functools.cached_property
    def _ingress(self) -> list[list[int]]:
        into: list[list[int]] = [[] for _ in self.names]
        for i, v in enumerate(self.link_dst):
            into[v].append(i)
        return into

    def toward(self, nic: tuple[str, int]) -> dict[int, list[int]]:
        """{switch index: its egress links on a shortest path to ``nic``,
        in hash order}.  A path ends on a link onto one of the NIC's
        ports and never passes through a host."""
        hit = self._toward.get(nic)
        if hit is not None:
            return hit
        server, k = nic
        onto: dict[int, list[int]] = {}
        for i in self._ingress[self.index[server]]:
            v = int(self.link_src[i])
            if _port_nic(self.links[i]["dst_port"]) == k and not (
                    self.is_server[v]):
                onto.setdefault(v, []).append(i)
        out = dict(self._upstream(frozenset(onto)))
        out.update((v, self._hash_order(c)) for v, c in onto.items())
        self._toward[nic] = out
        return out

    def _upstream(self, first: frozenset[int]) -> dict[int, list[int]]:
        """{switch index: its egress links on a shortest path to any of
        the switches ``first``, in hash order}, for the switches outside
        ``first``: a backward breadth-first search over the links into
        each device that never passes through a host.  Every NIC behind
        the same switches shares it."""
        hit = self._up.get(first)
        if hit is not None:
            return hit
        dist = dict.fromkeys(first, 0)
        cands: dict[int, list[int]] = {}
        frontier, d = sorted(first), 0
        while frontier:
            nxt = []
            for u in frontier:
                for i in self._ingress[u]:
                    w = int(self.link_src[i])
                    if self.is_server[w]:
                        continue
                    if w not in dist:
                        dist[w] = d + 1
                        nxt.append(w)
                    if dist[w] == d + 1:
                        cands.setdefault(w, []).append(i)
            frontier, d = nxt, d + 1
        out = {v: self._hash_order(c) for v, c in cands.items()}
        self._up[first] = out
        return out

    def candidates(self, device: str, src_ip: str, dst_ip: str) -> list[int]:
        """Equal-cost egress links of a flow at ``device``, in hash
        order."""
        if self.kind[device] == HOST:
            server, k = self.nic(src_ip)
            if server != device:
                raise ValueError(f"{device} does not own {src_ip}")
            return self.nics.get((server, k), [])
        return self.toward(self.nic(dst_ip)).get(self.index[device], [])

    def largest_fanout(self) -> int:
        """The largest candidate set over every device and NIC."""
        return max(max([len(v), *map(len, self.toward(key).values())])
                   for key, v in self.nics.items())


def flow_fields(flow: dict) -> list[int]:
    return [crc32(flow["src_ip"]), crc32(flow["dst_ip"]), flow["src_port"],
            flow["dst_port"], flow["protocol"]]


def route(topo: Topology, flows: list[dict], seeds: np.ndarray,
          max_hops: int = 16) -> np.ndarray:
    """(hops, N, S) link ids of every single-path flow under every seed,
    -1 after arrival."""
    N, S = len(flows), len(seeds)
    fields = np.array([flow_fields(f) for f in flows], np.uint32)
    seed_lo = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    src_nic = [topo.nic(f["src_ip"]) for f in flows]
    dst_nic = [topo.nic(f["dst_ip"]) for f in flows]
    keys = sorted(set(src_nic) | set(dst_nic))
    key_id = {k: i for i, k in enumerate(keys)}
    src_key = np.array([key_id[k] for k in src_nic])
    dst_key = np.array([key_id[k] for k in dst_nic])
    dst_dev = np.array([topo.index[f["dst"]] for f in flows])

    @functools.cache
    def table(v: int) -> tuple[np.ndarray, np.ndarray]:
        """Device ``v``'s candidate lists as (keys, C) link ids padded
        with -1 and (keys,) counts: row ``k`` is for ``keys[k]`` as the
        flow's source NIC at a host, its destination NIC elsewhere."""
        if topo.is_server[v]:
            lists = [topo.nics.get(k, []) if k[0] == topo.names[v] else []
                     for k in keys]
        else:
            lists = [topo.toward(k).get(v, []) for k in keys]
        n = np.array([len(c) for c in lists], np.int64)
        cand = np.full((len(keys), max(n.max(), 1)), -1, np.int64)
        for r, c in enumerate(lists):
            cand[r, :len(c)] = c
        return cand, n

    at = np.repeat(np.array([topo.index[f["src"]] for f in flows]), S)
    done = np.zeros(N * S, bool)
    rows = []
    for _ in range(max_hops):
        cells = np.flatnonzero(~done)
        if cells.size == 0:
            break
        cells = cells[np.argsort(at[cells], kind="stable")]
        cuts = np.flatnonzero(np.diff(at[cells])) + 1
        row = np.full(N * S, -1, np.int64)
        for grp in np.split(cells, cuts):
            v = int(at[grp[0]])
            f, s = np.divmod(grp, S)
            cand, n = table(v)
            key = (src_key if topo.is_server[v] else dst_key)[f]
            cnt = n[key]
            if (cnt == 0).any():
                bad = flows[int(f[np.argmax(cnt == 0)])]["flow_id"]
                raise RuntimeError(f"flow {bad} did not arrive: no path "
                                   f"from {topo.names[v]}")
            pick = np.zeros(grp.size, np.int64)
            many = cnt > 1
            if many.any():
                h = murmur3(fields[f[many]].T, seed_lo[s[many]]
                            ^ np.uint32(crc32(topo.names[v])))
                pick[many] = h % cnt[many].astype(np.uint32)
            row[grp] = cand[key, pick]
        rows.append(row.reshape(N, S))
        at[cells] = topo.link_dst[row[cells]]
        done[cells] = topo.is_server[at[cells]]
    arrived = (done & (at == np.repeat(dst_dev, S))).reshape(N, S)
    if not arrived.all():
        bad = flows[int(np.argmin(arrived.all(axis=1)))]["flow_id"]
        raise RuntimeError(f"flow {bad} did not arrive")
    return np.stack(rows)


# ---------------------------------------------------------------------------
# FIM
# ---------------------------------------------------------------------------


def fim(topo: Topology, paths: np.ndarray, weights: np.ndarray,
        dtype=np.float64) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Per-seed aggregate and per-layer FIM of the link loads of
    ``paths``, (hops, N, S) link ids."""
    S = paths.shape[2]
    # numpy's scatter-add has no bfloat16 loop: below float32 the counts
    # gather in float32 and are then rounded to ``dtype``
    acc = dtype if np.dtype(dtype).itemsize >= 4 else np.float32
    counts = np.zeros((S, topo.num_links), acc)
    by_flow = paths.transpose(1, 0, 2)          # flow, hop, seed order
    f, _, s = np.nonzero(by_flow >= 0)
    np.add.at(counts, (s, by_flow[by_flow >= 0]),
              np.asarray(weights).astype(acc)[f])
    counts = counts.astype(dtype)
    num, den = np.zeros(S, dtype), np.zeros(S, dtype)
    per_layer = {}
    for li, name in enumerate(topo.layers):
        c = counts[:, topo.link_layer == li]
        n = dtype(c.shape[1])
        total = c.sum(axis=1)
        live = total > 0
        ideal = np.where(live, total / n, dtype(1))
        mape = dtype(100) / n * (np.abs(c - ideal[:, None])
                                 / ideal[:, None]).sum(axis=1)
        mape = np.where(live, mape, dtype(0)).astype(dtype)
        if live.any():
            per_layer[name] = mape
            num += np.where(live, mape * n, dtype(0))
            den += np.where(live, n, dtype(0))
    agg = np.where(den > 0, num / np.where(den > 0, den, 1), 0).astype(dtype)
    return agg, per_layer


# ---------------------------------------------------------------------------
# max-min rates
# ---------------------------------------------------------------------------


def water_fill(paths: np.ndarray, w: np.ndarray, cap: np.ndarray,
               dtype=np.float64) -> np.ndarray:
    """Weighted max-min rates of the flows of one seed.

    ``paths`` (N, H) link ids (-1 pads), ``w`` (N,) weights, ``cap`` (L,)
    capacities.  Each round finds the lowest fair share per unit weight
    over all links and freezes every column that crosses a link at that
    level."""
    N, L = paths.shape[0], cap.size
    rates = np.zeros(N, dtype)
    res = cap.astype(dtype)
    live = np.ones(N, bool)
    haslink = (paths >= 0).any(axis=1)
    rates[live & ~haslink] = np.inf
    live &= haslink
    safe = np.where(paths >= 0, paths, L)
    while live.any():
        wsum = np.bincount(safe[live].ravel(),
                           weights=np.repeat(w[live], paths.shape[1]),
                           minlength=L + 1)[:L].astype(dtype)
        used = wsum > 0
        share = np.full(L + 1, np.inf, dtype)
        share[:L][used] = res[used] / wsum[used]
        level = share[:L][used].min()
        hit = share == level
        freeze = live & hit[safe].any(axis=1)
        rates[freeze] = w[freeze] * level
        drain = np.bincount(safe[freeze].ravel(),
                            weights=np.repeat(rates[freeze], paths.shape[1]),
                            minlength=L + 1)[:L].astype(dtype)
        res = (res - drain).astype(dtype)
        live &= ~freeze
    return rates


def demand_weights(flows: list[dict], mode: str) -> np.ndarray:
    b = np.array([f["bytes"] for f in flows], np.float64)
    if mode == "uniform" or (b == b[0]).all():
        return np.ones(len(flows))
    b = np.maximum(b, 1.0)
    return b / b.mean()


def throughput(topo: Topology, flows: list[dict], seeds: np.ndarray,
               demand: str = "uniform", dtype=np.float64) -> np.ndarray:
    """(N, S) max-min rates of single-path ECMP flows."""
    paths = route(topo, flows, seeds)
    w = demand_weights(flows, demand).astype(dtype)
    cap = topo.gbps.astype(dtype)
    out = np.empty((len(flows), len(seeds)), dtype)
    for s in range(len(seeds)):
        out[:, s] = water_fill(paths[:, :, s].T, w, cap, dtype)
    return out


def fim_sweep(topo: Topology, flows: list[dict], seeds: np.ndarray,
              demand: str = "uniform", dtype=np.float64):
    paths = route(topo, flows, seeds)
    return fim(topo, paths, demand_weights(flows, demand), dtype)

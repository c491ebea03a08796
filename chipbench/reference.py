"""Plain reference of the FlowTracer Monte-Carlo semantics.

Written from the deployment's own statement (the topology file, the flow
table, the forwarding rules and hash of ``configs/<name>/config.json``,
the demand mode of ``traffic/<mix>.json``), and independent of
the program under test: it imports nothing of ``repro`` and takes nothing
the program made.  Everything is plain numpy, seed by seed where the
arithmetic is per seed:

* routing: L3 Clos forwarding (a host hashes over the ports of the NIC
  that owns the flow's source address; a leaf goes down to the
  destination NIC's ports when it is attached there and otherwise hashes
  over all its spine uplinks; a spine hashes over its links to the
  destination's leaf), each choice ``murmur3(fields, crc32(device) ^
  seed) % candidates`` over the flow's 5-tuple fields;
* link counts and FIM: the mean absolute percentage error of each
  layer's link loads against that layer's ideal, link-weighted;
* max-min rates: classic water filling, one global bottleneck level per
  round, weighted by demand.

``dtype`` sets the precision of every rate, share, time and FIM
computation.  ``float64`` is the reference; one precision below it is
the control that a sound comparison has to reject.
"""

from __future__ import annotations

import zlib

import numpy as np

_C1, _C2 = np.uint32(0xCC9E2D51), np.uint32(0x1B873593)
_F1, _F2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)


def crc32(text: str) -> int:
    return zlib.crc32(text.encode())


def _rotl(x, r: int):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def murmur3(fields: list[int], init: np.ndarray) -> np.ndarray:
    """murmur3_32 of the 32-bit words ``fields`` with ``init`` (uint32,
    any shape) as the seed, one hash per element of ``init``."""
    h = init.astype(np.uint32)
    with np.errstate(over="ignore"):
        for f in fields:
            k = np.uint32(f & 0xFFFFFFFF) * _C1
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


class Topology:
    """The topology file: devices, unidirectional links, the NIC plan
    ``10.<nic>.<index // 256>.<index % 256>`` and Clos forwarding."""

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
        self.link_dst = np.array([self.index[ln["dst"]] for ln in self.links])
        self.is_server = np.array([self.kind[n] == "server"
                                   for n in self.names])
        self.server_of_index = {int(n.split("-")[-1]): n
                                for n, k in self.kind.items() if k == "server"}
        self._cands: dict[tuple[str, str, str], list[int]] = {}

    def nic(self, ip: str) -> tuple[str, int]:
        a = [int(p) for p in ip.split(".")]
        return self.server_of_index[a[2] * 256 + a[3]], a[1]

    def _nic_ports(self, server: str, nic: int) -> list[int]:
        pre = f"nic{nic}p"
        return [i for i, ln in enumerate(self.links)
                if ln["src"] == server and ln["src_port"].startswith(pre)]

    def candidates(self, device: str, src_ip: str, dst_ip: str) -> list[int]:
        """Equal-cost egress links at ``device``, in hash order."""
        key = (device, src_ip if self.kind[device] == "server" else "",
               "" if self.kind[device] == "server" else dst_ip)
        hit = self._cands.get(key)
        if hit is not None:
            return hit
        L = self.links
        kind = self.kind[device]
        if kind == "server":
            server, nic = self.nic(src_ip)
            assert server == device
            out = sorted(self._nic_ports(server, nic),
                         key=lambda i: L[i]["src_port"])
        else:
            dserver, dnic = self.nic(dst_ip)
            dleaf = L[self._nic_ports(dserver, dnic)[0]]["dst"]
            if kind == "leaf" and device == dleaf:
                out = sorted(
                    (i for i, ln in enumerate(L) if ln["src"] == device
                     and ln["dst"] == dserver
                     and ln["dst_port"].startswith(f"nic{dnic}p")),
                    key=lambda i: L[i]["src_port"])
            elif kind == "leaf":
                out = sorted(
                    (i for i, ln in enumerate(L) if ln["src"] == device
                     and self.kind[ln["dst"]] == "spine"),
                    key=lambda i: (L[i]["dst"], L[i]["src_port"]))
            elif kind == "spine":
                out = sorted(
                    (i for i, ln in enumerate(L)
                     if ln["src"] == device and ln["dst"] == dleaf),
                    key=lambda i: L[i]["src_port"])
            else:
                raise ValueError(f"unknown device kind {kind!r}")
        self._cands[key] = out
        return out


def flow_fields(flow: dict) -> list[int]:
    return [crc32(flow["src_ip"]), crc32(flow["dst_ip"]), flow["src_port"],
            flow["dst_port"], flow["protocol"]]


def walk(topo: Topology, flow: dict, seeds: np.ndarray,
         max_hops: int = 16) -> np.ndarray:
    """(hops, S) link ids of one flow under every seed,
    -1 after arrival."""
    fields = flow_fields(flow)
    S = len(seeds)
    seed_lo = (seeds & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    at = np.full(S, topo.index[flow["src"]])
    done = np.zeros(S, bool)
    rows = []
    for _ in range(max_hops):
        if done.all():
            break
        row = np.full(S, -1, np.int64)
        for v in np.unique(at[~done]):
            sel = np.flatnonzero((at == v) & ~done)
            dev = topo.names[v]
            cands = topo.candidates(dev, flow["src_ip"], flow["dst_ip"])
            if len(cands) == 1:
                pick = np.zeros(sel.size, np.int64)
            else:
                h = murmur3(fields, seed_lo[sel] ^ np.uint32(crc32(dev)))
                pick = (h % np.uint32(len(cands))).astype(np.int64)
            row[sel] = np.asarray(cands)[pick]
        rows.append(row)
        moved = row >= 0
        at = np.where(moved, topo.link_dst[np.maximum(row, 0)], at)
        done |= moved & topo.is_server[at]
    if not done.all() or not (at == topo.index[flow["dst"]]).all():
        raise RuntimeError(f"flow {flow['flow_id']} did not arrive")
    return np.stack(rows)


# ---------------------------------------------------------------------------
# FIM
# ---------------------------------------------------------------------------


def fim(topo: Topology, paths: list[np.ndarray], weights: np.ndarray,
        dtype=np.float64) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Per-seed aggregate and per-layer FIM of link loads."""
    S = paths[0].shape[1]
    # numpy's scatter-add has no bfloat16 loop: below float32 the counts
    # gather in float32 and are then rounded to ``dtype``
    acc = dtype if np.dtype(dtype).itemsize >= 4 else np.float32
    counts = np.zeros((S, topo.num_links), acc)
    for p, w in zip(paths, weights):
        for h in range(p.shape[0]):
            ok = p[h] >= 0
            np.add.at(counts, (np.flatnonzero(ok), p[h][ok]), acc(w))
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


def route(topo: Topology, flows: list[dict],
          seeds: np.ndarray) -> list[np.ndarray]:
    """(hops, S) link ids of every single-path flow."""
    return [walk(topo, f, seeds) for f in flows]


def _seed_paths(paths: list[np.ndarray], s: int) -> np.ndarray:
    H = max(p.shape[0] for p in paths)
    out = np.full((len(paths), H), -1, np.int64)
    for j, p in enumerate(paths):
        out[j, :p.shape[0]] = p[:, s]
    return out


def throughput(topo: Topology, flows: list[dict], seeds: np.ndarray,
               demand: str = "uniform", dtype=np.float64) -> np.ndarray:
    """(N, S) max-min rates of single-path ECMP flows."""
    paths = route(topo, flows, seeds)
    w = demand_weights(flows, demand).astype(dtype)
    cap = topo.gbps.astype(dtype)
    out = np.empty((len(flows), len(seeds)), dtype)
    for s in range(len(seeds)):
        out[:, s] = water_fill(_seed_paths(paths, s), w, cap, dtype)
    return out


def fim_sweep(topo: Topology, flows: list[dict], seeds: np.ndarray,
              demand: str = "uniform", dtype=np.float64):
    paths = route(topo, flows, seeds)
    return fim(topo, paths, demand_weights(flows, demand), dtype)

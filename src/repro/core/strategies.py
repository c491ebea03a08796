"""Pluggable routing strategies over the compiled-fabric candidate tables.

The paper's whole point is comparing routing schemes by flow imbalance
(Fig. 3: ECMP vs static).  PR 1-2 built the vectorized N-flows x S-seeds
path + max-min throughput engine; this module makes the *routing
decision* pluggable on top of it, so the schemes from the related work
can be evaluated under the same Monte-Carlo harness:

* ``EcmpStrategy`` — baseline per-flow ECMP, bit-identical to
  ``simulate_paths``'s default walk (and therefore to ``EcmpRouting`` +
  ``FlowTracer``); differential-tested in tests/test_strategies.py.
* ``PrimeSpraying`` — PRIME-style multi-part-entropy spraying
  (arXiv 2507.23012): each flow splits into K flowlets carrying 1/K of
  the demand, and every flowlet gets a distinct entropy label appended
  to its hash fields.  The label is *multi-part*: the flowlet index is
  decomposed into mixed-radix digits over ``parts`` and each digit rides
  as its own extra header field, so every switch's pseudo-random hash
  integrates several independently varying entropy sources.  K=1 appends
  nothing and degenerates to ECMP exactly.  ``min_bytes`` makes the
  spraying *demand-aware* (split only elephants, optionally with
  volume-proportional K) — spraying is not free (core/reordering.py
  prices the out-of-order delivery), so PRIME sprays selectively.
* ``CongestionAware`` — greedy congestion-aware path selection in the
  spirit of Predictive Load Balancing (arXiv 2506.08132): flows are
  placed one at a time and every hop picks the candidate egress link
  with the least demand already routed through it, falling back to the
  flow's ECMP hash only to break exact load ties (which keeps the
  hash-seed sweep meaningful: seeds explore the tie space).

A strategy consumes the compiled fabric + flow table + seed sweep and
returns a ``VectorTraceResult``; multi-path strategies emit flowlet
columns with ``flow_index`` / ``demand`` metadata, which
``link_flow_counts`` (demand-weighted FIM) and the weighted
``batched_max_min`` rate model aggregate back per parent flow.

Register custom schemes with ``register_strategy``; ``simulate_paths``
/ ``monte_carlo_fim`` / ``monte_carlo_throughput`` accept either a
registered name or a strategy instance via ``strategy=``.
"""

from __future__ import annotations

import dataclasses
import warnings
from collections.abc import Callable, Sequence

import numpy as np

from .compile_fabric import CompiledFabric
from .ecmp import FIELDS_5TUPLE, flow_fields_matrix
from .flows import Flow
from .vector_sim import (
    DEMAND_UNIFORM, ENGINE_NUMPY, EXACT, VectorTraceResult, ecmp_walk,
    flow_demand_weights, hash_grid,
)


class RoutingStrategy:
    """Interface: turn (compiled fabric, flows, seeds) into routed paths.

    ``route`` receives the already-normalized inputs from
    ``simulate_paths`` and must return a ``VectorTraceResult`` whose
    flowlet ``demand`` fractions sum to 1 per parent flow, carrying the
    ``demand_mode``-derived per-flow weights in ``flow_demand``
    (``flow_demand_weights`` is the standard derivation).  Strategies
    are free to *route* on the weights too — congestion-aware places
    heavy flows first.
    """

    #: registry name; instances may be configured, the name is the family
    name: str = "?"

    def route(
        self,
        comp: CompiledFabric,
        flows: list[Flow],
        seeds_u64: np.ndarray,
        *,
        fields: str = FIELDS_5TUPLE,
        hash_backend: str = EXACT,
        max_hops: int = 16,
        field_matrix: np.ndarray | None = None,
        demand_mode: str = DEMAND_UNIFORM,
        engine: str = ENGINE_NUMPY,
    ) -> VectorTraceResult:
        raise NotImplementedError


class EcmpStrategy(RoutingStrategy):
    """Per-flow ECMP — the baseline, bit-identical to the default walk."""

    name = "ecmp"

    def route(self, comp, flows, seeds_u64, *, fields=FIELDS_5TUPLE,
              hash_backend=EXACT, max_hops=16, field_matrix=None,
              demand_mode=DEMAND_UNIFORM, engine=ENGINE_NUMPY):
        from .vector_sim import SimSpec, simulate_paths
        return simulate_paths(
            comp, flows, seeds_u64, field_matrix=field_matrix,
            spec=SimSpec(fields=fields, hash_backend=hash_backend,
                         max_hops=max_hops, demand_mode=demand_mode,
                         engine=engine))


def _balanced_parts(k: int) -> tuple[int, ...]:
    """Default multi-part split of K flowlets: the most balanced two-factor
    decomposition (8 -> (2, 4)); prime or unit K stays single-part."""
    for a in range(int(np.sqrt(k)), 1, -1):
        if k % a == 0:
            return (a, k // a)
    return (k,)


#: default elephant threshold for demand-aware spraying: 64 MiB — on the
#: committed LLM scenarios this sprays the DP/FSDP ring elephants (which
#: carry ~80-97% of the bytes) and leaves the MB-scale MoE shuffles and
#: control mice on their ECMP paths
ELEPHANT_MIN_BYTES = 64 * 1024 * 1024


class PrimeSpraying(RoutingStrategy):
    """PRIME-style multi-part-entropy packet spraying (arXiv 2507.23012).

    Each flow is split into up to ``flowlets`` equal-demand flowlets;
    flowlet ``k``'s entropy label is the mixed-radix digit vector of
    ``k`` over ``parts`` (product must equal ``flowlets``), appended to
    the flow's hash fields as extra columns so every switch hash
    integrates all entropy parts.  With ``flowlets=1`` no label is
    appended and the walk is bit-identical to ``EcmpStrategy``.

    **Demand-aware spraying** (``min_bytes``): PRIME sprays adaptively,
    not blindly — splitting a mouse buys no balance (its bytes are
    noise) but still costs out-of-order delivery (core/reordering.py).
    With ``min_bytes`` set, only flows with ``Flow.bytes >= min_bytes``
    are split; the rest ride their exact per-flow ECMP path — the
    unsprayed columns are walked *without* entropy columns, so they stay
    bit-identical to ``EcmpStrategy`` flow by flow, and
    ``min_bytes=inf`` degenerates to ECMP wholesale.  ``volume_k=True``
    additionally makes K volume-proportional: ``min_bytes`` becomes the
    target bytes *per flowlet* and each flow splits into
    ``clip(ceil(bytes / min_bytes), 1, flowlets)`` flowlets, so a 2 GiB
    elephant fans wide while a 100 MiB flow (at the 64 MiB default
    target) splits in two; flows at or under one target-chunk stay
    single-path.

    ``min_bytes`` reads raw ``Flow.bytes`` — the elephant decision is a
    property of the workload, independent of the ``demand_mode``
    normalization used for FIM/max-min weighting.
    """

    name = "prime-spray"

    def __init__(self, flowlets: int = 8,
                 parts: Sequence[int] | None = None,
                 min_bytes: float | None = None,
                 volume_k: bool = False):
        if flowlets < 1:
            raise ValueError(f"flowlets must be >= 1, got {flowlets}")
        self.flowlets = int(flowlets)
        self.parts = (tuple(int(p) for p in parts) if parts is not None
                      else _balanced_parts(self.flowlets))
        if any(p < 1 for p in self.parts):
            raise ValueError(f"entropy parts must be >= 1: {self.parts}")
        if int(np.prod(self.parts)) != self.flowlets:
            raise ValueError(
                f"entropy parts {self.parts} do not multiply to "
                f"{self.flowlets} flowlets")
        if min_bytes is not None and not min_bytes > 0:
            raise ValueError(f"min_bytes must be > 0, got {min_bytes}")
        if volume_k and min_bytes is None:
            raise ValueError(
                "volume_k needs min_bytes (the target bytes per flowlet)")
        self.min_bytes = min_bytes
        self.volume_k = bool(volume_k)

    def entropy_labels(self) -> np.ndarray:
        """(K, P) uint64 mixed-radix digits, one row per flowlet."""
        k = np.arange(self.flowlets, dtype=np.uint64)
        cols = []
        for base in self.parts:
            cols.append(k % np.uint64(base))
            k = k // np.uint64(base)
        return np.stack(cols, axis=1)

    def flowlet_counts(self, flows: Sequence[Flow]) -> np.ndarray:
        """(N,) int64 flowlets per flow under the demand-aware policy."""
        n = len(flows)
        if self.min_bytes is None:
            return np.full(n, self.flowlets, np.int64)
        b = np.array([f.bytes for f in flows], np.float64)
        if self.volume_k:
            # ceil, not floor: one flowlet per started min_bytes chunk,
            # so anything over one chunk actually splits
            with np.errstate(invalid="ignore"):   # min_bytes=inf: b/inf -> 0
                k = np.ceil(b / self.min_bytes)
            return np.clip(np.nan_to_num(k), 1, self.flowlets).astype(np.int64)
        return np.where(b >= self.min_bytes, self.flowlets, 1).astype(np.int64)

    def route(self, comp, flows, seeds_u64, *, fields=FIELDS_5TUPLE,
              hash_backend=EXACT, max_hops=16, field_matrix=None,
              demand_mode=DEMAND_UNIFORM, engine=ENGINE_NUMPY):
        field_mat = (field_matrix if field_matrix is not None
                     else flow_fields_matrix(flows, fields))
        n = len(flows)
        k_f = self.flowlet_counts(flows)
        if (self.min_bytes is not None and np.isfinite(self.min_bytes)
                and n and all(f.bytes == 0 for f in flows)):
            # an explicit finite threshold against a volume-less workload
            # is almost certainly a mistake: every flow stays single-path
            # and the "spraying" comparison silently measures plain ECMP
            warnings.warn(
                f"{self.name}: min_bytes={self.min_bytes:g} but every "
                f"Flow.bytes is 0 (workload carries no volumes) — no flow "
                f"sprays, this is ECMP", stacklevel=2)
        total = int(k_f.sum())
        flow_index = np.repeat(np.arange(n, dtype=np.int32), k_f)
        starts = np.concatenate(([0], np.cumsum(k_f)[:-1]))
        local = np.arange(total, dtype=np.int64) - np.repeat(starts, k_f)
        demand = np.repeat(1.0 / k_f, k_f)
        endpoints = comp.flow_endpoint_ids(flows)
        sprayed = k_f[flow_index] > 1          # per column

        def walk(cols: np.ndarray, with_labels: bool) -> np.ndarray:
            fm = field_mat[flow_index[cols]]
            if with_labels:
                fm = np.concatenate(
                    [fm, self.entropy_labels()[local[cols]]], axis=1)
            ep = tuple(a[flow_index[cols]] for a in endpoints)
            return ecmp_walk(
                comp, *ep, fm, seeds_u64,
                hash_backend=hash_backend, max_hops=max_hops,
                engine=engine,
                describe=lambda j: (
                    f"flow {flows[int(flow_index[cols[int(j)]])].flow_id} "
                    f"flowlet {int(local[cols[int(j)]])}"))

        if sprayed.all():
            link_ids = walk(np.arange(total, dtype=np.int64), with_labels=True)
        elif not sprayed.any():
            # nothing crosses the elephant bar (or flowlets=1): one
            # label-free walk, bit-identical to EcmpStrategy
            link_ids = walk(np.arange(total, dtype=np.int64), with_labels=False)
        else:
            # mixed: sprayed columns walk with entropy labels, unsprayed
            # flows walk label-free (each stays on its exact ECMP path),
            # then the two tensors interleave back into parent order
            p_cols = np.flatnonzero(sprayed)
            u_cols = np.flatnonzero(~sprayed)
            p_ids = walk(p_cols, with_labels=True)
            u_ids = walk(u_cols, with_labels=False)
            hops = max(p_ids.shape[0], u_ids.shape[0])
            link_ids = np.full((hops, total, len(seeds_u64)), -1, np.int32)
            link_ids[:p_ids.shape[0], p_cols] = p_ids
            link_ids[:u_ids.shape[0], u_cols] = u_ids
        return VectorTraceResult(
            compiled=comp, flows=list(flows), seeds=seeds_u64,
            link_ids=link_ids, flow_index=flow_index,
            demand=demand, strategy=self.name,
            flow_demand=flow_demand_weights(flows, demand_mode))


def _weighted_link_loads(link_ids: np.ndarray, weights: np.ndarray,
                         num_links: int) -> np.ndarray:
    """(S, L) demand-weighted link loads of an ``(H, Nf, S)`` tensor —
    the same bincount ``VectorTraceResult.link_flow_counts`` runs, over
    an explicit tensor (adaptive re-spray recomputes it per round on its
    evolving paths)."""
    S = link_ids.shape[2]
    offset = np.arange(S, dtype=np.int64) * num_links
    keep = link_ids >= 0
    flat = (link_ids.astype(np.int64) + offset)[keep]
    w = np.broadcast_to(weights[None, :, None], link_ids.shape)[keep]
    return np.bincount(flat, weights=w,
                       minlength=S * num_links).reshape(S, num_links)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    """Vectorized SplitMix64 finalizer — a stateless uint64 mixer for the
    adaptive re-spray coin flips (deterministic in the cell/seed/round
    coordinates, no global RNG state)."""
    z = x + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _path_max_load(link_ids: np.ndarray, loads: np.ndarray) -> np.ndarray:
    """(C, S) hottest-link load along each column's path, given (S, L)
    link loads.  Link-free columns read 0 (they queue nowhere)."""
    S, L = loads.shape
    flat = loads.reshape(-1)
    cells = link_ids.astype(np.int64) + (np.arange(S, dtype=np.int64) * L)
    vals = np.where(link_ids >= 0,
                    flat[np.where(link_ids >= 0, cells, 0)], 0.0)
    return vals.max(axis=0) if link_ids.shape[0] else np.zeros(
        link_ids.shape[1:])


class AdaptiveSpraying(PrimeSpraying):
    """PRIME's headline *adaptive* mode: per-RTT entropy re-pick under
    congestion feedback (arXiv 2507.23012).

    Static spraying commits each flowlet to one entropy label for the
    whole transfer; PRIME instead treats the label as disposable — when
    the fabric's feedback (ECN marks / RTT inflation) says a flowlet's
    path is congested, the sender re-picks the entropy value on the next
    round, re-rolling every switch hash on that flowlet's walk.  This
    strategy simulates ``rounds`` such RTTs on top of the (bit-identical)
    ``PrimeSpraying`` round-0 allocation:

    1. **feedback**: demand-weighted link loads of the current paths,
       per seed; a flowlet is *marked* when its path's hottest link
       carries more than ``ecn_factor`` x that seed's mean loaded-link
       load (the ECN-threshold analogue);
    2. **re-pick**: every marked (flowlet, seed) cell draws a fresh
       entropy salt (a new label value) and walks its candidate path
       against the frozen load snapshot;
    3. **accept**: the move is kept only when the candidate's hottest
       link plus the flowlet's own demand undercuts its current path's
       hottest link — the sender keeps entropy that works and discards
       picks that land somewhere worse (REPS-style "recycle good
       entropy"; cf. the accept/repair policy of arXiv 2506.08132).

    Unmarked cells keep their salt, so their walks replay bit-identically
    (``x ^ 0 == x`` in the salted walk) and a run whose feedback never
    fires returns exactly the static allocation.  ``rounds=1`` *is*
    ``PrimeSpraying`` wholesale.

    Re-picking is not free: every accepted move is a mid-flow path
    change — a reordering burst the static skew/dispersion exposure
    cannot see — charged as ``respray_cost`` per accepted round, scaled
    by the moved flowlet's demand fraction, via
    ``VectorTraceResult.extra_exposure`` (core/reordering.py adds it to
    the transport model's exposure).  The PR-5 lesson priced blind
    spraying; this prices the adaptation itself.
    """

    name = "adaptive-spray"

    def __init__(self, flowlets: int = 8,
                 parts: Sequence[int] | None = None,
                 min_bytes: float | None = None,
                 volume_k: bool = False,
                 rounds: int = 4,
                 ecn_factor: float = 1.25,
                 respray_cost: float = 0.05,
                 move_prob: float = 0.25):
        super().__init__(flowlets, parts, min_bytes=min_bytes,
                         volume_k=volume_k)
        if rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {rounds}")
        if not ecn_factor > 0:
            raise ValueError(f"ecn_factor must be > 0, got {ecn_factor}")
        if respray_cost < 0:
            raise ValueError(
                f"respray_cost must be >= 0, got {respray_cost}")
        if not 0.0 < move_prob <= 1.0:
            raise ValueError(
                f"move_prob must be in (0, 1], got {move_prob}")
        self.rounds = int(rounds)
        self.ecn_factor = float(ecn_factor)
        self.respray_cost = float(respray_cost)
        self.move_prob = float(move_prob)

    def with_rounds(self, rounds: int) -> "AdaptiveSpraying":
        """A copy of this strategy with a different round budget — every
        other knob unchanged.  Event-timed replay (core/timeline.py)
        uses it to express ``rounds`` in RTTs of the *derived* step
        duration (``reordering.rtt_round_budget``): ``self.rounds``
        becomes the cap, and a step shorter than one RTT routes with the
        static round-1 allocation."""
        if rounds == self.rounds:
            return self
        return AdaptiveSpraying(
            self.flowlets, self.parts, min_bytes=self.min_bytes,
            volume_k=self.volume_k, rounds=rounds,
            ecn_factor=self.ecn_factor, respray_cost=self.respray_cost,
            move_prob=self.move_prob)

    def route(self, comp, flows, seeds_u64, *, fields=FIELDS_5TUPLE,
              hash_backend=EXACT, max_hops=16, field_matrix=None,
              demand_mode=DEMAND_UNIFORM, engine=ENGINE_NUMPY):
        res = super().route(comp, flows, seeds_u64, fields=fields,
                            hash_backend=hash_backend, max_hops=max_hops,
                            field_matrix=field_matrix,
                            demand_mode=demand_mode, engine=engine)
        if self.rounds == 1 or not res.is_multipath:
            return res                     # static spray / ECMP degenerate
        field_mat = (field_matrix if field_matrix is not None
                     else flow_fields_matrix(flows, fields))
        n, s = len(flows), len(seeds_u64)
        fi, demand = res.flow_index, res.demand
        col_w = res.column_weights()
        k_f = self.flowlet_counts(flows)
        spray_cols = np.flatnonzero(k_f[fi] > 1)
        starts = np.concatenate(([0], np.cumsum(k_f)[:-1]))
        local = np.arange(fi.size, dtype=np.int64) - starts[fi]
        # fixed walk inputs for the sprayed columns: the same entropy
        # labels as round 0, so salt == 0 replays the base walk exactly
        fm_s = np.concatenate(
            [field_mat[fi[spray_cols]],
             self.entropy_labels()[local[spray_cols]]], axis=1)
        endpoints = comp.flow_endpoint_ids(flows)
        ep_s = tuple(a[fi[spray_cols]] for a in endpoints)
        w_col = col_w[spray_cols][:, None]         # (C, 1)
        link_ids = res.link_ids
        salt = np.zeros((spray_cols.size, s), np.uint64)
        probe = np.zeros((spray_cols.size, s), np.uint64)
        resprays = np.zeros((spray_cols.size, s))

        def walk(cell_salt):
            return ecmp_walk(
                comp, *ep_s, fm_s, seeds_u64, hash_backend=hash_backend,
                max_hops=max_hops, cell_salt=cell_salt, engine=engine,
                describe=lambda j: (
                    f"flow {flows[int(fi[spray_cols[int(j)]])].flow_id} "
                    f"respray flowlet {int(local[spray_cols[int(j)]])}"))

        # per-cell coin identity: decorrelated across flowlets and seeds,
        # re-mixed with the round index below so each round flips fresh
        cell_id = (_splitmix64(spray_cols.astype(np.uint64))[:, None]
                   ^ seeds_u64[None, :])
        p_bits = np.uint64(int(self.move_prob * 2.0 ** 53))
        for rnd in range(self.rounds - 1):
            loads = _weighted_link_loads(link_ids, col_w, comp.num_links)
            cur = link_ids[:, spray_cols, :]
            path_max = _path_max_load(cur, loads)
            mean_load = (loads.sum(axis=1)
                         / np.maximum((loads > 0).sum(axis=1), 1))
            marked = path_max > self.ecn_factor * mean_load[None, :]
            if not marked.any():
                break
            # herd damping: every marked cell re-picks only with
            # probability ``move_prob`` per round — acceptance is judged
            # against a frozen load snapshot, so letting every congested
            # flowlet move at once stampedes them onto the same cool
            # links and *creates* the next hotspot
            coin = _splitmix64(
                cell_id ^ np.uint64((rnd + 1) * 0xD1B54A32D192ED03 &
                                    0xFFFFFFFFFFFFFFFF))
            marked &= (coin >> np.uint64(11)) < p_bits
            if not marked.any():
                continue
            probe = probe + marked                  # fresh salt per probe
            cand = walk(np.where(marked, probe, salt))
            cand_max = _path_max_load(cand, loads)
            accept = marked & (cand_max + w_col < path_max)
            if not accept.any():
                continue
            salt = np.where(accept, probe, salt)
            resprays += accept
            hops = max(link_ids.shape[0], cand.shape[0])
            merged = np.full((hops,) + cur.shape[1:], -1, np.int32)
            np.copyto(merged[:cur.shape[0]], cur)
            np.copyto(merged[:cand.shape[0]], cand[:hops],
                      where=accept[None, :, :])
            nxt = np.full((hops,) + link_ids.shape[1:], -1, np.int32)
            np.copyto(nxt[:link_ids.shape[0]], link_ids)
            nxt[:, spray_cols, :] = merged
            link_ids = nxt
        extra = np.zeros((n, s))
        np.add.at(extra, fi[spray_cols],
                  resprays * demand[spray_cols][:, None])
        return dataclasses.replace(res, link_ids=link_ids,
                                   extra_exposure=self.respray_cost * extra)


def _sequential_congestion_place(
    comp: CompiledFabric,
    flows: Sequence[Flow],
    field_mat: np.ndarray,
    seeds_u64: np.ndarray,
    endpoints: tuple,
    flow_demand: np.ndarray,
    order: np.ndarray,
    load: np.ndarray,
    link_ids: np.ndarray,
    *,
    hash_backend: str,
    max_hops: int,
    mask: np.ndarray | None = None,
) -> int:
    """The sequential greedy placement loop, shared by ``CongestionAware``
    (its whole route) and ``WaveCongestionAware`` (the round-cap fallback
    for still-conflicted residue).

    Routes the flows of ``order`` one at a time against — and charging —
    the ``(S, L)`` ``load`` tally, writing paths into ``link_ids`` (both
    mutated in place; ``load`` may arrive pre-seeded with already-committed
    demand).  With ``mask`` (an ``(N, S)`` bool of still-unplaced cells)
    only the True cells of each flow are written and charged: the walk is
    still vectorized over every seed, but committed cells keep their paths
    and are never double-counted.  Returns the hop-count high-water mark.
    """
    src_dev, dst_dev, src_key, dst_key = endpoints
    s = len(seeds_u64)
    load_flat = load.reshape(-1)           # writable view for scatters
    rows = np.arange(s, dtype=np.int64)
    row_off = rows * comp.num_links
    cand_w = comp.cand.shape[-1]
    col_idx = np.arange(cand_w, dtype=np.int64)[None, :]
    hops = 0
    for j in order:
        m = None if mask is None else mask[j]
        if m is not None and not m.any():
            continue
        w_j = flow_demand[j]
        state = np.full(s, int(src_dev[j]), np.int64)
        done = np.zeros(s, bool)
        t_end = 0
        for t in range(max_hops):
            if done.all():
                break
            t_end = t + 1
            key = np.where(comp.is_server[state], src_key[j], dst_key[j])
            nc = comp.cand_n[state, key]               # (S,)
            cw = min(int(nc.max()), cand_w) or 1       # live table width
            cands = comp.cand[state, key, :cw]         # (S, cw)
            valid = (col_idx[:, :cw] < nc[:, None]) & (cands >= 0)
            cl = np.where(valid,
                          load_flat[row_off[:, None]
                                    + np.maximum(cands, 0)],
                          np.inf)
            tie = valid & (cl == cl.min(axis=1)[:, None])
            n_tie = tie.sum(axis=1)
            multi = n_tie > 1
            if multi.any():                # hash only when a tie exists
                dev_seed = comp.dev_crc[state] ^ seeds_u64
                h = hash_grid(field_mat[j:j + 1], dev_seed[None, :],
                              hash_backend)[0]
                rank = np.where(
                    multi,
                    (h % np.maximum(n_tie, 1).astype(np.uint64)).astype(
                        np.int64),
                    0)
                col = (tie.cumsum(axis=1) <= rank[:, None]).sum(axis=1)
            else:
                col = tie.argmax(axis=1)   # unique minimum (or 0)
            link = cands[rows, np.minimum(col, cw - 1)]
            link = np.where(done | (nc == 0), -1, link)
            if m is None:
                link_ids[t, j] = link
            else:
                link_ids[t, j, m] = link[m]
            active = link >= 0
            nxt = np.where(active, comp.link_dst[np.maximum(link, 0)],
                           state)
            done |= ~active | comp.is_server[nxt]
            state = nxt
        hops = max(hops, t_end)
        settled = done if m is None else (done | ~m)
        if not settled.all():
            raise RuntimeError(
                f"flow {flows[j].flow_id} did not terminate in "
                f"{max_hops} hops")
        arrived = done & (state == dst_dev[j])
        if m is not None:
            arrived |= ~m
        if not arrived.all():
            bad = int(np.flatnonzero(~arrived)[0])
            raise RuntimeError(
                f"flow {flows[j].flow_id} (seed index {bad}) terminated "
                f"at {comp.device_names[int(state[bad])]}, expected "
                f"{flows[j].dst}")
        # fused load tally over all hops at once: (seed, link) cells of
        # one flow are unique (loop-free path, per-device link ids), so
        # a direct fancy-index add is exact — no ufunc.at needed
        taken = link_ids[:t_end, j]                    # (h, S)
        keep = taken >= 0
        if m is not None:
            keep = keep & m[None, :]
        cells = (taken.astype(np.int64) + row_off[None, :])[keep]
        load_flat[cells] += w_j
    return hops


class CongestionAware(RoutingStrategy):
    """Greedy congestion-aware selection (cf. arXiv 2506.08132).

    Flows are routed sequentially (the placement order models a
    connection-setup sequence); at every hop the flow takes the candidate
    egress link carrying the least demand routed so far *under that
    seed*, with the flow's ECMP hash breaking exact load ties.  Under
    ``demand_mode="bytes"`` flows are placed **largest-first** (the
    standard greedy bin-packing order — elephants claim the emptiest
    paths while the fabric is still balanced, mice fill the gaps) and
    each flow adds its demand weight, not 1, to the links it takes.

    The walk is a Python loop over flows but vectorized over seeds *and*
    batched over hops: the per-hop tie-break hash is only evaluated when
    some seed actually has a load tie (ties die out as loads
    differentiate), and the load tally is deferred to one fused scatter
    over all (hop, seed) cells of the finished flow — exact, because a
    loop-free walk never revisits a device, so a flow's later candidate
    sets cannot contain its own earlier links.  A 256-flow x 1024-seed
    sweep stays well under a second.
    """

    name = "congestion-aware"

    def route(self, comp, flows, seeds_u64, *, fields=FIELDS_5TUPLE,
              hash_backend=EXACT, max_hops=16, field_matrix=None,
              demand_mode=DEMAND_UNIFORM, engine=ENGINE_NUMPY):
        # ``engine`` is accepted (front-end contract) but the placement
        # loop itself stays host-side: greedy sequential routing is a
        # data-dependent chain over flows (each placement reads the loads
        # the previous ones wrote) — ``WaveCongestionAware`` below is the
        # device-friendly reformulation.  Downstream fill/exposure
        # still honor the engine via throughput_from_result(engine=).
        field_mat = (field_matrix if field_matrix is not None
                     else flow_fields_matrix(flows, fields))
        n, s = len(flows), len(seeds_u64)
        endpoints = comp.flow_endpoint_ids(flows)
        flow_demand = flow_demand_weights(flows, demand_mode)
        # stable largest-first placement: uniform demand keeps the
        # original order exactly (all keys equal), so demand_mode="bytes"
        # with homogeneous volumes stays bit-identical to "uniform"
        order = np.argsort(-flow_demand, kind="stable")
        load = np.zeros((s, comp.num_links))
        link_ids = np.full((max_hops, n, s), -1, np.int32)
        hops = _sequential_congestion_place(
            comp, flows, field_mat, seeds_u64, endpoints, flow_demand,
            order, load, link_ids, hash_backend=hash_backend,
            max_hops=max_hops)
        return VectorTraceResult(
            compiled=comp, flows=list(flows), seeds=seeds_u64,
            link_ids=link_ids[:hops], strategy=self.name,
            flow_demand=flow_demand)


def _wave_choice(cands: np.ndarray, valid: np.ndarray, cl: np.ndarray,
                 h: np.ndarray, cw: int, cool: bool = False,
                 near: bool = False) -> np.ndarray:
    """Hash tie-break over the eligible candidate set, batched over
    arbitrary leading axes: the documented wave decision rule.

    With ``cool=False`` the eligible set is the least-loaded candidates;
    exact (quantized) load ties are broken by ``hash % n_tie`` counted
    over the tied candidates in table order — the *same* arithmetic as
    the sequential loop (whose cumsum form degenerates to ``tie.argmax``
    when the minimum is unique), so wave and sequential replay identical
    decisions given identical loads.  On a fresh fabric every candidate
    ties at zero and the rule *is* plain ECMP
    (``rank == hash % n_candidates``).

    With ``cool=True`` the eligible set widens to every candidate no
    hotter than the (quantized) candidate *mean*: repair waves use it to
    hash-spread their *arrivals* across the whole cool half of the
    table.  A wave of movers all steering for the strict argmin piles
    onto it and mints a fresh hotspot (the sink side of the herd
    problem — departures are already rate-limited by the
    excess-proportional repair probability, but thousands of simultaneous
    movers share a handful of argmin links); landing uniformly on the
    cool set bounds arrivals per link by ``movers / |cool|``, and the
    accept-if-better filter discards the landings that didn't help.
    ``cl`` is quantized to integers, so the minimum is always <= the
    floored mean and the cool set is never empty.

    With ``near=True`` (only meaningful together with ``cool``) the
    eligible set narrows to candidates within one quantum of the
    minimum: the polish-phase arrival rule.  Once mover volume is small
    the herd risk is gone and uniform-over-cool arrivals stop helping —
    they never preferentially fill the *under*-loaded tail, which is
    where the remaining imbalance lives — so late repair steers
    near-min (still hash-spread across the whole near-min window, not
    the strict argmin)."""
    if cool and near:
        m = np.where(valid, cl, np.inf).min(axis=-1)
        tie = valid & (cl <= m[..., None] + 1.0)
    elif cool:
        n_valid = np.maximum(valid.sum(axis=-1), 1)
        mean = np.where(valid, cl, 0.0).sum(axis=-1) / n_valid
        tie = valid & (cl <= np.floor(mean)[..., None])
    else:
        tie = valid & (cl == cl.min(axis=-1)[..., None])
    n_tie = tie.sum(axis=-1)
    rank = np.where(
        n_tie > 1,
        (h % np.maximum(n_tie, 1).astype(np.uint64)).astype(np.int64),
        0)
    col = (tie.cumsum(axis=-1) <= rank[..., None]).sum(axis=-1)
    return np.take_along_axis(
        cands, np.minimum(col, cw - 1)[..., None], axis=-1)[..., 0]


def _wave_walk_numpy(comp, src_dev, dst_dev, src_key, dst_key, field_mat,
                     seeds_u64, loads, *, hash_backend, max_hops, quantum,
                     cool=False, near=False):
    """One speculative wave: every (flow, seed) cell walks the fabric
    against the *frozen* ``(S, L)`` load snapshot — fully vectorized over
    flows, seeds, and candidates (no per-flow Python loop).  Decisions
    compare loads quantized to ``quantum`` (see ``WaveCongestionAware``),
    so near-equal links tie and the hash spreads the wave across them
    instead of herding every cell onto one strict argmin.  Returns the
    ``(hops, N, S)`` link tensor plus the final state / done grids for
    the caller's arrival checks."""
    na, S = len(src_dev), len(seeds_u64)
    state = np.broadcast_to(src_dev[:, None], (na, S)).copy()
    done = np.zeros((na, S), bool)
    out = np.full((max_hops, na, S), -1, np.int32)
    flat = np.floor(loads.reshape(-1) / quantum)
    row_off = np.arange(S, dtype=np.int64) * comp.num_links
    cand_w = comp.cand.shape[-1]
    col_idx = np.arange(cand_w, dtype=np.int64)
    hops = 0
    for t in range(max_hops):
        if done.all():
            break
        hops = t + 1
        key = np.where(comp.is_server[state], src_key[:, None],
                       dst_key[:, None])
        nc = comp.cand_n[state, key]                   # (N, S)
        cw = min(int(nc.max()), cand_w) or 1           # live table width
        cands = comp.cand[state, key, :cw]             # (N, S, cw)
        valid = (col_idx[:cw] < nc[..., None]) & (cands >= 0)
        cl = np.where(valid,
                      flat[row_off[None, :, None] + np.maximum(cands, 0)],
                      np.inf)
        dev_seed = comp.dev_crc[state] ^ seeds_u64[None, :]
        h = hash_grid(field_mat, dev_seed, hash_backend)
        link = _wave_choice(cands, valid, cl, h, cw, cool, near)
        link = np.where(done | (nc == 0), -1, link)
        out[t] = link
        nxt = np.where(link >= 0, comp.link_dst[np.maximum(link, 0)], state)
        done |= (link < 0) | comp.is_server[nxt]
        state = nxt
    return out[:hops], state, done


def _wave_conflicts(comp, ids, src_dev, src_key, dst_key,
                    spec_loads, w_flow, *, quantum, tol=1.0):
    """``(conflict, rate)`` over the (N, S) cells of a routed assignment.

    ``conflict`` flags cells whose chosen link at some hop carries at
    least ``tol`` quanta *more than the mean of its candidate set*
    under ``spec_loads`` — ECN-style overload marking, the same
    mean-relative rule the adaptive re-spray uses.  It pairs with the
    cool-half arrival rule: movers land hash-uniformly on the
    at-most-mean half of the candidate table, so a cell is marked
    exactly when it sits above the level repair can take it to, and
    the mark needs no self-exclusion (a link ``tol`` quanta hotter
    than its neighbours is overloaded no matter which flows make up
    the load).

    Marking distance-to-*minimum* instead was measured and rejected:
    zero min-relative conflicts is discrepancy-``tol`` balance at every
    decision layer simultaneously — a fixpoint parallel repair cannot
    reach (and with integer layer means, literal perfection), so the
    marks never drain, every round re-walks thousands of movers, and
    the strategy runs slower than the sequential loop it replaces.

    ``rate`` is the excess-proportional repair probability: a marked
    cell on a link of quantized load ``L`` with context mean ``mu``
    gets ``(L - mu) / (2 L)`` —
    sampling movers at that rate takes an *expected* ``(L - m) / 2``
    flows off the link, half the excess, so repair is aggressive on a
    fresh ECMP stampede and self-anneals to single-flow nudges near the
    fixpoint instead of herding.

    The scan is context-factored: the candidate mean only depends on
    the (device, key, seed) forwarding context — a few thousand rows of
    the compiled tables — never on which cell is asking, so the means
    are tabulated once per round as a ``(V, K, S)`` grid and each hop
    of each cell costs two gathers (own load + context mean) instead
    of a per-cell sweep of the whole candidate row.  At bench scale
    this is the difference between the rescan dominating the round and
    the rescan being noise."""
    n_hops, na, S = ids.shape
    flatq = np.floor(spec_loads.reshape(-1) / quantum)
    row_off = np.arange(S, dtype=np.int64) * comp.num_links
    V, K, C = comp.cand.shape
    valid_vk = (np.arange(C, dtype=np.int64) < comp.cand_n[..., None]) \
        & (comp.cand >= 0)
    clq = flatq[np.maximum(comp.cand, 0)[..., None] + row_off]  # (V,K,C,S)
    n_valid = np.maximum(valid_vk.sum(axis=-1), 1)              # (V,K)
    mu = (np.where(valid_vk[..., None], clq, 0.0).sum(axis=2)
          / n_valid[..., None])                                 # (V,K,S)
    state = np.broadcast_to(src_dev[:, None], (na, S)).copy()
    conflict = np.zeros((na, S), bool)
    rate = np.zeros((na, S))
    cols = np.arange(S, dtype=np.int64)
    for t in range(n_hops):
        chosen = ids[t]                                # (N, S)
        walked = chosen >= 0
        if not walked.any():
            break
        key = np.where(comp.is_server[state], src_key[:, None],
                       dst_key[:, None])
        own = flatq[np.maximum(chosen, 0).astype(np.int64)
                    + row_off[None, :]]
        mu_c = mu[state, key, cols[None, :]]
        hop_conf = walked & (own >= mu_c + tol)
        conflict |= hop_conf
        hop_rate = np.where(
            hop_conf, (own - mu_c) / np.maximum(2.0 * own, 1.0), 0.0)
        rate = np.maximum(rate, hop_rate)
        state = np.where(walked, comp.link_dst[np.maximum(chosen, 0)], state)
    return conflict, rate


def _scatter_cell_loads(sel: np.ndarray, w_flow: np.ndarray,
                        row_off: np.ndarray, num_links: int) -> np.ndarray:
    """(S, L) demand scatter of an ``(H, N, S)`` link tensor (−1 skipped);
    bincount, because distinct flows legitimately share (seed, link)
    cells — the fused fancy-index add of the sequential loop is only
    exact within one flow."""
    S = sel.shape[2]
    keep = sel >= 0
    cells = (sel.astype(np.int64) + row_off[None, None, :])[keep]
    w = np.broadcast_to(w_flow[None, :, None], sel.shape)[keep]
    return np.bincount(cells, weights=w,
                       minlength=S * num_links).reshape(S, num_links)


def _mover_accept(new_ids: np.ndarray, old_ids: np.ndarray,
                  loads: np.ndarray, w_flow: np.ndarray,
                  quantum: float) -> np.ndarray:
    """(Na, S) bool: does the re-walked path strictly improve the mover's
    hottest *differing* hop, judged self-free under the frozen snapshot?

    Only the hops where old and new path disagree enter the comparison:
    a path's overall maximum usually sits on a forced or evenly-loaded
    layer (e.g. the per-NIC server links, identical for every choice at
    the layers below), and comparing whole-path maxima would let that
    shared bottleneck veto every repair beneath it.  ``loads`` still
    carries each mover's old path (nothing is retracted until the round
    commits), so loads are read with the cell's own demand removed:
    every old-path link carries it by construction, and a new-path link
    carries it exactly when it is also an old-path link.  The comparison
    is quantized like every other wave decision: an equal-quantum swap
    is NOT an improvement, so symmetric movers can never trade places
    forever (anti-flip-flop), and a re-walk that reproduces the old path
    exactly is simply not a move."""
    H = max(new_ids.shape[0], old_ids.shape[0])

    def pad(ids):
        if ids.shape[0] == H:
            return ids
        out = np.full((H,) + ids.shape[1:], -1, np.int32)
        out[:ids.shape[0]] = ids
        return out

    new_ids, old_ids = pad(new_ids), pad(old_ids)
    S, L = loads.shape
    flat = loads.reshape(-1)
    off = np.arange(S, dtype=np.int64) * L

    def path_loads(ids):
        cells = np.where(ids >= 0, ids.astype(np.int64) + off, 0)
        return np.where(ids >= 0, flat[cells], 0.0)

    w = w_flow[None, :, None]
    diff = new_ids != old_ids
    old_l = path_loads(old_ids) - np.where(old_ids >= 0, w, 0.0)
    member = ((new_ids[:, None] == old_ids[None]) & (new_ids[:, None] >= 0)
              ).any(axis=1)
    new_l = path_loads(new_ids) - np.where(member, w, 0.0)
    old_max = np.where(diff & (old_ids >= 0), old_l, -np.inf).max(axis=0)
    new_max = np.where(diff & (new_ids >= 0), new_l, -np.inf).max(axis=0)
    return (diff & (new_ids >= 0)).any(axis=0) & (
        np.floor((new_max + w_flow[:, None]) / quantum)
        < np.floor((old_max + w_flow[:, None]) / quantum))


class WaveCongestionAware(CongestionAware):
    """Wave-parallel congestion-aware placement: speculative accept/repair
    (the predictive routing policy of arXiv 2506.08132, vectorized).

    ``CongestionAware`` is a data-dependent chain — flow *k*'s placement
    reads the loads flows *1..k-1* wrote — so it runs as a Python loop
    over flows and caps the strategy matrix at toy scale.  This variant
    replaces the chain with speculate-then-repair:

    1. **wave**: every (flow, seed) cell walks the empty fabric in one
       vectorized shot.  With all loads zero every candidate ties and
       the load-tie-break rule *is* plain ECMP, so round 0 simply runs
       the engine-dispatched ``ecmp_walk`` — the speculative start of
       the accept/repair scheme — and the whole wave commits as a
       complete assignment at once;
    2. **detect** (``_wave_conflicts``): a cell is **conflicted** when
       its chosen link at some hop carries at least ``tol`` quanta more
       than the mean of that hop's candidate set (ECN-style overload
       marking, context-factored into a ``(V, K, S)`` mean table).
       Stampede rounds scan at ``tolerance``; once marks fall below a
       quarter of the cells the loop latches into *polish* rounds that
       scan at the minimum meaningful tolerance of one quantum;
    3. **repair**: a damped subset of the conflicted cells re-walks
       against the frozen load snapshot (``_wave_walk_numpy`` /
       ``jax_engine.jax_wave_walk``) — each conflicted cell moves with
       the excess-proportional probability from the scan (times
       ``move_prob``) under a deterministic splitmix64 coin (cell- and
       round-keyed), except the earliest conflicted flow in placement
       order per seed, which is always eligible (so a round can never
       select nobody).  Undamped repair stampedes every conflicted cell
       onto the same cool links and conflicts them right back — the
       same herd the adaptive re-spray damps.  Mover *arrivals* land
       hash-uniformly across the cool (at-most-mean) half of each
       candidate table during stampede rounds and across the near-min
       window during polish rounds (``_wave_choice``);
    4. **accept**: a move is kept only when it strictly improves the
       cell's hottest *differing* hop by at least one quantum, judged
       self-free under the frozen snapshot (``_mover_accept``) —
       "equally good elsewhere" is NOT a move, so symmetric conflicts
       cannot flip-flop;
    5. **commit**: per round, all accepted movers retract their old
       loads and charge their new ones in ONE atomic scatter pair —
       never flow by flow, which would re-introduce the sequential chain
       and make the conflict test order-dependent within a round;
    6. repeat until no cell is conflicted (fixpoint) or ``max_rounds``;
       residue still conflicted at the cap (scanned at ``tolerance``)
       is retracted and placed by the sequential greedy loop against
       the committed loads (masked so committed cells are neither
       rewritten nor double-charged).

    Quantized parallel repair works where flows are *interchangeable
    quanta*: it needs the mean per-link load to be several quanta deep
    before its fixpoint is as tight as the sequential chain
    (``min_wave_load``, measured crossover ~7 quanta), and it needs
    the per-flow weights to be equal — heterogeneous demand hands the
    sequential chain a heaviest-first ordering advantage the repair
    dynamics consistently fail to reproduce (measured across byte
    mixes, flow counts, equal-mass band decompositions, and round
    budgets).  Outside that regime — small problems, or
    ``demand_mode="bytes"`` with genuinely unequal volumes — ``route``
    delegates to the sequential loop wholesale and stays bit-identical
    to ``CongestionAware``.  Inside it, the vectorized path is both
    faster (5x+ at 10x the bench flow count) and, measured at bench
    scale, tighter-balanced than sequential greedy.

    **Tie-break policy (the documented contract):** decisions take the
    least-loaded candidate with the sequential loop's exact
    ``hash % n_tie`` tie-break over the tied set in table order
    (``_wave_choice``), where loads compare *quantized* to ``quantum`` —
    one mean flow demand (``flow_demand_weights`` normalizes both demand
    modes to mean 1).  Uniform demand therefore compares exact integer
    loads unchanged, while continuous byte-weighted loads keep a tie
    structure the hash can spread waves across (strict float argmin
    would herd every repair onto the single coolest link and immediately
    re-conflict it).

    The result is a *fixpoint of the repair dynamics*, not a replay of
    the sequential order: at convergence no chosen link sits a quantum
    above its candidate-set mean — flows are interchangeable under the
    greedy rule, so the wave reaches a different (measured: tighter)
    member of the same local-optimum family.  The differential test
    (tests/test_wave.py) pins the divergence contract: placements
    bit-identical to ``CongestionAware`` everywhere the cutover
    delegates (small problems, heterogeneous weights), and
    demand-weighted FIM <= sequential greedy on the wave path itself.
    """

    name = "wave-congestion-aware"

    def __init__(self, max_rounds: int = 16, quantum: float = 1.0,
                 move_prob: float = 1.0, tolerance: float = 2.0,
                 min_wave_load: float = 7.0):
        if max_rounds < 1:
            raise ValueError(f"max_rounds must be >= 1, got {max_rounds}")
        if not quantum > 0:
            raise ValueError(f"quantum must be > 0, got {quantum}")
        if not 0.0 < move_prob <= 1.0:
            raise ValueError(
                f"move_prob must be in (0, 1], got {move_prob}")
        if tolerance < 1:
            raise ValueError(f"tolerance must be >= 1, got {tolerance}")
        if min_wave_load < 0:
            raise ValueError(
                f"min_wave_load must be >= 0, got {min_wave_load}")
        self.max_rounds = int(max_rounds)
        self.quantum = float(quantum)
        self.move_prob = float(move_prob)
        self.tolerance = float(tolerance)
        self.min_wave_load = float(min_wave_load)

    def _wave_walk(self, comp, sub, field_mat, seeds_u64, loads, *,
                   hash_backend, max_hops, engine, cool=False, near=False):
        if engine != ENGINE_NUMPY:
            from .jax_engine import jax_wave_walk, resolve_engine
            resolve_engine(engine)
            return jax_wave_walk(
                comp, *sub, field_mat, seeds_u64, loads,
                hash_backend=hash_backend, max_hops=max_hops,
                quantum=self.quantum, cool=cool, near=near)
        return _wave_walk_numpy(
            comp, *sub, field_mat, seeds_u64, loads,
            hash_backend=hash_backend, max_hops=max_hops,
            quantum=self.quantum, cool=cool, near=near)

    @staticmethod
    def _check_wave(comp, flows, act, state, done, dst_dev, max_hops):
        if not np.asarray(done).all():
            raise RuntimeError(
                f"some flows did not terminate in {max_hops} hops")
        state = np.asarray(state)
        arrived = state == dst_dev[:, None]
        if not arrived.all():
            i, k = np.argwhere(~arrived)[0]
            raise RuntimeError(
                f"flow {flows[int(act[i])].flow_id} (seed index {int(k)}) "
                f"terminated at {comp.device_names[int(state[i, k])]}, "
                f"expected {flows[int(act[i])].dst}")

    def route(self, comp, flows, seeds_u64, *, fields=FIELDS_5TUPLE,
              hash_backend=EXACT, max_hops=16, field_matrix=None,
              demand_mode=DEMAND_UNIFORM, engine=ENGINE_NUMPY):
        n, s = len(flows), len(seeds_u64)
        flow_demand = flow_demand_weights(flows, demand_mode)
        # Cutover: quantized parallel repair can only discriminate loads
        # down to one quantum, so it needs the mean per-link load to be
        # several quanta deep before its fixpoint is as tight as the
        # sequential chain's placement (measured crossover ~7 quanta on
        # the paper fabric); below that the sequential loop is the
        # better tool on both axes and the wave simply delegates to it.
        # Heterogeneous per-flow weights delegate too: repair treats
        # flows as interchangeable quanta, which can never reproduce the
        # sequential chain's heaviest-first ordering advantage (measured
        # consistently behind it across byte mixes, flow counts, band
        # decompositions, and round budgets).
        if (n * 1.0 / comp.num_links < self.min_wave_load
                or (n > 0 and not (flow_demand == flow_demand[0]).all())):
            return super().route(
                comp, flows, seeds_u64, fields=fields,
                hash_backend=hash_backend, max_hops=max_hops,
                field_matrix=field_matrix, demand_mode=demand_mode,
                engine=engine)
        field_mat = (field_matrix if field_matrix is not None
                     else flow_fields_matrix(flows, fields))
        endpoints = comp.flow_endpoint_ids(flows)
        order = np.argsort(-flow_demand, kind="stable")  # same as sequential
        o_rank = np.empty(n, np.int64)
        o_rank[order] = np.arange(n, dtype=np.int64)
        row_off = np.arange(s, dtype=np.int64) * comp.num_links
        cols = np.arange(s, dtype=np.int64)
        # round 0: the whole wave walks the empty fabric — every
        # candidate ties at zero, so the wave decision rule degenerates
        # to plain ECMP and the round IS the (engine-dispatched)
        # optimized ECMP walk, committed as a complete assignment in
        # one atomic scatter
        ids0 = ecmp_walk(
            comp, *endpoints, field_mat, seeds_u64,
            hash_backend=hash_backend, max_hops=max_hops, engine=engine)
        hops = ids0.shape[0]
        link_ids = np.full((max_hops, n, s), -1, np.int32)
        link_ids[:hops] = ids0
        load = _scatter_cell_loads(ids0, flow_demand, row_off,
                                   comp.num_links)
        coin_id = (_splitmix64(np.arange(n, dtype=np.uint64))[:, None]
                   ^ seeds_u64[None, :])
        conflict = np.zeros((n, s), bool)
        # Two-phase repair: stampede rounds mark at ``tolerance`` and
        # spread arrivals over the whole cool half of each candidate
        # table (herd-proof while movers are plentiful); once marks drop
        # below a quarter of the cells the round latches into *polish* —
        # marking at the minimum meaningful tolerance of one quantum and
        # steering arrivals near-min, which is what fills the
        # under-loaded tail the cool-uniform rule never targets.
        polish = False
        for rnd in range(self.max_rounds):
            conflict, rate = _wave_conflicts(
                comp, link_ids[:hops], endpoints[0], endpoints[2],
                endpoints[3], load, flow_demand, quantum=self.quantum,
                tol=1.0 if polish else self.tolerance)
            if not conflict.any():
                break
            polish = polish or conflict.sum() < 0.25 * conflict.size
            # damped repair: each conflicted cell moves with the
            # excess-proportional probability from the scan (scaled by
            # move_prob) under a deterministic cell+round-keyed coin ...
            coin = _splitmix64(
                coin_id ^ np.uint64((rnd + 1) * 0x9E3779B97F4A7C15
                                    & 0xFFFFFFFFFFFFFFFF))
            coin_u = (coin >> np.uint64(11)) * 2.0 ** -53
            movers = conflict & (coin_u < self.move_prob * rate)
            # ... except the earliest conflicted flow in placement order
            # per seed, which is always eligible — a repair round can
            # never select nobody
            rk = np.where(conflict, o_rank[:, None], np.iinfo(np.int64).max)
            first = rk.argmin(axis=0)                    # (S,)
            movers[first, cols] |= conflict[first, cols]
            act = np.flatnonzero(movers.any(axis=1))
            sub = tuple(a[act] for a in endpoints)
            w_a = flow_demand[act]
            ids_a, state, done = self._wave_walk(
                comp, sub, field_mat[act], seeds_u64, load,
                hash_backend=hash_backend, max_hops=max_hops, engine=engine,
                cool=True, near=polish)
            self._check_wave(comp, flows, act, state, done, sub[1], max_hops)
            old = link_ids[:hops][:, act, :]
            accept = movers[act] & _mover_accept(
                ids_a, old, load, w_a, self.quantum)
            if not accept.any():
                continue
            t_end = max(hops, ids_a.shape[0])
            pad_new = np.full((t_end,) + ids_a.shape[1:], -1, np.int32)
            pad_new[:ids_a.shape[0]] = ids_a
            pad_old = np.full_like(pad_new, -1)
            pad_old[:hops] = old
            sel_new = np.where(accept[None], pad_new, -1)
            sel_old = np.where(accept[None], pad_old, -1)
            # atomic per-round commit: every accepted mover's old demand
            # is retracted and its new demand charged in ONE scatter
            # pair — never flow by flow
            load += (_scatter_cell_loads(sel_new, w_a, row_off,
                                         comp.num_links)
                     - _scatter_cell_loads(sel_old, w_a, row_off,
                                           comp.num_links))
            merged = link_ids[:t_end][:, act, :]
            np.copyto(merged, pad_new, where=accept[None])
            link_ids[:t_end][:, act, :] = merged
            hops = t_end
        else:
            # round cap without fixpoint: retract whatever is still
            # conflicted and place it with the sequential greedy loop
            # against the committed loads (documented fallback)
            residue, _ = _wave_conflicts(
                comp, link_ids[:hops], endpoints[0], endpoints[2],
                endpoints[3], load, flow_demand, quantum=self.quantum,
                tol=self.tolerance)
            if residue.any():
                sel = np.where(residue[None], link_ids[:hops], -1)
                load -= _scatter_cell_loads(sel, flow_demand, row_off,
                                            comp.num_links)
                np.copyto(link_ids[:hops], np.int32(-1),
                          where=residue[None])
                hops = max(hops, _sequential_congestion_place(
                    comp, flows, field_mat, seeds_u64, endpoints,
                    flow_demand, order, load, link_ids,
                    hash_backend=hash_backend, max_hops=max_hops,
                    mask=residue))
        return VectorTraceResult(
            compiled=comp, flows=list(flows), seeds=seeds_u64,
            link_ids=link_ids[:hops], strategy=self.name,
            flow_demand=flow_demand)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, Callable[[], RoutingStrategy]] = {}


def register_strategy(name: str,
                      factory: Callable[[], RoutingStrategy],
                      *, replace: bool = False) -> None:
    """Register a strategy factory under ``name`` so benchmarks and the
    ``strategy="..."`` string form can construct it on demand.

    A duplicate name raises unless ``replace=True``: every benchmark
    matrix and Monte-Carlo front end resolves strategies by name, so a
    silent overwrite of e.g. ``"ecmp"`` would swap the baseline out from
    under all of them without a trace."""
    if not replace and name in _REGISTRY:
        raise ValueError(
            f"routing strategy {name!r} is already registered "
            f"(registered: {available_strategies()}); pass replace=True "
            f"to overwrite it")
    _REGISTRY[name] = factory


def available_strategies() -> list[str]:
    return sorted(_REGISTRY)


def resolve_strategy(strategy: RoutingStrategy | str) -> RoutingStrategy:
    """A ``RoutingStrategy`` instance passes through; a string constructs
    the registered default configuration of that family."""
    if isinstance(strategy, RoutingStrategy):
        return strategy
    if isinstance(strategy, str):
        try:
            return _REGISTRY[strategy]()
        except KeyError:
            raise ValueError(
                f"unknown routing strategy {strategy!r}; "
                f"registered: {available_strategies()}") from None
    raise TypeError(
        f"strategy must be a RoutingStrategy or registered name, "
        f"got {type(strategy).__name__}")


register_strategy("ecmp", EcmpStrategy)
register_strategy("prime-spray", PrimeSpraying)
register_strategy("prime-spray-elephant",
                  lambda: PrimeSpraying(min_bytes=ELEPHANT_MIN_BYTES,
                                        volume_k=True))
register_strategy("congestion-aware", CongestionAware)
register_strategy("wave-congestion-aware", WaveCongestionAware)
register_strategy("adaptive-spray", AdaptiveSpraying)
register_strategy("adaptive-spray-elephant",
                  lambda: AdaptiveSpraying(min_bytes=ELEPHANT_MIN_BYTES,
                                           volume_k=True))

"""Batched progressive-filling max-min fairness: all seeds fill at once.

``max_min_throughput`` (core/fim.py) is the readable reference: one seed,
dict-of-sets bookkeeping, one bottleneck link frozen per iteration.  The
paper's headline comparison (Fig. 3a) is only half FIM — the other half
is *throughput*: colliding RoCE flows halving each other under max-min
sharing (paper Section I).  Evaluating a routing scheme therefore needs
the per-pair **rate distribution** over thousands of hash seeds, and the
scalar loop is orders of magnitude too slow for that.

This module runs the same filling on the dense ``(H, N, S)`` link-id
tensor that ``vector_sim.simulate_paths`` produces, using the classic
*parallel* formulation of progressive filling: a (link, seed) cell is a
bottleneck as soon as its fair share ``residual / active_flows`` equals
the minimum share seen anywhere on the path of **every** flow crossing
it — not just when it is the global minimum of its seed.  Freezing all
such local bottlenecks at once collapses the ~1-per-distinct-rate-level
iteration count of the scalar loop into the depth of the bottleneck
dependency chain (~10 rounds for thousands of seeds), and every round is
whole-array numpy:

* per-flow bottleneck shares are one gather + running ``minimum`` over
  the hop axis;
* per-cell neighbourhood minima are one ``minimum.at`` scatter;
* the drain of frozen flows is two ``bincount``s over their cells.

Because max-min rates are unique, freezing any local bottleneck (rather
than the scalar code's global minimum) yields the same allocation; float
drift from the different freeze order is ~1e-15 relative, and the engine
is differentially tested against the scalar reference at 1e-9 on
randomized fabrics, workloads, and seeds (tests/test_vector_throughput.py).

Seeds are processed in blocks sized so the per-cell state (share,
residual, counts) stays cache-resident; cell ids are block-local, which
also keeps them safely within int32 for any realistic sweep.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Sequence

import numpy as np

from .compile_fabric import CompiledFabric, compile_fabric
from .contracts import check_throughput, contracts_enabled
from .fabric import Fabric
from .flows import Flow, WorkloadDescription
from .spans import count, span
from .vector_sim import (
    ENGINE_NUMPY, SimSpec, VectorTraceResult, _UNSET,
    _is_plain_ecmp, resolve_flows, resolve_spec,
    segment_reduce, simulate_paths,
)

# Seeds per cache block: per-cell state is ~5 arrays of seed_block * L
# float64, which stays L2-resident for typical fabrics (L ~ a few hundred).
DEFAULT_SEED_BLOCK = 48


def dedup_link_ids(link_ids: np.ndarray) -> np.ndarray:
    """Copy of an ``(H, N, S)`` link-id tensor with repeated links within
    one (flow, seed) path collapsed to a single entry (-1 elsewhere).

    The scalar engine keys link membership on *sets* of flow ids, so a
    flow crossing the same link twice is counted (and drained) once.
    Fabric-walked paths are loop-free, but synthetic tensors (and future
    multi-path schemes) may not be.

    Each hop row is compared against all earlier rows in ONE broadcast
    (``(ids[h] == ids[:h]).any(0)``) — quadratic in H but vectorized
    over the big (N, S) axes, which is what matters: H is capped by
    ``max_hops`` (16) while flowlet tensors grow N into the thousands.
    A value match against any earlier hop suffices (matching a -1 can
    only happen when ``ids[h]`` is itself -1, which the write guard
    excludes), so the old per-pair ``ids[g] >= 0`` masks are gone.  The
    prescribed sort-along-hop + shift-compare rewrite was measured and
    rejected: numpy's axis sorts cost 3-5x these compares at every
    realistic shape (still 1.5x slower at H=128, far past any walk).
    """
    with span("dedup"):
        ids = np.array(link_ids, copy=True)
        for h in range(1, ids.shape[0]):
            dup = (ids[h] == ids[:h]).any(axis=0)
            np.copyto(ids[h], -1, where=dup & (ids[h] >= 0))
        return ids


def _fill_block(sub: np.ndarray, sentinel: int, cap: np.ndarray,
                rates_out: np.ndarray, ws: dict) -> None:
    """Progressive-fill one seed block in place.

    ``sub``: (H, cols) int32 cell ids (cell = seed_in_block * L + link),
    ``sentinel`` past-the-end cell id for "no link at this hop",
    ``cap``: (cells,) float64 capacity per cell, ``rates_out``: (cols,)
    output view.  ``ws`` holds reusable scratch buffers.
    """
    H, NS = sub.shape
    SL = sentinel

    counts = ws["counts"][:SL + 1]         # sentinel slot absorbs the
    residual = ws["residual"][:SL + 1]     # no-link hops of short paths
    counts[:] = np.bincount(sub.ravel(), minlength=SL + 1)
    residual[:SL] = cap
    residual[SL] = 0.0
    share = np.full(SL + 1, np.inf)
    nz = counts[:SL] > 0
    share[:SL][nz] = residual[:SL][nz] / counts[:SL][nz]

    haslink = sub[0] < SL
    for h in range(1, H):
        haslink |= sub[h] < SL
    if haslink.all():
        aidx = None                       # common case: every flow routed
        A = NS
        first = sub                       # round 1 reads sub in place
    else:
        rates_out[~haslink] = np.inf      # fim.py's infinite-rate branch
        idx = np.flatnonzero(haslink).astype(np.int32)
        aidx = idx
        A = idx.size
        np.take(sub, idx, axis=1, out=ws["subw"][0][:, :A])
        first = None
    subw, sv, fzb, ek, wk, nbr = (ws["subw"], ws["sv"], ws["fzb"],
                                  ws["ek"], ws["wk"], ws["nbr"])
    freezable = ws["freezable"]
    freezable[SL] = False
    cur = 0
    while A:
        s = first if first is not None else subw[cur][:, :A]
        svv = sv[:, :A]
        for h in range(H):                 # per-flow bottleneck share
            np.take(share, s[h], out=svv[h])
        fm = svv[0]
        for h in range(1, H):
            np.minimum(fm, svv[h], out=fm)
        nbr_v = nbr[:SL + 1]               # per-cell min of member shares
        nbr_v.fill(np.inf)
        for h in range(H):
            np.minimum.at(nbr_v, s[h], fm)
        np.equal(nbr_v[:SL], share[:SL], out=freezable[:SL])
        fzv = fzb[:, :A]                   # flow crosses a local bottleneck
        for h in range(H):
            np.take(freezable, s[h], out=fzv[h])
        fz = fzv[0]
        for h in range(1, H):
            fz |= fzv[h]
        fidx = np.flatnonzero(fz)
        F = fidx.size
        w_f = fm[fidx]
        if aidx is None:
            rates_out[fidx] = w_f
        else:
            rates_out[aidx[fidx]] = w_f
        if F == A:                         # everything froze: no survivors
            break                          # to drain for
        ekv = ek[:H * F].reshape(H, F)     # drain the frozen flows
        np.take(s, fidx, axis=1, out=ekv)
        wkv = wk[:H * F].reshape(H, F)
        wkv[:] = w_f
        ekf = ek[:H * F]
        np.subtract.at(counts, ekf, 1.0)
        np.subtract.at(residual, ekf, wk[:H * F])
        # recompute shares at the touched cells; duplicate entries simply
        # rewrite the same value, so no dedup pass is needed
        c2 = counts[ekf]
        r2 = residual[ekf]
        share[ekf] = np.where(c2 > 0, r2 / np.maximum(c2, 1.0), np.inf)
        share[SL] = np.inf                 # sentinel must stay unroutable
        kidx = np.flatnonzero(~fz)         # compact to surviving flows
        A = kidx.size
        nxt = 1 - cur
        np.take(s, kidx, axis=1, out=subw[nxt][:, :A])
        if aidx is not None:
            aidx = aidx[kidx]
        else:
            aidx = kidx.astype(np.int32)
        first = None
        cur = nxt


def _fill_block_weighted(sub: np.ndarray, sentinel: int, cap: np.ndarray,
                         w: np.ndarray, rates_out: np.ndarray) -> None:
    """Weighted progressive-fill of one seed block (flowlet demand model).

    Same parallel local-bottleneck formulation as ``_fill_block``, with
    every flow (column) carrying a positive demand weight ``w``: a link's
    fair share is ``residual / sum of member weights`` (share *per unit
    demand*), a flow's rate is ``w * min share over its path``, and the
    max-min objective is over normalized rates — the standard weighted
    max-min fairness that makes K equal flowlets of one flow share
    exactly like the single parent flow when their paths coincide.

    Weighted link occupancy drifts by float epsilons as flows drain, so
    emptiness is tracked by an exact integer membership count alongside
    the weighted sum.  Kept separate from the unweighted path, which
    stays byte-identical to the PR-2 engine.
    """
    H, NS = sub.shape
    SL = sentinel
    mem = np.bincount(sub.ravel(), minlength=SL + 1).astype(np.float64)
    counts = np.bincount(sub.ravel(),
                         weights=np.broadcast_to(w, (H, NS)).ravel(),
                         minlength=SL + 1)
    residual = np.empty(SL + 1)
    residual[:SL] = cap
    residual[SL] = 0.0
    share = np.full(SL + 1, np.inf)
    nz = mem[:SL] > 0
    share[:SL][nz] = residual[:SL][nz] / counts[:SL][nz]

    haslink = (sub < SL).any(axis=0)
    rates_out[~haslink] = np.inf           # fim.py's infinite-rate branch
    aidx = np.flatnonzero(haslink)
    s = sub[:, aidx]
    wa = w[aidx]
    freezable = np.zeros(SL + 1, bool)
    while aidx.size:
        fm = share[s].min(axis=0)          # per-flow bottleneck share
        nbr = np.full(SL + 1, np.inf)      # per-cell min of member shares
        for h in range(H):
            np.minimum.at(nbr, s[h], fm)
        np.equal(nbr[:SL], share[:SL], out=freezable[:SL])
        fz = freezable[s].any(axis=0)      # flow crosses a local bottleneck
        fidx = np.flatnonzero(fz)
        fnorm = fm[fidx]
        rates_out[aidx[fidx]] = wa[fidx] * fnorm
        if fidx.size == aidx.size:         # everything froze: no survivors
            break                          # to drain for
        cells = s[:, fidx]                 # (H, F) drain the frozen flows
        flat = cells.ravel()
        np.subtract.at(mem, flat, 1.0)
        np.subtract.at(counts, flat,
                       np.broadcast_to(wa[fidx], cells.shape).ravel())
        np.subtract.at(residual, flat,
                       np.broadcast_to(wa[fidx] * fnorm, cells.shape).ravel())
        m2 = mem[flat]
        share[flat] = np.where(
            m2 > 0, residual[flat] / np.maximum(counts[flat], 1e-300), np.inf)
        share[SL] = np.inf                 # sentinel must stay unroutable
        keep = ~fz
        s = np.ascontiguousarray(s[:, keep])
        aidx = aidx[keep]
        wa = wa[keep]


def batched_max_min(
    link_ids: np.ndarray,
    link_gbps: np.ndarray,
    *,
    assume_unique: bool = False,
    seed_block: int = DEFAULT_SEED_BLOCK,
    weights: np.ndarray | None = None,
    engine: str = ENGINE_NUMPY,
) -> np.ndarray:
    """Max-min fair rates (Gb/s) for an ``(H, N, S)`` link-id tensor.

    ``engine="jax"`` runs the same parallel local-bottleneck fill as a
    jitted ``lax.while_loop`` on the accelerator
    (``jax_engine.jax_batched_max_min``; results agree to float-epsilon
    freeze-order drift, differential-tested at 1e-6).

    ``link_ids[h, n, s]`` is the id of the h-th link flow ``n`` crosses
    under seed ``s`` (-1 past the end of the path); ``link_gbps`` maps
    link id -> capacity.  Returns ``(N, S)`` rates; a flow crossing zero
    links gets ``inf`` exactly like the scalar reference.

    ``weights`` optionally gives every tensor column a positive demand
    weight (flowlets of a sprayed flow carry fractions of the parent's
    demand): the allocation becomes weighted max-min — fair share per
    unit demand — and a column's rate is its weight times its bottleneck
    share.  ``None`` (or all-ones) is the exact unweighted PR-2 engine.

    ``assume_unique`` skips the within-path duplicate-link collapse —
    safe for tensors from ``simulate_paths``, whose walked paths are
    loop-free by construction.  ``seed_block`` tunes the cache-residency
    granularity and never changes results.
    """
    if engine != ENGINE_NUMPY:
        from .jax_engine import jax_batched_max_min, resolve_engine
        resolve_engine(engine)
        return jax_batched_max_min(link_ids, link_gbps,
                                   assume_unique=assume_unique,
                                   weights=weights)
    link_ids = np.asarray(link_ids)
    if link_ids.ndim != 3:
        raise ValueError(f"link_ids must be (H, N, S), got {link_ids.shape}")
    if not assume_unique:
        link_ids = dedup_link_ids(link_ids)
    H, N, S = link_ids.shape
    if weights is not None:
        weights = np.asarray(weights, np.float64)
        if weights.shape != (N,):
            raise ValueError(
                f"weights must be ({N},) to match link_ids columns, "
                f"got {weights.shape}")
        if not (weights > 0).all():
            raise ValueError("weights must be strictly positive")
        if (weights == 1.0).all():
            weights = None                 # uniform: take the exact path
    L = len(link_gbps)
    cap = np.asarray(link_gbps, np.float64)
    rates = np.empty((S, N))
    if H == 0 or N == 0 or S == 0:
        rates[:] = np.inf if H == 0 else 0.0
        return rates.T
    # seed-major layout: all cells of one seed share one L-window of the
    # per-cell state, so gathers/scatters are cache-local
    ids_all = np.ascontiguousarray(link_ids.transpose(0, 2, 1))  # (H, S, N)

    Sb = max(1, min(seed_block, S))
    NSb, SLb = N * Sb, Sb * L
    offs = np.repeat(np.arange(Sb, dtype=np.int32) * np.int32(L), N)
    ws = {
        "subw": np.empty((2, H, NSb), np.int32),
        "sv": np.empty((H, NSb)),
        "fzb": np.empty((H, NSb), bool),
        "ek": np.empty(H * NSb, np.int32),
        "wk": np.empty(H * NSb),
        "nbr": np.empty(SLb + 1),
        "freezable": np.zeros(SLb + 1, bool),
        "residual": np.empty(SLb + 1),
        "counts": np.empty(SLb + 1),
        "sub": np.empty((H, NSb), np.int32),
        "cap": np.empty(SLb),
    } if weights is None else {
        "sub": np.empty((H, NSb), np.int32),
        "cap": np.empty(SLb),
    }
    for s0 in range(0, S, Sb):
        s1 = min(s0 + Sb, S)
        Sc = s1 - s0
        NS, SL = N * Sc, Sc * L
        blk = ids_all[:, s0:s1, :].reshape(H, NS)
        sub = ws["sub"][:, :NS]
        np.add(blk, offs[None, :NS], out=sub)
        sub[blk < 0] = SL
        capb = ws["cap"][:SL]
        capb[:] = np.broadcast_to(cap, (Sc, L)).ravel()
        if weights is None:
            _fill_block(sub, SL, capb, rates[s0:s1].reshape(-1), ws)
        else:
            _fill_block_weighted(sub, SL, capb, np.tile(weights, Sc),
                                 rates[s0:s1].reshape(-1))
    return rates.T                         # (N, S) transposed view


def max_min_rates(result: VectorTraceResult,
                  engine: str = ENGINE_NUMPY) -> np.ndarray:
    """``(Nf, S)`` max-min rates for every tensor column (flowlet) under
    every traced seed.  Single-path unit-demand results: one column per
    flow, the PR-2 behaviour exactly.  Otherwise every column carries
    its *effective* demand — the parent flow's ``flow_demand`` times the
    flowlet fraction (``column_weights``) — as its max-min weight, so a
    byte-weighted elephant claims share proportional to its volume; a
    plain ``result.demand`` here would silently revert every flow to
    unit demand.  Aggregate per parent flow with
    ``flow_rates_from_flowlets``."""
    w = result.column_weights()
    if (w == 1.0).all():
        w = None
    return batched_max_min(result.link_ids, result.compiled.link_gbps,
                           assume_unique=True, weights=w, engine=engine)


def flow_rates_from_flowlets(result: VectorTraceResult,
                             flowlet_rates: np.ndarray) -> np.ndarray:
    """Aggregate ``(Nf, S)`` flowlet rates into ``(N, S)`` per-flow rates
    by summing columns of the same parent (``result.flow_index``) — the
    same segment reduction (``vector_sim.segment_reduce``) the exposure
    model runs, so the two can never disagree on the grouping."""
    fi = result.flow_index
    if not result.is_multipath and (
            fi == np.arange(len(fi), dtype=np.int64)).all():
        return flowlet_rates
    return np.ascontiguousarray(
        segment_reduce(flowlet_rates, fi, result.num_flows, np.add, 0.0),
        dtype=np.float64)


@dataclasses.dataclass
class DepartureFill:
    """Result of a departure-ordered max-min drain (``departure_fill``).

    ``completion[n, s]`` is the absolute time (seconds) at which tensor
    column ``n``'s bytes finish under seed ``s``; ``duration[s]`` is the
    completion time of the slowest column — the step's derived duration;
    ``rounds`` counts the re-fill rounds the drain needed (one per
    distinct departure epoch, bounded by the column count).
    """

    completion: np.ndarray               # (Nf, S) seconds per column
    duration: np.ndarray                 # (S,) slowest-column completion
    rounds: int


def departure_fill(
    link_ids: np.ndarray,
    link_gbps: np.ndarray,
    col_gbits: np.ndarray,
    *,
    weights: np.ndarray | None = None,
    efficiency: np.ndarray | None = None,
    assume_unique: bool = False,
    seed_block: int = DEFAULT_SEED_BLOCK,
    initial_rates: np.ndarray | None = None,
    engine: str = ENGINE_NUMPY,
) -> DepartureFill:
    """Water-filling with departures over an ``(H, N, S)`` link-id tensor.

    Every column ``n`` carries ``col_gbits[n]`` gigabits.  All columns
    start draining at their max-min rate (``batched_max_min``, weighted
    by ``weights`` exactly like ``max_min_rates``); the earliest-finishing
    cells *depart* — their remaining bytes hit zero — and the survivors'
    rates are re-filled over the **same** path tensor with the departed
    (column, seed) cells deactivated, so tail flows speed up as elephants
    drain.  No re-walk happens: deactivating a cell is writing ``-1``
    over its link ids, which the fill already treats as "crosses no
    links" per (column, seed) cell.  Seeds progress independently (each
    has its own departure order); the fill itself stays batched across
    the surviving seed-set every round, and fully-drained columns are
    compacted out of the tensor between rounds.

    ``efficiency`` optionally scales each cell's drain rate (goodput =
    rate x efficiency, the transport reordering model); it is held fixed
    across re-fills — the exposure a routing assignment induces is a
    property of the committed paths, not of who has already left the
    wire.  ``initial_rates`` lets callers that already ran the full-set
    fill (``throughput_from_result``) reuse it as round 1; it is only
    trusted when every column starts active, otherwise it is recomputed.

    Zero-gigabit columns complete at t=0 and never contend; columns that
    cross no links drain at infinite rate and also complete at t=0.
    Times are seconds for ``col_gbits`` in gigabits and ``link_gbps`` in
    Gb/s (``bytes * 8e-9`` converts).

    ``engine="jax"`` delegates the drain to this host loop (after
    validating the engine name): every departure epoch re-fills a
    *shrunken* column set, which under jit would re-trace per shape —
    and the numpy compacting fill already dominates the jax fill ~17x on
    CPU (PR 7 measurement, see ROADMAP) before paying any of that.  The
    walk that produced ``link_ids`` may of course come from either
    engine; the drain is bit-identical downstream of it.
    """
    with span("departure_fill"):
        if engine != ENGINE_NUMPY:
            from .jax_engine import resolve_engine
            resolve_engine(engine)
        link_ids = np.asarray(link_ids)
        if link_ids.ndim != 3:
            raise ValueError(f"link_ids must be (H, N, S), got {link_ids.shape}")
        if not assume_unique:
            link_ids = dedup_link_ids(link_ids)
        H, N, S = link_ids.shape
        gb = np.asarray(col_gbits, np.float64)
        if gb.shape != (N,):
            raise ValueError(
                f"col_gbits must be ({N},) to match link_ids columns, "
                f"got {gb.shape}")
        if (gb < 0).any() or not np.isfinite(gb).all():
            raise ValueError("col_gbits must be finite and >= 0")
        if efficiency is None:
            eff = np.ones((N, S))
        else:
            eff = np.asarray(efficiency, np.float64)
            if eff.shape != (N, S):
                raise ValueError(
                    f"efficiency must be ({N}, {S}), got {eff.shape}")
            if not ((eff > 0) & np.isfinite(eff)).all():
                raise ValueError("efficiency must be finite and > 0")
        completion = np.zeros((N, S))
        if N == 0 or S == 0 or H == 0:
            return DepartureFill(completion=completion,
                                 duration=completion.max(axis=0, initial=0.0),
                                 rounds=0)
        t = np.zeros(S)
        rem = np.broadcast_to(gb[:, None], (N, S)).copy()
        active = rem > 0.0
        ids = link_ids.copy()
        ids[:, ~active] = -1                   # zero-gigabit cells never contend
        rounds = 0
        while True:
            alive = active.any(axis=1)         # column compaction
            if not alive.any():
                break
            rounds += 1
            if rounds > N + 1:                 # >= 1 cell departs per round per
                raise RuntimeError(            # active seed, so N+1 is unreachable
                    "departure_fill failed to converge (rate degeneracy?)")
            sel = np.flatnonzero(alive)
            sub_ids = ids[:, sel]
            if rounds == 1 and initial_rates is not None and alive.all():
                rates = np.asarray(initial_rates, np.float64)
                if rates.shape != (N, S):
                    raise ValueError(
                        f"initial_rates must be ({N}, {S}), got {rates.shape}")
            else:
                rates = batched_max_min(
                    sub_ids, link_gbps, assume_unique=True,
                    seed_block=seed_block,
                    weights=None if weights is None else
                    np.asarray(weights, np.float64)[sel])
            act = active[sel]
            good = rates * eff[sel]
            with np.errstate(divide="ignore", invalid="ignore"):
                fin = np.where(act, rem[sel] / good, np.inf)
            fin = np.where(np.isnan(fin), np.inf, fin)
            dt = fin.min(axis=0)               # (S,) next departure horizon
            seed_active = act.any(axis=0)
            if (seed_active & ~np.isfinite(dt)).any():
                raise RuntimeError(
                    "departure_fill: active flow with zero goodput can never "
                    "finish (zero-capacity bottleneck link?)")
            dt0 = np.where(seed_active, dt, 0.0)
            # everything within float tolerance of the horizon departs together
            depart = act & (fin <= dt[None, :] * (1.0 + 1e-12))
            comp_sel = completion[sel]
            comp_sel[depart] = (t[None, :] + fin)[depart]
            completion[sel] = comp_sel
            drain = np.where(act & np.isfinite(good), good, 0.0) * dt0[None, :]
            rem_sel = np.maximum(rem[sel] - drain, 0.0)
            rem_sel[depart] = 0.0
            rem[sel] = rem_sel
            t += dt0
            active[sel] = act & ~depart
            sub_ids[:, depart] = -1            # departed cells leave the wire
            ids[:, sel] = sub_ids
        count("rounds", rounds)
        return DepartureFill(completion=completion,
                             duration=completion.max(axis=0, initial=0.0),
                             rounds=rounds)


@dataclasses.dataclass
class MonteCarloThroughput:
    """Per-flow and per-pair max-min rate distributions over a seed sweep.

    ``rates`` is the raw max-min allocation (what the fabric *delivers*);
    ``goodput`` is what the transport can *use* after paying the flowlet
    reordering cost — ``rates x efficiency`` under the ``transport``
    profile (core/reordering.py).  Under the default ``"ideal"``
    transport (and for any single-path strategy, whose exposure is zero)
    ``goodput`` is bit-identical to ``rates``.
    """

    seeds: np.ndarray                    # (S,)
    flows: list[Flow]
    rates: np.ndarray                    # (N, S) Gb/s per flow per seed
    pairs: list[tuple[str, str]]         # (src, dst) in first-seen order
    per_pair: np.ndarray                 # (P, S) Gb/s per pair per seed
    transport: str = "ideal"             # reordering profile name
    exposure: np.ndarray | None = None   # (N, S) out-of-order exposure
    efficiency: np.ndarray | None = None  # (N, S) goodput multiplier
    goodput: np.ndarray | None = None    # (N, S) effective Gb/s per flow

    def __post_init__(self):
        if self.exposure is None:
            self.exposure = np.zeros_like(self.rates)
        if self.efficiency is None:
            self.efficiency = np.ones_like(self.rates)
        if self.goodput is None:
            # a copy, not an alias: in-place edits of one must never
            # leak into the other
            self.goodput = self.rates.copy()

    @property
    def num_seeds(self) -> int:
        return len(self.seeds)

    def pair_throughput_for_seed(
        self, seed_index: int
    ) -> dict[tuple[str, str], float]:
        """One seed's pair throughputs in ``per_pair_throughput`` format."""
        return {p: float(self.per_pair[i, seed_index])
                for i, p in enumerate(self.pairs)}

    def summary(self) -> dict[str, dict[str, float]]:
        rows = {
            "flow_rate": self.rates,
            "flow_goodput": self.goodput,
            "pair_total": self.per_pair,
            "pair_min": self.per_pair.min(axis=0),
            "pair_median": np.median(self.per_pair, axis=0),
        }
        out = {}
        for name, v in rows.items():
            v = np.asarray(v, np.float64).ravel()
            out[name] = {
                "mean": float(v.mean()),
                "std": float(v.std()),
                "min": float(v.min()),
                "p50": float(np.percentile(v, 50)),
                "p95": float(np.percentile(v, 95)),
                "max": float(v.max()),
            }
        return out


def pair_rate_matrix(
    flows: Sequence[Flow], rates: np.ndarray
) -> tuple[list[tuple[str, str]], np.ndarray]:
    """Aggregate ``(N, S)`` flow rates into ``(P, S)`` per-pair totals.

    Pairs are ordered by first appearance in ``flows``, matching the dict
    insertion order of the scalar ``per_pair_throughput``.
    """
    pair_index: dict[tuple[str, str], int] = {}
    idx = np.empty(len(flows), np.int64)
    for j, f in enumerate(flows):
        idx[j] = pair_index.setdefault((f.src, f.dst), len(pair_index))
    if len(flows) and (np.diff(idx) >= 0).all():
        # flows grouped by pair (synthesize_flows order): segment-sum
        starts = np.flatnonzero(np.diff(idx, prepend=-1) > 0)
        per_pair = np.add.reduceat(rates, starts, axis=0)
        per_pair = np.ascontiguousarray(per_pair, dtype=np.float64)
    else:
        per_pair = np.zeros((len(pair_index), rates.shape[1]))
        np.add.at(per_pair, idx, rates)
    return list(pair_index), per_pair


def throughput_from_result(
    result: VectorTraceResult,
    *,
    transport=None,
    flowlet_rates: np.ndarray | None = None,
    engine: str = ENGINE_NUMPY,
) -> MonteCarloThroughput:
    """Rate distributions for an already-simulated ``VectorTraceResult``
    (lets callers share one ``simulate_paths`` pass between FIM and
    throughput, as ``benchmarks/fig3a_routing_comparison.py`` does).

    Multi-path results run the weighted fill over flowlet columns and
    aggregate rates per parent flow, so ``rates`` is always ``(N, S)``
    over ``result.flows`` regardless of strategy.

    ``transport`` selects the reordering cost model (a
    ``TransportProfile``, a registered name like ``"roce-nack"`` /
    ``"strack"``, or ``None`` for the free ``"ideal"`` model): flowlet
    out-of-order exposure is computed from the same fill
    (``flowlet_exposure`` reuses the per-flowlet rates, and folds in any
    strategy-charged ``VectorTraceResult.extra_exposure`` — adaptive
    re-spray bills its mid-flow path changes there) and
    ``goodput = rates x efficiency``.  Zero-exposure flows — every flow
    of a single-path strategy, and every unsprayed flow of demand-aware
    spraying — keep ``goodput`` bit-identical to ``rates``.  A profile
    with ``alpha == 0`` or ``floor == 1`` makes every flow's efficiency
    1 regardless of exposure, so the exposure pass is skipped outright
    (``.exposure`` reads 0 — the pre-reordering behaviour at the
    pre-reordering cost); request a lossy profile to get exposure
    diagnostics.

    ``flowlet_rates`` optionally supplies a precomputed
    ``max_min_rates(result)`` tensor so callers evaluating the same
    routed result under several transports run the progressive fill —
    the dominant cost — once.

    ``engine="jax"`` runs the fill and the exposure segment reductions
    on the device engine (``jax_engine``); the pair aggregation and the
    efficiency map are output-sized and stay host-side."""
    from .reordering import (
        flowlet_exposure, reordering_efficiency, resolve_transport,
    )
    profile = resolve_transport(transport)
    if flowlet_rates is None:
        flowlet_rates = max_min_rates(result, engine=engine)
    rates = flow_rates_from_flowlets(result, flowlet_rates)
    pairs, per_pair = pair_rate_matrix(result.flows, rates)
    if profile.alpha == 0.0 or profile.floor == 1.0:
        tp = MonteCarloThroughput(seeds=result.seeds, flows=result.flows,
                                  rates=rates, pairs=pairs,
                                  per_pair=per_pair,
                                  transport=profile.name)
    else:
        exposure = flowlet_exposure(result, flowlet_rates, engine=engine)
        efficiency = reordering_efficiency(exposure, profile)
        tp = MonteCarloThroughput(seeds=result.seeds, flows=result.flows,
                                  rates=rates, pairs=pairs,
                                  per_pair=per_pair,
                                  transport=profile.name, exposure=exposure,
                                  efficiency=efficiency,
                                  goodput=rates * efficiency)
    if contracts_enabled():
        check_throughput(tp)
    return tp


def monte_carlo_throughput(
    fabric: Fabric | CompiledFabric,
    workload: WorkloadDescription | Sequence[Flow],
    seeds: Sequence[int] | np.ndarray,
    *,
    spec: SimSpec | None = None,
    fields=_UNSET,
    hash_backend=_UNSET,
    field_matrix: np.ndarray | None = None,
    strategy=_UNSET,
    demand_mode=_UNSET,
    transport=_UNSET,
    engine=_UNSET,
    max_hops=_UNSET,
) -> MonteCarloThroughput:
    """Max-min throughput distribution of a routing strategy across a
    seed sweep.

    ``workload`` may be a ``WorkloadDescription`` (flows synthesized the
    standard way, NIC count inferred from the fabric) or an explicit flow
    list — the same front-end contract as ``monte_carlo_fim``.  How to
    simulate comes from a ``SimSpec`` — pass one as ``spec=`` or the
    legacy kwargs, not both.  ``strategy`` and ``demand_mode`` follow
    the ``simulate_paths`` contract (default: per-flow ECMP, unit
    demand; ``demand_mode="bytes"`` allocates weighted max-min shares);
    ``transport`` the ``throughput_from_result`` contract (reordering
    cost model for ``goodput``; default ``"ideal"`` = reordering-free).

    ``engine="jax"`` with plain ECMP takes the fused device pipeline
    (walk + fill in one device-resident pass, ``jax_engine``); other
    strategies route on the jax walk and fill/expose on device with
    host glue in between.
    """
    with span("monte_carlo_throughput", seeds=len(seeds)):
        s = resolve_spec(spec, dict(
            fields=fields, hash_backend=hash_backend, strategy=strategy,
            demand_mode=demand_mode, transport=transport, engine=engine,
            max_hops=max_hops))
        comp = (fabric if isinstance(fabric, CompiledFabric)
                else compile_fabric(fabric))
        if s.engine != ENGINE_NUMPY and _is_plain_ecmp(s.strategy):
            from .jax_engine import (
                fused_monte_carlo_throughput, resolve_engine)
            resolve_engine(s.engine)
            return fused_monte_carlo_throughput(
                comp, workload, seeds, fields=s.fields,
                hash_backend=s.hash_backend,
                demand_mode=s.demand_mode, transport=s.transport,
                field_matrix=field_matrix, max_hops=s.max_hops)
        flows = resolve_flows(comp, workload)
        count("flows", len(flows))
        res = simulate_paths(comp, flows, seeds, spec=s,
                             field_matrix=field_matrix)
        return throughput_from_result(res, transport=s.transport,
                                      engine=s.engine)

"""Vectorized fabric path simulation: all flows x all hash seeds at once.

``FlowTracer`` discovers paths the way the paper's tool does — one flow,
one hop, one (simulated) device query at a time.  That is the right model
for the *measurement* tool, but evaluating routing schemes (paper Fig. 3a
"repeated multiple times"; PRIME/congestion-aware selection in PAPERS.md)
needs Monte-Carlo over thousands of hash seeds, where the per-hop Python
walk is ~1000x too slow.

This module replays the exact same forwarding process as whole-array
operations on a ``CompiledFabric``:

* state is an ``(N flows, S seeds)`` array of current-device ids;
* each hop gathers the candidate row for every (flow, seed), evaluates
  ``ecmp_hash`` — the same splitmix64-over-CRC32-fields mix, lifted to
  numpy uint64 (which wraps mod 2**64 exactly like the masked Python
  int arithmetic) — and indexes the chosen egress link;
* the walk stops when every (flow, seed) lands on a server.

The result is **bit-identical** to ``EcmpRouting`` + ``FlowTracer``
(differential-tested in tests/test_vector_sim.py) while ~100-1000x
faster per seed.  Link loads and FIM come from one ``bincount`` over the
link-id tensor instead of dict loops.

An optional ``hash_backend="murmur"`` routes the per-hop hash through the
``bulk_hash`` Pallas kernel path (TPU-native murmur3 avalanche) instead
— statistically equivalent, *not* bit-identical to the Python tracer; use
it for accelerator-scale sweeps.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Sequence

import numpy as np

from .compile_fabric import CompiledFabric, compile_fabric
from .contracts import check_spec, check_trace_result, contracts_enabled
from .ecmp import (
    FIELDS_5TUPLE, FIELDS_IP_PAIR, FIELDS_VXLAN, HASH_INIT,
    flow_fields_matrix,
)
from .fabric import Fabric
from .flows import Flow, WorkloadDescription, synthesize_flows
from .fim import Path
from .spans import count, span


def resolve_flows(
    comp: CompiledFabric,
    workload: WorkloadDescription | Sequence[Flow],
) -> list[Flow]:
    """Standard Monte-Carlo front-end contract: a ``WorkloadDescription``
    is synthesized into flows (NIC plan read from the compiled fabric's
    recorded ``nic_indices``); an explicit flow sequence passes through.

    Synthesis round-robins over the *recorded* NIC indices, not over
    ``range(max_index + 1)``: a fabric whose servers expose a sparse NIC
    numbering (say NICs 0 and 4 on a half-populated host) must never
    synthesize traffic for NICs that have no links."""
    if isinstance(workload, WorkloadDescription):
        from .fabric import nic_ip
        idx = comp.nic_indices
        return synthesize_flows(
            workload, nic_ip=lambda srv, k: nic_ip(srv, idx[k]),
            nics_per_server=len(idx))
    return list(workload)

EXACT = "exact"    # splitmix64 over CRC32 fields == core/ecmp.py bit-for-bit
MURMUR = "murmur"  # kernels/flowhash murmur3 (TPU bulk_hash path)

ENGINE_NUMPY = "numpy"  # host engine: the differential reference
ENGINE_JAX = "jax"      # jitted device engine (core/jax_engine.py)


def resolve_hash_backend(hash_backend: str | None, engine: str) -> str:
    """``None`` means "the engine's natural backend": the numpy engine
    (and jax on CPU, where the differential CI runs) keep the exact
    tracer-identical splitmix64; the jax engine on a real accelerator
    defaults to the murmur kernel path (64-bit multiplies are hostile
    there).  An explicit backend always wins; an unknown one fails here,
    before any routing work happens."""
    if hash_backend is not None:
        if hash_backend not in (EXACT, MURMUR):
            raise ValueError(
                f"unknown hash_backend {hash_backend!r}; "
                f"have {(EXACT, MURMUR)}")
        return hash_backend
    if engine == ENGINE_JAX:
        from .jax_engine import default_hash_backend
        return default_hash_backend(engine)
    return EXACT

DEMAND_UNIFORM = "uniform"  # every flow weighs 1 (the PR 1-3 behaviour)
DEMAND_BYTES = "bytes"      # flows weigh their wire bytes (mean-normalized)


def flow_demand_weights(flows: Sequence[Flow], demand_mode: str) -> np.ndarray:
    """(N,) strictly positive per-flow demand weights.

    ``"uniform"`` is all-ones — the historical unit-demand model.
    ``"bytes"`` weighs each flow by ``Flow.bytes``, normalized to mean 1
    so weighted link loads stay magnitude-comparable with unweighted
    counts (total demand is N either way, FIM is scale-invariant
    regardless).  All-equal bytes — including the all-zero fallback —
    return exact ones, so ``demand_mode="bytes"`` on a homogeneous
    workload is bit-identical to ``"uniform"``.  Zero-byte flows inside
    a heterogeneous workload (barriers, control traffic) are floored at
    1 byte: they still exist on the wire and the max-min fill requires
    strictly positive demand.
    """
    n = len(flows)
    if demand_mode == DEMAND_UNIFORM:
        return np.ones(n)
    if demand_mode != DEMAND_BYTES:
        raise ValueError(
            f"unknown demand_mode {demand_mode!r}; "
            f"expected {DEMAND_UNIFORM!r} or {DEMAND_BYTES!r}")
    b = np.array([f.bytes for f in flows], np.float64)
    if n == 0 or (b == b[0]).all():
        return np.ones(n)
    b = np.maximum(b, 1.0)
    return b / b.mean()


# ---------------------------------------------------------------------------
# SimSpec: the one validated description of *how* to simulate
# ---------------------------------------------------------------------------

_KNOWN_FIELDS = (FIELDS_5TUPLE, FIELDS_VXLAN, FIELDS_IP_PAIR)

TIMING_STATIC = "static"  # exogenous step durations (TimelineStep.duration)
TIMING_EVENT = "event"    # durations derived from routed goodput: a step
#                           ends when its slowest flow's bytes finish, and
#                           flows depart mid-step (vector_throughput.
#                           departure_fill); see core/timeline.py


@dataclasses.dataclass(frozen=True)
class SimSpec:
    """Every knob that selects *how* a simulation runs, in one place.

    The four Monte-Carlo front ends (``simulate_paths``,
    ``monte_carlo_fim``, ``monte_carlo_throughput``,
    ``simulate_timeline``) take it as ``spec=`` and nothing else says
    how they simulate; ``resolve()`` validates and normalizes every
    field in one place.

    Fields (all optional — the zero-argument ``SimSpec()``, which is
    also what ``spec=None`` means, is the default everywhere):

    * ``strategy`` — ``None`` (per-flow ECMP), a registry name string
      (``"wave-congestion-aware"``), or a ``RoutingStrategy`` instance;
    * ``demand_mode`` — ``"uniform"`` or ``"bytes"``
      (``flow_demand_weights``);
    * ``engine`` — ``"numpy"`` or ``"jax"``;
    * ``hash_backend`` — ``"exact"``, ``"murmur"``, or ``None`` for the
      engine's natural backend (``resolve_hash_backend`` owns the
      engine->backend coupling);
    * ``transport`` — ``None``/name/``TransportProfile`` for the
      reordering-cost model (only throughput-bearing front ends read
      it; carrying it on a paths-only spec is harmless);
    * ``fields`` — the hash-field mode (``"5tuple"``/``"vxlan"``/
      ``"ip-pair"``);
    * ``max_hops`` — walk hop budget;
    * ``timing`` — how ``simulate_timeline`` prices the time axis:
      ``"static"`` (exogenous ``TimelineStep.duration`` weights, the
      historical model) or ``"event"`` (step durations *derived* from
      the achieved max-min goodput, with flows departing as their bytes
      finish — core/timeline.py).  Snapshot front ends ignore it.

    ``resolve()`` is idempotent, so a resolved spec can be handed from
    front end to front end, and whole into the fused device pipeline
    (``jax_engine.fused_monte_carlo_*``), without re-validating work:
    names become registry instances, ``hash_backend=None`` becomes the
    engine's concrete backend, and every enum-ish field is
    range-checked.
    Per-*call* inputs (the fabric, flows, seeds, a precomputed
    ``field_matrix``, FIM layer selections) stay arguments — a spec
    describes the simulation contract, not one invocation's data."""

    strategy: object = None
    demand_mode: str = DEMAND_UNIFORM
    engine: str = ENGINE_NUMPY
    hash_backend: str | None = None
    transport: object = None
    fields: str = FIELDS_5TUPLE
    max_hops: int = 16
    timing: str = TIMING_STATIC

    def resolve(self) -> "SimSpec":
        if self.engine not in (ENGINE_NUMPY, ENGINE_JAX):
            raise ValueError(
                f"unknown engine {self.engine!r}; "
                f"expected {ENGINE_NUMPY!r} or {ENGINE_JAX!r}")
        if self.timing not in (TIMING_STATIC, TIMING_EVENT):
            raise ValueError(
                f"unknown timing {self.timing!r}; "
                f"expected {TIMING_STATIC!r} or {TIMING_EVENT!r}")
        if self.demand_mode not in (DEMAND_UNIFORM, DEMAND_BYTES):
            raise ValueError(
                f"unknown demand_mode {self.demand_mode!r}; "
                f"expected {DEMAND_UNIFORM!r} or {DEMAND_BYTES!r}")
        if self.fields not in _KNOWN_FIELDS:
            raise ValueError(
                f"unknown fields mode {self.fields!r}; "
                f"have {_KNOWN_FIELDS}")
        if int(self.max_hops) < 1:
            raise ValueError(f"max_hops must be >= 1, got {self.max_hops}")
        strategy = self.strategy
        if strategy is not None:
            from .strategies import resolve_strategy
            strategy = resolve_strategy(strategy)
        transport = self.transport
        if transport is not None:
            from .reordering import resolve_transport
            transport = resolve_transport(transport)
        return dataclasses.replace(
            self, strategy=strategy, transport=transport,
            hash_backend=resolve_hash_backend(self.hash_backend, self.engine),
            max_hops=int(self.max_hops))


def resolve_spec(spec: SimSpec | None) -> SimSpec:
    """Front-end glue: the resolved ``spec=`` (``None`` is ``SimSpec()``).
    A non-SimSpec ``spec`` fails as a type error rather than an attribute
    error three calls deep."""
    if spec is None:
        spec = SimSpec()
    elif not isinstance(spec, SimSpec):
        raise TypeError(
            f"spec must be a SimSpec, got {type(spec).__name__}")
    s = spec.resolve()
    if contracts_enabled():
        check_spec(s)
    return s


_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_INIT = np.uint64(HASH_INIT)


def _mix64_vec(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer on uint64 arrays — numpy wraparound arithmetic
    matches ``ecmp._mix64``'s masked Python ints exactly."""
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


def ecmp_hash_vec(fields: np.ndarray, seeds: np.ndarray) -> np.ndarray:
    """Batched ``ecmp_hash``: fields (N, F) uint64, seeds (...,) uint64
    broadcastable against (N, ...) -> hashes of fields under each seed."""
    h = _mix64_vec(seeds ^ _INIT)
    for f in range(fields.shape[1]):
        h = _mix64_vec(h ^ fields[:, f].reshape(
            (-1,) + (1,) * (h.ndim - 1)))
    return h


def _murmur_hash_grid(fields: np.ndarray, dev_seed: np.ndarray) -> np.ndarray:
    """Per-(flow, seed) murmur3 hash grid, seed-as-init convention.

    The ONE murmur definition, shared across every consumer: the hash
    starts at the (truncated) device seed and folds the field columns —
    exactly what the Pallas ``bulk_hash`` kernel computes for a scalar
    seed and what ``jax_engine``'s device grid computes per cell.  The
    fold/fmix formulas are imported from the kernel module (they are
    polymorphic over numpy and jnp arrays), so the numpy backend can
    never drift from the kernel — and needs no jax round-trip."""
    from ..kernels.flowhash.kernel import murmur_fmix, murmur_fold

    h = (dev_seed & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    f32 = fields.astype(np.uint32)
    for f in range(fields.shape[1]):
        h = murmur_fold(h, f32[:, f].reshape((-1,) + (1,) * (h.ndim - 1)))
    return murmur_fmix(h).astype(np.uint64)


def hash_grid(field_mat: np.ndarray, dev_seed: np.ndarray,
              hash_backend: str) -> np.ndarray:
    """Per-(flow, seed) hash grid under the selected backend — the one
    dispatch point shared by the ECMP walk and the routing strategies
    (so e.g. the congestion-aware tie-break honors ``hash_backend`` the
    same way the main walk does)."""
    if hash_backend == EXACT:
        return ecmp_hash_vec(field_mat, dev_seed)
    if hash_backend == MURMUR:
        return _murmur_hash_grid(field_mat, dev_seed)
    raise ValueError(f"unknown hash backend: {hash_backend}")


@dataclasses.dataclass
class VectorTraceResult:
    """Paths for N flows under S seeds, as a dense link-id tensor.

    Multi-path strategies (PRIME-style spraying) emit more tensor columns
    than there are flows: each column is a *flowlet* — ``flow_index[j]``
    names its parent flow (row into ``flows``) and ``demand[j]`` the
    fraction of the parent's demand it carries (flowlet demands sum to 1
    per flow).  Single-path strategies leave the defaults
    (``flow_index == arange(N)``, ``demand == 1``), and every consumer
    below degenerates to the PR-1 behaviour exactly.

    ``flow_demand`` carries the *per-flow* demand weight (paper Step 1
    names flow volumes, not just pairs): ``demand_mode="bytes"`` derives
    it from ``Flow.bytes`` normalized to mean 1.  It composes
    multiplicatively with the flowlet fractions — a column's effective
    weight is ``flow_demand[flow_index[j]] * demand[j]``
    (``column_weights``) — so a sprayed elephant's flowlets each carry
    1/K of the elephant's weight, not of a unit.
    """

    compiled: CompiledFabric
    flows: list[Flow]
    seeds: np.ndarray        # (S,) uint64 (as given, masked to 64 bit)
    link_ids: np.ndarray     # (H, Nf, S) int32 link ids, -1 past arrival
    flow_index: np.ndarray | None = None   # (Nf,) parent-flow row per column
    demand: np.ndarray | None = None       # (Nf,) demand fraction per column
    strategy: str = "ecmp"
    flow_demand: np.ndarray | None = None  # (N,) per-flow demand weight
    #: optional (N, S) strategy-induced reordering exposure on top of what
    #: the flowlet tensors imply — adaptive re-spray charges each accepted
    #: mid-flow path change here (core/strategies.AdaptiveSpraying), and
    #: ``flowlet_exposure`` adds it to the skew + dispersion terms.  None
    #: (every static strategy) keeps the PR-5 exposure model bit-exact.
    extra_exposure: np.ndarray | None = None

    def __post_init__(self):
        nf = self.link_ids.shape[1]
        if self.flow_index is None:
            self.flow_index = np.arange(nf, dtype=np.int32)
        if self.demand is None:
            self.demand = np.ones(nf)
        if self.flow_demand is None:
            self.flow_demand = np.ones(len(self.flows))

    @property
    def num_flows(self) -> int:
        return len(self.flows)

    @property
    def num_flowlets(self) -> int:
        return self.link_ids.shape[1]

    @property
    def num_seeds(self) -> int:
        return len(self.seeds)

    @property
    def is_multipath(self) -> bool:
        return self.num_flowlets != self.num_flows

    def hop_counts(self) -> np.ndarray:
        """(Nf, S) links crossed per tensor column per seed — the
        path-length grid the reordering model's skew term reads."""
        return (self.link_ids >= 0).sum(axis=0)

    def paths_for_seed(self, seed_index: int) -> dict[int, Path]:
        """Materialize one seed's paths in ``FlowTracer`` format (for
        differential testing / drop-in use with the dict-based tools).
        Single-path results only; multi-path callers want
        ``flowlet_paths_for_seed``."""
        if self.is_multipath:
            raise ValueError(
                f"{self.strategy!r} result has {self.num_flowlets} flowlets "
                f"for {self.num_flows} flows; use flowlet_paths_for_seed")
        links = self.compiled.links
        out: dict[int, Path] = {}
        ids = self.link_ids[:, :, seed_index]
        for j, flow in enumerate(self.flows):
            out[flow.flow_id] = [links[i] for i in ids[:, j] if i >= 0]
        return out

    def flowlet_paths_for_seed(self, seed_index: int) -> dict[int, list[Path]]:
        """One seed's paths per flow id, as a *list* of flowlet paths."""
        links = self.compiled.links
        out: dict[int, list[Path]] = {f.flow_id: [] for f in self.flows}
        ids = self.link_ids[:, :, seed_index]
        for j in range(self.num_flowlets):
            fid = self.flows[int(self.flow_index[j])].flow_id
            out[fid].append([links[i] for i in ids[:, j] if i >= 0])
        return out

    def column_weights(self) -> np.ndarray:
        """(Nf,) effective demand per tensor column: the parent flow's
        ``flow_demand`` times the column's flowlet fraction.  Uniform
        flow demand short-circuits to ``demand`` itself so the
        single-path / unit-demand fast paths stay bit-identical."""
        if (self.flow_demand == 1.0).all():
            return self.demand
        return self.flow_demand[self.flow_index] * self.demand

    def link_flow_counts(self) -> np.ndarray:
        """(S, L) flow load per link per seed — one bincount, no dicts.

        Columns contribute their effective demand (``column_weights``):
        a sprayed flow still adds up to its ``flow_demand`` per layer
        crossing, total load per layer is demand-invariant across
        strategies, and uniform unit demand keeps the exact integer
        counts of the single-path engine.
        """
        L, S = self.compiled.num_links, self.num_seeds
        ids = self.link_ids                      # (H, Nf, S)
        offset = np.arange(S, dtype=np.int64) * L
        keep = ids >= 0
        flat = (ids.astype(np.int64) + offset)[keep]
        weights = self.column_weights()
        if (weights == 1.0).all():
            return np.bincount(flat, minlength=S * L).reshape(S, L)
        w = np.broadcast_to(weights[None, :, None], ids.shape)[keep]
        return np.bincount(flat, weights=w, minlength=S * L).reshape(S, L)


def segment_reduce(values: np.ndarray, fi: np.ndarray, n: int,
                   ufunc: np.ufunc, fill: float) -> np.ndarray:
    """Per-parent ``ufunc`` reduction over the column axis of an
    ``(Nf, S)`` array, grouping columns by ``fi`` (their parent-flow
    rows) into ``(n, S)``.  Parent-sorted contiguous ``fi`` — the
    flowlet layout every built-in multi-path strategy emits — takes the
    ``reduceat`` fast path; anything else falls back to a scatter
    reduction seeded with ``fill``.  Shared by the flowlet->flow rate
    aggregation (vector_throughput) and the reordering exposure model,
    so the two can never disagree on the grouping."""
    if fi.size and (np.diff(fi) >= 0).all():
        starts = np.flatnonzero(np.diff(fi, prepend=-1) > 0)
        if starts.size == n:               # every parent has >= 1 column
            return ufunc.reduceat(values, starts, axis=0)
    out = np.full((n, values.shape[1]), fill)
    ufunc.at(out, fi, values)
    return out


def normalize_seeds(seeds: Sequence[int] | np.ndarray) -> np.ndarray:
    """(S,) uint64 seed array, masked to 64 bit like the Python tracer."""
    return np.array(
        [int(s) & 0xFFFFFFFFFFFFFFFF for s in np.asarray(seeds).tolist()],
        np.uint64)


def ecmp_walk(
    comp: CompiledFabric,
    src_dev: np.ndarray,
    dst_dev: np.ndarray,
    src_key: np.ndarray,
    dst_key: np.ndarray,
    field_mat: np.ndarray,
    seeds_u64: np.ndarray,
    *,
    hash_backend: str | None = None,
    max_hops: int = 16,
    cell_salt: np.ndarray | None = None,
    describe=lambda n: f"column {n}",
    engine: str = ENGINE_NUMPY,
) -> np.ndarray:
    """The raw hop-by-hop hashed walk over explicit endpoint/field arrays.

    Exactly ``EcmpRouting``'s decision at each hop: candidates from the
    compiled ``Forwarder`` tables, ``hash % n_candidates`` when the set
    has more than one member, first (only) candidate otherwise.  Returns
    the ``(hops, N, S)`` link-id tensor.  ``simulate_paths`` is the
    flow-level front end; routing strategies (``core/strategies.py``)
    call this directly with expanded per-flowlet arrays.

    ``engine="jax"`` runs the identical walk on the accelerator
    (``core/jax_engine.py``), over per-flow route tables built from the
    same compiled tables — bit-identical to the numpy walk backend for
    backend (the differential contract).  ``hash_backend=None`` resolves to the
    engine's natural backend (``resolve_hash_backend``).

    ``cell_salt`` optionally perturbs the entropy of individual
    ``(column, seed)`` cells: a ``(N, S)`` uint64 array XORed into every
    hop's device seed before hashing.  A zero cell leaves that cell's
    walk bit-identical to the salt-free walk (``x ^ 0 == x``), a nonzero
    cell re-rolls every hop decision — the vector equivalent of a sender
    re-picking its flowlet's entropy header value, which adaptive
    per-RTT re-spray does per cell under congestion feedback.
    """
    hash_backend = resolve_hash_backend(hash_backend, engine)
    if engine != ENGINE_NUMPY:
        from .jax_engine import jax_ecmp_walk, resolve_engine
        resolve_engine(engine)
        return jax_ecmp_walk(
            comp, src_dev, dst_dev, src_key, dst_key, field_mat, seeds_u64,
            hash_backend=hash_backend, max_hops=max_hops,
            cell_salt=cell_salt, describe=describe)
    N, S = len(src_dev), len(seeds_u64)
    state = np.broadcast_to(src_dev[:, None], (N, S)).copy()   # (N, S)
    done = np.zeros((N, S), bool)
    link_ids = np.full((max_hops, N, S), -1, np.int32)

    hops = 0
    for t in range(max_hops):
        if done.all():
            break
        hops = t + 1
        # src-keyed on the source host (hop 0), dst-keyed at every switch
        key = np.where(comp.is_server[state], src_key[:, None], dst_key[:, None])
        n = comp.cand_n[state, key]                    # (N, S)
        dev_seed = comp.dev_crc[state] ^ seeds_u64[None, :]
        if cell_salt is not None:
            dev_seed = dev_seed ^ cell_salt
        h = hash_grid(field_mat, dev_seed, hash_backend)
        safe_n = np.maximum(n, 1).astype(np.uint64)
        choice = np.where(n > 1, (h % safe_n).astype(np.int64), 0)
        link = comp.cand[state, key, choice]
        link = np.where(done | (n == 0), -1, link)
        link_ids[t] = link
        nxt = np.where(link >= 0, comp.link_dst[np.maximum(link, 0)], state)
        done |= (link < 0) | comp.is_server[nxt]
        state = nxt

    if not done.all():
        raise RuntimeError(f"some flows did not terminate in {max_hops} hops")
    arrived = state == np.broadcast_to(dst_dev[:, None], (N, S))
    if not arrived.all():
        bad = np.argwhere(~arrived)[0]
        raise RuntimeError(
            f"{describe(bad[0])} (seed index {bad[1]}) terminated "
            f"at {comp.device_names[state[bad[0], bad[1]]]}")
    return link_ids[:hops]


def simulate_paths(
    fabric: Fabric | CompiledFabric,
    flows: Sequence[Flow],
    seeds: Sequence[int] | np.ndarray,
    *,
    spec: SimSpec | None = None,
    field_matrix: np.ndarray | None = None,
) -> VectorTraceResult:
    """Walk every flow through the fabric under every seed, vectorized.

    How to simulate is described by ``spec`` (see ``SimSpec`` for the
    field contracts; ``None`` is ``SimSpec()``).  The default is
    per-flow ECMP, bit-identical to ``EcmpRouting`` + ``FlowTracer``; a
    ``spec.strategy`` (name string or instance) routes the whole
    simulation through that strategy's vectorized implementation
    instead (the result may carry flowlet columns — see
    ``VectorTraceResult``).

    ``field_matrix`` optionally supplies precomputed
    ``flow_fields_matrix`` output so repeated sweeps over the same flow
    table skip the per-flow CRC pass (per-call data, so it stays an
    argument rather than a spec field).
    """
    s = resolve_spec(spec)
    comp = fabric if isinstance(fabric, CompiledFabric) else compile_fabric(fabric)
    flows = list(flows)
    seeds_u64 = normalize_seeds(seeds)
    if len(flows) == 0:
        raise ValueError("simulate_paths needs at least one flow")
    if s.strategy is not None:
        # demand_mode / engine are only forwarded when they actually ask
        # for something: custom strategies registered against the older
        # route() signatures keep working under the defaults, and a
        # non-default request against one fails loudly (TypeError)
        # instead of silently dropping the ask
        extra = ({} if s.demand_mode == DEMAND_UNIFORM
                 else {"demand_mode": s.demand_mode})
        if s.engine != ENGINE_NUMPY:
            extra["engine"] = s.engine
        res = s.strategy.route(
            comp, flows, seeds_u64, fields=s.fields,
            hash_backend=s.hash_backend, max_hops=s.max_hops,
            field_matrix=field_matrix, **extra)
        if contracts_enabled():
            check_trace_result(res)
        return res
    flow_demand = flow_demand_weights(flows, s.demand_mode)
    field_mat = (field_matrix if field_matrix is not None
                 else flow_fields_matrix(flows, s.fields))  # (N, F) uint64
    src_dev, dst_dev, src_key, dst_key = comp.flow_endpoint_ids(flows)
    link_ids = ecmp_walk(
        comp, src_dev, dst_dev, src_key, dst_key, field_mat, seeds_u64,
        hash_backend=s.hash_backend, max_hops=s.max_hops,
        describe=lambda n: f"flow {flows[n].flow_id}", engine=s.engine)
    res = VectorTraceResult(
        compiled=comp, flows=flows, seeds=seeds_u64, link_ids=link_ids,
        flow_demand=flow_demand)
    if contracts_enabled():
        check_trace_result(res)
    return res


# ---------------------------------------------------------------------------
# Vectorized link loads / FIM (array twin of core/fim.py)
# ---------------------------------------------------------------------------


def fim_from_counts(
    counts: np.ndarray,
    comp: CompiledFabric,
    *,
    layers: Sequence[str] | None = None,
    only_used_leaves: bool = False,
) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Aggregate and per-layer FIM per seed from an (S, L) count matrix.

    Mirrors ``fim``/``per_layer_fim`` semantics exactly: per layer,
    ideal = total/links, MAPE over links; layers with zero traffic are
    dropped; the aggregate weights each layer by its link count.  With
    ``only_used_leaves`` links are restricted per seed to those whose both
    endpoints carried traffic under that seed.
    """
    S = counts.shape[0]
    # `layers or ...` mirrors fim()/per_layer_fim(): an empty list also
    # means "all layers"
    layer_list = list(layers) if layers else comp.layer_names
    if only_used_leaves:
        present = counts > 0                       # (S, L)
        used = np.zeros((S, comp.num_devices), bool)
        rows = np.broadcast_to(
            np.arange(S, dtype=np.int64)[:, None], present.shape)
        np.logical_or.at(used, (rows, comp.link_src[None, :]), present)
        np.logical_or.at(used, (rows, comp.link_dst[None, :]), present)

    num = np.zeros(S)
    den = np.zeros(S)
    per_layer: dict[str, np.ndarray] = {}
    for layer in layer_list:
        if layer not in comp.layer_names:
            continue
        lid = comp.layer_names.index(layer)
        sel = np.flatnonzero(comp.link_layer == lid)
        if sel.size == 0:
            continue
        c = counts[:, sel].astype(np.float64)      # (S, Ll)
        if only_used_leaves:
            mask = (used[:, comp.link_src[sel]]
                    & used[:, comp.link_dst[sel]]).astype(np.float64)
        else:
            mask = np.ones_like(c)
        n_links = mask.sum(axis=1)                 # (S,)
        total = (c * mask).sum(axis=1)
        live = (total > 0) & (n_links > 0)
        ideal = np.where(live, total / np.maximum(n_links, 1), 1.0)
        mape = (100.0 / np.maximum(n_links, 1)
                * (np.abs(c - ideal[:, None]) / ideal[:, None] * mask).sum(1))
        mape = np.where(live, mape, 0.0)
        if not live.any():
            continue
        per_layer[layer] = mape
        num += np.where(live, mape * n_links, 0.0)
        den += np.where(live, n_links, 0.0)
    agg = np.divide(num, den, out=np.zeros(S), where=den > 0)
    return agg, per_layer


def fim_vector(
    result: VectorTraceResult,
    *,
    layers: Sequence[str] | None = None,
    only_used_leaves: bool = False,
) -> np.ndarray:
    """(S,) aggregate FIM per seed — vectorized ``fim()``."""
    agg, _ = fim_from_counts(result.link_flow_counts(), result.compiled,
                             layers=layers, only_used_leaves=only_used_leaves)
    return agg


# ---------------------------------------------------------------------------
# Monte-Carlo front end
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MonteCarloFim:
    """FIM distributions over a hash-seed sweep."""

    seeds: np.ndarray                       # (S,)
    aggregate: np.ndarray                   # (S,) FIM per seed
    per_layer: dict[str, np.ndarray]        # layer -> (S,) FIM per seed

    def summary(self) -> dict[str, dict[str, float]]:
        out = {}
        rows = {"aggregate": self.aggregate, **self.per_layer}
        for name, v in rows.items():
            out[name] = {
                "mean": float(v.mean()),
                "std": float(v.std()),
                "min": float(v.min()),
                "p50": float(np.percentile(v, 50)),
                "p95": float(np.percentile(v, 95)),
                "max": float(v.max()),
            }
        return out


def monte_carlo_fim(
    fabric: Fabric | CompiledFabric,
    workload: WorkloadDescription | Sequence[Flow],
    seeds: Sequence[int] | np.ndarray,
    *,
    spec: SimSpec | None = None,
    layers: Sequence[str] | None = None,
    only_used_leaves: bool = False,
) -> MonteCarloFim:
    """FIM distribution of a routing strategy across a hash-seed sweep.

    ``workload`` may be a ``WorkloadDescription`` (flows are synthesized
    the standard way, NIC count inferred from the fabric) or an explicit
    flow list.  How to simulate comes from ``spec`` (the
    ``simulate_paths`` contract; default: per-flow ECMP, unit demand;
    ``demand_mode="bytes"`` makes the FIM byte-weighted).  ``layers`` /
    ``only_used_leaves`` describe what to *measure*, not how to route,
    so they stay per-call arguments.

    A spec that ``runs_fused`` (``engine="jax"`` with plain ECMP) takes
    the fused device pipeline (walk + counts + FIM in one pass,
    ``jax_engine``); other strategies route on the jax walk and
    aggregate on host.
    """
    with span("monte_carlo_fim", seeds=len(seeds)):
        s = resolve_spec(spec)
        comp = (fabric if isinstance(fabric, CompiledFabric)
                else compile_fabric(fabric))
        if runs_fused(s):
            from .jax_engine import fused_monte_carlo_fim
            return fused_monte_carlo_fim(
                comp, workload, seeds, s,
                layers=layers, only_used_leaves=only_used_leaves)
        flows = resolve_flows(comp, workload)
        count("flows", len(flows))
        res = simulate_paths(comp, flows, seeds, spec=s)
        agg, per_layer = fim_from_counts(
            res.link_flow_counts(), comp,
            layers=layers, only_used_leaves=only_used_leaves)
        return MonteCarloFim(seeds=res.seeds, aggregate=agg,
                             per_layer=per_layer)


def _is_plain_ecmp(strategy) -> bool:
    """True when ``strategy`` requests the default per-flow ECMP walk —
    the shape the fused device pipeline implements.  Configured or custom
    strategies (including subclasses of ``EcmpStrategy``) route through
    their own ``route`` with the device walk underneath instead."""
    if strategy is None or strategy == "ecmp":
        return True
    from .strategies import EcmpStrategy
    return type(strategy) is EcmpStrategy


def runs_fused(spec: SimSpec) -> bool:
    """True when the resolved ``spec`` takes the fused device pipeline
    (``jax_engine.fused_monte_carlo_*``): the jax engine on the plain
    per-flow ECMP walk.  The aggregate front ends ask this, and only
    this, to choose it."""
    return spec.engine == ENGINE_JAX and _is_plain_ecmp(spec.strategy)

"""Device-resident JAX engine: walk -> counts/FIM -> max-min fill -> goodput.

The numpy engine (``vector_sim`` / ``vector_throughput`` / ``reordering``)
is the differential reference; this module re-expresses the same hot path
as jitted jax so a Monte-Carlo sweep runs on the accelerator with no host
round-trips between stages:

* the per-hop ECMP/flowlet walk follows each flow's own route tables,
  one per hop, and picks every entry it reads with compares and selects
  over the (N, S) grid, not with gathers from the fabric's tables —
  bit-identical to ``vector_sim.ecmp_walk`` under both hash backends
  (uint64 wraparound is exact under x64);
* link counts under unit demand are factored one-hot matmuls on the
  MXU (``_unit_counts``), under any other demand one masked reduction
  per (seed, link) cell; the per-layer FIM (MAPE vs per-layer ideal) is
  a handful of masked reductions per layer;
* the weighted progressive max-min fill keeps the numpy engine's
  parallel local-bottleneck formulation, as a ``lax.while_loop`` over
  static-shape (seed, link) tables and an (N, S) active mask;
* flowlet exposure -> transport efficiency -> goodput fuse on top as
  per-parent segment reductions.

Hash backends: ``"exact"`` is the splitmix64 chain (bit-identical to the
Python tracer, and to the numpy engine — the differential contract).
``"murmur"`` is the murmur3 avalanche of ``kernels/flowhash`` (the same
``murmur_fold``/``murmur_fmix`` formulas, evaluated here as jnp inside
the walk; the Pallas ``bulk_hash`` kernel is a separate entry point); it
is the default for real accelerator backends, where 64-bit multiplies
are emulated.  ``default_hash_backend`` encodes that policy.

Everything here enters through ``jax.enable_x64`` as a *scoped* context
(never the global flag): the exact backend needs uint64 and the fill
needs float64, but flipping x64 globally would change default dtypes
for every other jax user in the process.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from collections.abc import Sequence

import numpy as np

from .compile_fabric import CompiledFabric
from .ecmp import HASH_INIT, flow_fields_matrix
from .flows import Flow, WorkloadDescription
from .spans import count, enabled, span
from .vector_sim import (
    ENGINE_JAX, ENGINE_NUMPY, EXACT, MURMUR, MonteCarloFim, SimSpec,
    VectorTraceResult, flow_demand_weights, normalize_seeds, resolve_flows,
)

__all__ = [
    "ENGINE_NUMPY", "ENGINE_JAX", "default_hash_backend",
    "jax_ecmp_walk", "jax_wave_walk", "jax_link_flow_counts",
    "jax_fim_from_counts",
    "jax_batched_max_min", "jax_flowlet_exposure",
    "fused_monte_carlo_fim", "fused_monte_carlo_throughput",
]

# Seeds per device pass in the fused front ends (``seed_chunk``), sized
# for one TPU v5e (16 GB HBM).  A pass over N flows x Sc seeds holds the
# walk's (H, N, Sc) int32 link-id tensor, 4 B per hop, budgeted here at
# max_hops >= H hops, and the fill's working set over its (H, N, Sc)
# cells.  XLA:TPU's memory
# analysis of the compiled fill gives 27-31 B per cell (arguments,
# (S, L) tables, (N, S) masks and gathered shares) at 4 x 4096 x 1024,
# 4 x 7168 x 1024 and 4 x 100000 x 128; _FILL_BYTES_PER_CELL budgets 48.
# Taking H <= max_hops, one seed costs N * max_hops * (4 + 48) B: 832 B
# per flow at max_hops=16, so the 4 GiB budget (half of the 8 GB kept
# free of the 16 GB, the rest left to tables, outputs and the
# allocator) holds 4096 flows x 1260 seeds.  Sc is a multiple of the
# 128-lane tile: the walk's (N, Sc) arrays put seeds on the lanes, and a
# ragged Sc compiles ~10x slower for v5e (39 s at 100000 x 20 seeds,
# 4.6 s at 100000 x 128).  One lane tile is the floor, so above
# 4 GiB / (128 * 832 B) ~ 40k flows a pass holds 128 seeds and the
# budget no longer bounds it; a pass then costs N * 128 * (4 * max_hops
# + 48 * H) B with H the walk's real hop count: 3.3 GB at 100k flows on
# the paper testbed (H = 4), and the 8 GB line at ~250k flows.
_CHUNK_BYTES = 4 << 30
_WALK_BYTES_PER_HOP = 4
_FILL_BYTES_PER_CELL = 48
_SEED_LANES = 128


def _jx():
    """Lazy jax import bundle — core stays importable (and the numpy
    engine usable) on hosts without jax."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    return jax, jnp, lax


def _x64():
    """Scoped 64-bit mode for one engine call (uint64 hashes, float64
    rates); the process-wide default stays as it was."""
    import jax
    return jax.enable_x64(True)


def default_hash_backend(engine: str = ENGINE_JAX) -> str:
    """Backend policy when the caller doesn't pin one: the numpy engine
    (and jax-on-CPU, where CI differential tests run) keep the exact
    tracer-identical splitmix64; real accelerator backends default to the
    uint32 murmur hash."""
    if engine != ENGINE_JAX:
        return EXACT
    import jax
    return MURMUR if jax.default_backend() in ("tpu", "gpu") else EXACT


def resolve_engine(engine: str) -> str:
    if engine not in (ENGINE_NUMPY, ENGINE_JAX):
        raise ValueError(
            f"unknown engine {engine!r}; "
            f"expected {ENGINE_NUMPY!r} or {ENGINE_JAX!r}")
    return engine


# ---------------------------------------------------------------------------
# Compiled-fabric tables on device (cached per CompiledFabric instance)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _DeviceTables:
    cand: object
    cand_n: object
    dev_crc: object
    is_server: object
    link_dst: object


_TABLE_CACHE: dict[int, tuple[object, _DeviceTables]] = {}


def device_tables(comp: CompiledFabric) -> _DeviceTables:
    """Device copies of the forwarding tables, uploaded once per compiled
    fabric (keyed by identity — CompiledFabric is frozen, and the weakref
    anchor in the cache value keeps ids from being recycled under us)."""
    hit = _TABLE_CACHE.get(id(comp))
    if hit is not None and hit[0] is comp:
        return hit[1]
    _, jnp, _ = _jx()
    tabs = _DeviceTables(
        cand=jnp.asarray(comp.cand),
        cand_n=jnp.asarray(comp.cand_n),
        dev_crc=jnp.asarray(comp.dev_crc),
        is_server=jnp.asarray(comp.is_server),
        link_dst=jnp.asarray(comp.link_dst),
    )
    if len(_TABLE_CACHE) > 16:
        _TABLE_CACHE.clear()
    _TABLE_CACHE[id(comp)] = (comp, tabs)
    return tabs


# ---------------------------------------------------------------------------
# Hash grids (device twins of vector_sim.hash_grid)
# ---------------------------------------------------------------------------


def _mix64_j(x):
    _, jnp, _ = _jx()
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def _exact_grid_j(fields, dev_seed):
    """splitmix64 over (N, F) fields x (N, S) device seeds -> (N, S)
    uint64 — the exact ``ecmp_hash_vec`` chain, bit-identical under x64."""
    _, jnp, _ = _jx()
    h = _mix64_j(dev_seed ^ jnp.uint64(HASH_INIT))
    for f in range(fields.shape[1]):
        h = _mix64_j(h ^ fields[:, f][:, None])
    return h


def _murmur_grid_j(fields, dev_seed):
    """murmur3 grid with the per-(flow, seed) device seed as the hash
    init — the seed-as-init convention shared with ``bulk_hash`` (whose
    scalar seed is the same init broadcast) and the numpy murmur grid."""
    from ..kernels.flowhash.kernel import murmur_fmix, murmur_fold
    _, jnp, _ = _jx()
    h = (dev_seed & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    f32 = fields.astype(jnp.uint32)
    for f in range(fields.shape[1]):
        h = murmur_fold(h, f32[:, f][:, None])
    return murmur_fmix(h).astype(jnp.uint64)


def _hash_grid_j(fields, dev_seed, hash_backend: str):
    if hash_backend == EXACT:
        return _exact_grid_j(fields, dev_seed)
    if hash_backend == MURMUR:
        return _murmur_grid_j(fields, dev_seed)
    raise ValueError(f"unknown hash backend: {hash_backend}")


# ---------------------------------------------------------------------------
# Stage 1: the walk (per-flow route tables, chosen by compares and selects)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _RouteTables:
    """One row per flow of the walk's route tables, on the device.

    ``hops[t]`` is hop t's ``(dev_crc, n, steps)``: the hash salt
    (uint32) and candidate count (int32) of each of the ``W_t`` devices
    the flow can be at, ``(N, W_t)``, and ``(N, W_t * C_t)`` int32 steps
    ``(link + 1) << shift | code`` for candidate slot c of device w at
    column ``w * C_t + c``.  ``code`` is the device's index in hop
    t + 1's row, or ``base + e`` where the walk ends: ``exits[:, e]`` is
    the device it ends at (the server the link reaches; the device
    itself where it has no candidate, in slot 0 with link -1)."""

    hops: tuple
    exits: object
    widths: tuple[tuple[int, int], ...]     # (W_t, C_t) per hop
    shift: int
    base: int

    @property
    def select_width(self) -> int:
        """Entries the walk selects among per (flow, seed): sum W_t * C_t."""
        return sum(w * c for w, c in self.widths)


def _route_tables_host(comp: CompiledFabric, src_dev, src_key, dst_key,
                       max_hops: int):
    """The walk's route tables per distinct (source device, source key,
    destination key), enumerated hop by hop: ``(hops, exits, widths,
    shift, base, inverse)``, ``inverse`` the triple of each flow.

    Hop 0's frontier is the source device; a device's candidates are
    keyed by the source NIC on a server and by the destination NIC
    elsewhere (``ecmp_walk``'s rule), and hop t + 1's frontier is every
    device a candidate reaches that is not a server, in device-id order.
    The frontier is followed until it empties or for ``max_hops`` hops.
    """
    V, K = comp.num_devices, max(1, len(comp.key_of_ip))
    trip = (np.asarray(src_dev, np.int64) * K
            + np.asarray(src_key, np.int64)) * K + np.asarray(dst_key,
                                                            np.int64)
    uniq, inverse = np.unique(trip, return_inverse=True)
    u_dev, u_skey, u_dkey = uniq // (K * K), uniq // K % K, uniq % K
    rows = np.arange(uniq.size, dtype=np.int64)

    def number(codes):
        """Index of each ``u * V + device`` code within its row's sorted
        distinct codes; the (U, width) device table of those codes."""
        distinct = np.unique(codes)
        u = distinct // V
        local = (np.arange(distinct.size, dtype=np.int64)
                 - np.searchsorted(u, rows)[u])
        table = np.full((rows.size, int(local.max(initial=-1)) + 1), -1,
                        np.int64)
        table[u, local] = distinct % V
        return local[np.searchsorted(distinct, codes)], table

    front, base = u_dev[:, None], 1      # base: the widest next frontier
    hops = []                            # (crc, n, link, code, ending)
    for _ in range(max_hops):
        if front.shape[1] == 0:
            break
        live = front >= 0
        dev = np.maximum(front, 0)
        key = np.where(comp.is_server[dev], u_skey[:, None],
                       u_dkey[:, None])
        n = np.where(live, comp.cand_n[dev, key], 0)
        fan = max(1, int(n.max(initial=0)))
        slot = np.arange(fan, dtype=np.int64)
        link = np.where(slot < n[..., None], comp.cand[dev, key, :fan], -1)
        nxt = comp.link_dst[np.maximum(link, 0)]
        walks_on = (link >= 0) & ~comp.is_server[nxt]
        # a walk ends on the server its link reaches, or where it stands
        # when it has no candidate (slot 0, link -1)
        ending = (live[..., None] & (slot < np.maximum(n, 1)[..., None])
                  & ~walks_on)
        at = rows[:, None, None] * V + np.where(link >= 0, nxt,
                                                dev[..., None])
        code = np.zeros(link.shape, np.int64)
        code[walks_on], front = number(at[walks_on])
        code[ending] = at[ending]        # made exit indices below
        base = max(base, front.shape[1])
        hops.append((np.where(live, comp.dev_crc[dev], 0).astype(np.uint32),
                     n.astype(np.int32), link, code, ending))
    exit_of, exits = number(np.concatenate(
        [np.zeros(0, np.int64), *(code[ending] for *_, code, ending in hops)]))
    if exits.shape[1] == 0:
        exits = np.full((rows.size, 1), -1, np.int64)
    shift = (base + exits.shape[1] - 1).bit_length()
    if (comp.num_links + 1) << shift >= 2**31:
        raise ValueError(f"{comp.num_links} links and {base} devices a hop "
                         "do not pack into the walk's int32 steps")
    out, widths, done = [], [], 0
    for crc, n, link, code, ending in hops:
        code[ending] = base + exit_of[done: done + ending.sum()]
        done += int(ending.sum())
        steps = ((link + 1) << shift) | code
        width, fan = link.shape[1:]
        out.append((crc, n,
                    steps.reshape(len(rows), width * fan).astype(np.int32)))
        widths.append((width, fan))
    return (out, exits.astype(np.int32), tuple(widths), shift, base,
            inverse.reshape(-1))


_ROUTE_CACHE: dict[tuple, tuple[object, _RouteTables]] = {}


def route_tables(comp: CompiledFabric, src_dev, src_key, dst_key,
                 max_hops: int) -> _RouteTables:
    """The walk's per-flow route tables on the device, built and uploaded
    once per compiled fabric (by identity, as ``device_tables``), flow
    endpoints (by digest) and ``max_hops``.  A build is the
    ``walk.tables`` span, counting the distinct endpoint ``triples`` and
    the ``bytes`` uploaded."""
    ends = [np.ascontiguousarray(a, np.int32)
            for a in (src_dev, src_key, dst_key)]
    digest = hashlib.blake2b(b"".join(a.tobytes() for a in ends),
                             digest_size=16).digest()
    key = (id(comp), digest, max_hops)
    hit = _ROUTE_CACHE.get(key)
    if hit is not None and hit[0] is comp:
        return hit[1]
    jax, jnp, _ = _jx()
    with span("walk.tables"):
        hops, exits, widths, shift, base, inverse = _route_tables_host(
            comp, *ends, max_hops)
        host = ([tuple(a[inverse] for a in hop) for hop in hops],
                exits[inverse])
        count("triples", len(exits))
        count("bytes", sum(a.nbytes for a in jax.tree.leaves(host)))
        dev_hops, dev_exits = jax.block_until_ready(
            jax.tree.map(jnp.asarray, host))
    tabs = _RouteTables(hops=tuple(map(tuple, dev_hops)), exits=dev_exits,
                        widths=widths, shift=shift, base=base)
    if len(_ROUTE_CACHE) > 16:
        _ROUTE_CACHE.clear()
    _ROUTE_CACHE[key] = (comp, tabs)
    return tabs


def _select(idx, table):
    """``table[n, idx[n, s]]`` of an (N, K) per-flow table at an (N, S)
    column index, without a gather: a compare and select per column,
    broadcast over the seeds, summed over the K columns (one matches; an
    index past K reads 0)."""
    _, jnp, _ = _jx()
    col = jnp.arange(table.shape[1], dtype=idx.dtype)[:, None, None]
    return jnp.where(idx[None] == col, table.T[:, :, None],
                     0).sum(0, dtype=table.dtype)


def _walk_jit():
    jax, jnp, _ = _jx()

    @functools.partial(
        jax.jit, static_argnames=("hash_backend", "shift", "base"))
    def walk(hops, exits, fields, seeds, cell_salt,
             *, hash_backend: str, shift: int, base: int):
        N, S = fields.shape[0], seeds.shape[0]
        j = jnp.zeros((N, S), jnp.int32)     # device index in hop t's row
        end = jnp.zeros((N, S), jnp.int32)   # exit index, once done
        done = jnp.zeros((N, S), bool)
        t = jnp.int32(0)                     # hops begun by a walking cell
        ids = []
        for hop in range(len(hops)):         # unrolled: widths per hop
            crc_t, n_t, steps_t = hops[hop]
            t = t + (~done).any()
            fan = steps_t.shape[1] // crc_t.shape[1]
            choice = 0
            if fan > 1:                      # one slot: nothing to hash
                n = _select(j, n_t)
                dev_seed = _select(j, crc_t).astype(jnp.uint64) \
                    ^ seeds[None, :]
                if cell_salt is not None:
                    dev_seed = dev_seed ^ cell_salt
                h = _hash_grid_j(fields, dev_seed, hash_backend)
                safe_n = jnp.maximum(n, 1).astype(jnp.uint64)
                choice = jnp.where(n > 1, (h % safe_n).astype(jnp.int32), 0)
            step = _select(j * fan + choice, steps_t)
            ids.append(jnp.where(done, -1, (step >> shift) - 1))
            j = step & ((1 << shift) - 1)
            ends = ~done & (j >= base)
            end = jnp.where(ends, j - base, end)
            done = done | ends
        state = jnp.where(done, _select(end, exits), -1)
        ids = jnp.stack(ids) if ids else jnp.zeros((0, N, S), jnp.int32)
        return ids, state, done, t

    return walk


@functools.lru_cache(maxsize=1)
def _walk_fn():
    return _walk_jit()


def _jax_walk_device(routes: _RouteTables, field_mat, seeds_u64, *,
                     hash_backend, cell_salt=None):
    """Run the walk on device; returns device (hops, N, S) link ids (-1
    past a flow's last hop), the device each walk ended at (-1 where it
    did not end), the done mask and the hop count (all device-side)."""
    _, jnp, _ = _jx()
    salt = None if cell_salt is None else jnp.asarray(cell_salt)
    return _walk_fn()(
        routes.hops, routes.exits, jnp.asarray(field_mat),
        jnp.asarray(seeds_u64), salt, hash_backend=hash_backend,
        shift=routes.shift, base=routes.base)


def _check_walk(comp, state, dst_dev, describe, first_seed=0):
    """The numpy engine's arrival contract (termination is checked on
    the ``done`` scalar before this runs); state is (N, S)-small, so the
    host pull costs nothing next to the link-id tensor it replaces.
    ``first_seed`` is the seed index of the state's first column."""
    state = np.asarray(state)
    arrived = state == np.broadcast_to(
        np.asarray(dst_dev)[:, None], state.shape)
    if not arrived.all():
        bad = np.argwhere(~arrived)[0]
        raise RuntimeError(
            f"{describe(bad[0])} (seed index {first_seed + bad[1]}) "
            f"terminated at {comp.device_names[state[bad[0], bad[1]]]}")


def jax_ecmp_walk(
    comp: CompiledFabric,
    src_dev: np.ndarray,
    dst_dev: np.ndarray,
    src_key: np.ndarray,
    dst_key: np.ndarray,
    field_mat: np.ndarray,
    seeds_u64: np.ndarray,
    *,
    hash_backend: str = EXACT,
    max_hops: int = 16,
    cell_salt: np.ndarray | None = None,
    describe=lambda n: f"column {n}",
) -> np.ndarray:
    """Drop-in twin of ``vector_sim.ecmp_walk`` on the jax engine:
    same signature, same (hops, N, S) numpy result, same termination
    errors — bit-identical under both hash backends."""
    with _x64():
        routes = route_tables(comp, src_dev, src_key, dst_key, max_hops)
        ids, state, done, t = _jax_walk_device(
            routes, field_mat, seeds_u64, hash_backend=hash_backend,
            cell_salt=cell_salt)
        hops = int(t)
        if not bool(done.all()):
            raise RuntimeError(
                f"some flows did not terminate in {max_hops} hops")
        _check_walk(comp, state, dst_dev, describe)
        return np.asarray(ids[:hops])


def _wave_walk_jit():
    jax, jnp, lax = _jx()

    @functools.partial(
        jax.jit, static_argnames=("max_hops", "hash_backend", "n_fields",
                                  "cool", "near"))
    def wave_walk(cand, cand_n, dev_crc, is_server, link_dst,
                  src_dev, src_key, dst_key, fields, seeds, loads_q,
                  *, max_hops: int, hash_backend: str, n_fields: int,
                  cool: bool, near: bool):
        N, S = src_dev.shape[0], seeds.shape[0]
        C = cand.shape[-1]
        flat = loads_q.reshape(-1)
        row_off = jnp.arange(S, dtype=jnp.int64) * loads_q.shape[1]
        col_idx = jnp.arange(C, dtype=jnp.int32)
        state0 = jnp.broadcast_to(
            src_dev[:, None].astype(jnp.int32), (N, S))
        done0 = jnp.zeros((N, S), bool)
        ids0 = jnp.full((max_hops, N, S), -1, jnp.int32)

        def cond(c):
            t, state, done, ids = c
            return (t < max_hops) & ~done.all()

        def body(c):
            t, state, done, ids = c
            key = jnp.where(is_server[state], src_key[:, None],
                            dst_key[:, None])
            n = cand_n[state, key]
            cands = cand[state, key]                       # (N, S, C)
            valid = (col_idx < n[..., None]) & (cands >= 0)
            cl = jnp.where(
                valid,
                flat[row_off[None, :, None] + jnp.maximum(cands, 0)],
                jnp.inf)
            dev_seed = dev_crc[state] ^ seeds[None, :]
            h = _hash_grid_j(fields, dev_seed, hash_backend)
            # the three _wave_choice eligibility modes, selected
            # statically (cool/near are jit-static):
            if cool and near:
                m = cl.min(axis=-1)
                tie = valid & (cl <= m[..., None] + 1.0)
            elif cool:
                n_valid = jnp.maximum(valid.sum(axis=-1), 1)
                mean = jnp.where(valid, cl, 0.0).sum(axis=-1) / n_valid
                tie = valid & (cl <= jnp.floor(mean)[..., None])
            else:
                tie = valid & (cl == cl.min(axis=-1)[..., None])
            n_tie = tie.sum(axis=-1)
            rank = jnp.where(
                n_tie > 1,
                (h % jnp.maximum(n_tie, 1).astype(jnp.uint64)
                 ).astype(jnp.int64),
                0)
            col = (tie.cumsum(axis=-1) <= rank[..., None]).sum(axis=-1)
            link = jnp.take_along_axis(
                cands, jnp.minimum(col, C - 1)[..., None], axis=-1)[..., 0]
            link = jnp.where(done | (n == 0), -1, link)
            ids = lax.dynamic_update_index_in_dim(ids, link, t, 0)
            nxt = jnp.where(link >= 0, link_dst[jnp.maximum(link, 0)], state)
            done = done | (link < 0) | is_server[nxt]
            return t + 1, nxt, done, ids

        t, state, done, ids = lax.while_loop(
            cond, body, (jnp.int32(0), state0, done0, ids0))
        return ids, state, done, t

    return wave_walk


@functools.lru_cache(maxsize=1)
def _wave_walk_fn():
    return _wave_walk_jit()


def jax_wave_walk(
    comp: CompiledFabric,
    src_dev: np.ndarray,
    dst_dev: np.ndarray,
    src_key: np.ndarray,
    dst_key: np.ndarray,
    field_mat: np.ndarray,
    seeds_u64: np.ndarray,
    loads: np.ndarray,
    *,
    hash_backend: str = EXACT,
    max_hops: int = 16,
    quantum: float = 1.0,
    cool: bool = False,
    near: bool = False,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Device twin of ``strategies._wave_walk_numpy``: one speculative
    wave-routing pass of every (flow, seed) cell against a *frozen*
    ``(S, L)`` load snapshot, decisions quantized to ``quantum`` and
    tie-broken with the documented ``hash % n_tie`` rule — bit-identical
    to the numpy wave walk under ``hash_backend="exact"`` (the
    cross-engine differential contract).  ``cool``/``near`` select the
    repair-arrival eligibility modes of ``_wave_choice``; they are
    jit-static, so each mode compiles once.  Returns host-side
    ``(ids[:hops], state, done)`` for the caller's arrival checks."""
    with _x64():
        _, jnp, _ = _jx()
        tabs = device_tables(comp)
        loads_q = jnp.asarray(np.floor(np.asarray(loads) / quantum))
        ids, state, done, t = _wave_walk_fn()(
            tabs.cand, tabs.cand_n, tabs.dev_crc, tabs.is_server,
            tabs.link_dst, jnp.asarray(src_dev), jnp.asarray(src_key),
            jnp.asarray(dst_key), jnp.asarray(field_mat),
            jnp.asarray(seeds_u64), loads_q,
            max_hops=max_hops, hash_backend=hash_backend,
            n_fields=int(field_mat.shape[1]),
            cool=bool(cool), near=bool(near))
        hops = int(t)
        return np.asarray(ids[:hops]), np.asarray(state), np.asarray(done)


# ---------------------------------------------------------------------------
# Stage 2: link counts + FIM (per-cell reduction + per-layer MAPE)
# ---------------------------------------------------------------------------


def _cell_reduce(ids, v, L: int, reduce, empty):
    """Reduce an (N, S) per-flow value over each (seed, link) cell:
    ``out[s, l] = reduce(v[n, s] for every (h, n) with ids[h, n, s] == l)``,
    ``empty`` where no flow crosses the cell.  Traced inside the fill
    (``_fill_jit``: float64 sums and mins of values that change every
    round) and the weighted counts (``_counts_jit`` under unequal
    demand); unit demand counts with ``_unit_counts``.  The compare
    against ``arange(L)`` sits inside the reduction, where XLA fuses it,
    so no (H, N, S, L) tensor is built.  It replaces a scatter and a sort
    on purpose: on a TPU v5e a float64 ``segment_sum`` over the
    4 x 4096 x 1024 cells of a paper-testbed sweep took 1.75 s against
    0.034 s for this reduction, and a sort over the flattened cells
    compiles for minutes."""
    _, jnp, _ = _jx()
    hit = ids[..., None] == jnp.arange(L, dtype=ids.dtype)
    return reduce(jnp.where(hit, v[None, :, :, None], empty), axis=(0, 1))


# Unit-demand counts (``_unit_counts``): a link id l = hi * B + lo is one
# row of a (A, K) one-hot of hi times one of a (B, K) one-hot of lo, so a
# seed's (A, B) hit table is one bfloat16 matmul over its K = H * N
# (hop, flow) entries.  Operands are 0/1 and every count is an integer of
# at most H * N, which float32 accumulation keeps exactly up to 2**24.
# XLA:TPU fuses the one-hot compares into the matmul, so no one-hot
# reaches HBM (v5e compile of a 6 x 21504 x 128 pass: 87 MB of temps);
# the seed blocks bound what a backend that builds them (XLA:CPU) holds.
_ONEHOT_LANES = 128                # widest factor, one lane tile
_ONEHOT_BYTES = 256 << 20          # bfloat16 one-hots per seed block
_EXACT_F32 = 1 << 24


def onehot_split(L: int) -> tuple[int, int]:
    """Widths ``(A, B)`` of the factored one-hots that count ``L`` links:
    ``B`` the power of two at or above sqrt(L), at most one lane tile,
    and ``A = ceil(L / B)``."""
    B = 1
    while B * B < L and B < _ONEHOT_LANES:
        B *= 2
    return -(-L // B), B


def _unit_counts(ids, L: int):
    """(S, L) float64 hit counts of an (H, N, S) link-id tensor, every
    entry of weight 1: per seed, the (A, K) one-hot of ``ids >> log2(B)``
    contracted with the (B, K) one-hot of ``ids & (B - 1)`` on the MXU.
    An id of -1 (past a path's end) gives all-zero columns in both.
    Seeds go in blocks of at most ``_ONEHOT_BYTES`` of one-hots.  Traced
    inside ``counts_fn``; bit-identical to a float64 sum of ones while
    H * N <= 2**24 (``jax_link_flow_counts`` checks)."""
    _, jnp, lax = _jx()
    H, N, S = ids.shape
    A, B = onehot_split(L)
    shift = B.bit_length() - 1
    block = max(1, min(S, _ONEHOT_BYTES // max(1, H * N * (A + B) * 2)))
    hi_of = jnp.arange(A, dtype=ids.dtype)[:, None]
    lo_of = jnp.arange(B, dtype=ids.dtype)[:, None]

    def seed(row):                 # (K,) link ids of one seed
        hi = (row >> shift)[None, :] == hi_of        # -1 >> shift is -1
        lo = jnp.where(row >= 0, row & (B - 1), B)[None, :] == lo_of
        return lax.dot_general(
            hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    hits = lax.map(seed, ids.reshape(H * N, S).T, batch_size=block)
    return hits.reshape(S, A * B)[:, :L].astype(jnp.float64)


def _counts_jit():
    jax, jnp, _ = _jx()

    @functools.partial(jax.jit, static_argnames=("L", "unit"))
    def counts_fn(ids, weights, *, L: int, unit: bool):
        # ids: (H, Nf, S) device link ids; weights: (Nf,) demand
        # weights, None under unit demand
        if unit:
            return _unit_counts(ids, L)
        v = jnp.broadcast_to(weights[:, None], ids.shape[1:])
        return _cell_reduce(ids, v, L, jnp.sum, 0.0)

    return counts_fn


@functools.lru_cache(maxsize=1)
def _counts_fn():
    return _counts_jit()


def jax_link_flow_counts(ids, weights, L: int):
    """(S, L) demand-weighted link loads from a device (H, Nf, S) link-id
    tensor — twin of ``VectorTraceResult.link_flow_counts``.

    All-ones weights (the numpy twin's own test) count on the MXU
    (``_unit_counts``) while H * Nf <= 2**24 keeps float32 exact; the
    innermost span then counts ``unit_counts`` 1 and ``onehot_width``
    A + B.  Any other weights sum with ``_cell_reduce``; the span then
    counts ``weighted_counts`` 1 and ``link_compares`` H * Nf * S * L."""
    _, jnp, _ = _jx()
    w = np.asarray(weights, np.float64)
    H, N, S = ids.shape
    unit = bool((w == 1.0).all()) and H * N <= _EXACT_F32
    if unit:
        count("unit_counts", 1)
        count("onehot_width", sum(onehot_split(L)))
        return _counts_fn()(ids, None, L=L, unit=True)
    count("weighted_counts", 1)
    count("link_compares", H * N * S * L)
    return _counts_fn()(ids, jnp.asarray(w), L=L, unit=False)


def _fim_jit():
    jax, jnp, _ = _jx()

    @functools.partial(jax.jit,
                       static_argnames=("only_used_leaves", "num_devices"))
    def fim_fn(counts, layer_sel, link_src, link_dst, n_real,
               *, only_used_leaves: bool, num_devices: int):
        # counts: (S, L) float; layer_sel: (NL, L) bool one-hot per layer;
        # the first n_real seed rows are real, the rest chunk padding.
        # Returns the (1 + NL, S) aggregate and per-layer MAPEs, and the
        # (NL,) flags of layers live under some real seed.
        S, L = counts.shape
        if only_used_leaves:
            present = counts > 0
            used_src = jax.ops.segment_max(
                present.astype(jnp.int32).T, link_src,
                num_segments=num_devices)          # (V, S)
            used_dst = jax.ops.segment_max(
                present.astype(jnp.int32).T, link_dst,
                num_segments=num_devices)
            used = (jnp.maximum(used_src, used_dst) > 0)   # (V, S)
            leaf_mask = (used[link_src] & used[link_dst]).T  # (S, L)
        else:
            leaf_mask = jnp.ones((S, L), bool)

        num = jnp.zeros(S, jnp.float64)
        den = jnp.zeros(S, jnp.float64)
        mapes = []
        for li in range(layer_sel.shape[0]):
            lm = layer_sel[li][None, :]            # (1, L)
            mask = (lm & leaf_mask).astype(jnp.float64)
            n_links = mask.sum(axis=1)
            total = (counts * mask).sum(axis=1)
            live = (total > 0) & (n_links > 0)
            ideal = jnp.where(live, total / jnp.maximum(n_links, 1), 1.0)
            mape = (100.0 / jnp.maximum(n_links, 1)
                    * (jnp.abs(counts - ideal[:, None])
                       / ideal[:, None] * mask).sum(1))
            mape = jnp.where(live, mape, 0.0)
            mapes.append((mape, live))
            num = num + jnp.where(live, mape * n_links, 0.0)
            den = den + jnp.where(live, n_links, 0.0)
        agg = jnp.where(den > 0, num / jnp.maximum(den, 1.0), 0.0)
        real = jnp.arange(S, dtype=jnp.int32) < n_real
        return (jnp.stack([agg] + [m for m, _ in mapes]),
                jnp.stack([(lv & real).any() for _, lv in mapes]))

    return fim_fn


@functools.lru_cache(maxsize=1)
def _fim_fn():
    return _fim_jit()


_FIM_TABLE_CACHE: dict[tuple, tuple[object, tuple]] = {}


def _fim_tables(comp: CompiledFabric, names: tuple[str, ...]):
    """Device ``(NL, L)`` layer selection, ``link_src`` and ``link_dst``
    of the FIM over ``names``, uploaded once per compiled fabric and
    layer list (cached as ``device_tables``); the upload is the
    ``fim.to_device`` span."""
    key = (id(comp), names)
    hit = _FIM_TABLE_CACHE.get(key)
    if hit is not None and hit[0] is comp:
        return hit[1]
    jax, jnp, _ = _jx()
    host = (np.stack([comp.link_layer == comp.layer_names.index(n)
                      for n in names]), comp.link_src, comp.link_dst)
    with span("fim.to_device", bytes=sum(a.nbytes for a in host)):
        tabs = tuple(jax.block_until_ready([jnp.asarray(a) for a in host]))
    if len(_FIM_TABLE_CACHE) > 16:
        _FIM_TABLE_CACHE.clear()
    _FIM_TABLE_CACHE[key] = (comp, tabs)
    return tabs


def _fim_chunks(comp, chunks, *, layers, only_used_leaves):
    """FIM of device ``(Sc, L)`` count chunks, each given as
    ``(n_real, counts)`` with its first ``n_real`` seed rows real: one
    ``fim_fn`` per chunk at its own shape, then every chunk's answers in
    one pull.  Returns host arrays with ``fim_from_counts``'s
    layer-dropping semantics."""
    jax = _jx()[0]
    layer_list = list(layers) if layers else comp.layer_names
    names = tuple(layer for layer in layer_list
                  if layer in comp.layer_names
                  and (comp.link_layer
                       == comp.layer_names.index(layer)).any())
    if not names:          # the chunks still run: the walk checks arrival
        return np.zeros(sum(n_real for n_real, _ in chunks)), {}
    tabs = _fim_tables(comp, names)
    sizes, outs = [], []
    for n_real, counts in chunks:
        with span("fim.run"):
            outs.append(jax.block_until_ready(_fim_fn()(
                counts, *tabs, n_real, only_used_leaves=only_used_leaves,
                num_devices=comp.num_devices)))
        sizes.append(n_real)
    with span("fim.to_host", bytes=sum(a.nbytes for o in outs for a in o)):
        outs = jax.device_get(outs)
    fim = np.concatenate([m[:, :n] for (m, _), n in zip(outs, sizes)], 1)
    live = np.logical_or.reduce([lv for _, lv in outs])
    # all-dead layers are dropped
    per_layer = {name: fim[1 + i] for i, name in enumerate(names)
                 if live[i]}
    return fim[0], per_layer


def jax_fim_from_counts(
    counts,
    comp: CompiledFabric,
    *,
    layers: Sequence[str] | None = None,
    only_used_leaves: bool = False,
) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Twin of ``vector_sim.fim_from_counts`` on the device: takes the
    (S, L) count matrix, a device array used where it is or a host array
    uploaded (``fim.to_device``), and returns host arrays with the same
    layer-dropping semantics."""
    jax, jnp, _ = _jx()
    with _x64():
        if not isinstance(counts, jax.Array):
            counts = np.asarray(counts)
            with span("fim.to_device", bytes=counts.nbytes):
                counts = jax.block_until_ready(jnp.asarray(counts))
        return _fim_chunks(comp, [(int(counts.shape[0]), counts)],
                           layers=layers, only_used_leaves=only_used_leaves)


# ---------------------------------------------------------------------------
# Stage 3: weighted progressive max-min fill (lax.while_loop over cells)
# ---------------------------------------------------------------------------


def _fill_jit():
    jax, jnp, lax = _jx()

    @jax.jit
    def fill(ids, w, cap):
        """ids: (H, N, S) int32 link ids (-1 past the path's end),
        w: (N,) float64 positive weights, cap: (L,) float64 capacity.
        Returns (N, S) max-min rates, a flow crossing no link getting
        inf, and the int32 count of freeze rounds the loop ran.

        Same parallel local-bottleneck formulation as the numpy
        ``_fill_block_weighted``: freeze every flow crossing a cell whose
        fair share equals the min share over its members' bottlenecks,
        drain, repeat.  A cell is one (seed, link) pair, held in
        ``(S, L)`` tables; frozen-ness lives in an ``(N, S)`` mask, so
        every shape stays static.  Per-cell sums and mins go through
        ``_cell_reduce``; per-flow reads gather from the ``(S, L)`` table.
        The bottleneck test compares the min member bottleneck with the
        cell's share: every member's bottleneck is at most the share (the
        cell's own share enters its min), so equality means no member is
        held lower elsewhere.
        """
        H, N, S = ids.shape
        L = cap.shape[0]
        flat = jnp.where(ids >= 0, ids, 0) + jnp.arange(
            S, dtype=ids.dtype) * L                 # (H, N, S) into (S*L,)
        real = ids >= 0

        def per_cell(v, reduce=jnp.sum, empty=0.0):  # (N, S) -> (S, L)
            return _cell_reduce(ids, v, L, reduce, empty)

        def per_flow(table, empty):                 # (S, L) -> (H, N, S)
            return jnp.where(real, table.ravel()[flat], empty)

        residual0 = jnp.broadcast_to(cap, (S, L))
        haslink = real.any(axis=0)
        rates0 = jnp.where(haslink, 0.0, jnp.inf)
        w = w[:, None]

        def cond(c):
            return c[0].any()

        def body(c):
            active, residual, rates, rounds = c
            wsum = per_cell(jnp.where(active, w, 0.0))
            live = wsum > 0
            share = jnp.where(live, residual / jnp.where(live, wsum, 1.0),
                              jnp.inf)
            fm = per_flow(share, jnp.inf).min(axis=0)   # flow bottleneck
            nbr = per_cell(jnp.where(active, fm, jnp.inf), jnp.min,
                           jnp.inf)
            freezable = (nbr == share) & live
            fz = per_flow(freezable, False).any(axis=0) & active
            rates = jnp.where(fz, w * fm, rates)
            drained = per_cell(jnp.where(fz, w * fm, 0.0))
            return active & ~fz, residual - drained, rates, rounds + 1

        out = lax.while_loop(cond, body, (haslink, residual0, rates0,
                                          jnp.int32(0)))
        return out[2], out[3]

    return fill


@functools.lru_cache(maxsize=1)
def _fill_fn():
    return _fill_jit()


def _fill_device(ids, link_gbps, weights):
    """Run the fill on a device (H, N, S) link-id tensor; returns the
    device (N, S) rate grid and the device count of freeze rounds."""
    _, jnp, _ = _jx()
    return _fill_fn()(jnp.asarray(ids),
                      jnp.asarray(np.asarray(weights, np.float64)),
                      jnp.asarray(np.asarray(link_gbps, np.float64)))


def jax_batched_max_min(
    link_ids: np.ndarray,
    link_gbps: np.ndarray,
    *,
    assume_unique: bool = False,
    weights: np.ndarray | None = None,
) -> np.ndarray:
    """Drop-in twin of ``vector_throughput.batched_max_min`` on the jax
    engine (the ``seed_block`` knob does not apply: the device fill runs
    all seeds in one static-shape pass)."""
    link_ids = np.asarray(link_ids)
    if link_ids.ndim != 3:
        raise ValueError(f"link_ids must be (H, N, S), got {link_ids.shape}")
    if not assume_unique:
        from .vector_throughput import dedup_link_ids
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
    if weights is None:
        weights = np.ones(N)
    if H == 0 or N == 0 or S == 0:
        out = np.empty((N, S))
        out[:] = np.inf if H == 0 else 0.0
        return out
    with _x64():
        rates, _ = _fill_device(link_ids.astype(np.int32), link_gbps,
                                weights)
        return np.asarray(rates)


# ---------------------------------------------------------------------------
# Stage 4: flowlet exposure -> transport efficiency -> goodput
# ---------------------------------------------------------------------------


def _exposure_jit():
    jax, jnp, _ = _jx()

    @functools.partial(jax.jit, static_argnames=("n",))
    def exposure_fn(hop_counts, unit_rates, fi, *, n: int):
        # hop_counts/unit_rates: (Nf, S); fi: (Nf,) parent rows
        hops = hop_counts.astype(jnp.float64)
        hmin = jax.ops.segment_min(hops, fi, num_segments=n)
        hmax = jax.ops.segment_max(hops, fi, num_segments=n)
        skew = (hmax - hmin) / jnp.maximum(hmin, 1.0)
        finite = jnp.isfinite(unit_rates)
        rmin = jax.ops.segment_min(
            jnp.where(finite, unit_rates, jnp.inf), fi, num_segments=n)
        rmax = jax.ops.segment_max(
            jnp.where(finite, unit_rates, -jnp.inf), fi, num_segments=n)
        live = jnp.isfinite(rmax) & (rmax > 0)
        dispersion = jnp.where(
            live, (rmax - jnp.where(live, rmin, 0.0))
            / jnp.where(live, rmax, 1.0), 0.0)
        exposure = skew + dispersion
        return jnp.where(jnp.isfinite(exposure), exposure, 0.0)

    return exposure_fn


@functools.lru_cache(maxsize=1)
def _exposure_fn():
    return _exposure_jit()


def jax_flowlet_exposure(
    result: VectorTraceResult,
    flowlet_rates: np.ndarray | None = None,
) -> np.ndarray:
    """Twin of ``reordering.flowlet_exposure`` on the jax engine."""
    n, s = result.num_flows, result.num_seeds
    extra = result.extra_exposure
    fi = np.asarray(result.flow_index)
    if not result.is_multipath and fi.size == n and (
            fi == np.arange(n, dtype=np.int64)).all():
        base = np.zeros((n, s))
        return base if extra is None else base + extra
    if flowlet_rates is None:
        flowlet_rates = jax_batched_max_min(
            result.link_ids, result.compiled.link_gbps,
            assume_unique=True, weights=_column_weights_or_none(result))
    with _x64():
        _, jnp, _ = _jx()
        unit = np.asarray(flowlet_rates) / result.column_weights()[:, None]
        exposure = np.asarray(_exposure_fn()(
            jnp.asarray(result.hop_counts()), jnp.asarray(unit),
            jnp.asarray(fi.astype(np.int32)), n=n))
    return exposure if extra is None else exposure + extra


def _column_weights_or_none(result: VectorTraceResult):
    w = result.column_weights()
    return None if (w == 1.0).all() else w


# ---------------------------------------------------------------------------
# Fused front ends (plain-ECMP fast path: everything stays on device)
# ---------------------------------------------------------------------------


def seed_chunk(n_flows: int, max_hops: int, S: int) -> int:
    """Seeds per device pass of the fused front ends.

    The largest multiple of ``_SEED_LANES`` whose working set fits
    ``_CHUNK_BYTES`` (at least one lane tile), then shrunk to split
    ``S`` into equal chunks; ``S`` itself when it fits in one pass."""
    per_seed = n_flows * max_hops * (_WALK_BYTES_PER_HOP
                                     + _FILL_BYTES_PER_CELL)
    per = max(_SEED_LANES,
              _CHUNK_BYTES // per_seed // _SEED_LANES * _SEED_LANES)
    if S <= per:
        return S
    even = -(-S // -(-S // per))               # ceil(S / n_chunks)
    return -(-even // _SEED_LANES) * _SEED_LANES


def _walked_chunks(comp, flows, endpoints, field_mat, seeds_u64, *,
                   hash_backend, max_hops):
    """Walk the seeds in equal device passes; yields ``(s0, s1, ids)``
    with ``ids`` the device ``(hops, N, Sc)`` link ids of seeds
    ``s0:s1``.  The last chunk is padded to the chunk size with repeats
    of its own seeds, so every pass has one shape and compiles once;
    callers keep the first ``s1 - s0`` seed columns.

    The flows' route tables come from ``route_tables`` (the
    ``walk.tables`` span where they are built).  Each pass is three
    spans: ``walk.to_device`` (its inputs), ``walk.run`` (the walk, its
    ``done`` flags, arrival check and hop count; counters ``hops``, the
    hops begun by a walking cell, and ``select_width``, the route-table
    entries each (flow, seed) selects among) and ``walk.to_host`` (the
    arrival check's one bool; the (N, s1 - s0) state too where a flow
    did not arrive)."""
    jax, jnp, _ = _jx()
    src_dev, dst_dev, src_key, dst_key = endpoints
    routes = route_tables(comp, src_dev, src_key, dst_key, max_hops)
    S = len(seeds_u64)
    Sc = seed_chunk(len(flows), max_hops, S)
    for s0 in range(0, S, Sc):
        s1 = min(s0 + Sc, S)
        host = (field_mat, np.resize(seeds_u64[s0:s1], Sc), dst_dev)
        with span("walk.to_device", bytes=sum(a.nbytes for a in host)):
            fields, seeds, dst = jax.block_until_ready(
                [jnp.asarray(a) for a in host])
        with span("walk.run"):
            ids, state, done, t = _jax_walk_device(
                routes, fields, seeds, hash_backend=hash_backend)
            # the reductions are queued behind the walk before the wait,
            # so the device does not idle while they are dispatched
            ids, all_done, arrived, t = jax.block_until_ready(
                (ids, done.all(), (state == dst[:, None]).all(), t))
            if not bool(all_done):
                raise RuntimeError(
                    f"some flows did not terminate in {max_hops} hops")
            hops = int(t)
            count("hops", hops)
            count("select_width", routes.select_width)
            ids = ids[:hops]               # rows past the last hop are -1
        with span("walk.to_host", bytes=arrived.nbytes):
            if not bool(arrived):
                state = state[:, : s1 - s0]
                count("bytes", state.nbytes)
                _check_walk(comp, state, dst_dev,
                            lambda n: f"flow {flows[n].flow_id}",
                            first_seed=s0)
        yield s0, s1, ids


def _fused_walk_counts(comp, flows, endpoints, field_mat, seeds_u64, *,
                       hash_backend, max_hops, flow_demand):
    """One device pass per seed chunk: walk + demand-weighted counts.
    Yields ``(s1 - s0, counts)`` per chunk, ``counts`` the device
    ``(Sc, L)`` count matrix whose first ``s1 - s0`` rows are real."""
    jax = _jx()[0]
    for s0, s1, ids in _walked_chunks(comp, flows, endpoints, field_mat,
                                      seeds_u64, hash_backend=hash_backend,
                                      max_hops=max_hops):
        with span("counts.run"):
            counts = jax.block_until_ready(
                jax_link_flow_counts(ids, flow_demand, comp.num_links))
        yield s1 - s0, counts


def _fused_prep(comp, workload, seeds, spec, field_matrix):
    """The host inputs of a fused sweep, in the ``prep`` span: flows,
    uint64 seeds, demand weights, hash fields and flow endpoints."""
    with span("prep"):
        flows = resolve_flows(comp, workload)
        seeds_u64 = normalize_seeds(seeds)
        if len(flows) == 0:
            raise ValueError("simulate_paths needs at least one flow")
        flow_demand = flow_demand_weights(flows, spec.demand_mode)
        field_mat = (field_matrix if field_matrix is not None
                     else flow_fields_matrix(flows, spec.fields))
        endpoints = comp.flow_endpoint_ids(flows)
    count("flows", len(flows))
    return flows, seeds_u64, flow_demand, field_mat, endpoints


def fused_monte_carlo_fim(
    comp: CompiledFabric,
    workload: WorkloadDescription | Sequence[Flow],
    seeds: Sequence[int] | np.ndarray,
    spec: SimSpec,
    *,
    layers: Sequence[str] | None = None,
    only_used_leaves: bool = False,
    field_matrix: np.ndarray | None = None,
) -> MonteCarloFim:
    """Plain-ECMP Monte-Carlo FIM with walk + counts + FIM on device.

    ``spec`` is the resolved ``SimSpec`` of a call that
    ``vector_sim.runs_fused``; its ``fields``, ``hash_backend``,
    ``demand_mode`` and ``max_hops`` are read here, whole."""
    flows, seeds_u64, flow_demand, field_mat, endpoints = _fused_prep(
        comp, workload, seeds, spec, field_matrix)
    with _x64():
        chunks = _fused_walk_counts(
            comp, flows, endpoints, field_mat, seeds_u64,
            hash_backend=spec.hash_backend, max_hops=spec.max_hops,
            flow_demand=flow_demand)
        agg, per_layer = _fim_chunks(
            comp, chunks, layers=layers, only_used_leaves=only_used_leaves)
    with span("assemble"):
        return MonteCarloFim(seeds=seeds_u64, aggregate=agg,
                             per_layer=per_layer)


def fused_monte_carlo_throughput(
    comp: CompiledFabric,
    workload: WorkloadDescription | Sequence[Flow],
    seeds: Sequence[int] | np.ndarray,
    spec: SimSpec,
    *,
    field_matrix: np.ndarray | None = None,
):
    """Plain-ECMP Monte-Carlo throughput with walk + fill on device.

    ``spec`` is the resolved ``SimSpec`` of a call that
    ``vector_sim.runs_fused``; its ``fields``, ``hash_backend``,
    ``demand_mode``, ``max_hops`` and ``transport`` are read here,
    whole.  Single-path ECMP has zero flowlet exposure, so (exactly like
    the numpy fast path) goodput is the raw rate grid under every
    transport profile — the exposure/efficiency stages engage through
    ``throughput_from_result(engine="jax")`` for multi-path strategies.
    """
    from .reordering import resolve_transport
    from .vector_throughput import MonteCarloThroughput, pair_rate_matrix
    flows, seeds_u64, flow_demand, field_mat, endpoints = _fused_prep(
        comp, workload, seeds, spec, field_matrix)
    profile = resolve_transport(spec.transport)
    rates = np.empty((len(flows), len(seeds_u64)))
    with _x64():
        jax = _jx()[0]
        for s0, s1, ids in _walked_chunks(
                comp, flows, endpoints, field_mat, seeds_u64,
                hash_backend=spec.hash_backend, max_hops=spec.max_hops):
            with span("fill.run"):
                chunk, rounds = jax.block_until_ready(
                    _fill_device(ids, comp.link_gbps, flow_demand))
            with span("fill.to_host", bytes=chunk.nbytes):
                rates[:, s0:s1] = np.asarray(chunk)[:, : s1 - s0]
                if enabled():          # the untraced path pulls no count
                    count("rounds", int(rounds))
    with span("assemble"):
        pairs, per_pair = pair_rate_matrix(flows, rates)
        return MonteCarloThroughput(
            seeds=seeds_u64, flows=flows, rates=rates, pairs=pairs,
            per_pair=per_pair, transport=profile.name)

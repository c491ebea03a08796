"""Bulk flow hashing + vectorized paper-testbed path simulation.

``simulate_paper_paths`` evaluates the four cross-rack ECMP decisions of
the paper's 2-rack fabric for N flows at once (source LAG, leaf uplink,
spine downlink, destination LAG) and returns per-stage link indices —
enough to compute link loads / FIM for millions of flows in one shot.
This is FlowTracer-at-scale: same decisions the hop-by-hop tracer makes,
evaluated as four fused hash passes instead of per-flow SSH queries.

``simulate_paper_paths`` is hard-wired to the 4-stage paper testbed; for
arbitrary fabrics (and bit-identical parity with ``EcmpRouting``) use
``repro.core.vector_sim`` / ``repro.core.jax_engine``, whose
``hash_backend="murmur"`` evaluates the SAME hash as ``bulk_hash`` here:
one murmur definition — seed-as-init, fold the field columns, fmix
(``kernel.murmur_fold``/``murmur_fmix``) — shared by the Pallas kernel,
the jnp oracle, the numpy engine grid, and the jitted device grid.
``tests/test_kernels.py`` pins the per-stage choice distribution so the
unification can never drift the paper-testbed statistics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import bulk_hash_kernel, bulk_hash_seeded_kernel
from .ref import bulk_hash_ref, bulk_hash_seeded_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _check_kernel_mode(force_kernel: bool, interpret: bool) -> None:
    """The compiled kernel exists only on the TPU; elsewhere the kernel
    runs only when the caller asks for the interpreter by name."""
    if force_kernel and not interpret and not _on_tpu():
        raise ValueError(
            "force_kernel=True off the TPU needs interpret=True: the "
            f"compiled Pallas kernel targets the TPU, the backend here "
            f"is {jax.default_backend()!r}")


def bulk_hash(fields, seed, *, force_kernel: bool = False,
              interpret: bool = False, block: int = 4096):
    """fields: (N, F) uint32 -> (N,) uint32.  seed: any int (wrapped u32).

    The seed-as-init murmur convention: the hash starts at ``seed`` and
    folds the field columns — the same definition the engines' murmur
    grids (``vector_sim._murmur_hash_grid``, ``jax_engine``) evaluate
    per (flow, seed) cell, and ``bulk_hash_seeded`` evaluates per row.
    """
    _check_kernel_mode(force_kernel, interpret)
    seed = np.uint32(int(seed) & 0xFFFFFFFF)
    return _bulk_hash_impl(fields, seed, force_kernel=force_kernel,
                           interpret=interpret, block=block)


@functools.partial(jax.jit, static_argnames=("force_kernel", "interpret", "block"))
def _bulk_hash_impl(fields, seed, *, force_kernel: bool = False,
                    interpret: bool = False, block: int = 4096):
    N, F = fields.shape
    pad = (-N) % block
    if pad:
        fields = jnp.pad(fields, ((0, pad), (0, 0)))
    if force_kernel or _on_tpu():
        out = bulk_hash_kernel(fields, jnp.uint32(seed),
                               block=block, interpret=interpret)
    else:
        out = bulk_hash_ref(fields, jnp.uint32(seed))
    return out[:N, 0]


def bulk_hash_seeded(fields, seeds, *, force_kernel: bool = False,
                     interpret: bool = False, block: int = 4096):
    """fields: (N, F) uint32, seeds: (N,) uint32 per-row hash init ->
    (N,) uint32.  The per-row-seed twin of ``bulk_hash`` (same fold/fmix
    chain); ``bulk_hash(fields, s) == bulk_hash_seeded(fields, full(N, s))``
    bit-for-bit, which is what pins all murmur consumers to one
    definition."""
    _check_kernel_mode(force_kernel, interpret)
    return _bulk_hash_seeded_impl(
        fields, seeds, force_kernel=force_kernel, interpret=interpret,
        block=block)


@functools.partial(jax.jit, static_argnames=("force_kernel", "interpret", "block"))
def _bulk_hash_seeded_impl(fields, seeds, *, force_kernel: bool = False,
                           interpret: bool = False, block: int = 4096):
    N, F = fields.shape
    pad = (-N) % block
    if pad:
        fields = jnp.pad(fields, ((0, pad), (0, 0)))
        seeds = jnp.pad(seeds, ((0, pad),))
    seeds = seeds.astype(jnp.uint32).reshape(-1, 1)
    if force_kernel or _on_tpu():
        out = bulk_hash_seeded_kernel(
            fields, seeds, block=block, interpret=interpret)
    else:
        out = bulk_hash_seeded_ref(fields, seeds)
    return out[:N, 0]


def bulk_ecmp_choice(fields, seed, n_choices: int, **kw):
    return (bulk_hash(fields, seed, **kw) % jnp.uint32(n_choices)).astype(jnp.int32)


def simulate_paper_paths(
    fields: jax.Array,            # (N, 5) uint32 flow 5-tuples
    *,
    num_spines: int = 4,
    links_per_leaf_spine: int = 4,
    ports_per_lag: int = 2,
    seeds: tuple[int, int, int, int] = (101, 202, 303, 404),
    **kw,
) -> dict[str, jax.Array]:
    """Four-stage ECMP decision vector for every flow (paper Fig. 2).

    Returns int32 arrays: src_port (LAG), uplink (leaf->spine link index
    in [0, spines*links)), spine_link (spine->dst-leaf link in [0, links)),
    dst_port (LAG).  Stage seeds model per-switch hash seeds.
    """
    return {
        "src_port": bulk_ecmp_choice(fields, seeds[0], ports_per_lag, **kw),
        "uplink": bulk_ecmp_choice(fields, seeds[1],
                                   num_spines * links_per_leaf_spine, **kw),
        "spine_link": bulk_ecmp_choice(fields, seeds[2],
                                       links_per_leaf_spine, **kw),
        "dst_port": bulk_ecmp_choice(fields, seeds[3], ports_per_lag, **kw),
    }


def link_loads_fim(choices: jax.Array, n_links: int) -> tuple[np.ndarray, float]:
    """Per-link flow counts + FIM (eq. 1) from a choice vector."""
    counts = np.bincount(np.asarray(choices), minlength=n_links)
    ideal = counts.sum() / n_links
    fim = 100.0 / n_links * float(np.abs(counts - ideal).sum() / ideal) \
        if ideal > 0 else 0.0
    return counts, fim

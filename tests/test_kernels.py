"""Pallas kernel sweeps: shapes x dtypes against the pure-jnp oracles,
executed in interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flowhash.ops import (
    bulk_hash, bulk_hash_seeded, link_loads_fim, simulate_paper_paths,
)
from repro.kernels.ssd.ops import ssd_scan
from repro.models.ssm import ssd_chunked

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("S", [128, 256])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [
    pytest.param(jnp.float32, marks=pytest.mark.slow), jnp.bfloat16])
def test_flash_attention_sweep(S, hd, causal, dtype):
    q = jax.random.normal(KEY, (2, S, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, S, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, S, hd), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 64)])
def test_flash_attention_block_shapes(block_q, block_k):
    S, hd = 256, 64
    q = jax.random.normal(KEY, (2, S, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, S, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, S, hd))
    out = flash_attention_fwd(q, k, v, causal=True, block_q=block_q,
                              block_k=block_k, interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("S,H,hd,N,Q", [
    (64, 2, 16, 8, 16),
    (128, 4, 32, 16, 32),
    (96, 1, 64, 32, 32),   # S not a multiple of Q (pad path)... 96%32==0
    (80, 2, 16, 8, 32),    # pad path: 80 % 32 != 0
])
@pytest.mark.parametrize("dtype", [
    pytest.param(jnp.float32, marks=pytest.mark.slow), jnp.bfloat16])
def test_ssd_kernel_sweep(S, H, hd, N, Q, dtype):
    Bz = 2
    x = (jax.random.normal(KEY, (Bz, S, H, hd)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (Bz, S, H)))
    A = -jnp.exp(jnp.linspace(0.0, 1.0, H))
    Bm = (jax.random.normal(jax.random.fold_in(KEY, 2), (Bz, S, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(jax.random.fold_in(KEY, 3), (Bz, S, N)) * 0.3).astype(dtype)
    y_k, s_k = ssd_scan(x, dt, A, Bm, Cm, chunk=Q, force_kernel=True,
                        interpret=True)
    y_o, s_o = ssd_chunked(x, dt, A, Bm, Cm, chunk=Q)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(y_k.astype(jnp.float32),
                               y_o.astype(jnp.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(s_k, s_o, atol=tol, rtol=tol)


def test_flowhash_kernel_equals_ref():
    fields = jax.random.randint(KEY, (5000, 5), 0, 2**31 - 1).astype(jnp.uint32)
    hk = bulk_hash(fields, 7, force_kernel=True, interpret=True)
    hr = bulk_hash(fields, 7)
    assert (hk == hr).all()


def test_flowhash_force_kernel_off_tpu_needs_interpret():
    """Off the TPU the kernel runs only in the interpreter, and only
    when the caller asks for it: no silent switch."""
    if jax.default_backend() == "tpu":
        pytest.skip("the compiled kernel runs here")
    fields = jnp.zeros((8, 5), jnp.uint32)
    with pytest.raises(ValueError, match="interpret=True"):
        bulk_hash(fields, 7, force_kernel=True)
    with pytest.raises(ValueError, match="interpret=True"):
        bulk_hash_seeded(fields, jnp.zeros((8,), jnp.uint32),
                         force_kernel=True)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_flowhash_deterministic_and_seed_sensitive(seed):
    fields = jnp.arange(50, dtype=jnp.uint32).reshape(10, 5)
    h1 = bulk_hash(fields, seed)
    h2 = bulk_hash(fields, seed)
    assert (h1 == h2).all()
    h3 = bulk_hash(fields, seed ^ 0xDEADBEEF)
    assert not bool((h1 == h3).all())


def test_flowhash_uniformity():
    """Hash choices over n links approach uniform as flows grow (the
    statistical core of the paper's ECMP analysis)."""
    rng = np.random.default_rng(0)
    fields = jnp.asarray(rng.integers(0, 2**31, (200_000, 5)), jnp.uint32)
    ch = simulate_paper_paths(fields)
    _, fim_large = link_loads_fim(ch["uplink"], 16)
    _, fim_small = link_loads_fim(ch["uplink"][:256], 16)
    assert fim_large < 2.0       # ~uniform at 200k flows
    assert fim_small > 5.0       # visibly imbalanced at paper scale


def test_flowhash_seeded_kernel_equals_ref():
    fields = jax.random.randint(KEY, (5000, 5), 0, 2**31 - 1).astype(jnp.uint32)
    seeds = jax.random.randint(jax.random.fold_in(KEY, 9), (5000,),
                               0, 2**31 - 1).astype(jnp.uint32)
    hk = bulk_hash_seeded(fields, seeds, force_kernel=True, interpret=True)
    hr = bulk_hash_seeded(fields, seeds)
    assert (hk == hr).all()
    # a broadcast seed row degenerates to the scalar-seed entry point:
    # the seed-as-init convention is ONE definition, not two
    full = jnp.full((5000,), 7, jnp.uint32)
    assert (bulk_hash_seeded(fields, full) == bulk_hash(fields, 7)).all()


def test_flowhash_choice_distribution_pinned():
    """Hard-coded pre-unification values of ``simulate_paper_paths`` /
    ``bulk_hash``: the one-murmur-definition refactor (seed-as-init,
    shared with the engines' hash grids) must never drift the
    paper-testbed choice statistics by a single flow."""
    rng = np.random.default_rng(42)
    fields = jnp.asarray(rng.integers(0, 2**31, (4096, 5)), jnp.uint32)
    ch = simulate_paper_paths(fields)
    want = {
        "src_port": ([1, 1, 0, 1, 1, 0, 1, 0], 1958, [2138, 1958]),
        "uplink": ([14, 12, 13, 9, 2, 8, 1, 8], 30992,
                   [245, 268, 264, 235, 244, 247, 276, 258]),
        "spine_link": ([0, 1, 0, 3, 1, 0, 1, 1], 6196,
                       [1028, 992, 1024, 1052]),
        "dst_port": ([1, 1, 1, 1, 0, 1, 0, 1], 2086, [2010, 2086]),
    }
    for stage, (first8, total, counts) in want.items():
        got = np.asarray(ch[stage])
        assert got[:8].tolist() == first8, stage
        assert int(got.sum()) == total, stage
        assert np.bincount(got)[: len(counts)].tolist() == counts, stage
    h = np.asarray(bulk_hash(fields, 12345), np.uint64)
    assert h[:4].tolist() == [1282828036, 453300701, 462728589, 1920719609]
    assert int(h.sum()) == 8712584361707

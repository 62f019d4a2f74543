"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import MIPSCatalog, fm_interaction
from repro.kernels.ref import fm_interaction_ref


@pytest.mark.parametrize("m,r,k,block", [
    (256, 8, 1, 64), (512, 32, 10, 128), (1000, 64, 5, 256),
    (128, 128, 16, 128), (300, 17, 3, 64),
])
def test_topk_mips_shapes(m, r, k, block):
    rng = np.random.default_rng(m + r)
    T = rng.standard_normal((m, r)).astype(np.float32)
    cat = MIPSCatalog(T, block_m=block)
    u = rng.standard_normal(r).astype(np.float32)
    vals, ids, stats = cat.query(jnp.asarray(u), k)
    scores = T @ u
    ref = np.sort(scores)[::-1][:k]
    np.testing.assert_allclose(np.asarray(vals), ref, atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(scores[np.asarray(ids)], np.asarray(vals),
                               atol=1e-3, rtol=1e-4)


def test_topk_mips_prunes_decaying_catalogue():
    rng = np.random.default_rng(0)
    T = rng.standard_normal((4096, 16)).astype(np.float32)
    T *= (1.0 / (1.0 + np.arange(4096)))[:, None] ** 0.5
    cat = MIPSCatalog(T, block_m=128)
    u = rng.standard_normal(16).astype(np.float32)
    vals, ids, stats = cat.query(jnp.asarray(u), 5)
    assert int(stats[1]) < 4096 // 128          # visited < all blocks
    ref = np.sort(T @ u)[::-1][:5]
    np.testing.assert_allclose(np.asarray(vals), ref, atol=1e-4)


def test_topk_mips_two_level_bounds_skip_dma():
    """The scalar-prefetch pre-screen must cut DMA'd blocks (stats col 2)
    below the full block count on a decaying catalogue — and results plus
    scored/visited counts must match the runtime-only bound exactly."""
    rng = np.random.default_rng(9)
    T = rng.standard_normal((2048, 16)).astype(np.float32)
    T *= (1.0 / (1.0 + np.arange(2048)))[:, None].astype(np.float32) ** 0.7
    cat = MIPSCatalog(T, block_m=128, superblock=4)
    U = jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32))
    vals, ids, stats = cat.query_batch(U, 5)
    stats = np.asarray(stats)
    n_blocks = cat.n_blocks
    assert np.all(stats[:, 2] < n_blocks), "pre-screen skipped no DMA"
    assert np.all(stats[:, 1] <= stats[:, 2]), "scored more than loaded"
    ref = np.sort(np.asarray(U) @ T.T, axis=1)[:, ::-1][:, :5]
    np.testing.assert_allclose(np.asarray(vals), ref, atol=1e-3)
    # single-query path too
    u = jnp.asarray(np.asarray(U)[0])
    v1, i1, s1 = cat.query(u, 5)
    np.testing.assert_allclose(np.asarray(v1), ref[0], atol=1e-3)
    assert int(np.asarray(s1)[2]) < n_blocks


def test_topk_mips_flat_norms_stay_exact():
    """Constant-norm catalogue: the pre-screen can prune nothing (lb0
    equals every bound at best) — the two-level kernel must degrade to a
    full scan, not to a wrong answer."""
    rng = np.random.default_rng(10)
    T = rng.standard_normal((512, 8)).astype(np.float32)
    T /= np.linalg.norm(T, axis=1, keepdims=True)
    cat = MIPSCatalog(T, block_m=64, superblock=4)
    U = jnp.asarray(rng.standard_normal((3, 8)).astype(np.float32))
    vals, ids, stats = cat.query_batch(U, 5)
    ref = np.sort(np.asarray(U) @ T.T, axis=1)[:, ::-1][:, :5]
    np.testing.assert_allclose(np.asarray(vals), ref, atol=1e-3)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("b,f,d", [(16, 4, 8), (50, 39, 10), (128, 26, 16),
                                   (7, 2, 3)])
def test_fm_interaction_sweep(b, f, d, dtype):
    rng = np.random.default_rng(b + f + d)
    emb = (rng.standard_normal((b, f, d)) * 0.5).astype(dtype)
    out = fm_interaction(jnp.asarray(emb), block_b=16)
    ref = fm_interaction_ref(jnp.asarray(emb).astype(jnp.float32))
    tol = 1e-3 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)


def test_fm_interaction_matches_explicit_pairwise():
    rng = np.random.default_rng(2)
    emb = rng.standard_normal((4, 6, 5)).astype(np.float32)
    out = np.asarray(fm_interaction(jnp.asarray(emb), block_b=4))
    for b in range(4):
        explicit = sum(float(emb[b, i] @ emb[b, j])
                       for i in range(6) for j in range(i + 1, 6))
        assert abs(out[b] - explicit) < 1e-3

"""jit'd public wrappers around the Pallas kernels.

``topk_mips`` handles the catalogue preparation (norm ordering, padding,
per-block Cauchy-Schwarz bounds) and maps kernel-local indices back to
catalogue ids; kernels themselves stay shape-strict.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.naive import SCORE_PRECISION
from repro.kernels.fm_interaction import fm_interaction_pallas
from repro.kernels.topk_mips import (NEG_INF,
                                     topk_mips_pallas_batched_prefetch,
                                     topk_mips_pallas_prefetch)

Array = jnp.ndarray


class MIPSCatalog:
    """Norm-ordered, block-padded catalogue for the topk_mips kernels.

    Owns the TWO-LEVEL bound hierarchy (DESIGN.md §6): per-tile
    Cauchy-Schwarz bounds for the in-kernel runtime test, plus a
    superblock-granular pre-screen derived from an a-priori lower bound
    lb0 — the K-th best score of the first (largest-norm) superblock,
    computed with one cheap XLA matmul before the kernel launches. Blocks
    whose bound is already below lb0 are delivered to the kernel as
    scalar-prefetch skip instructions, so their HBM->VMEM DMA never
    happens. The pre-screen can only drop blocks the runtime test would
    drop anyway (lb0 is a true lower bound on the final K-th best), so
    results AND statistics match a runtime-only scan exactly.

    ``interpret=None`` (the default on both query paths) autodetects the
    Pallas execution mode: interpreter off-TPU, compiled on TPU (where
    the compiler refuses the kernels, ROADMAP A2).

    Args:
      T: ``[M, R]`` catalogue.
      block_m: tile rows (the runtime bound-test granularity).
      superblock: tiles per superblock — the pre-screen/DMA granularity
        and the batched kernel's multi-tile grid-step size (clamped to the
        tile count of small catalogues).
    """

    def __init__(self, T, block_m: int = 256, superblock: int = 8):
        T = np.asarray(T, np.float32)
        M, R = T.shape
        norms = np.linalg.norm(T, axis=1)
        order = np.argsort(-norms, kind="stable")
        self.superblock = int(max(1, min(superblock, -(-M // block_m))))
        span = block_m * self.superblock
        M_pad = -(-M // span) * span
        T_sorted = np.zeros((M_pad, R), np.float32)
        T_sorted[:M] = T[order]
        self.block_m = block_m
        self.num_real = M
        self.n_blocks = M_pad // block_m
        self.n_super = M_pad // span
        self.order = jnp.asarray(order.astype(np.int32))
        self.T_sorted = jnp.asarray(T_sorted)
        # max norm per block/superblock = norm of its first row (sorted)
        norms_pad = np.pad(norms[order], (0, M_pad - M))
        self.block_max_norm = jnp.asarray(norms_pad[::block_m].copy())
        self.super_max_norm = jnp.asarray(norms_pad[::span].copy())
        # head slab (the first superblock) that seeds lb0
        self.head_rows = min(span, M_pad)
        self._head = self.T_sorted[:self.head_rows]
        self._head_valid = jnp.arange(self.head_rows) < self.num_real

    def _to_catalogue_ids(self, local_idx: Array) -> Array:
        return jnp.where(
            local_idx >= 0,
            self.order[jnp.clip(local_idx, 0, self.num_real - 1)],
            -1)

    def _lower_bound0(self, U: Array, k: int) -> Array:
        """A-priori per-query lower bound on the final K-th best score.

        The K-th best of the head superblock's REAL rows — fully scored,
        so a certificate, not an estimate. Returns -inf (prescreen off,
        still exact) when the head holds fewer than k real rows.
        """
        hs = jnp.where(self._head_valid[None, :],
                       jnp.matmul(U, self._head.T,
                                  precision=SCORE_PRECISION), NEG_INF)
        kk = min(k, self.head_rows)
        lb0 = jax.lax.top_k(hs, kk)[0][:, kk - 1]
        if kk < k or self.num_real < k:
            lb0 = jnp.full_like(lb0, NEG_INF)
        return lb0

    def query(self, u: Array, k: int, interpret=None):
        """Exact top-K. Returns (values, catalogue ids, stats [3])."""
        u = jnp.asarray(u, jnp.float32)
        bounds = jnp.linalg.norm(u) * self.block_max_norm
        lb0 = self._lower_bound0(u[None, :], k)[0]
        steps = jnp.arange(self.n_blocks, dtype=jnp.int32)
        # head tiles stay live: lb0's witnesses must reach the merge
        live = jnp.logical_or(bounds > lb0, steps < self.superblock)
        n_live = jnp.sum(live.astype(jnp.int32))      # live is a prefix
        tile_idx = jnp.minimum(steps, n_live - 1)
        vals, local_idx, stats = topk_mips_pallas_prefetch(
            self.T_sorted, bounds, tile_idx, live.astype(jnp.int32), u, k,
            self.block_m, interpret=interpret, num_real=self.num_real)
        return vals, self._to_catalogue_ids(local_idx), stats

    def query_batch(self, U: Array, k: int, interpret=None):
        """Exact top-K for a query batch ``U: [B, R]`` in ONE kernel launch.

        Returns (values [B, k], catalogue ids [B, k], stats [B, 3]).
        """
        U = jnp.atleast_2d(jnp.asarray(U, jnp.float32))
        u_norm = jnp.linalg.norm(U, axis=1)
        bounds = u_norm[:, None] * self.block_max_norm[None, :]
        lb0 = self._lower_bound0(U, k)
        super_bounds = u_norm[:, None] * self.super_max_norm[None, :]
        live = (super_bounds > lb0[:, None]).at[:, 0].set(True)
        n_live = jnp.sum(live.astype(jnp.int32), axis=1)  # prefix length
        steps = jnp.arange(self.n_super, dtype=jnp.int32)[None, :]
        sb_idx = jnp.minimum(steps, n_live[:, None] - 1)
        tile_bounds = bounds.reshape(U.shape[0], self.n_super,
                                     self.superblock)
        vals, local_idx, stats = topk_mips_pallas_batched_prefetch(
            self.T_sorted, tile_bounds, sb_idx,
            (steps < n_live[:, None]).astype(jnp.int32), U, k,
            block_m=self.block_m, tiles_per_step=self.superblock,
            interpret=interpret, num_real=self.num_real)
        return vals, self._to_catalogue_ids(local_idx), stats


def fm_interaction(emb: Array, block_b: int = 64,
                   interpret: bool = True) -> Array:
    """Fused FM sum-square interaction. emb: [B, F, d] -> [B]."""
    B = emb.shape[0]
    pad = (-B) % block_b
    if pad:
        emb = jnp.pad(emb, ((0, pad), (0, 0), (0, 0)))
    out = fm_interaction_pallas(emb, block_b, interpret)
    return out[:B]

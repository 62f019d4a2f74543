"""Pallas TPU kernel: threshold-pruned blocked MIPS top-K.

The hardware form of the paper's pruning idea (DESIGN.md §4, §6): the
catalogue is stored in DECREASING-NORM order so that a whole VMEM tile of
candidates can be skipped with one Cauchy-Schwarz bound test

    max possible score in block b  <=  ||u|| * max_norm(block b)  <=  lowerBound

TPU mapping:
  * grid = (n_blocks,); TPU grid steps run sequentially on a core, so the
    running top-K lives in VMEM scratch and carries across blocks,
  * the tile load (block_m x R) is a contiguous HBM->VMEM DMA declared by
    BlockSpec (the norm ordering is what makes it contiguous — the paper's
    per-dimension lists would gather scattered rows),
  * scoring is one (block_m x R) @ (R x 1) MXU matvec per tile,
  * the merge is lax.top_k over K + block_m lanes,
  * the bound test is @pl.when on a scalar — a skipped block costs only
    its (prefetched) DMA, no MXU work.

**Two-level bound hierarchy**: the runtime ``@pl.when`` test above can
only skip MXU work — by the time the bound is known false, the BlockSpec
pipeline has already issued the tile's HBM->VMEM DMA. The kernels add a
second, coarser level: the
caller derives an a-priori lower bound lb0 (top-K of the first,
largest-norm superblock, one cheap XLA matmul) and pre-screens blocks
whose Cauchy-Schwarz bound is already below lb0. The surviving scan
prefix is delivered via SCALAR PREFETCH — ``tile_idx[i]`` names the tile
grid step ``i`` should map, and pre-pruned steps repeat the last live
tile, so the pipeline sees an unchanged block index and issues NO DMA at
all. Because the catalogue is norm-sorted, pre-pruned blocks form a
suffix, and every pre-pruned block would also have been runtime-pruned
(its bound <= lb0 <= the running lower bound), so ``n_scored`` /
``blocks_visited`` statistics equal a runtime-only scan's.

The batched variant adds the query dimension to the grid —
``grid = (B, n_steps)`` with steps innermost, so each query's scan is
still sequential (the scratch top-K resets at step 0 of every query) and
the whole batch is one kernel launch. Its grid steps are MULTI-TILE: one
step DMAs a whole superblock (``tiles_per_step * block_m`` rows) and the
kernel body walks the resident tiles with per-tile runtime bound tests,
keeping statistics tile-granular while amortising grid and DMA overhead.

Exactness: identical guarantee as core.blocked.norm_pruned_topk (blocks
are visited in decreasing max-norm order; once the K-th best exceeds the
bound no later block can contribute; lb0 is a true lower bound because it
is the K-th best of real, fully scored rows). Rows past ``num_real`` are
zero padding added by the catalogue wrapper; their scores are masked to
-inf so a pad row can never displace a real (possibly negative) score
from the top-K.

Stats layout (both kernels): ``(rows_scored, blocks_visited,
blocks_dma)`` — the third column is what the two-level hierarchy saves.

``interpret=None`` autodetects: interpret mode off TPU (CPU CI runs the
kernel bodies in the Pallas interpreter), compiled on TPU. The TPU
compiler refuses both kernels as written (ROADMAP A2): the bounds and
output blocks are not (8, 128)-aligned, and Mosaic has no lowering for
the ``lax.top_k`` in :func:`_merge_block`. The ``pallas`` engine
therefore refuses a TPU backend instead of compiling them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def resolve_interpret(interpret):
    """None -> interpret everywhere except on real TPU backends."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _merge_block(scores, block_start, scratch_vals, scratch_idx,
                 *, k: int, block_m: int, num_real: int):
    ids = block_start + jax.lax.iota(jnp.int32, block_m)
    scores = jnp.where(ids < num_real, scores, NEG_INF)  # mask zero padding
    # two-stage (DESIGN.md §6): top_k over the BARE block, then a 2K-lane
    # fold with the carry — top_k over the K+C concatenation falls off
    # the fast path on CPU (interpret mode) and wastes lanes on TPU
    kk = min(k, block_m)
    bv, bpos = jax.lax.top_k(scores, kk)
    bi = jnp.take(ids, bpos)
    if kk < k:
        bv = jnp.concatenate([bv, jnp.full((k - kk,), NEG_INF, bv.dtype)])
        bi = jnp.concatenate([bi, jnp.full((k - kk,), -1, bi.dtype)])
    cand_vals = jnp.concatenate([scratch_vals[...], bv])
    cand_idx = jnp.concatenate([scratch_idx[...], bi])
    top, pos = jax.lax.top_k(cand_vals, k)
    scratch_vals[...] = top
    scratch_idx[...] = jnp.take(cand_idx, pos)


# ---------------------------------------------------------------------------
# Two-level kernels: scalar-prefetched pre-screen skips the DMA itself
# ---------------------------------------------------------------------------


def _kernel_prefetch(tile_idx_ref, live_ref, bound_ref, t_ref, u_ref,
                     vals_ref, idx_ref, stats_ref, scratch_vals, scratch_idx,
                     *, k: int, block_m: int, num_real: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        scratch_vals[...] = jnp.full_like(scratch_vals, NEG_INF)
        scratch_idx[...] = jnp.full_like(scratch_idx, -1)
        stats_ref[...] = jnp.zeros_like(stats_ref)

    lb = scratch_vals[k - 1]
    bound = bound_ref[0]
    live = live_ref[i] > 0      # pre-screen survivor: its tile is resident

    @pl.when(jnp.logical_and(live, bound > lb))
    def _score():
        tile = t_ref[...]                                  # [block_m, R]
        u = u_ref[...]                                     # [R, 1]
        scores = jnp.dot(tile, u,
                         preferred_element_type=jnp.float32)[:, 0]
        # live steps map tile i (prefix property), so ids start at i*block_m
        _merge_block(scores, i * block_m, scratch_vals, scratch_idx,
                     k=k, block_m=block_m, num_real=num_real)
        stats_ref[0] += block_m
        stats_ref[1] += 1

    @pl.when(live)
    def _dma():
        stats_ref[2] += 1       # pre-pruned steps re-map the resident tile

    vals_ref[...] = scratch_vals[...]
    idx_ref[...] = scratch_idx[...]


def topk_mips_pallas_prefetch(T_sorted, block_bounds, tile_idx, live, u,
                              k: int, block_m: int = 256, interpret=None,
                              num_real: int = -1):
    """Two-level single-query kernel (DESIGN.md §6).

    tile_idx: [n_blocks] int32 — the tile grid step ``i`` maps; pre-pruned
    steps repeat the last live tile so the BlockSpec pipeline issues no
    DMA for them. live: [n_blocks] int32 — 1 where the pre-screen kept the
    step. Both are SCALAR-PREFETCH operands: they are resident before the
    pipeline starts, which is what lets the index map depend on them.
    ``block_bounds``: [n_blocks] = ||u|| * max norm per block; ``u``: [R].
    Returns (values [k], local indices [k], stats [3]); ``num_real``
    marks the tail of zero-padded rows (default: no padding).
    """
    M, R = T_sorted.shape
    assert M % block_m == 0, (M, block_m)
    n_blocks = M // block_m
    num_real = M if num_real < 0 else num_real
    kernel = functools.partial(_kernel_prefetch, k=k, block_m=block_m,
                               num_real=num_real)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1,), lambda i, ti, lv: (i,)),            # bound
            pl.BlockSpec((block_m, R), lambda i, ti, lv: (ti[i], 0)),
            pl.BlockSpec((R, 1), lambda i, ti, lv: (0, 0)),        # u
        ],
        out_specs=[
            pl.BlockSpec((k,), lambda i, ti, lv: (0,)),
            pl.BlockSpec((k,), lambda i, ti, lv: (0,)),
            pl.BlockSpec((3,), lambda i, ti, lv: (0,)),
        ],
        scratch_shapes=[
            pltpu.VMEM((k,), jnp.float32),
            pltpu.VMEM((k,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((k,), jnp.float32),
            jax.ShapeDtypeStruct((k,), jnp.int32),
            jax.ShapeDtypeStruct((3,), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(tile_idx, live, block_bounds, T_sorted, u[:, None])


def _kernel_batched_prefetch(sb_idx_ref, live_ref, bound_ref, t_ref, u_ref,
                             vals_ref, idx_ref, stats_ref, scratch_vals,
                             scratch_idx, *, k: int, block_m: int,
                             tiles: int, num_real: int):
    b = pl.program_id(0)
    s = pl.program_id(1)  # superblock step — innermost, sequential per query

    @pl.when(s == 0)
    def _init():
        scratch_vals[...] = jnp.full_like(scratch_vals, NEG_INF)
        scratch_idx[...] = jnp.full_like(scratch_idx, -1)
        stats_ref[...] = jnp.zeros_like(stats_ref)

    @pl.when(live_ref[b, s] > 0)
    def _step():
        # live ⇒ the resident superblock IS s (prefix property); walk its
        # tiles with per-tile runtime bound tests so statistics stay
        # tile-granular even though the DMA was superblock-granular.
        u = u_ref[0]                                       # [R, 1]
        for t in range(tiles):
            lb = scratch_vals[k - 1]
            bnd = bound_ref[0, 0, t]

            @pl.when(bnd > lb)
            def _score(t=t):
                tile = t_ref[t * block_m:(t + 1) * block_m, :]
                scores = jnp.dot(tile, u,
                                 preferred_element_type=jnp.float32)[:, 0]
                _merge_block(scores, (s * tiles + t) * block_m,
                             scratch_vals, scratch_idx,
                             k=k, block_m=block_m, num_real=num_real)
                stats_ref[0, 0] += block_m
                stats_ref[0, 1] += 1

        stats_ref[0, 2] += tiles

    vals_ref[0, :] = scratch_vals[...]
    idx_ref[0, :] = scratch_idx[...]


def topk_mips_pallas_batched_prefetch(T_sorted, tile_bounds, sb_idx, live,
                                      U, k: int, block_m: int = 256,
                                      tiles_per_step: int = 8,
                                      interpret=None, num_real: int = -1):
    """Two-level batched kernel with multi-tile grid steps.

    T_sorted: [M, R] decreasing-norm order, M % (block_m * tiles_per_step)
    == 0; tile_bounds: [B, n_steps, tiles_per_step] per-tile
    Cauchy-Schwarz bounds; sb_idx / live: [B, n_steps] int32 scalar-
    prefetch operands — the superblock each step maps (pre-pruned steps
    repeat the last live superblock: no DMA) and the pre-screen survivor
    mask. U: [B, R].

    Returns (values [B, k], local indices [B, k], stats [B, 3]).
    """
    M, R = T_sorted.shape
    B = U.shape[0]
    span = block_m * tiles_per_step
    assert M % span == 0, (M, span)
    n_steps = M // span
    assert tile_bounds.shape == (B, n_steps, tiles_per_step), \
        tile_bounds.shape
    assert sb_idx.shape == (B, n_steps) and live.shape == (B, n_steps)
    num_real = M if num_real < 0 else num_real
    kernel = functools.partial(_kernel_batched_prefetch, k=k,
                               block_m=block_m, tiles=tiles_per_step,
                               num_real=num_real)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_steps),
        in_specs=[
            pl.BlockSpec((1, 1, tiles_per_step),
                         lambda b, s, si, lv: (b, s, 0)),          # bounds
            pl.BlockSpec((span, R),
                         lambda b, s, si, lv: (si[b, s], 0)),      # supertile
            pl.BlockSpec((1, R, 1), lambda b, s, si, lv: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda b, s, si, lv: (b, 0)),
            pl.BlockSpec((1, k), lambda b, s, si, lv: (b, 0)),
            pl.BlockSpec((1, 3), lambda b, s, si, lv: (b, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((k,), jnp.float32),
            pltpu.VMEM((k,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
            jax.ShapeDtypeStruct((B, 3), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(sb_idx, live, tile_bounds, T_sorted, U[:, :, None])

"""Distributed exact top-K over a sharded catalogue (pod-scale serving).

The catalogue ``T`` is row-sharded over one or more mesh axes (DESIGN.md §5).
Four exact strategies, all returning the identical set as the unsharded
algorithms (global top-K is always contained in the union of per-shard
top-Ks):

1. ``sharded_naive_topk`` — per-shard matmul + local ``lax.top_k(K)``,
   then all-gather of ``P*K`` (value, global-id) candidates and a final
   merge. Wire bytes: ``P*K*8`` instead of ``M*4`` — the communication-
   optimal exact merge.

2. ``sharded_blocked_topk`` — per-shard BTA with **cross-shard threshold
   tightening**: after every block, the per-shard lower bounds are
   ``pmax``-combined so each shard prunes against the *global* K-th best,
   not its local one. Shards therefore stop as soon as the globally-found
   top-K certifies their remaining blocks irrelevant. This is the paper's
   "parallel extensions can be easily implemented" remark made concrete
   for a TPU mesh.

3. ``hierarchical_merge`` — tree merge over multiple mesh axes (pod, data)
   so the cross-DCI hop only ever carries ``K`` candidates per pod.

4. ``sharded_norm_topk`` — the shared-tile batched norm scan
   (DESIGN.md §6) run per shard over a round-robin-dealt norm layout
   (:class:`repro.core.layout.ShardedNormLayout`), with cross-shard
   ``pmax`` threshold tightening after every block: each shard prunes
   against the GLOBAL K-th best, so all shards stop as soon as the
   globally-found top-K certifies their remaining norm blocks
   irrelevant. Backs the ``norm_sharded`` registry engine.

All functions are written with ``jax.shard_map`` (replication checking
off: every function all-gathers before returning, so outputs are
replicated by construction) and are used by the serving layer
(`repro.serving`) and the retrieval_cand dry-run cells.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.driver import (_dedup_first_occurrence,
                               merge_block_into_carry_batched)
from repro.core.naive import SCORE_PRECISION, TopKResult

Array = jnp.ndarray
NEG_INF = float("-inf")


def shard_fold_topk(carry_vals: Array, carry_ids: Array,
                    scores: Array, gids: Array, k: int):
    """Two-level exact merge of shard-major stacked score blocks — the
    mesh-free counterpart of :func:`hierarchical_merge_topk`, used inside
    a host-side scan loop (the LSM catalogue's L1 tier, DESIGN.md §15).

    ``scores [S, B, C]`` are one dense block per shard over the SAME
    query batch; ``gids [S, C]`` (or per-lane ``[S, B, C]``) carry
    global ids with ``-1`` marking dead/padding lanes (already masked to
    ``-inf`` in ``scores`` by the caller). Level 1 cuts each shard's
    block to ``K`` candidates (the block-local ``top_k`` inside
    :func:`repro.core.driver.merge_block_into_carry_batched`); level 2
    folds the per-shard candidate lists through the O(K) sorted merge —
    so only ``K`` candidates per shard ever cross the merge boundary,
    the same communication shape the mesh version's all-gather carries.
    Exact for the same reason as every sharded strategy here: the global
    top-K is contained in the union of per-shard top-Ks.
    """
    for s in range(scores.shape[0]):
        carry_vals, carry_ids = merge_block_into_carry_batched(
            carry_vals, carry_ids, scores[s], gids[s], k)
    return carry_vals, carry_ids


def _axis_size(axis_names: Sequence[str]) -> Array:
    size = 1
    for a in axis_names:
        size = size * jax.lax.axis_size(a)
    return size


def _axis_index(axis_names: Sequence[str]) -> Array:
    """Linearised index over (possibly multiple) mesh axes."""
    idx = jnp.int32(0)
    for a in axis_names:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def sharded_naive_topk(mesh, T_spec: P, axis_names: Sequence[str]):
    """Build a jit-able exact sharded top-K: ``f(T, U, k) -> TopKResult``.

    Args:
      mesh: the device mesh.
      T_spec: PartitionSpec of the catalogue, rows sharded over
        ``axis_names`` (e.g. ``P(('data',), None)``).
      axis_names: mesh axes the catalogue rows are split over.
    """
    axis_names = tuple(axis_names)

    def fn(T: Array, U: Array, k: int) -> TopKResult:
        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(T_spec, P()),
            out_specs=(P(), P(), P(), P()),
        )
        def _local(T_local, U_rep):
            m_local = T_local.shape[0]
            shard = _axis_index(axis_names)
            scores = jnp.einsum("br,mr->bm", U_rep, T_local,
                                preferred_element_type=jnp.float32,
                                precision=SCORE_PRECISION)
            vals, idx = jax.lax.top_k(scores, min(k, m_local))
            gidx = idx + shard * m_local
            # all-gather K candidates per shard over every sharded axis
            for a in axis_names:
                vals = jax.lax.all_gather(vals, a, axis=1, tiled=True)
                gidx = jax.lax.all_gather(gidx, a, axis=1, tiled=True)
            fvals, fpos = jax.lax.top_k(vals, k)
            fidx = jnp.take_along_axis(gidx, fpos, axis=1)
            b = U_rep.shape[0]
            n = jnp.full((b,), T_local.shape[0], jnp.int32) * _axis_size(axis_names)
            return fvals, fidx, n, jnp.zeros((b,), jnp.int32)

        return TopKResult(*_local(T, U))

    return fn


def sharded_blocked_topk(mesh, specs, axis_names: Sequence[str]):
    """Sharded BTA with cross-shard threshold tightening.

    ``specs``: PartitionSpecs for ``(T, order_desc, t_sorted_desc)`` —
    the index arrays are sharded along their item axis (axis=1) with the
    same layout as T's rows.

    Per-shard ids are *local*; the final merge converts to global ids.
    All shards iterate in lockstep (the while_loop condition is a
    collective ``any shard still active``), so the collectives inside the
    body stay congruent.
    """
    axis_names = tuple(axis_names)
    T_spec, order_spec, tsorted_spec = specs

    def fn(T, order_desc, t_sorted_desc, U, k: int, block_size: int = 512):
        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(T_spec, order_spec, tsorted_spec, P()),
            out_specs=(P(), P(), P(), P()),
        )
        def _local(T_l, order_l, tsort_l, U_rep):
            m_local, r = T_l.shape
            bq = U_rep.shape[0]
            kk = min(k, m_local)
            n_blocks = -(-m_local // block_size)
            shard = _axis_index(axis_names)
            neg = U_rep < 0  # [B, R]

            def one_query_init():
                return (
                    jnp.full((bq, kk), NEG_INF, T_l.dtype),
                    jnp.full((bq, kk), -1, jnp.int32),
                    jnp.zeros((bq, m_local), bool),
                    jnp.zeros((bq,), jnp.int32),
                    jnp.full((bq,), NEG_INF, T_l.dtype),   # global lower
                    jnp.full((bq,), jnp.inf, T_l.dtype),   # local upper
                )

            def cond(state):
                b, *_ , active = state
                return active

            def body(state):
                b, vals, ids_k, visited, n_scored, lower, upper, _ = state
                d0 = b * block_size
                cols = jnp.minimum(d0 + jnp.arange(block_size, dtype=jnp.int32),
                                   m_local - 1)

                def per_query(u_q, neg_q, vals_q, ids_q, vis_q, ns_q):
                    cols_eff = jnp.where(neg_q[:, None],
                                         m_local - 1 - cols[None, :],
                                         cols[None, :])
                    cand = jnp.take_along_axis(order_l, cols_eff, axis=1).reshape(-1)
                    fresh = jnp.logical_and(
                        _dedup_first_occurrence(cand, m_local), ~vis_q[cand])
                    scores = jnp.where(
                        fresh, jnp.matmul(T_l[cand], u_q,
                                          precision=SCORE_PRECISION),
                        NEG_INF)
                    mv, pos = jax.lax.top_k(
                        jnp.concatenate([vals_q, scores]), kk)
                    mi = jnp.concatenate([ids_q, cand])[pos]
                    end = jnp.minimum(d0 + block_size - 1, m_local - 1)
                    end_eff = jnp.where(neg_q, m_local - 1 - end, end)
                    t_end = tsort_l[jnp.arange(r), end_eff]
                    ub = jnp.sum(u_q * t_end)
                    return (mv, mi, vis_q.at[cand].set(True),
                            ns_q + jnp.sum(fresh).astype(jnp.int32), ub)

                vals, ids_k, visited, n_scored, upper = jax.vmap(per_query)(
                    U_rep, neg, vals, ids_k, visited, n_scored)
                # cross-shard threshold tightening: global K-th best
                local_kth = vals[:, kk - 1]
                lower = local_kth
                for a in axis_names:
                    # the true global K-th best is >= the max of local K-th
                    # bests, which is a valid (conservative) global lower
                    # bound for pruning.
                    lower = jax.lax.pmax(lower, a)
                shard_active = jnp.logical_and(b + 1 < n_blocks,
                                               jnp.any(lower < upper))
                any_active = shard_active
                for a in axis_names:
                    any_active = jax.lax.pmax(any_active, a)
                return (b + 1, vals, ids_k, visited, n_scored, lower, upper,
                        any_active)

            vals0, ids0, vis0, ns0, low0, up0 = one_query_init()
            state = (jnp.int32(0), vals0, ids0, vis0, ns0, low0, up0,
                     jnp.asarray(True))
            b, vals, ids_k, _, n_scored, _, _, _ = jax.lax.while_loop(
                cond, body, state)
            gids = jnp.where(ids_k >= 0, ids_k + shard * m_local, -1)
            for a in axis_names:
                vals = jax.lax.all_gather(vals, a, axis=1, tiled=True)
                gids = jax.lax.all_gather(gids, a, axis=1, tiled=True)
                n_scored = jax.lax.psum(n_scored, a)
            fvals, fpos = jax.lax.top_k(vals, k)
            fidx = jnp.take_along_axis(gids, fpos, axis=1)
            return fvals, fidx, n_scored, jnp.broadcast_to(b * block_size,
                                                           n_scored.shape)

        return TopKResult(*_local(T, order_desc, t_sorted_desc, U))

    return fn


def hierarchical_merge_topk(mesh, T_spec: P, inner_axes: Sequence[str],
                            outer_axes: Sequence[str]):
    """Two-level exact merge: all-gather K inside the pod (ICI), then only
    K candidates per pod cross the DCI (``outer_axes``). Communication-
    optimal for multi-pod serving."""
    inner_axes, outer_axes = tuple(inner_axes), tuple(outer_axes)
    all_axes = outer_axes + inner_axes

    def fn(T: Array, U: Array, k: int) -> TopKResult:
        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(T_spec, P()),
            out_specs=(P(), P(), P(), P()),
        )
        def _local(T_local, U_rep):
            m_local = T_local.shape[0]
            shard = _axis_index(all_axes)
            scores = jnp.einsum("br,mr->bm", U_rep, T_local,
                                preferred_element_type=jnp.float32,
                                precision=SCORE_PRECISION)
            vals, idx = jax.lax.top_k(scores, min(k, m_local))
            gidx = idx + shard * m_local
            # level 1: merge within the pod (fast ICI)
            for a in inner_axes:
                vals = jax.lax.all_gather(vals, a, axis=1, tiled=True)
                gidx = jax.lax.all_gather(gidx, a, axis=1, tiled=True)
            vals, pos = jax.lax.top_k(vals, k)
            gidx = jnp.take_along_axis(gidx, pos, axis=1)
            # level 2: only K cross the DCI per pod
            for a in outer_axes:
                vals = jax.lax.all_gather(vals, a, axis=1, tiled=True)
                gidx = jax.lax.all_gather(gidx, a, axis=1, tiled=True)
            fvals, fpos = jax.lax.top_k(vals, k)
            fidx = jnp.take_along_axis(gidx, fpos, axis=1)
            b = U_rep.shape[0]
            n = jnp.full((b,), m_local, jnp.int32) * _axis_size(all_axes)
            return fvals, fidx, n, jnp.zeros((b,), jnp.int32)

        return TopKResult(*_local(T, U))

    return fn


def sharded_norm_topk(mesh, axis_names: Sequence[str]):
    """Sharded shared-tile norm scan with cross-shard threshold tightening.

    Builder for the ``norm_sharded`` engine: returns
    ``f(T_sh, norms_sh, ids_sh, U, k, block_size, max_blocks)`` operating
    on a :class:`repro.core.layout.ShardedNormLayout`'s arrays (shard-major
    slabs of the round-robin-dealt norm order; rows with id -1 are
    padding). Per shard the loop is exactly the batched-native norm scan
    (one contiguous ``[block, R]`` tile + one ``[B, R] @ [R, block]``
    matmul per step for the whole batch, DESIGN.md §6); after every block
    the per-shard K-th-best lower bounds are ``pmax``-combined so each
    shard prunes against the GLOBAL K-th best. Because the deal is
    strided, every shard's local norm spectrum mirrors the global one and
    all shards certify at nearly the same block depth — the lockstep
    collective loop wastes almost nothing.

    Exactness: an item not yet enumerated on shard s is bounded by
    ``||u|| * next_local_norm(s) <= global lower bound`` at that shard's
    stop, so it cannot enter the global top-K; the final merge
    all-gathers only ``P * K`` candidates (values + GLOBAL catalogue
    ids), never rows.
    """
    axis_names = tuple(axis_names)

    def fn(T_sh: Array, norms_sh: Array, ids_sh: Array, U: Array, k: int,
           block_size: int = 256, max_blocks: int = -1) -> TopKResult:
        @functools.partial(
            jax.shard_map, mesh=mesh, check_vma=False,
            in_specs=(P(axis_names, None), P(axis_names), P(axis_names),
                      P()),
            out_specs=(P(), P(), P(), P()),
        )
        def _local(T_l, norms_l, ids_l, U_rep):
            m_local, r = T_l.shape
            B = U_rep.shape[0]
            kk = min(k, m_local)
            blk = min(block_size, m_local)
            n_steps = -(-m_local // blk)
            cap = n_steps if max_blocks < 0 else min(max_blocks, n_steps)
            # pad rows (id -1: slab equalisation and the engine layer's
            # M-bucket padding, DESIGN.md §10) are a slab SUFFIX — cap
            # the loop at the real rows so a worst-case (never-certified)
            # query still stops where the unpadded scan would
            n_real_l = jnp.sum((ids_l >= 0).astype(jnp.int32))
            cap_rt = jnp.minimum(jnp.int32(cap), -(-n_real_l // blk))
            # the loop body contains collectives, so every shard must
            # enter it the same number of times: the INITIAL active flag
            # is pmax-combined (an all-padding shard — M_real < n_shards
            # — iterates with live all-False instead of skipping a loop
            # its peers are running collectives inside)
            active0 = cap_rt > 0
            for a in axis_names:
                active0 = jax.lax.pmax(active0, a)
            u_norms = jnp.linalg.norm(U_rep, axis=1)          # [B]
            next_starts = jnp.minimum(
                (jnp.arange(n_steps, dtype=jnp.int32) + 1) * blk,
                m_local - 1)
            bound_norms = norms_l[next_starts]                # [n_steps]
            offs = jnp.arange(blk, dtype=jnp.int32)
            neg_inf = jnp.asarray(NEG_INF, T_l.dtype)

            def cond(s):
                return s[-1]

            def body(s):
                step, tv, ti, ns, dp, lower, upper, _ = s
                # per-query liveness, gated on THIS shard's real-row cap:
                # the collective lockstep loop keeps running while any
                # shard is active, and a capped-out shard must not keep
                # accumulating depth over its pad suffix
                live = jnp.logical_and(lower < upper, step < cap_rt)  # [B]
                d0 = step * blk
                start = jnp.maximum(0, jnp.minimum(d0, m_local - blk))
                tile = jax.lax.dynamic_slice_in_dim(T_l, start, blk)
                scores = jnp.matmul(U_rep, tile.T,
                                    precision=SCORE_PRECISION)  # [B, blk]
                rows = start + offs
                # tail block slides back (mask re-reads) + padding rows
                valid = jnp.logical_and(rows >= d0, ids_l[rows] >= 0)
                masked = jnp.where(valid[None, :], scores, neg_inf)
                nv, ni = merge_block_into_carry_batched(
                    tv, ti, masked, rows, kk)
                gate = live[:, None]
                tv = jnp.where(gate, nv, tv)
                ti = jnp.where(gate, ni, ti)
                ns = jnp.where(live,
                               ns + jnp.sum(valid).astype(jnp.int32), ns)
                dp = jnp.where(live, dp + 1, dp)
                upper = jnp.where(live, u_norms * bound_norms[step], upper)
                # cross-shard tightening: the global K-th best >= the max
                # of local K-th bests — a valid (conservative) global
                # lower bound for every shard's pruning test
                local_kth = tv[:, kk - 1]
                glob = local_kth
                for a in axis_names:
                    glob = jax.lax.pmax(glob, a)
                lower = jnp.maximum(lower, glob)
                shard_active = jnp.logical_and(step + 1 < cap_rt,
                                               jnp.any(lower < upper))
                any_active = shard_active
                for a in axis_names:
                    any_active = jax.lax.pmax(any_active, a)
                return (step + 1, tv, ti, ns, dp, lower, upper, any_active)

            state = (jnp.int32(0),
                     jnp.full((B, kk), NEG_INF, T_l.dtype),
                     jnp.full((B, kk), -1, jnp.int32),
                     jnp.zeros((B,), jnp.int32),
                     jnp.zeros((B,), jnp.int32),
                     jnp.full((B,), NEG_INF, T_l.dtype),
                     jnp.full((B,), jnp.inf, T_l.dtype),
                     active0)
            _, tv, ti, ns, dp, _, _, _ = jax.lax.while_loop(cond, body,
                                                            state)
            # local rows -> GLOBAL catalogue ids, then the P*K merge
            gids = jnp.where(ti >= 0,
                             ids_l[jnp.clip(ti, 0, m_local - 1)], -1)
            vals = tv
            for a in axis_names:
                vals = jax.lax.all_gather(vals, a, axis=1, tiled=True)
                gids = jax.lax.all_gather(gids, a, axis=1, tiled=True)
                ns = jax.lax.psum(ns, a)
                dp = jax.lax.psum(dp, a)
            width = vals.shape[1]
            if width < k:
                vals = jnp.concatenate(
                    [vals, jnp.full((B, k - width), NEG_INF, vals.dtype)], 1)
                gids = jnp.concatenate(
                    [gids, jnp.full((B, k - width), -1, gids.dtype)], 1)
            fvals, fpos = jax.lax.top_k(vals, k)
            fidx = jnp.take_along_axis(gids, fpos, axis=1)
            return fvals, fidx, ns, dp * blk

        return TopKResult(*_local(T_sh, norms_sh, ids_sh, U))

    return fn

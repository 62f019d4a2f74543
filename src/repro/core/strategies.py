"""The built-in scan strategies (DESIGN.md §2).

Each constructor closes over the catalogue index/layout arrays and one
query and returns a :class:`repro.core.driver.ScanStrategy` for
:func:`repro.core.driver.pruned_block_scan`:

* :func:`ta_round_strategy` — the paper's Algorithm 2 round structure
  (one list depth per step). Negative query weights are resolved by
  INDEX ARITHMETIC (depth d of list r reads column ``M-1-d`` when
  ``u_r < 0``), never by materialising flipped ``[R, M]`` copies.
* :func:`blocked_lists_strategy` — the Block Threshold Algorithm: a depth
  block of ``B`` entries from all R lists per step, with the sign flip
  applied on the gather side (``block_size=1`` recovers TA rounds exactly,
  id-for-id and bound-for-bound).
* :func:`list_prefix_strategy` — the same enumeration over the
  contiguous :class:`repro.core.layout.ListMajorLayout` prefix: scoring
  is a ``[R, B, R]`` slice + matmul (no row gathers), candidate ids are
  slices of the walk-order id tables, and freshness comes from one
  O(R*P) per-query scatter instead of the O(R*M) key precompute. Covers
  depths ``< prefix_depth``; a scan that outlives the prefix chains into
  a gather-side :func:`blocked_lists_strategy` tail (DESIGN.md §7).
* :func:`norm_block_strategy` — contiguous blocks in decreasing-norm order
  bounded by Cauchy-Schwarz (the layout the Pallas backend consumes).

The list strategies leave ``ScanStrategy.score`` as the default dense
gather + matvec unless a layout supplies a contiguous path.

**Pad-aware index arithmetic** (DESIGN.md §10): every strategy accepts an
optional ``m_real`` — a TRACED scalar carrying the real catalogue size
when the index/layout arrays have been padded to an M-bucket (so one
compiled executable serves every snapshot of the bucket). All walk
positions, direction flips (``m - 1 - d``), Eq. 3 bound lookups,
freshness keys, and the dynamic step/round caps the driver consumes
(`ScanStrategy.num_steps_dynamic` / ``num_rounds_dynamic``) are computed
against ``m_real``, never against the padded array length — pad rows are
therefore never enumerated, never scored, and never counted, and results
are bit-identical to the unpadded scan. ``m_real=None`` (the default)
keeps the static-shape behaviour.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.driver import BatchedScanStrategy, ScanStrategy
from repro.core.naive import SCORE_PRECISION

Array = jnp.ndarray

_INT_MAX = 2147483647


def _dot(a: Array, b: Array) -> Array:
    return jnp.matmul(a, b, precision=SCORE_PRECISION)


def sign_bucket(U) -> tuple:
    """Host-side sign bucket of a query batch: ``(sign, dense)``.

    ``sign`` is ``+1`` when every weight in the batch is >= 0 (the scan
    only ever walks HEAD prefixes), ``-1`` when every weight is <= 0
    (tail prefixes only), ``0`` otherwise (mixed — per-(query, list)
    direction select). ``dense`` is True when NO weight is zero, which
    lets the single-sign batched strategies share ONE freshness-key tile
    across the whole batch (the keys become query-independent); the
    mixed bucket always reports ``dense=False`` — its keys are per-query
    regardless, so fewer buckets means fewer compiles.

    This is a HOST read of the query values (``np.asarray``). Query
    batches are host-origin in the serving path; a device-resident batch
    pays one transfer, never a trace.
    """
    arr = np.asarray(U)
    if arr.size == 0:
        return (0, False)
    has_neg = bool((arr < 0).any())
    has_pos = bool((arr > 0).any())
    if has_neg and has_pos:
        return (0, False)
    dense = not bool((arr == 0).any())
    return ((-1, dense) if has_neg else (1, dense))


def sign_bucket_label(bucket: tuple) -> str:
    """Readable label for a :func:`sign_bucket` value (stats/artifacts)."""
    if not bucket:
        return "unbucketed"
    sign, dense = bucket
    name = {1: "nonneg", -1: "nonpos", 0: "mixed"}[sign]
    return f"{name}-{'dense' if dense else 'sparse'}"


def _keys_from_ranks(ranks: Array, u: Array, m: int) -> Array:
    """Round-major first-occurrence keys from a ``[..., R]`` rank array.

    THE single implementation of the freshness-key formula. The
    sequential scan enumerates ROUND-major (depth d, then list r), so an
    item's first enumeration is the minimum of ``pos_r(y) * R + r`` over
    its active lists, where ``pos_r`` is the walk position in list r's
    per-query view (``m-1-rank`` when ``u_r < 0`` — the same flip
    ``query_views`` reports). Inactive (zero-weight) lists are masked to
    int32 max. A slot ``(r, d)`` is fresh iff ``first_key[id] == d*R+r``.
    This invariant is load-bearing for count-faithfulness: every
    freshness path — the O(R*M) per-query precompute, the tail's
    per-block row gather, and the prefix's offline rank tiles — must
    compute bit-identical keys, so they all route through here.
    """
    R = ranks.shape[-1]
    shape = (1,) * (ranks.ndim - 1) + (R,)
    pos = jnp.where((u < 0).reshape(shape), m - 1 - ranks, ranks)
    keys = pos * R + jnp.arange(R, dtype=jnp.int32).reshape(shape)
    keys = jnp.where((u != 0).reshape(shape), keys, _INT_MAX)
    return jnp.min(keys, axis=-1)                                # [...]


def _first_occurrence_keys(rank_desc: Array, u: Array,
                           m_real=None) -> Array:
    """Per-item keys for the whole catalogue (O(R*M) per-query precompute,
    the non-layout gather path's freshness table). ``m_real`` is the real
    (unpadded) catalogue size when the rank array is M-bucket padded."""
    R, M = rank_desc.shape
    m = M if m_real is None else m_real
    return _keys_from_ranks(rank_desc.T, u, m)                   # [M]


def rank_gather_first_keys(rank_by_item: Array, u: Array,
                           ids: Array, m_real=None) -> Array:
    """Keys for ONE block of candidates, by row gather.

    Computed only for the ``C`` candidates at hand from the transposed
    inverse permutations
    (:attr:`repro.core.layout.ListMajorLayout.rank_by_item`, ``[M, R]``):
    a ``[C, R]`` int gather per block instead of an O(R*M) per-query
    precompute. Used by the post-prefix tail of the layout path, where
    blocks are rare (DESIGN.md §7). ``m_real`` is the real catalogue
    size when ``rank_by_item`` is M-bucket padded.
    """
    M, R = rank_by_item.shape
    m = M if m_real is None else m_real
    return _keys_from_ranks(rank_by_item[ids], u, m)             # [C]


def ta_round_strategy(order_desc: Array, t_sorted_desc: Array, u: Array,
                      rank_desc: Optional[Array] = None,
                      m_real=None) -> ScanStrategy:
    """Paper-faithful TA rounds with gather-side direction resolution.

    Args:
      order_desc / t_sorted_desc: the query-independent ``[R, M]`` index
        arrays (:meth:`repro.core.index.TopKIndex.query_views` returns
        them untouched plus the direction flags). Walk depth ``d`` of
        list ``r`` reads column ``M-1-d`` when ``u_r < 0`` — an O(R)
        index transform per round, replacing the two O(R*M) flipped
        copies the pre-flip views used to materialise per query.
      u: ``[R]`` query.
      rank_desc: optional ``[R, M]`` inverse permutations
        (:attr:`repro.core.index.TopKIndex.rank_desc`). When given,
        freshness runs on cursor arithmetic (same round-major key as the
        blocked strategy) and the driver drops the O(M) visited bitmap
        from the loop carry — identical results and counts.
      m_real: optional traced real catalogue size (arrays M-bucket
        padded); walks, bounds, and the dynamic round cap use it.
    """
    R, M = order_desc.shape
    m = M if m_real is None else m_real
    neg = u < 0
    active = u != 0  # sparse queries: zero-weight lists are never walked
    rows_r = jnp.arange(R, dtype=jnp.int32)

    def candidates(step):
        cols = jnp.where(neg, m - 1 - step, step)
        ids = order_desc[rows_r, cols]
        return ids, active

    def bound(step):
        # Eq. 3 at the depth just consumed
        cols = jnp.where(neg, m - 1 - step, step)
        t_at = t_sorted_desc[rows_r, cols]
        return jnp.sum(u * t_at)

    fresh_mask = None
    if rank_desc is not None:
        first_key = _first_occurrence_keys(rank_desc, u, m_real)
        slot_r = jnp.arange(R, dtype=jnp.int32)

        def fresh_mask(step, ids, active_slots):
            return jnp.logical_and(active_slots,
                                   first_key[ids] == step * R + slot_r)

    return ScanStrategy(candidates=candidates, bound=bound, num_steps=M,
                        track_visited=True, fresh_mask=fresh_mask,
                        num_steps_dynamic=m_real)


def blocked_lists_strategy(
    order_desc: Array,
    t_sorted_desc: Array,
    u: Array,
    block_size: int,
    rank_desc: Optional[Array] = None,
    ta_rounds: bool = False,
    rank_by_item: Optional[Array] = None,
    m_real=None,
) -> ScanStrategy:
    """BTA enumeration: ``R * block_size`` candidates per step.

    Negative query weights are handled without materialising per-query
    flipped lists: depth ``d`` in list ``r`` reads position ``M-1-d`` when
    ``u_r < 0`` (a gather-side index transform, not a data transform) —
    which is why this strategy, unlike :func:`ta_round_strategy`, stays
    O(R*B) memory per query under ``vmap``.

    Args:
      rank_desc: optional ``[R, M]`` inverse permutations
        (:attr:`repro.core.index.TopKIndex.rank_desc`). When given,
        freshness is answered by per-list cursor arithmetic — an item's
        first enumeration position is computed once per query from the
        cursors, so the driver drops the O(M) visited bitmap from its loop
        carry (DESIGN.md §6).
      ta_rounds: treat each of the ``block_size`` depths as its own
        sequential TA round (chunked TA): per-round Eq. 3 bounds and the
        driver's prefix masking keep ``n_scored``/``depth`` identical to
        the item-at-a-time paper algorithm while the gather + matvec stay
        block-shaped. Requires ``rank_desc`` or ``rank_by_item``.
      rank_by_item: optional ``[M, R]`` transposed inverse permutations
        (:attr:`repro.core.layout.ListMajorLayout.rank_by_item`).
        Freshness then comes from a per-block ``[C, R]`` row gather
        (:func:`rank_gather_first_keys`) instead of the O(R*M) per-query
        key precompute — the right trade when this strategy is only the
        rare post-prefix TAIL of a layout scan (DESIGN.md §7). Takes
        precedence over ``rank_desc``.
      m_real: optional traced real catalogue size (arrays M-bucket
        padded). Clamps, direction flips, bound lookups, freshness keys,
        and the dynamic step/round caps all use it, so pad entries past
        the real list ends are never walked.
    """
    R, M = order_desc.shape
    m = M if m_real is None else m_real
    neg = u < 0
    active = u != 0
    active_rep = jnp.repeat(active, block_size,
                            total_repeat_length=R * block_size)
    offs = jnp.arange(block_size, dtype=jnp.int32)

    def candidates(step):
        d0 = step * block_size
        cols = jnp.minimum(d0 + offs, m - 1)
        cols_eff = jnp.where(neg[:, None], m - 1 - cols[None, :],
                             cols[None, :])
        ids = jnp.take_along_axis(order_desc, cols_eff, axis=1).reshape(-1)
        return ids, active_rep

    def block_bound(step):
        # bound at the block's last processed depth — valid for every unseen
        # item because the lists are monotone (Eq. 3 holds at any depth)
        end = jnp.minimum(step * block_size + block_size - 1, m - 1)
        end_eff = jnp.where(neg, m - 1 - end, end)
        t_end = t_sorted_desc[jnp.arange(R), end_eff]
        return jnp.sum(u * t_end)

    def round_bounds(step):
        # Eq. 3 at EVERY depth of the block — the chunked-TA driver stops
        # mid-block at exactly the sequential algorithm's round
        d = jnp.minimum(step * block_size + offs, m - 1)            # [B]
        d_eff = jnp.where(neg[:, None], m - 1 - d[None, :], d[None, :])
        t_at = jnp.take_along_axis(t_sorted_desc, d_eff, axis=1)    # [R, B]
        return jnp.sum(u[:, None] * t_at, axis=0)                   # [B]

    fresh_mask = None
    if rank_by_item is not None or rank_desc is not None:
        # Round-major first-occurrence keys: also the slot the sequential
        # oracle scores an item at (this matters for chunked TA's
        # per-round counts; for the block-granular scan any slot of the
        # item's first block would do, and the minimum is in that block
        # either way).
        slot_r = jnp.repeat(jnp.arange(R, dtype=jnp.int32), block_size,
                            total_repeat_length=R * block_size)
        slot_depth = jnp.tile(offs, R)                               # [R*B]
        if rank_by_item is not None:
            def fresh_mask(step, ids, active_slots):
                fk = rank_gather_first_keys(rank_by_item, u, ids, m_real)
                d = step * block_size + slot_depth  # unclamped true depth
                sk = d * R + slot_r
                return jnp.logical_and(
                    jnp.logical_and(active_slots, fk == sk), d < m)
        else:
            first_key = _first_occurrence_keys(rank_desc, u, m_real)

            def fresh_mask(step, ids, active_slots):
                d = step * block_size + slot_depth  # unclamped true depth
                sk = d * R + slot_r
                return jnp.logical_and(
                    jnp.logical_and(active_slots, first_key[ids] == sk),
                    d < m)

    steps_dyn = None if m_real is None else -(-m_real // block_size)
    if ta_rounds and block_size > 1:
        # block_size == 1 falls through: one round per step IS the plain
        # blocked strategy, and the driver's scalar-bound path handles it.
        if fresh_mask is None:
            raise ValueError(
                "ta_rounds (chunked TA) requires rank_desc or rank_by_item")
        return ScanStrategy(candidates=candidates, bound=round_bounds,
                            num_steps=-(-M // block_size),
                            track_visited=False, fresh_mask=fresh_mask,
                            rounds_per_step=block_size, num_rounds=M,
                            num_steps_dynamic=steps_dyn,
                            num_rounds_dynamic=m_real)
    return ScanStrategy(candidates=candidates, bound=block_bound,
                        num_steps=-(-M // block_size),
                        track_visited=fresh_mask is None,
                        fresh_mask=fresh_mask,
                        num_steps_dynamic=steps_dyn)


def list_prefix_strategy(
    layout,
    t_sorted_desc: Array,
    u: Array,
    block_size: int,
    ta_rounds: bool = False,
    m_real=None,
) -> ScanStrategy:
    """Gather-free TA/BTA enumeration over the contiguous list prefix.

    Block ``step`` covers depths ``[step*B, (step+1)*B)`` of every list —
    the same candidates, bounds, and freshness keys as
    :func:`blocked_lists_strategy`, but every memory access inside the
    prefix is CONTIGUOUS (DESIGN.md §7):

    * scoring slices ``[R, B, R]`` tiles of the layout's ``head_rows``
      (descending walks) and ``tail_rows`` (ascending walks, i.e.
      negative query weights), selects per-list by the direction flag,
      and runs one ``[R*B, R] @ [R]`` matvec — no row gather;
    * candidate ids are slices of the walk-order id tables;
    * freshness slices the pre-materialised rank tiles
      (``head_ranks``/``tail_ranks``: each prefix item's positions in
      ALL lists, in walk order) and reduces them to round-major
      first-occurrence keys with a vectorised min — per-STEP O(C*R)
      arithmetic on contiguous memory, replacing both the O(R*M)
      per-query key precompute and any scatter/gather (a batched
      scatter-min was measured to dominate the whole scan on XLA:CPU).

    Covers ``layout.prefix_steps(block_size)`` blocks; the caller chains
    a gather-side tail via the driver's ``init_state`` for the rare scan
    that outlives the prefix.

    Args:
      layout: a :class:`repro.core.layout.ListMajorLayout`.
      t_sorted_desc: ``[R, M]`` sorted values (bounds only).
      ta_rounds: chunked-TA mode, as in :func:`blocked_lists_strategy`
        (``num_rounds`` is capped at the prefix depth).
      m_real: optional traced real catalogue size when the layout's
        ``rank_by_item`` / the index arrays are M-bucket padded. The
        prefix TILES themselves are never padded (their shape is set by
        ``prefix_depth``, which is ≤ the real size by construction), so
        only the freshness keys and direction-flip bound lookups need
        the real size.
    """
    R, P = layout.head_ids.shape
    M = layout.rank_by_item.shape[0]
    m = M if m_real is None else m_real
    neg = u < 0
    active = u != 0
    n_steps = layout.prefix_steps(block_size)
    active_rep = jnp.repeat(active, block_size,
                            total_repeat_length=R * block_size)
    offs = jnp.arange(block_size, dtype=jnp.int32)

    def _dir_slice(head, tail, step):
        """[R, B, ...] walk-order tile: head for positive lists, tail for
        negative — two contiguous slices + one select, never a gather."""
        d0 = step * block_size
        sizes = (R, block_size) + head.shape[2:]
        h = jax.lax.dynamic_slice(head, (0, d0) + (0,) * (head.ndim - 2),
                                  sizes)
        t = jax.lax.dynamic_slice(tail, (0, d0) + (0,) * (tail.ndim - 2),
                                  sizes)
        return jnp.where(neg.reshape((R,) + (1,) * (head.ndim - 1)), t, h)

    def candidates(step):
        ids = _dir_slice(layout.head_ids, layout.tail_ids, step)
        return ids.reshape(-1), active_rep

    def score(step, ids, active_slots):
        tile = _dir_slice(layout.head_rows, layout.tail_rows, step)
        return jnp.matmul(tile.reshape(R * block_size, -1), u,
                          precision=SCORE_PRECISION)

    # round-major first-occurrence keys from the pre-materialised rank
    # tiles: ranks[r, j, r'] is candidate (r, j)'s position in list r'
    slot_key = (jnp.arange(block_size, dtype=jnp.int32)[None, :] * R
                + jnp.arange(R, dtype=jnp.int32)[:, None])      # [R, B]

    def fresh_mask(step, ids, active_slots):
        ranks = _dir_slice(layout.head_ranks, layout.tail_ranks, step)
        fk = _keys_from_ranks(ranks, u, m)                      # [R, B]
        d0 = step * block_size
        return jnp.logical_and(active[:, None],
                               fk == d0 * R + slot_key).reshape(-1)

    def block_bound(step):
        # prefix steps never clamp: d0 + B - 1 < P <= m
        end = step * block_size + block_size - 1
        end_eff = jnp.where(neg, m - 1 - end, end)
        t_end = t_sorted_desc[jnp.arange(R), end_eff]
        return jnp.sum(u * t_end)

    def round_bounds(step):
        d = step * block_size + offs                                # [B]
        d_eff = jnp.where(neg[:, None], m - 1 - d[None, :], d[None, :])
        t_at = jnp.take_along_axis(t_sorted_desc, d_eff, axis=1)    # [R, B]
        return jnp.sum(u[:, None] * t_at, axis=0)                   # [B]

    if ta_rounds and block_size > 1:
        return ScanStrategy(candidates=candidates, bound=round_bounds,
                            num_steps=n_steps, track_visited=False,
                            fresh_mask=fresh_mask, score=score,
                            rounds_per_step=block_size,
                            num_rounds=n_steps * block_size)
    return ScanStrategy(candidates=candidates, bound=block_bound,
                        num_steps=n_steps, track_visited=False,
                        fresh_mask=fresh_mask, score=score)


def batched_list_prefix_strategy(
    layout,
    t_sorted_desc: Array,
    U: Array,
    block_size: int,
    sign: int = 0,
    dense: bool = False,
    ta_rounds: bool = False,
    m_real=None,
) -> BatchedScanStrategy:
    """Batch-native :func:`list_prefix_strategy`: one shared tile per step.

    The whole batch consumes the SAME contiguous prefix block each step
    (the enumeration axis — walk depth — is query-independent), so the
    tile slice happens once and scoring is a single ``[C, R] @ [R, B]``
    matmul instead of B vmapped matvecs (DESIGN.md §11). What remains
    per-query is exactly what the sequential semantics require: scores,
    Eq. 3 bounds, and the freshness masks, all computed batched from the
    shared rank tiles via :func:`_keys_from_ranks` — never a scatter
    (standing XLA:CPU gotcha).

    ``sign`` is the STATIC sign bucket of the batch
    (:func:`sign_bucket`): ``+1`` (all weights >= 0) reads only the HEAD
    tiles, ``-1`` (all <= 0) only the TAIL tiles — halving prefix
    traffic and making candidate ids shared ``[C]`` vectors — while
    ``0`` (mixed) reads both and selects per (query, list). ``dense``
    (no zero weights, single-sign only) makes the freshness keys
    query-INDEPENDENT: with every list active and all flips identical,
    ``_keys_from_ranks`` collapses to one shared ``[R, B]`` key tile for
    the batch, evaluated with a constant direction surrogate so the keys
    are bit-identical to any dense query's of that sign.

    The caller guarantees the bucket matches the batch (host-side exact
    check in :func:`sign_bucket`); the bucket joins the engine executor
    compile key, so each variant traces once per process.
    """
    side_ids = layout.head_ids if sign >= 0 else layout.tail_ids
    R, P = side_ids.shape
    M = layout.rank_by_item.shape[0]
    m = M if m_real is None else m_real
    B = U.shape[0]
    C = R * block_size
    neg = U < 0                                                # [B, R]
    active = U != 0
    n_steps = layout.prefix_steps(block_size)
    offs = jnp.arange(block_size, dtype=jnp.int32)
    rows_r = jnp.arange(R, dtype=jnp.int32)
    # slot (r, j) lives at r*block_size + j; round-major key within block 0
    slot_key = offs[None, :] * R + rows_r[:, None]             # [R, Bk]

    def _slice(arr, step):
        d0 = step * block_size
        sizes = (R, block_size) + arr.shape[2:]
        return jax.lax.dynamic_slice(
            arr, (0, d0) + (0,) * (arr.ndim - 2), sizes)

    def _single_sign_block(step):
        if sign > 0:
            ids_a, rows_a, ranks_a = (layout.head_ids, layout.head_rows,
                                      layout.head_ranks)
        else:
            ids_a, rows_a, ranks_a = (layout.tail_ids, layout.tail_rows,
                                      layout.tail_ranks)
        ids = _slice(ids_a, step).reshape(-1)                  # [C] shared
        tile = _slice(rows_a, step).reshape(C, R)
        scores = _dot(tile, U.T).T                             # [B, C]
        ranks = _slice(ranks_a, step)                          # [R, Bk, R]
        abs_key = step * block_size * R + slot_key             # [R, Bk]
        if dense:
            # every list active, every flip identical -> the keys are
            # query-independent; evaluate them ONCE with a constant
            # direction surrogate of the bucket's sign
            u_dir = jnp.full((R,), float(sign), U.dtype)
            fk = _keys_from_ranks(ranks, u_dir, m)             # [R, Bk]
            fresh = jnp.broadcast_to(
                (fk == abs_key).reshape(1, C), (B, C))
        else:
            fk = jax.vmap(
                lambda uq: _keys_from_ranks(ranks, uq, m))(U)  # [B, R, Bk]
            fresh = jnp.logical_and(fk == abs_key[None],
                                    active[:, :, None]).reshape(B, C)
        return ids, scores, fresh

    def _mixed_block(step):
        h_ids = _slice(layout.head_ids, step)                  # [R, Bk]
        t_ids = _slice(layout.tail_ids, step)
        ids = jnp.where(neg[:, :, None], t_ids[None],
                        h_ids[None]).reshape(B, C)             # [B, C]
        h_tile = _slice(layout.head_rows, step).reshape(C, R)
        t_tile = _slice(layout.tail_rows, step).reshape(C, R)
        sh = _dot(h_tile, U.T).T                               # [B, C]
        st = _dot(t_tile, U.T).T
        neg_rep = jnp.repeat(neg, block_size, axis=1,
                             total_repeat_length=C)
        scores = jnp.where(neg_rep, st, sh)
        h_rk = _slice(layout.head_ranks, step)                 # [R, Bk, R]
        t_rk = _slice(layout.tail_ranks, step)
        rk = jnp.where(neg[:, :, None, None], t_rk[None], h_rk[None])
        fk = jax.vmap(
            lambda rq, uq: _keys_from_ranks(rq, uq, m))(rk, U)  # [B, R, Bk]
        abs_key = step * block_size * R + slot_key
        fresh = jnp.logical_and(fk == abs_key[None],
                                active[:, :, None]).reshape(B, C)
        return ids, scores, fresh

    block = _single_sign_block if sign != 0 else _mixed_block

    def _t_head(step):
        """[R, Bk] sorted values at depths d0 .. d0+Bk-1 (never clamps:
        prefix blocks satisfy d0 + Bk <= P <= m)."""
        return jax.lax.dynamic_slice(
            t_sorted_desc, (0, step * block_size), (R, block_size))

    def _t_tail(step):
        """[R, Bk] sorted values at ASCENDING-walk depths: column j holds
        ``t[:, m-1-(d0+j)]``."""
        start = m - block_size - step * block_size
        sl = jax.lax.dynamic_slice(t_sorted_desc, (0, start),
                                   (R, block_size))
        return sl[:, ::-1]

    u_pos = jnp.where(neg, 0.0, U)                             # [B, R]
    u_neg = jnp.where(neg, U, 0.0)

    def round_bounds(step):
        # Eq. 3 at every depth of the block, per query: [B, Bk]
        if sign > 0:
            return _dot(U, _t_head(step))
        if sign < 0:
            return _dot(U, _t_tail(step))
        return _dot(u_pos, _t_head(step)) + _dot(u_neg, _t_tail(step))

    def block_bound(step):
        # bound at the block's last depth only — one [R] column per side
        end = step * block_size + block_size - 1
        t_h = jax.lax.dynamic_slice(t_sorted_desc, (0, end), (R, 1))[:, 0]
        if sign > 0:
            return _dot(U, t_h)
        t_t = jax.lax.dynamic_slice(t_sorted_desc, (0, m - 1 - end),
                                    (R, 1))[:, 0]
        if sign < 0:
            return _dot(U, t_t)
        return _dot(u_pos, t_h) + _dot(u_neg, t_t)

    if ta_rounds and block_size > 1:
        return BatchedScanStrategy(block=block, bound=round_bounds,
                                   num_steps=n_steps,
                                   rounds_per_step=block_size,
                                   num_rounds=n_steps * block_size)
    return BatchedScanStrategy(block=block, bound=block_bound,
                               num_steps=n_steps)


def norm_block_strategy(
    norm_order: Array,
    norms_sorted: Array,
    u: Array,
    block_size: int,
    targets_by_norm: Optional[Array] = None,
    m_real=None,
) -> ScanStrategy:
    """Decreasing-norm contiguous blocks with Cauchy-Schwarz bounds.

    Block ``b`` covers items ``norm_order[b*B:(b+1)*B]`` (a contiguous
    gather); every unseen score is bounded by ``||u|| * norms_sorted[(b+1)*B]``.
    Items never repeat across blocks, so the driver skips visited tracking.

    When ``targets_by_norm`` (the catalogue pre-permuted into decreasing-
    norm order, :attr:`repro.core.index.TopKIndex.targets_by_norm`) is
    given, the whole block step goes memory-layout native (DESIGN.md §6):
    scoring is a contiguous ``dynamic_slice`` + matvec instead of a row
    gather (the Pallas kernel's DMA layout, in pure XLA), candidate ids
    are the norm-ordered ROW numbers (an iota — no id gather in the loop;
    the caller maps rows back to catalogue ids once, after the scan, via
    ``norm_order``), and the per-block Cauchy-Schwarz bounds are one
    precomputed vector indexed per step. The tail block slides back to
    stay in bounds; rows re-entering from the previous block are masked
    inactive, so counts are unchanged.

    ``m_real`` (traced) is the real catalogue size when the norm arrays
    are M-bucket padded (pad rows zero, norm 0, id -1 — sorted last by
    construction): the tail block then slides back against the REAL end,
    pad rows are masked out of scoring and counting, and the dynamic
    step cap stops the scan where the unpadded scan would.
    """
    M = norm_order.shape[0]
    m = M if m_real is None else m_real
    u_norm = jnp.linalg.norm(u)
    offs = jnp.arange(block_size, dtype=jnp.int32)
    use_slices = targets_by_norm is not None and M >= block_size
    n_steps = -(-M // block_size)
    # bound after step b = ||u|| * norm of the first unseen row; one
    # vectorised precompute, one dynamic index per step
    next_starts = jnp.minimum(
        (jnp.arange(n_steps, dtype=jnp.int32) + 1) * block_size, m - 1)
    block_bounds = u_norm * norms_sorted[next_starts]

    def candidates(step):
        d0 = step * block_size
        if use_slices:
            start = jnp.maximum(0, jnp.minimum(d0, m - block_size))
            rows = start + offs
            # mask rows the previous block scored, and pad rows
            valid = jnp.logical_and(rows >= d0, rows < m)
            return rows, valid     # local rows; caller remaps after scan
        rows = jnp.minimum(d0 + offs, m - 1)
        valid = (d0 + offs) < m
        return norm_order[rows], valid

    score = None
    if use_slices:
        def score(step, ids, active):
            d0 = step * block_size
            start = jnp.maximum(0, jnp.minimum(d0, m - block_size))
            tile = jax.lax.dynamic_slice_in_dim(targets_by_norm, start,
                                                block_size)
            return jnp.matmul(tile, u, precision=SCORE_PRECISION)

    def bound(step):
        return block_bounds[step]

    return ScanStrategy(candidates=candidates, bound=bound,
                        num_steps=n_steps, track_visited=False,
                        score=score,
                        num_steps_dynamic=(
                            None if m_real is None
                            else -(-m_real // block_size)))

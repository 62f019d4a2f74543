"""Unified pruned-block-scan driver (DESIGN.md §2).

Every exact engine in this repo — the paper's Threshold Algorithm, the
TPU-native Block Threshold Algorithm, and the norm-ordered Cauchy-Schwarz
scan — is the SAME state machine:

    while lower_bound < upper_bound and blocks remain:
        ids    <- enumerate the next block of candidates
        scores <- score the fresh candidates against the query
        top-K  <- merge
        bounds <- tighten (lower = running K-th best; upper = strategy bound)

:func:`pruned_block_scan` is that state machine, written once as a
``jax.lax.while_loop``, parameterised by a :class:`ScanStrategy` that
answers three questions — *which* candidates a block holds
(``candidates``), *how* to score them (``score``, defaulting to the dense
gather + matvec every current engine uses), and what *upper bound* holds
for every item not yet enumerated after the block (``bound``).

Three properties the copy-pasted per-engine loops did not have:

* **Uniform halting** — ``max_steps`` caps any strategy, so the paper's
  halted TA (§4.3) is a driver argument, not a per-engine reimplementation.
* **Faithful batched statistics** — every state update is gated on the
  per-query ``live`` predicate, so under ``jax.vmap`` a query that has
  already certified its top-K stops accumulating ``n_scored``/``depth``
  even though the lockstep loop keeps running for slower queries in the
  batch. Counts therefore match the sequential oracle exactly.
* **Cheap merging** (DESIGN.md §6) — the per-block merge is a block-local
  ``lax.top_k`` followed by an O(K)-output sorted merge of two
  descending-sorted lists (:func:`merge_topk_sorted`), never a
  ``lax.top_k`` over ``K + C`` lanes, and strategies that can answer
  freshness by cursor arithmetic (``fresh_mask``) drop the O(M) visited
  bitmap from the loop carry entirely — the carried state is O(K), so the
  per-step ``live`` select stops costing O(M).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.naive import SCORE_PRECISION, TopKResult

Array = jnp.ndarray

NEG_INF = float("-inf")


def _dedup_first_occurrence(ids: Array, m: int) -> Array:
    """Boolean mask: True where ids[i] is the first occurrence of that id.

    Scatter-min of positions — O(|ids|) work, O(M) memory, jit-friendly.
    """
    n = ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    first_pos = jnp.full((m,), n, dtype=jnp.int32).at[ids].min(pos)
    return first_pos[ids] == pos


def merge_topk_sorted(a_vals: Array, a_ids: Array,
                      b_vals: Array, b_ids: Array, k: int):
    """Top-``k`` of two DESCENDING-sorted (vals, ids) lists (DESIGN.md §6).

    Invariant both inputs must satisfy: sorted descending; ties rank the
    ``a`` side first, so the running top-K's ids win ties against fresh
    candidates (the same preference ``lax.top_k`` gives earlier operands).
    Two lowerings with identical semantics, picked at trace time:

    * off-CPU: a rank-arithmetic merge NETWORK — each element's merged
      rank is its own index plus a comparison-count against the other
      list (a dense ``[K, K]`` compare), and placement is a one-hot
      combine. O(K^2) VPU-friendly lanes, no ``lax.top_k``, no scatter —
      the shape TPUs want.
    * CPU: ``lax.top_k`` over the 2K-lane concatenation — XLA:CPU's
      ``top_k`` over 2K lanes is faster than scatter/one-hot placement at
      serving sizes, and for two sorted inputs it IS the O(K)-output
      sorted merge.

    Either way the driver never runs ``lax.top_k`` over ``K + C`` lanes:
    blocks are reduced block-locally first (:func:`_block_topk`), so the
    merge cost no longer scales with the block width.
    """
    if jax.default_backend() == "cpu":
        cand_vals = jnp.concatenate([a_vals, b_vals])
        cand_ids = jnp.concatenate([a_ids, b_ids])
        top, pos = jax.lax.top_k(cand_vals, k)
        return top, cand_ids[pos]
    ra = (jnp.arange(a_vals.shape[0], dtype=jnp.int32)
          + jnp.sum(b_vals[None, :] > a_vals[:, None], axis=1,
                    dtype=jnp.int32))
    rb = (jnp.arange(b_vals.shape[0], dtype=jnp.int32)
          + jnp.sum(a_vals[:, None] >= b_vals[None, :], axis=0,
                    dtype=jnp.int32))
    # one-hot placement via where (never multiply: values can be -inf, and
    # -inf * 0 would poison the sum with NaN). Merged ranks are distinct
    # and cover [0, ka+kb), so every output slot < k <= ka+kb is filled
    # exactly once.
    slots = jnp.arange(k, dtype=jnp.int32)
    oh_a = ra[:, None] == slots[None, :]            # [ka, k] one-hot place
    oh_b = rb[:, None] == slots[None, :]            # [kb, k]
    zero = jnp.zeros((), a_vals.dtype)
    out_vals = (jnp.sum(jnp.where(oh_a, a_vals[:, None], zero), axis=0)
                + jnp.sum(jnp.where(oh_b, b_vals[:, None], zero), axis=0))
    out_ids = (jnp.sum(jnp.where(oh_a, a_ids[:, None], 0), axis=0)
               + jnp.sum(jnp.where(oh_b, b_ids[:, None], 0), axis=0))
    return out_vals, out_ids


def _block_topk(masked_scores: Array, ids: Array, k: int):
    """Block-local top-k (sorted descending), padded to k slots."""
    c = masked_scores.shape[0]
    kk = min(k, c)
    vals, pos = jax.lax.top_k(masked_scores, kk)
    bids = ids[pos]
    if kk < k:
        vals = jnp.concatenate(
            [vals, jnp.full((k - kk,), NEG_INF, vals.dtype)])
        bids = jnp.concatenate(
            [bids, jnp.full((k - kk,), -1, bids.dtype)])
    return vals, bids


def _merge_block_into_carry(top_vals, top_ids, masked_scores, ids, k):
    """carry (sorted desc) + one block of masked scores -> new carry.

    Always two-stage: block-local ``top_k(C -> K)`` then the O(K) sorted
    merge. Never ``lax.top_k`` over the ``K + C`` concatenation — beyond
    the asymptotics, XLA:CPU's top_k degrades sharply once the lane count
    slips off the raw block width (measured ~6x on a C=8192 block: the
    K+C concatenation defeats the fast path the bare scores array hits).
    """
    bv, bi = _block_topk(masked_scores, ids, k)
    return merge_topk_sorted(top_vals, top_ids, bv, bi, k)


def merge_block_into_carry_batched(top_vals, top_ids, masked_scores,
                                   rows, k):
    """Batched :func:`_merge_block_into_carry`: a shared tile's scores.

    One block of ``[B, C]`` masked scores over an id vector ``rows`` that
    is either SHARED across the batch (``[C]`` — the lockstep batched
    scans where every query reads the same contiguous tile: the norm
    scan, the single-sign list prefix) or per-query (``[B, C]`` — the
    mixed-sign batched list scan, whose head/tail direction select gives
    each query its own candidate ids), merged into every query's
    ``[B, K]`` carry. Same two-stage invariant as the per-query helper:
    block-local ``top_k(C -> K)`` over the bare scores, pad to K lanes,
    then the O(K) sorted merge — never ``top_k`` over a ``K + C``
    concatenation.
    """
    B, c = masked_scores.shape
    kk = min(k, c)
    bv, bpos = jax.lax.top_k(masked_scores, kk)          # [B, kk]
    bi = rows[bpos] if rows.ndim == 1 \
        else jnp.take_along_axis(rows, bpos, axis=1)
    if kk < k:
        bv = jnp.concatenate(
            [bv, jnp.full((B, k - kk), NEG_INF, bv.dtype)], axis=1)
        bi = jnp.concatenate(
            [bi, jnp.full((B, k - kk), -1, bi.dtype)], axis=1)
    return jax.vmap(
        lambda tv, ti, v, i: merge_topk_sorted(tv, ti, v, i, k)
    )(top_vals, top_ids, bv, bi)


@dataclasses.dataclass(frozen=True)
class ScanStrategy:
    """What a pruned-scan engine must answer; everything else is the driver.

    Attributes:
      candidates: ``step -> (ids [C], active [C])`` — the candidate item ids
        enumerated by block ``step`` plus a mask of which slots are real
        (inactive lists, tail padding). ``C`` is static.
      bound: ``step -> scalar`` — an upper bound on the score of every item
        NOT yet enumerated once block ``step`` has been consumed. This is
        the exactness certificate: the scan may stop as soon as the running
        K-th best reaches it. When ``rounds_per_step > 1`` it returns a
        ``[rounds_per_step]`` vector — one Eq. 3 bound per sub-round.
      num_steps: static number of blocks needed to enumerate the whole
        catalogue (the exact engine's worst case).
      track_visited: list-based strategies enumerate the same item from
        several lists and need the driver's visited-set + dedup pass;
        partition-based strategies (norm blocks) never repeat an item and
        skip that O(M) state entirely. Ignored when ``fresh_mask`` is set.
      score: optional ``(step, ids, active) -> scores [C]`` override;
        ``None`` uses the dense gather + matvec ``targets[ids] @ u``.
        Strategies whose blocks are contiguous in some materialised layout
        use the ``step`` to slice instead of gather.
      fresh_mask: optional ``(step, ids, active) -> [C] bool`` answering
        "is this slot the FIRST enumeration of its item?" by cursor
        arithmetic (inverse-permutation positions) instead of the visited
        bitmap. Setting it removes the O(M) visited array from the loop
        carry — the per-step ``live`` select becomes O(K).
      rounds_per_step: >1 turns a step into ``rounds_per_step`` sequential
        paper rounds processed from one gather+matvec (chunked TA). The
        candidate layout must then be ``[R, rounds_per_step]`` flattened
        row-major (slot ``r * rounds_per_step + j`` holds list ``r``'s
        round-``j`` candidate), and ``fresh_mask`` is required so prefix
        masking can keep ``n_scored``/``depth`` count-faithful to the
        sequential algorithm.
      num_rounds: total sub-rounds in the exact scan (chunked mode only;
        e.g. M for TA).
      num_steps_dynamic: optional TRACED tighter step cap (DESIGN.md §10).
        Strategies over catalogue arrays padded to an M-bucket keep
        ``num_steps`` static at the padded worst case (the while_loop
        shape contract) and report the number of steps the REAL catalogue
        needs here, as a runtime scalar derived from the ``m_real``
        argument. The driver caps the loop at
        ``min(num_steps, num_steps_dynamic)``, so pad rows beyond the
        real catalogue are never enumerated and every counter stays
        sequential-faithful to the unpadded scan.
      num_rounds_dynamic: the same runtime cap in sub-rounds (chunked
        mode): typically ``m_real`` for TA. Caps the per-chunk
        ``cap_local`` masking, so a chunk straddling the real catalogue
        end scores and counts only real rounds.
    """

    candidates: Callable[[Array], Tuple[Array, Array]]
    bound: Callable[[Array], Array]
    num_steps: int
    track_visited: bool = True
    score: Optional[Callable[[Array, Array, Array], Array]] = None
    fresh_mask: Optional[Callable[[Array, Array, Array], Array]] = None
    rounds_per_step: int = 1
    num_rounds: Optional[int] = None
    num_steps_dynamic: Optional[Array] = None
    num_rounds_dynamic: Optional[Array] = None


class ScanState(NamedTuple):
    step: Array         # blocks consumed
    top_vals: Array     # [K] running top scores, descending
    top_ids: Array      # [K] their item ids
    visited: Array      # [M] bool ([1] dummy when the strategy never repeats)
    n_scored: Array     # score evaluations (the paper's cost metric)
    rounds: Array       # sub-rounds consumed (chunked strategies only)
    lower: Array        # running K-th best
    upper: Array        # strategy bound on every unseen item


def pruned_block_scan(
    targets: Array,
    u: Array,
    strategy: ScanStrategy,
    k: int,
    max_steps: int = -1,
    max_rounds: int = -1,
    init_state: Optional[ScanState] = None,
    return_state: bool = False,
):
    """Run ``strategy`` to exactness (or to the ``max_steps`` halt budget).

    Returns a :class:`TopKResult` whose ``depth`` field is the number of
    *blocks* consumed (engines convert to their public depth unit), except
    for chunked strategies (``rounds_per_step > 1``) where it is the exact
    number of sequential rounds processed — count-faithful to the
    item-at-a-time algorithm. ``max_rounds`` is the halted budget in
    rounds for chunked strategies (``max_steps`` still caps outer steps).

    **Phase chaining** (DESIGN.md §7): ``return_state=True`` additionally
    returns the final :class:`ScanState`; passing it as another scan's
    ``init_state`` resumes with the carried top-K, bounds, and counters
    intact. The step counter is ABSOLUTE across phases — the second
    strategy's ``candidates``/``bound`` must interpret ``step`` on the
    same global block axis, and ``num_steps``/``max_steps`` cap that
    global counter. A query already certified at the phase boundary
    (``lower >= upper``) never executes a body iteration of the second
    phase. Both phases must agree on the visited representation (the
    list-layout phases both use ``fresh_mask``, so the O(M) bitmap never
    appears).
    """
    M = targets.shape[0]
    k = min(k, M)
    chunk = strategy.rounds_per_step
    cap = strategy.num_steps if max_steps < 0 else min(max_steps,
                                                       strategy.num_steps)
    if chunk > 1:
        if strategy.fresh_mask is None:
            raise ValueError("chunked strategies require fresh_mask")
        total_rounds = (strategy.num_rounds if strategy.num_rounds is not None
                        else strategy.num_steps * chunk)
        round_cap = (total_rounds if max_rounds < 0
                     else min(max_rounds, total_rounds))
        cap = min(cap, -(-round_cap // chunk))
    else:
        round_cap = cap
    # Pad-aware halting (DESIGN.md §10): `cap`/`round_cap` above are STATIC
    # (the padded worst case — while_loop shapes must not depend on the
    # real catalogue size); strategies over M-bucket-padded arrays supply
    # the real catalogue's step/round budget as traced scalars, and the
    # loop condition uses the minimum. Pad rows therefore never execute a
    # step, and `n_scored`/`depth` match the unpadded sequential scan.
    cap_eff = cap
    round_cap_eff = round_cap
    if chunk > 1 and strategy.num_rounds_dynamic is not None:
        round_cap_eff = jnp.minimum(round_cap,
                                    strategy.num_rounds_dynamic)
        cap_eff = jnp.minimum(cap_eff,
                              (round_cap_eff + chunk - 1) // chunk)
    if strategy.num_steps_dynamic is not None:
        cap_eff = jnp.minimum(cap_eff, strategy.num_steps_dynamic)
    score = strategy.score or (lambda step, ids, active: jnp.matmul(
        targets[ids], u, precision=SCORE_PRECISION))
    use_visited = strategy.track_visited and strategy.fresh_mask is None

    def cond(s: ScanState):
        return jnp.logical_and(s.step < cap_eff, s.lower < s.upper)

    def chunked_body(s: ScanState, ids, active, fresh, scores):
        """rounds_per_step sequential paper rounds from one gather+matvec.

        The sequential semantics are recovered in closed form, not by an
        inner loop: the stopping test ``lower_j >= ub_j`` (the K-th best
        after merging rounds ``<= j`` reaching round j's Eq. 3 bound) is
        equivalent to "at least K candidates of rounds ``<= j`` (or the
        carry) score ``>= ub_j``" — a pure counting reduction over a
        ``[chunk, K + C]`` broadcast, no per-round sort. Candidates of
        rounds after the stop are masked out of the merge and the
        counters, so ``n_scored``/``depth`` equal the item-at-a-time
        algorithm's even though the whole chunk was gathered and scored in
        one MXU-shaped pass.
        """
        ubs = strategy.bound(s.step)              # [chunk] per-round bounds
        base_round = s.step * chunk
        # rounds allowed by the halted budget (and the real, unpadded
        # catalogue size), local to this chunk
        cap_local = jnp.clip(round_cap_eff - base_round, 0, chunk)
        tags = jnp.tile(jnp.arange(chunk, dtype=jnp.int32),
                        scores.shape[0] // chunk)   # slot -> round (r-major)
        eligible = jnp.logical_and(fresh, tags < cap_local)
        cand = jnp.where(eligible, scores, NEG_INF)
        # row j counts the carry (tag -1) + candidates of rounds <= j that
        # reach round j's bound; lower_j >= ub_j  <=>  count >= k
        all_vals = jnp.concatenate([s.top_vals, cand])
        all_tags = jnp.concatenate(
            [jnp.full((k,), -1, jnp.int32), tags])
        js = jnp.arange(chunk, dtype=jnp.int32)[:, None]
        reach = jnp.logical_and(all_tags[None, :] <= js,
                                all_vals[None, :] >= ubs[:, None])
        stop = jnp.logical_and(jnp.sum(reach, axis=1) >= k,
                               js[:, 0] < cap_local)
        j_stop = jnp.argmax(stop)                   # first True (or 0)
        processed = jnp.where(jnp.any(stop), j_stop + 1, cap_local)
        done = jnp.logical_and(fresh, tags < processed)
        masked = jnp.where(done, scores, NEG_INF)
        top_vals, top_ids = _merge_block_into_carry(
            s.top_vals, s.top_ids, masked, ids, k)
        upper = jnp.where(processed > 0, ubs[jnp.maximum(processed - 1, 0)],
                          s.upper)
        return ScanState(
            step=s.step + 1, top_vals=top_vals, top_ids=top_ids,
            visited=s.visited,
            n_scored=s.n_scored + jnp.sum(done).astype(jnp.int32),
            rounds=s.rounds + processed.astype(jnp.int32),
            lower=top_vals[k - 1], upper=upper)

    def body(s: ScanState):
        # per-query liveness: under vmap the lockstep loop keeps running for
        # the slowest query; frozen lanes must not mutate state (else the
        # paper's score-count metric is inflated for fast queries).
        live = jnp.logical_and(s.step < cap_eff, s.lower < s.upper)
        ids, active = strategy.candidates(s.step)
        if strategy.fresh_mask is not None:
            fresh = strategy.fresh_mask(s.step, ids, active)
            visited = s.visited
        elif use_visited:
            # sentinel id M for inactive slots: never shadows an active
            # occurrence of the same item in the dedup pass
            ids_eff = jnp.where(active, ids, M)
            fresh = jnp.logical_and(
                _dedup_first_occurrence(ids_eff, M + 1),
                jnp.logical_and(active, ~s.visited[ids]))
            visited = s.visited.at[ids].max(active)
        else:
            fresh = active
            visited = s.visited
        scores = score(s.step, ids, active)
        if chunk > 1:
            nxt = chunked_body(s, ids, active, fresh, scores)
            nxt = nxt._replace(visited=visited)
        else:
            masked = jnp.where(fresh, scores, NEG_INF)
            top_vals, top_ids = _merge_block_into_carry(
                s.top_vals, s.top_ids, masked, ids, k)
            nxt = ScanState(
                step=s.step + 1,
                top_vals=top_vals,
                top_ids=top_ids,
                visited=visited,
                n_scored=s.n_scored + jnp.sum(fresh).astype(jnp.int32),
                rounds=s.rounds,      # identity: depth is step-counted here
                lower=top_vals[k - 1],
                upper=strategy.bound(s.step),
            )
        # identity leaves (dummy visited, rounds outside chunked mode)
        # skip their select entirely — fewer ops per loop iteration
        return jax.tree_util.tree_map(
            lambda new, old: old if new is old else jnp.where(live, new, old),
            nxt, s)

    if init_state is not None:
        init = init_state
    else:
        visited0 = jnp.zeros((M if use_visited else 1,), dtype=bool)
        init = ScanState(
            step=jnp.int32(0),
            top_vals=jnp.full((k,), NEG_INF, dtype=targets.dtype),
            top_ids=jnp.full((k,), -1, dtype=jnp.int32),
            visited=visited0,
            n_scored=jnp.int32(0),
            rounds=jnp.int32(0),
            lower=jnp.asarray(NEG_INF, dtype=targets.dtype),
            upper=jnp.asarray(jnp.inf, dtype=targets.dtype),
        )
        if cap >= 1:
            # the first block is unconditionally live (lower = -inf < upper
            # = +inf), so unroll it: XLA folds the literal init state into
            # the block-0 computation and the loop runs one iteration
            # fewer. (Chained phases skip this: their first block is NOT
            # unconditionally live — the prior phase may have certified.)
            init = body(init)
    final = jax.lax.while_loop(cond, body, init)
    depth = final.rounds if chunk > 1 else final.step
    # Certificate tightening: when the scan consumed every REAL block
    # (not a budget halt — the full, pad-aware step/round count), no item
    # is left un-enumerated and the vacuous bound -inf replaces the last
    # block bound, which only speaks for items BEYOND the blocks scanned.
    # Exact-but-unpruned scans (tiny M, k ~ M) then certify fully.
    if chunk > 1:
        full_rounds = (strategy.num_rounds_dynamic
                       if strategy.num_rounds_dynamic is not None
                       else total_rounds)
        exhausted = final.rounds >= full_rounds
    else:
        full_steps = (strategy.num_steps_dynamic
                      if strategy.num_steps_dynamic is not None
                      else strategy.num_steps)
        exhausted = final.step >= full_steps
    upper = jnp.where(exhausted,
                      jnp.asarray(NEG_INF, dtype=final.upper.dtype),
                      final.upper)
    res = TopKResult(final.top_vals, final.top_ids, final.n_scored, depth,
                     upper=upper)
    return (res, final) if return_state else res


@dataclasses.dataclass(frozen=True)
class BatchedScanStrategy:
    """A batch-NATIVE strategy: one shared enumeration for the whole batch.

    Where :class:`ScanStrategy` under ``jax.vmap`` replicates every slice,
    matvec, and bound lookup per query, a batched strategy answers each
    step ONCE for the batch — the tile slice and the score matmul are
    shared, and only the quantities that genuinely vary per query
    (scores, freshness, bounds) carry a leading ``B`` axis.

    Attributes:
      block: ``step -> (ids, scores, fresh)`` where ``ids`` is ``[C]``
        (shared candidate row — every query reads the same tile) or
        ``[B, C]`` (per-query ids, e.g. the mixed-sign list scan whose
        head/tail select differs per query), ``scores`` is ``[B, C]``,
        and ``fresh`` is ``[B, C]`` bool — True where the slot is the
        FIRST enumeration of its item for that query AND the slot is
        active. Inactive/pad slots must be False.
      bound: ``step -> [B]`` upper bound per query on every item not yet
        enumerated after the block (``[B, rounds_per_step]`` per-round
        Eq. 3 bounds in chunked mode).
      num_steps / rounds_per_step / num_rounds / num_steps_dynamic /
      num_rounds_dynamic: as in :class:`ScanStrategy` (the dynamic caps
        are shared scalars — the enumeration axis is query-independent).
    """

    block: Callable[[Array], Tuple[Array, Array, Array]]
    bound: Callable[[Array], Array]
    num_steps: int
    rounds_per_step: int = 1
    num_rounds: Optional[int] = None
    num_steps_dynamic: Optional[Array] = None
    num_rounds_dynamic: Optional[Array] = None


class BatchedScanState(NamedTuple):
    step: Array         # scalar: blocks consumed by the batch-level loop
    steps: Array        # [B] blocks each query consumed while live
    top_vals: Array     # [B, K] running top scores, descending
    top_ids: Array      # [B, K] their item ids
    n_scored: Array     # [B] per-query score evaluations
    rounds: Array       # [B] per-query sub-rounds (chunked mode)
    lower: Array        # [B] running K-th best
    upper: Array        # [B] bound on every unseen item


def batched_pruned_scan(
    U: Array,
    strategy: BatchedScanStrategy,
    k: int,
    dtype,
    max_steps: int = -1,
    max_rounds: int = -1,
    return_state: bool = False,
):
    """The batch-level pruned scan: ONE ``while_loop`` for the whole batch.

    Replaces ``vmap(pruned_block_scan)`` for strategies that can share
    their enumeration across queries (the list prefix, the norm order):
    the loop runs until every query has certified (``cond`` is an
    ``any``), so its step count is the MAX live query's depth, and every
    per-query state update is gated on that query's own ``live``
    predicate — a lane whose ``lower >= upper`` is frozen, exactly as a
    certified query under the vmapped driver stops accumulating. Counts
    (``n_scored``, per-query ``steps``/``rounds``) therefore equal the
    sequential per-query oracle's even though slower queries keep the
    shared loop running (DESIGN.md §11).

    ``depth`` in the returned :class:`~repro.core.naive.TopKResult` is
    per-query blocks consumed (``rounds`` in chunked mode), matching
    ``vmap(pruned_block_scan)`` field-for-field. ``return_state=True``
    additionally returns the final :class:`BatchedScanState`; its
    per-lane ``steps`` is the ABSOLUTE per-query block cursor a chained
    per-query tail phase resumes from (DESIGN.md §7).
    """
    B = U.shape[0]
    chunk = strategy.rounds_per_step
    cap = strategy.num_steps if max_steps < 0 else min(max_steps,
                                                       strategy.num_steps)
    if chunk > 1:
        total_rounds = (strategy.num_rounds if strategy.num_rounds is not None
                        else strategy.num_steps * chunk)
        round_cap = (total_rounds if max_rounds < 0
                     else min(max_rounds, total_rounds))
        cap = min(cap, -(-round_cap // chunk))
    else:
        round_cap = cap
    cap_eff = cap
    round_cap_eff = round_cap
    if chunk > 1 and strategy.num_rounds_dynamic is not None:
        round_cap_eff = jnp.minimum(round_cap, strategy.num_rounds_dynamic)
        cap_eff = jnp.minimum(cap_eff, (round_cap_eff + chunk - 1) // chunk)
    if strategy.num_steps_dynamic is not None:
        cap_eff = jnp.minimum(cap_eff, strategy.num_steps_dynamic)

    def cond(s: BatchedScanState):
        return jnp.logical_and(s.step < cap_eff,
                               jnp.any(s.lower < s.upper))

    def body(s: BatchedScanState):
        live = s.lower < s.upper                              # [B]
        ids, scores, fresh = strategy.block(s.step)
        C = scores.shape[1]
        if chunk > 1:
            # the closed-form sequential-round recovery of `chunked_body`,
            # vectorised over the batch: each lane stops at ITS sequential
            # round, candidates past it are masked from merge and counts
            ubs = strategy.bound(s.step)                      # [B, chunk]
            base_round = s.step * chunk
            cap_local = jnp.clip(round_cap_eff - base_round, 0, chunk)
            tags = jnp.tile(jnp.arange(chunk, dtype=jnp.int32), C // chunk)
            eligible = jnp.logical_and(fresh, tags[None, :] < cap_local)
            cand = jnp.where(eligible, scores, NEG_INF)
            all_vals = jnp.concatenate([s.top_vals, cand], axis=1)
            all_tags = jnp.concatenate(
                [jnp.full((k,), -1, jnp.int32), tags])        # [k + C]
            js = jnp.arange(chunk, dtype=jnp.int32)
            reach = jnp.logical_and(
                all_tags[None, None, :] <= js[None, :, None],
                all_vals[:, None, :] >= ubs[:, :, None])      # [B, chunk, k+C]
            stop = jnp.logical_and(
                jnp.sum(reach, axis=2) >= k,
                js[None, :] < cap_local)                      # [B, chunk]
            j_stop = jnp.argmax(stop, axis=1)                 # [B]
            processed = jnp.where(jnp.any(stop, axis=1), j_stop + 1,
                                  cap_local)                  # [B]
            done = jnp.logical_and(fresh, tags[None, :] < processed[:, None])
            masked = jnp.where(done, scores, NEG_INF)
            new_vals, new_ids = merge_block_into_carry_batched(
                s.top_vals, s.top_ids, masked, ids, k)
            upper_new = jnp.where(
                processed > 0,
                jnp.take_along_axis(
                    ubs, jnp.maximum(processed - 1, 0)[:, None],
                    axis=1)[:, 0],
                s.upper)
            n_inc = jnp.sum(done, axis=1).astype(jnp.int32)
            r_inc = processed.astype(jnp.int32)
        else:
            masked = jnp.where(fresh, scores, NEG_INF)
            new_vals, new_ids = merge_block_into_carry_batched(
                s.top_vals, s.top_ids, masked, ids, k)
            upper_new = strategy.bound(s.step)                # [B]
            n_inc = jnp.sum(fresh, axis=1).astype(jnp.int32)
            r_inc = jnp.zeros((B,), jnp.int32)
        gate = live[:, None]
        return BatchedScanState(
            step=s.step + 1,
            steps=jnp.where(live, s.steps + 1, s.steps),
            top_vals=jnp.where(gate, new_vals, s.top_vals),
            top_ids=jnp.where(gate, new_ids, s.top_ids),
            n_scored=jnp.where(live, s.n_scored + n_inc, s.n_scored),
            rounds=jnp.where(live, s.rounds + r_inc, s.rounds),
            lower=jnp.where(live, new_vals[:, k - 1], s.lower),
            upper=jnp.where(live, upper_new, s.upper),
        )

    init = BatchedScanState(
        step=jnp.int32(0),
        steps=jnp.zeros((B,), jnp.int32),
        top_vals=jnp.full((B, k), NEG_INF, dtype=dtype),
        top_ids=jnp.full((B, k), -1, dtype=jnp.int32),
        n_scored=jnp.zeros((B,), jnp.int32),
        rounds=jnp.zeros((B,), jnp.int32),
        lower=jnp.full((B,), NEG_INF, dtype=dtype),
        upper=jnp.full((B,), jnp.inf, dtype=dtype),
    )
    if cap >= 1:
        # first block is unconditionally live for every lane — unroll it
        # (same literal-folding win as the per-query driver)
        init = body(init)
    final = jax.lax.while_loop(cond, body, init)
    depth = final.rounds if chunk > 1 else final.steps
    # Same certificate tightening as the per-query driver: a lane whose
    # scan consumed every REAL block/round has nothing un-enumerated —
    # its upper drops to the vacuous -inf (a budget halt keeps the live
    # block bound; per-lane because frozen lanes stop at their own depth)
    if chunk > 1:
        full_rounds = (strategy.num_rounds_dynamic
                       if strategy.num_rounds_dynamic is not None
                       else total_rounds)
        exhausted = final.rounds >= full_rounds
    else:
        full_steps = (strategy.num_steps_dynamic
                      if strategy.num_steps_dynamic is not None
                      else strategy.num_steps)
        exhausted = final.steps >= full_steps
    upper = jnp.where(exhausted,
                      jnp.asarray(NEG_INF, dtype=final.upper.dtype),
                      final.upper)
    res = TopKResult(final.top_vals, final.top_ids, final.n_scored, depth,
                     upper=upper)
    return (res, final) if return_state else res

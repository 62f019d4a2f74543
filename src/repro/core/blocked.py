"""Block Threshold Algorithm (BTA) — the TPU-native adaptation of TA.

The paper's TA pops ONE item per list per round: pointer chasing, hash-set
dedup, heap update — shapes a TPU cannot execute efficiently (DESIGN.md §4).
BTA restructures the same exact algorithm around the MXU:

* one round pops a **depth block** of ``B`` entries from all R lists at once
  (``R*B`` candidate ids),
* the candidates are scored as a single gather + matvec/matmul,
* the running top-K is merged block-locally and folded into the carry with
  an O(K) sorted merge (:func:`repro.core.driver.merge_topk_sorted`),
* the stopping bound is evaluated at the block's LAST depth — still a valid
  upper bound for every unseen item because the lists are monotone (Eq. 3
  holds at any depth), so **exactness is preserved**; at most one extra
  block of items is scored compared to item-at-a-time TA.

``chunked_ta_topk`` keeps the paper's item-at-a-time *accounting* while
executing block-shaped work: a chunk of ``chunk`` rounds is gathered and
scored at once, then the driver's per-candidate prefix masking replays the
rounds sequentially so ``n_scored``/``depth`` equal the sequential
algorithm's exactly (the `ta` registry engine runs on this path).

Also here: ``norm_pruned_topk`` — a beyond-paper exact pruner that walks the
catalogue in decreasing ``||t(y)||`` order and bounds whole *contiguous*
blocks with Cauchy-Schwarz ``s(x,y) <= ||u|| * max_norm(block)`` (LEMP-style
screening, but block-synchronous for the MXU; gathers are contiguous, which
the Pallas kernel exploits).

All are thin wrappers: the loop itself is
:func:`repro.core.driver.pruned_block_scan` running
:func:`repro.core.strategies.blocked_lists_strategy` /
:func:`repro.core.strategies.norm_block_strategy`. ``block_size=1``
recovers paper-faithful TA rounds; ``max_blocks`` is the uniform halted
variant across every strategy.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.driver import (ScanState, batched_pruned_scan,
                               merge_block_into_carry_batched,
                               pruned_block_scan)
from repro.core.index import TopKIndex
from repro.core.naive import SCORE_PRECISION, TopKResult
from repro.core.strategies import (
    batched_list_prefix_strategy,
    blocked_lists_strategy,
    list_prefix_strategy,
    norm_block_strategy,
)

Array = jnp.ndarray


def _two_phase_list_scan(targets, order_desc, t_sorted_desc, u, k,
                         block_size, max_blocks, max_rounds, layout,
                         ta_rounds, m_real=None):
    """Contiguous prefix phase chained into a gather-side tail phase.

    Phase 1 runs :func:`repro.core.strategies.list_prefix_strategy` over
    the layout's contiguous prefix; its final :class:`ScanState` seeds a
    :func:`repro.core.strategies.blocked_lists_strategy` tail whose
    freshness comes from per-block ``rank_by_item`` gathers — so the tail
    needs neither the O(M) visited bitmap nor the O(R*M) key precompute,
    and a query that certifies inside the prefix (virtually all of them)
    never executes a tail iteration (DESIGN.md §7). Results and
    ``n_scored``/``depth`` are identical to the single-phase gather scan.
    ``m_real`` (traced) flows into both phases when the index arrays are
    M-bucket padded (DESIGN.md §10).
    """
    prefix = list_prefix_strategy(layout, t_sorted_desc, u, block_size,
                                  ta_rounds=ta_rounds, m_real=m_real)
    _, state = pruned_block_scan(
        targets, u, prefix, k, max_steps=max_blocks, max_rounds=max_rounds,
        return_state=True)
    tail = blocked_lists_strategy(order_desc, t_sorted_desc, u, block_size,
                                  rank_by_item=layout.rank_by_item,
                                  ta_rounds=ta_rounds, m_real=m_real)
    return pruned_block_scan(targets, u, tail, k, max_steps=max_blocks,
                             max_rounds=max_rounds, init_state=state)


def _batched_two_phase_list_scan(targets, order_desc, t_sorted_desc, U, k,
                                 block_size, max_blocks, max_rounds, layout,
                                 ta_rounds, sign, dense, m_real=None):
    """Batch-native prefix phase chained into a vmapped gather tail.

    Phase 1 is :func:`repro.core.driver.batched_pruned_scan` over
    :func:`repro.core.strategies.batched_list_prefix_strategy` — ONE
    shared tile enumeration per step for the whole batch, per-query
    liveness/freshness keeping every counter sequential-faithful
    (DESIGN.md §11). The final :class:`BatchedScanState` is split into
    per-lane :class:`ScanState` s (each lane's ABSOLUTE block cursor is
    its gated ``steps`` counter) seeding the same vmapped gather-side
    tail the per-query path uses; a batch whose every query certified
    inside the prefix — virtually all of them — executes ZERO tail
    iterations, and a prefix-overflowing lane resumes exactly where its
    sequential scan would.
    """
    prefix = batched_list_prefix_strategy(
        layout, t_sorted_desc, U, block_size, sign=sign, dense=dense,
        ta_rounds=ta_rounds, m_real=m_real)
    _, bstate = batched_pruned_scan(
        U, prefix, k, targets.dtype, max_steps=max_blocks,
        max_rounds=max_rounds, return_state=True)
    B = U.shape[0]
    states = ScanState(
        step=bstate.steps,                       # [B] absolute block cursor
        top_vals=bstate.top_vals, top_ids=bstate.top_ids,
        visited=jnp.zeros((B, 1), bool),         # tail is fresh_mask-based
        n_scored=bstate.n_scored, rounds=bstate.rounds,
        lower=bstate.lower, upper=bstate.upper)

    def tail_one(u, st):
        tail = blocked_lists_strategy(
            order_desc, t_sorted_desc, u, block_size,
            rank_by_item=layout.rank_by_item, ta_rounds=ta_rounds,
            m_real=m_real)
        return pruned_block_scan(targets, u, tail, k, max_steps=max_blocks,
                                 max_rounds=max_rounds, init_state=st)

    return jax.vmap(tail_one)(U, states)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_size", "max_blocks", "sign",
                                    "dense"))
def blocked_topk_batched_native(
    targets: Array,
    order_desc: Array,
    t_sorted_desc: Array,
    U: Array,
    k: int,
    block_size: int = 256,
    max_blocks: int = -1,
    layout=None,
    sign: int = 0,
    dense: bool = False,
    m_real=None,
) -> TopKResult:
    """Batch-native BTA over the list-prefix layout (DESIGN.md §11).

    The batched counterpart of ``vmap(blocked_topk)``: one shared prefix
    tile per step for the whole batch, a single batch-level while_loop
    whose step count is the max live query's depth, per-query
    freshness/liveness so results AND ``n_scored``/``depth`` equal the
    per-query scan's. ``sign``/``dense`` are the batch's STATIC sign
    bucket (:func:`repro.core.strategies.sign_bucket`); the caller
    guarantees they match ``U`` and that ``layout`` has the needed
    side(s). Requires a layout whose prefix covers at least one block.
    """
    if layout is None or layout.prefix_steps(block_size) < 1:
        raise ValueError("blocked_topk_batched_native requires a "
                         "ListMajorLayout with >= 1 prefix block")
    if not layout.serves_sign(sign):
        raise ValueError(
            f"layout with sides {layout.sides!r} cannot serve sign "
            f"bucket {sign} (mixed batches need both directions)")
    k = min(k, targets.shape[0])
    res = _batched_two_phase_list_scan(
        targets, order_desc, t_sorted_desc, U, k, block_size, max_blocks,
        -1, layout, ta_rounds=False, sign=sign, dense=dense,
        m_real=m_real)
    return res._replace(depth=res.depth * block_size)


@functools.partial(jax.jit,
                   static_argnames=("k", "block_size", "max_blocks"))
def blocked_topk(
    targets: Array,
    order_desc: Array,
    t_sorted_desc: Array,
    u: Array,
    k: int,
    block_size: int = 256,
    max_blocks: int = -1,
    rank_desc: Optional[Array] = None,
    layout=None,
    m_real=None,
) -> TopKResult:
    """Exact top-K via the Block Threshold Algorithm (single query).

    Args:
      targets: ``[M, R]`` catalogue factors.
      order_desc / t_sorted_desc: the query-independent index
        (:class:`repro.core.index.TopKIndex` fields).
      u: ``[R]`` query.
      k: top-K size (static).
      block_size: list depth consumed per round (static). ``block_size=1``
        degenerates to the paper's TA round structure.
      max_blocks: optional round budget — the halted variant.
      rank_desc: optional inverse permutations
        (:attr:`repro.core.index.TopKIndex.rank_desc`); when given, dedup
        runs on cursor arithmetic and the O(M) visited bitmap disappears
        from the scan carry (identical results and counts, much cheaper
        per step).
      layout: optional :class:`repro.core.layout.ListMajorLayout`. Blocks
        inside the layout's prefix are then scored from contiguous
        ``[R, B, R]`` tiles (no row gathers) and the scan only falls back
        to gathers past the prefix — identical results and counts
        (DESIGN.md §7).
      m_real: optional TRACED real catalogue size when the index arrays
        (and ``layout.rank_by_item``) are padded to an M-bucket
        (DESIGN.md §10) — pad entries are never walked, scored, or
        counted, so results equal the unpadded scan bit for bit.
    """
    if layout is not None and layout.prefix_steps(block_size) > 0:
        res = _two_phase_list_scan(targets, order_desc, t_sorted_desc, u,
                                   k, block_size, max_blocks, -1, layout,
                                   ta_rounds=False, m_real=m_real)
    else:
        strategy = blocked_lists_strategy(order_desc, t_sorted_desc, u,
                                          block_size, rank_desc=rank_desc,
                                          m_real=m_real)
        res = pruned_block_scan(targets, u, strategy, k,
                                max_steps=max_blocks)
    # public depth unit is list depth, not blocks
    return res._replace(depth=res.depth * block_size)


def blocked_topk_batched(
    targets: Array,
    index: TopKIndex,
    U: Array,
    k: int,
    block_size: int = 256,
    max_blocks: int = -1,
) -> TopKResult:
    """vmap of :func:`blocked_topk` over a query batch ``U: [B, R]``.

    Each query carries its own bound state; the vmapped while_loop runs
    until the slowest query terminates (lockstep on TPU), which is the
    batched-serving semantics discussed in DESIGN.md §4. The driver's
    per-query liveness gating keeps ``n_scored``/``depth`` faithful to the
    sequential algorithm even for queries that certified early.
    """
    def one(u):
        return blocked_topk(targets, index.order_desc, index.t_sorted_desc,
                            u, k, block_size, max_blocks,
                            rank_desc=index.rank_desc)

    return jax.vmap(one)(U)


# ---------------------------------------------------------------------------
# Chunked TA: block-shaped execution, item-at-a-time accounting
# ---------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("k", "chunk", "max_rounds"))
def chunked_ta_topk(
    targets: Array,
    order_desc: Array,
    t_sorted_desc: Array,
    rank_desc: Array,
    u: Array,
    k: int,
    chunk: int = 32,
    max_rounds: int = -1,
    layout=None,
    m_real=None,
) -> TopKResult:
    """Exact TA whose rounds are processed ``chunk`` at a time.

    One driver step gathers and scores ``R * chunk`` candidates (one
    MXU-shaped pass), then replays the chunk as ``chunk`` sequential paper
    rounds with per-candidate prefix masking — so the returned
    ``n_scored``/``depth`` are identical to the ``chunk=1`` sequential
    algorithm (and to :func:`repro.core.threshold.threshold_topk_np`),
    while the wall-clock cost per round drops by ~``chunk``.

    ``max_rounds`` is the paper's halted-TA budget, enforced at ROUND
    granularity even mid-chunk. ``depth`` is returned in rounds
    (= list depth), the same unit as ``blocked_topk`` at ``block_size=1``.

    ``layout`` (a :class:`repro.core.layout.ListMajorLayout`) makes the
    rounds inside the layout prefix gather-free — contiguous tile slices
    and a per-query O(R*P) freshness scatter instead of row gathers and
    the O(R*M) key precompute — chaining into a gather-side tail only for
    scans that outlive the prefix. Counts stay sequential-faithful on
    both phases (DESIGN.md §7).

    ``m_real`` (traced) is the real catalogue size when the index arrays
    are M-bucket padded (DESIGN.md §10); rounds past it never execute.
    """
    if (layout is not None and chunk > 1
            and layout.prefix_steps(chunk) > 0):
        return _two_phase_list_scan(targets, order_desc, t_sorted_desc, u,
                                    k, chunk, -1, max_rounds, layout,
                                    ta_rounds=True, m_real=m_real)
    strategy = blocked_lists_strategy(order_desc, t_sorted_desc, u, chunk,
                                      rank_desc=rank_desc, ta_rounds=True,
                                      m_real=m_real)
    # at chunk=1 the strategy degenerates to the plain blocked scan, whose
    # halting budget is counted in (single-round) steps
    return pruned_block_scan(targets, u, strategy, k,
                             max_steps=max_rounds if chunk == 1 else -1,
                             max_rounds=max_rounds)


def chunked_ta_topk_batched(
    targets: Array,
    index: TopKIndex,
    U: Array,
    k: int,
    chunk: int = 32,
    max_rounds: int = -1,
) -> TopKResult:
    """vmap of :func:`chunked_ta_topk` over a query batch ``U: [B, R]``."""
    def one(u):
        return chunked_ta_topk(targets, index.order_desc,
                               index.t_sorted_desc, index.rank_desc, u, k,
                               chunk=chunk, max_rounds=max_rounds)

    return jax.vmap(one)(U)


@functools.partial(jax.jit,
                   static_argnames=("k", "chunk", "max_rounds", "sign",
                                    "dense"))
def chunked_ta_topk_batched_native(
    targets: Array,
    order_desc: Array,
    t_sorted_desc: Array,
    U: Array,
    k: int,
    chunk: int = 32,
    max_rounds: int = -1,
    layout=None,
    sign: int = 0,
    dense: bool = False,
    m_real=None,
) -> TopKResult:
    """Batch-native chunked TA over the list-prefix layout (DESIGN.md §11).

    The batched counterpart of ``vmap(chunked_ta_topk)``: the shared
    prefix tiles feed the driver's closed-form sequential-round
    recovery per lane, so each query's ``n_scored``/``depth`` equal the
    item-at-a-time paper algorithm's (and
    :func:`repro.core.threshold.threshold_topk_np`'s) exactly, while the
    whole batch shares one enumeration loop. ``sign``/``dense`` are the
    batch's static sign bucket, as in
    :func:`blocked_topk_batched_native`.
    """
    if layout is None or layout.prefix_steps(chunk) < 1:
        raise ValueError("chunked_ta_topk_batched_native requires a "
                         "ListMajorLayout with >= 1 prefix block")
    if not layout.serves_sign(sign):
        raise ValueError(
            f"layout with sides {layout.sides!r} cannot serve sign "
            f"bucket {sign} (mixed batches need both directions)")
    k = min(k, targets.shape[0])
    # chunk=1 degenerates to plain blocked steps (depth unit = rounds
    # either way); the halted budget then caps steps, as in the
    # per-query wrapper
    return _batched_two_phase_list_scan(
        targets, order_desc, t_sorted_desc, U, k, chunk,
        max_rounds if chunk == 1 else -1,
        max_rounds, layout, ta_rounds=chunk > 1, sign=sign, dense=dense,
        m_real=m_real)


# ---------------------------------------------------------------------------
# Norm-ordered Cauchy-Schwarz block pruning (beyond paper; exact)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "block_size", "max_blocks"))
def norm_pruned_topk_batched(
    targets_by_norm: Array,
    norm_order: Array,
    norms_sorted: Array,
    U: Array,
    k: int,
    block_size: int = 256,
    max_blocks: int = -1,
    m_real=None,
) -> TopKResult:
    """Batched-native norm scan: ONE shared tile per step for the batch.

    Unlike the list-based engines, the norm scan enumerates the SAME
    catalogue prefix in the same order for every query — so a lockstep
    batch never needs per-query gathers. Each step slices one contiguous
    ``[block, R]`` tile of the norm-ordered catalogue and scores the whole
    batch with a single ``[B, R] @ [R, block]`` matmul (the Pallas
    kernel's execution shape, in pure XLA; DESIGN.md §6). Per-query
    liveness gates every state update, so each query's
    ``n_scored``/``depth`` equal its own sequential scan's; the loop runs
    until the slowest live query certifies.

    ``m_real`` (traced) is the real catalogue size when the norm arrays
    are M-bucket padded (pad rows zero, norm 0 — sorted last;
    DESIGN.md §10): the tail block slides back against the real end, pad
    rows are masked from the merge and the counters, and the runtime
    step cap stops the loop exactly where the unpadded scan stops.

    Returns catalogue ids (rows are remapped through ``norm_order`` once,
    after the loop).
    """
    M, R = targets_by_norm.shape
    m = M if m_real is None else m_real
    B = U.shape[0]
    k = min(k, M)
    n_steps = -(-M // block_size)
    cap = n_steps if max_blocks < 0 else min(max_blocks, n_steps)
    cap_eff = cap if m_real is None else jnp.minimum(
        cap, -(-m_real // block_size))
    next_starts = jnp.minimum(
        (jnp.arange(n_steps, dtype=jnp.int32) + 1) * block_size, m - 1)
    bound_norms = norms_sorted[next_starts]              # [n_steps]
    u_norms = jnp.linalg.norm(U, axis=1)                 # [B]
    offs = jnp.arange(block_size, dtype=jnp.int32)
    neg_inf = jnp.asarray(float("-inf"), targets_by_norm.dtype)

    def cond(s):
        step, _, _, _, _, lower, upper = s
        return jnp.logical_and(step < cap_eff, jnp.any(lower < upper))

    def body(s):
        step, top_vals, top_ids, n_scored, depth, lower, upper = s
        live = lower < upper                             # [B]
        d0 = step * block_size
        start = jnp.maximum(0, jnp.minimum(d0, m - block_size))
        tile = jax.lax.dynamic_slice_in_dim(targets_by_norm, start,
                                            block_size)  # [block, R]
        scores = jnp.matmul(U, tile.T,
                            precision=SCORE_PRECISION)   # [B, block]
        rows = start + offs
        # tail block slides back (mask re-reads); pad rows masked too
        valid = jnp.logical_and(rows >= d0, rows < m)
        masked = jnp.where(valid[None, :], scores, neg_inf)
        new_vals, new_ids = merge_block_into_carry_batched(
            top_vals, top_ids, masked, rows, k)
        fresh = jnp.sum(valid).astype(jnp.int32)
        gate = live[:, None]
        return (step + 1,
                jnp.where(gate, new_vals, top_vals),
                jnp.where(gate, new_ids, top_ids),
                jnp.where(live, n_scored + fresh, n_scored),
                jnp.where(live, depth + 1, depth),
                jnp.where(live, new_vals[:, k - 1], lower),
                jnp.where(live, u_norms * bound_norms[step], upper))

    init = (jnp.int32(0),
            jnp.full((B, k), float("-inf"), targets_by_norm.dtype),
            jnp.full((B, k), -1, jnp.int32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32),
            jnp.full((B,), float("-inf"), targets_by_norm.dtype),
            jnp.full((B,), jnp.inf, targets_by_norm.dtype))
    if cap >= 1:
        init = body(init)       # block 0 is unconditionally live: unroll
    _, top_vals, top_ids, n_scored, depth, _, upper = jax.lax.while_loop(
        cond, body, init)
    ids = jnp.where(top_ids >= 0,
                    norm_order[jnp.clip(top_ids, 0, M - 1)], -1)
    # certificate tightening (as in the shared driver): a lane that
    # consumed every REAL block has nothing un-enumerated — vacuous -inf
    # bound; only a budget halt keeps the live block bound
    full_steps = -(-m // block_size)
    upper = jnp.where(depth >= full_steps, neg_inf, upper)
    return TopKResult(top_vals, ids, n_scored, depth * block_size,
                      upper=upper)


@functools.partial(jax.jit, static_argnames=("k", "block_size", "max_blocks"))
def norm_pruned_topk(
    targets: Array,
    norm_order: Array,
    norms_sorted: Array,
    u: Array,
    k: int,
    block_size: int = 256,
    max_blocks: int = -1,
    targets_by_norm: Optional[Array] = None,
    m_real=None,
) -> TopKResult:
    """Exact top-K scanning blocks in decreasing-norm order.

    Block ``b`` covers items ``norm_order[b*B:(b+1)*B]`` (a *contiguous*
    gather). Every unseen score is bounded by ``||u|| * norms_sorted[b*B]``;
    once the running K-th best exceeds that, no later block can contribute.
    Best when the catalogue norm spectrum decays (CF popularity, PLS factor
    scales); degenerates to a full scan for constant-norm catalogues
    (e.g. cosine-normalised items), where BTA should be used instead.

    ``max_blocks`` is the uniform halted variant (same contract as
    :func:`blocked_topk`). ``targets_by_norm``
    (:attr:`repro.core.index.TopKIndex.targets_by_norm`) turns the per-
    block row gather into a contiguous slice + matvec — same results,
    Pallas-layout memory traffic. ``m_real`` (traced) is the real
    catalogue size when the norm arrays are M-bucket padded
    (DESIGN.md §10).
    """
    strategy = norm_block_strategy(norm_order, norms_sorted, u, block_size,
                                   targets_by_norm=targets_by_norm,
                                   m_real=m_real)
    res = pruned_block_scan(targets, u, strategy, k, max_steps=max_blocks)
    if targets_by_norm is not None and targets.shape[0] >= block_size:
        # the slice path scans over norm-ordered ROW numbers (no id gather
        # inside the loop); map the k winners back to catalogue ids once
        m = targets.shape[0]
        ids = jnp.where(res.indices >= 0,
                        norm_order[jnp.clip(res.indices, 0, m - 1)], -1)
        res = res._replace(indices=ids)
    return res._replace(depth=res.depth * block_size)

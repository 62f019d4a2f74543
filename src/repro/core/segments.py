"""Streaming catalogue: segmented (base + delta) exact top-K (DESIGN.md §9).

The paper's TA/BTA/norm pruning assumes a *static* catalogue: the sorted
lists, the norm order, and every layout in :mod:`repro.core.layout` are
built offline. A production retrieval tier must absorb item inserts,
updates, and deletions without a full index rebuild per mutation and
WITHOUT giving up the paper's exactness guarantee. This module is the
LSM-style answer:

* **Base segment** — an immutable snapshot of the catalogue: a normal
  :class:`repro.core.engines.EngineContext` (index, layouts, compile
  cache) plus the row -> global-id map. Queries run ANY registry engine
  over it, so every pruned scan in the repo is streaming-capable without
  touching the engines themselves.
* **Delta segment** — a fixed-capacity append buffer of inserted target
  rows. It is never indexed: every query scores the live delta slots
  densely with ONE ``[B, R] @ [R, D]`` matmul (exact trivially). The
  device view of the buffer is padded to a power-of-four occupancy
  bucket, so an insert changes array *contents*, never compiled
  *shapes* — zero retraces per insert once the buckets are warm
  (:meth:`warm`).
* **Tombstones** — deletes (and the delete half of updates) mark the
  victim row dead wherever it lives: a ``[M_base]`` mask over the base
  snapshot, a per-slot mask over the delta. The base fetch is
  TOMBSTONE-ADAPTIVE: plain ``k`` while the snapshot has no dead rows
  (the common warmed compile key — inserts never retrace), and the
  OVER-FETCHED ``k + reserve`` rung (also pre-warmed) the moment
  tombstones exist, so a dead row in the top-``k`` costs nothing. The
  merge tail counts the tombstoned rows that landed in the fetched
  slice; only when some query's dropped count exceeds its over-fetch
  margin (``dropped > k_base - k`` — more than ``reserve`` dead rows
  inside ONE query's top slice) does the fetch climb an escalation
  ladder (x4 per rung). A rung is exact as soon as the margin holds:
  at least ``k`` live base candidates survived the drop and every live
  row outside the fetched slice scores below all of them — one line
  per rung, and a full-base fetch is unconditionally exact
  (DESIGN.md §9).
* **Merge** — the dropped-and-resorted base list and each delta
  segment's dense scores fold through the SAME two-stage merge helpers
  every engine already uses (:func:`repro.core.driver.merge_topk_sorted`
  via :func:`repro.core.driver.merge_block_into_carry_batched`), so the
  result is exact by construction at any mutation rate.
* **Compaction** — when the delta fills (or tombstones cross a
  fraction of the base) the live rows of base + delta are folded into a
  FRESH snapshot (new index, new layouts, readied before the swap)
  under a monotonically increasing ``version``. The build can run on a
  background thread (``compact_async=True``): queries keep serving the
  old snapshot + a frozen delta + a fresh active delta until the swap,
  and deletes that land during the build are re-applied to the new
  snapshot at swap time (``pending dead``), so no mutation is ever
  lost. Compaction is COMPILE-FREE under the argument-passing engine
  contract (DESIGN.md §10): engines take the snapshot state — layout
  pytrees, index arrays, the catalogue itself, padded to a power-of-two
  M-bucket — as runtime ARGUMENTS of module-level executors whose
  compile keys carry no snapshot identity, so the new snapshot
  re-dispatches every existing trace (``stats.engine_compiles_total``
  records the traces a build into a never-warmed bucket pays, off the
  query path). In-flight calls hold references to the old snapshot's
  pytrees, which stay valid until released; the one closure-compiled
  engine left (``pallas``) still keys its per-context cache by
  ``EngineContext.version``, so even there an executable traced against
  snapshot v can never be fed snapshot v+1's arrays.

Per-query accounting extends the paper's cost metric to the delta:
``n_scored`` adds the number of LIVE delta slots scored (the dense
matmul's useful work; dead and padding lanes are masked, not candidates)
and ``depth`` stays the base engine's depth.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import faults
from repro.core.driver import NEG_INF, merge_block_into_carry_batched
from repro.core.engines import (Engine, EngineContext, batch_bucket,
                                pad_to_bucket)
from repro.core.naive import SCORE_PRECISION, TopKResult
from repro.core.sharded import shard_fold_topk

Array = jnp.ndarray

# Named fault points (DESIGN.md §12): no-ops until a test arms them via
# repro.core.faults. The seams cover exactly the failure modes the
# recovery logic below exists for.
FAULT_BUILD = faults.register_point(
    "compaction.build",
    "raise inside the compaction builder before the snapshot swap")
FAULT_STALL = faults.register_point(
    "compaction.stall",
    "sleep inside the compaction builder (slow/stuck build)")
FAULT_WARM = faults.register_point(
    "compaction.warm",
    "raise during the post-build readiness warmup")
FAULT_DELTA_OVERFLOW = faults.register_point(
    "delta.overflow",
    "report the active delta as full on an append (mutation burst)")

#: Default delta-buffer capacity (rows). Power of two; a full delta
#: triggers compaction. 256 keeps warmup to 9 tail buckets while giving
#: the hot path hundreds of mutations between rebuilds.
DEFAULT_DELTA_CAPACITY = 256

#: Compact when dead base rows exceed this fraction of the base — more
#: tombstones mean more escalated (over-fetched) reruns, and past this
#: point re-packing is cheaper than dragging dead rows through every scan.
DEFAULT_TOMBSTONE_COMPACT_FRACTION = 0.25

#: Absolute tombstone count that triggers compaction regardless of the
#: base size. Bounds the escalated over-fetch (and therefore the number
#: of distinct escalated compile shapes) on delete-heavy streams against
#: large catalogues, where the fraction threshold alone would let the
#: over-fetch grow into the thousands. ``None`` couples it to
#: ``2 * delta_capacity`` — tombstone pressure compacts on the same scale
#: as append pressure.
DEFAULT_MAX_TOMBSTONES = None

#: First rung of the escalation ladder: a tombstone hit in the base
#: top-``k`` reruns at ``k + reserve`` (pre-warmed — the common retry is
#: retrace-free), then climbs x4 per rung only while some query's dropped
#: count exceeds the over-fetch margin (the per-rung exactness check).
DEFAULT_OVERFETCH_RESERVE = 32

#: Ladder growth factor between escalation rungs.
ESCALATION_STEP = 4


def delta_bucket(n: int) -> int:
    """Power-of-FOUR device-view bucket for ``n`` delta rows (min 1).

    Coarser than the batch buckets on purpose: each bucket is one tail
    compile at warmup time and the wasted lanes cost only a slice of the
    tiny ``[B, D]`` delta matmul, so x4 steps halve the number of compiled
    shapes for the same capacity.
    """
    b = 1
    while b < n:
        b <<= 2
    return b


@dataclasses.dataclass(frozen=True)
class QueryInfo:
    """Side-channel accounting for one segmented query batch.

    Attributes:
      delta_scored: live delta slots dense-scored per query (added into
        the returned ``TopKResult.n_scored``).
      overfetch_k: the ``k`` the AUTHORITATIVE base engine run used —
        plain ``k`` with no tombstones, ``k + reserve`` while any base
        row is dead, higher (x4 per climb) only when a query had more
        than ``reserve`` dead rows inside its fetched slice.
      n_segments: delta segments scored (0 pristine, 1 steady state,
        2 while a background compaction has a frozen delta in flight).
      version: snapshot version the batch was served from.
      retried: True when the first fetch was discarded and the batch
        re-ran up the escalation ladder (dropped count exceeded the
        over-fetch margin).
    """

    delta_scored: int
    overfetch_k: int
    n_segments: int
    version: int
    retried: bool = False


@dataclasses.dataclass
class SegmentStats:
    """Cumulative mutation/compaction counters (monotonic).

    ``engine_compiles_total`` counts the ENGINE traces a compaction
    build needed to make its new snapshot serveable at the warmed
    shapes (attributed from the new context's own ``trace_counts`` —
    traces a concurrent serving thread causes are never charged here).
    Under the argument-passing contract (DESIGN.md §10) a compaction
    into a warmed M-bucket contributes 0 — the acceptance criterion the
    streaming bench asserts; a build into a bucket nobody warmed pays
    its compiles here, on the build (background in ``compact_async``
    mode), never on the query hot path. ``headroom_compiles_total``
    separately counts the traces each build invests in the NEXT
    M-bucket (renewing the server's boot headroom so the guarantee is
    standing) — future capacity, not a cost of serving this snapshot.
    ``compaction_s_total``/``last_compaction_s`` time the whole build
    (live-row fold + index + layouts + readiness + swap).
    """

    n_inserts: int = 0
    n_deletes: int = 0
    n_updates: int = 0
    n_compactions: int = 0
    n_failed_compactions: int = 0
    max_delta_occupancy: int = 0
    engine_compiles_total: int = 0
    headroom_compiles_total: int = 0
    compaction_s_total: float = 0.0
    last_compaction_s: float = 0.0
    # recovery counters (DESIGN.md §12): build attempts launched while
    # recovering from a failure, sync compactions forced by the L0 chain
    # cap, watchdog detections of a stuck build thread, and the longest
    # sealed-segment chain ever observed
    n_build_retries: int = 0
    n_forced_sync_compactions: int = 0
    n_stuck_builds: int = 0
    max_l0_chain: int = 0
    # LSM ladder counters (DESIGN.md §15): zero on the single-level
    # catalogue. Folds are the cheap L0 -> per-shard-L1 moves that
    # REPLACE most full base rebuilds; their failures have their own
    # retry/backoff stream (mirroring the build machinery) so the
    # mutation_stats schema covers both recovery paths.
    n_l1_folds: int = 0
    n_failed_l1_folds: int = 0
    n_l1_fold_retries: int = 0
    l1_fold_s_total: float = 0.0


class Snapshot:
    """One immutable base segment: an EngineContext + the row/gid maps.

    The target ROWS never change after construction (engines, layouts,
    and the jit cache all hold them); only the tombstone mask mutates,
    and it mutates FUNCTIONALLY on the device side (``.at[].set`` builds
    a new array), so an in-flight jitted call that captured the previous
    mask keeps a valid pytree.
    """

    def __init__(self, targets_np: np.ndarray, gids_np: np.ndarray,
                 version: int, ctx: EngineContext):
        self.targets_np = targets_np          # [Mb, R] float32 (host copy)
        self.gids_np = gids_np.astype(np.int64)
        self.version = int(version)
        self.ctx = ctx
        mb = targets_np.shape[0]
        self.gids_dev = jnp.asarray(gids_np.astype(np.int32))
        self.dead_np = np.zeros((mb,), bool)
        self.dead_dev = jnp.zeros((mb,), bool)
        self.n_dead = 0
        self.gid_to_row = {int(g): i for i, g in enumerate(self.gids_np)}
        # identity snapshots (gid i lives at row i) can serve the
        # never-mutated fast path with raw engine indices
        self.identity = bool(
            mb == 0 or np.array_equal(self.gids_np, np.arange(mb)))

    @property
    def num_rows(self) -> int:
        return int(self.targets_np.shape[0])

    def kill_rows(self, rows: Sequence[int]) -> None:
        rows = np.asarray(list(rows), np.int32)
        fresh = ~self.dead_np[rows]
        self.dead_np[rows] = True
        self.dead_dev = self.dead_dev.at[rows].set(True)
        self.n_dead += int(np.sum(fresh))


class DeltaSegment:
    """Fixed-capacity append buffer of (row, gid) pairs with a dead mask.

    The device view is padded to the power-of-four bucket covering the
    current occupancy (:func:`delta_bucket`), so appends within a bucket
    re-upload contents but never change compiled shapes. ``seal()``
    freezes the segment for a background compaction — further appends
    are a bug (asserted).
    """

    def __init__(self, capacity: int, rank: int):
        cap = batch_bucket(capacity)          # power-of-two storage
        self.capacity = cap
        self.rows = np.zeros((cap, rank), np.float32)
        self.gids = np.full((cap,), -1, np.int64)
        self.dead = np.zeros((cap,), bool)
        self.count = 0
        self.sealed = False
        self._pos: Dict[int, int] = {}        # live gid -> slot
        self._dev: Optional[Tuple[Array, Array, Array]] = None

    @property
    def n_live(self) -> int:
        return self.count - int(np.sum(self.dead[:self.count]))

    @property
    def full(self) -> bool:
        return self.count >= self.capacity

    def append(self, row: np.ndarray, gid: int) -> int:
        assert not self.sealed, "appending to a sealed (compacting) delta"
        assert self.count < self.capacity
        slot = self.count
        self.rows[slot] = row
        self.gids[slot] = gid
        self._pos[gid] = slot
        self.count += 1
        self._dev = None
        return slot

    def kill(self, gid: int) -> None:
        slot = self._pos.pop(gid)
        self.dead[slot] = True
        self._dev = None

    def seal(self) -> None:
        self.sealed = True
        self._dev = None          # rebuild the view at the capacity bucket

    def live_rows(self) -> Tuple[np.ndarray, np.ndarray]:
        live = ~self.dead[:self.count]
        return (self.rows[:self.count][live].copy(),
                self.gids[:self.count][live].copy())

    def device_view(self) -> Tuple[Array, Array, Array]:
        """``(rows [D, R], gids [D], live [D])`` padded to the pow4 bucket.

        A SEALED segment always presents the full-capacity bucket: the
        two-segment tail shapes warmed ahead of time are
        ``(capacity, active_bucket)``, so mid-build queries stay on
        compiled executables even when a tombstone-threshold compaction
        froze a partially full delta (the extra lanes cost one slice of
        the tiny delta matmul, not a compile).
        """
        if self._dev is None:
            d = (self.capacity if self.sealed
                 else min(delta_bucket(max(self.count, 1)), self.capacity))
            live = np.zeros((d,), bool)
            live[:self.count] = ~self.dead[:self.count]
            self._dev = (jnp.asarray(self.rows[:d]),
                         jnp.asarray(self.gids[:d].astype(np.int32)),
                         jnp.asarray(live))
        return self._dev


def _segmented_tail(base_vals, tomb, base_gids, U, segs, l1=None, *, k, kb):
    """Drop tombstones from the base top-``kb``, fold in the delta segments.

    Pure function of device arrays (jitted per shape by the catalogue's
    tail cache). ``base_vals [B, kb]`` is the base engine's exact
    top-``kb`` (descending), ``tomb [B, kb]`` flags the tombstoned
    entries, ``base_gids [B, kb]`` carries the global ids (``-1`` for
    engine padding). The caller resolves both from the snapshot's
    ``[M_base]`` mask/gid arrays EAGERLY — two primitive gathers — so
    nothing in this program depends on the base size and every compiled
    tail is reused across snapshot versions (a compaction adds ZERO tail
    compiles). Masking dead rows to ``-inf`` breaks the sort, so the
    survivors are re-topped to ``k`` lanes (``kb`` is at most
    ``k + bucket(n_dead)`` — a few dozen lanes, nowhere near the
    ``K + C`` concat pattern the driver bans). Each delta segment then
    merges through the shared two-stage helper: block-local
    ``top_k(D -> K)`` + the O(K) sorted merge.

    Returns ``(values, gids, n_dropped)`` — ``n_dropped [B]`` counts the
    TOMBSTONED base rows that sat inside this top-``kb`` (engine ``-1``
    padding is not a drop). The optimistic query path (``kb == k``)
    reads it to decide whether the over-fetched escalation is needed at
    all: 0 dropped means nothing was lost and the result is exact as is.

    ``l1`` is the LSM catalogue's per-shard L1 tier (DESIGN.md §15):
    ``None`` for the single-level catalogue, else a shard-major stack
    ``(rows [S, C, R], gids [S, C], live [S, C])`` padded to the FIXED
    per-shard slab capacity, so the whole tier is one compile shape
    regardless of occupancy. It folds in through the two-level
    :func:`repro.core.sharded.shard_fold_topk` merge — each shard's
    dense block is cut to K locally, then K candidates per shard cross
    the O(K) sorted merge — before the (newer) L0/delta segments, so the
    scan-loop merge order mirrors the ladder's age order.
    """
    drop = jnp.logical_or(base_gids < 0, tomb)
    n_dropped = jnp.sum(tomb, axis=1, dtype=jnp.int32)
    v = jnp.where(drop, NEG_INF, base_vals)
    gi = jnp.where(drop, -1, base_gids)
    v, pos = jax.lax.top_k(v, min(k, kb))
    gi = jnp.take_along_axis(gi, pos, axis=1)
    if kb < k:                                # base smaller than k: pad
        b = v.shape[0]
        v = jnp.concatenate(
            [v, jnp.full((b, k - kb), NEG_INF, v.dtype)], axis=1)
        gi = jnp.concatenate(
            [gi, jnp.full((b, k - kb), -1, gi.dtype)], axis=1)
    if l1 is not None:
        l1_rows, l1_gids, l1_live = l1
        # one [B, R] x [S, C, R] einsum scores every shard's slab densely
        l1_scores = jnp.einsum("br,scr->sbc", U, l1_rows,
                               precision=SCORE_PRECISION)
        l1_scores = jnp.where(l1_live[:, None, :], l1_scores, NEG_INF)
        v, gi = shard_fold_topk(v, gi, l1_scores, l1_gids, k)
    for rows, gid, live in segs:
        scores = jnp.matmul(U, rows.T,        # [B, D] — one dense matmul
                            precision=SCORE_PRECISION)
        scores = jnp.where(live[None, :], scores, NEG_INF)
        v, gi = merge_block_into_carry_batched(v, gi, scores, gid, k)
    return v, gi, n_dropped


class SegmentedCatalogue:
    """Base snapshot + delta buffer + tombstones: exact streaming top-K.

    Thread-safe for one writer + concurrent readers (a single lock
    guards the mutable maps; queries copy references out under it and
    compute outside it). All mutation entry points may trigger
    compaction; queries never do.

    Args:
      targets: initial ``[M, R]`` catalogue (global ids ``0..M-1``).
      delta_capacity: delta-buffer rows (rounded up to a power of two).
      tombstone_compact_fraction: compact once dead base rows exceed
        this fraction of the base.
      max_tombstones: absolute dead-row count that triggers compaction
        (bounds the escalated over-fetch on delete-heavy streams).
        ``None`` (default) uses ``2 * delta_capacity``.
      overfetch_reserve: first escalation rung — a tombstone hit in the
        base top-``k`` reruns at ``k + reserve`` (pre-warmed), climbing
        x4 per rung only while the per-query dropped count exceeds the
        over-fetch margin.
      compact_async: build replacement snapshots on a background thread
        (queries keep serving base + frozen delta + active delta until
        the swap). Synchronous by default — deterministic for tests.
      max_l0_segments: cap on the sealed-segment (L0) chain. Mutations
        that would grow the chain past it force a SYNCHRONOUS compaction
        (blocking that mutation call) instead of letting query latency
        degrade unboundedly under sustained build failure
        (DESIGN.md §12).
      build_retry_limit: consecutive failed builds after which automatic
        retries stop (an explicit :meth:`compact` or the chain cap still
        force attempts).
      build_backoff_s: initial retry backoff after a failed build,
        doubling per consecutive failure up to ``build_backoff_max_s``.
      build_watchdog_s: a background build older than this is flagged as
        STUCK (``SegmentStats.n_stuck_builds``) by the watchdog check
        that runs on query/mutation entry. Detection only — the build
        thread is never killed (it may still finish and swap in).
      ctx_kwargs: forwarded to every :class:`EngineContext` this
        catalogue builds (``block_size``, ``prefix_depth``, ...).
    """

    def __init__(self, targets, *, delta_capacity: int = DEFAULT_DELTA_CAPACITY,
                 tombstone_compact_fraction: float =
                 DEFAULT_TOMBSTONE_COMPACT_FRACTION,
                 max_tombstones: Optional[int] = DEFAULT_MAX_TOMBSTONES,
                 overfetch_reserve: int = DEFAULT_OVERFETCH_RESERVE,
                 compact_async: bool = False,
                 max_l0_segments: int = 4,
                 build_retry_limit: int = 3,
                 build_backoff_s: float = 0.05,
                 build_backoff_max_s: float = 2.0,
                 build_watchdog_s: float = 30.0,
                 auto_retry: bool = False, **ctx_kwargs):
        T = np.ascontiguousarray(np.asarray(targets, np.float32))
        self.rank = int(T.shape[1])
        self.delta_capacity = batch_bucket(max(int(delta_capacity), 1))
        self.tombstone_compact_fraction = float(tombstone_compact_fraction)
        if max_tombstones is None:
            max_tombstones = 2 * self.delta_capacity
        self.max_tombstones = int(max_tombstones)
        self.overfetch_reserve = batch_bucket(max(int(overfetch_reserve), 1))
        self.compact_async = bool(compact_async)
        self.max_l0_segments = max(int(max_l0_segments), 1)
        self.build_retry_limit = max(int(build_retry_limit), 0)
        self.build_backoff_s = float(build_backoff_s)
        self.build_backoff_max_s = float(build_backoff_max_s)
        self.build_watchdog_s = float(build_watchdog_s)
        # auto_retry=True makes a FAILED async build schedule its own
        # timed retry (backoff-spaced, bounded by build_retry_limit), so
        # a quiet catalogue heals without waiting for the next mutation.
        # Off by default: retries then ride the next compaction trigger,
        # preserving the legacy "flush() after a failure is passive"
        # semantics tests rely on.
        self.auto_retry = bool(auto_retry)
        self._ctx_kwargs = dict(ctx_kwargs)
        self._lock = threading.RLock()
        self._snapshot = Snapshot(
            T, np.arange(T.shape[0], dtype=np.int64), 0,
            EngineContext(T, version=0, **self._ctx_kwargs))
        self._delta = DeltaSegment(self.delta_capacity, self.rank)
        # sealed segments awaiting compaction (an L0 chain: normally one,
        # more only if a background build failed — nothing is ever lost,
        # sealed segments stay queryable and fold on the next compaction)
        self._frozen: List[DeltaSegment] = []
        self._next_gid = int(T.shape[0])
        self._pending_dead: set = set()       # deletes landed mid-build
        self._build_thread: Optional[threading.Thread] = None
        self._tail_cache: Dict[tuple, Callable] = {}
        self.trace_counts: Dict[str, int] = {}
        self.stats = SegmentStats()
        self.last_build_error: Optional[BaseException] = None
        # build-failure recovery state machine (DESIGN.md §12)
        self._consec_build_failures = 0
        self._retry_not_before = 0.0          # monotonic deadline (backoff)
        self._last_backoff_s = 0.0
        self._retry_timer: Optional[threading.Timer] = None
        self._build_started_at: Optional[float] = None
        self._watchdog_flagged = False
        self._warm_spec: Optional[tuple] = None
        # highest M-bucket any warmup has traced (DESIGN.md §10): the
        # headroom-renewal memo, so the pre-pay happens once per doubling
        self._headroom_bucket = 0
        # mutation epoch: bumped under the lock by EVERY visible mutation
        # (append/tombstone/update AND the compaction swap, which applies
        # pending deletes). (version, epoch) is the result-cache token —
        # version alone is NOT enough, deltas mutate visibility without
        # bumping it (DESIGN.md §13).
        self._epoch = 0
        self._invalidation_listeners: List[Callable[[], None]] = []

    # -- introspection -------------------------------------------------------

    @property
    def snapshot(self) -> Snapshot:
        return self._snapshot

    @property
    def version(self) -> int:
        return self._snapshot.version

    def _segments(self) -> List[DeltaSegment]:
        """Sealed segments (oldest first) + the active delta. Lock held."""
        return [*self._frozen, self._delta]

    def cache_token(self) -> Tuple[int, int]:
        """``(snapshot version, mutation epoch)`` — the identity of the
        CURRENTLY VISIBLE catalogue contents. Any visible mutation
        changes the token, so a result cached under a token captured
        BEFORE its scan dispatched can never serve contents older than
        that token. Compare tokens only for equality: a swap bumps
        version while epoch keeps counting."""
        with self._lock:
            return (self._snapshot.version, self._epoch)

    def add_invalidation_listener(self, fn: Callable[[], None]) -> None:
        """Register ``fn`` to run after every visible mutation (append /
        tombstone / update / compaction swap — including a swap that
        applied mid-build deletes). Listeners may fire while a mutating
        caller still holds the catalogue lock (the synchronous
        compaction path), so they MUST NOT call back into the catalogue;
        bumping a flag or clearing a cache's own structures is the
        intended use."""
        with self._lock:
            self._invalidation_listeners.append(fn)

    def _bump_epoch_locked(self, kind: str) -> None:
        self._epoch += 1
        # journal the new (version, epoch) identity under the catalogue
        # lock — obs emission takes only its own lock, never calls back
        # (the same constraint invalidation listeners live under)
        obs.on_epoch_bump(kind, self._snapshot.version, self._epoch)

    def _notify_invalidation(self) -> None:
        with self._lock:
            listeners = list(self._invalidation_listeners)
        for fn in listeners:
            fn()

    @property
    def delta_occupancy(self) -> int:
        with self._lock:
            return sum(seg.count for seg in self._segments())

    @property
    def n_tombstones(self) -> int:
        with self._lock:
            return self._snapshot.n_dead + sum(
                int(np.sum(seg.dead[:seg.count]))
                for seg in self._segments())

    @property
    def num_live(self) -> int:
        with self._lock:
            return (self._snapshot.num_rows - self._snapshot.n_dead
                    + sum(seg.n_live for seg in self._segments()))

    @property
    def pristine(self) -> bool:
        """No mutation is visible: raw engine results need no rewriting."""
        with self._lock:
            return (self._snapshot.identity and self._snapshot.n_dead == 0
                    and not self._frozen and self._delta.count == 0)

    @property
    def l0_chain_len(self) -> int:
        """Sealed segments currently awaiting compaction."""
        with self._lock:
            return len(self._frozen)

    # -- L1-tier hooks (no-ops here; the LSM ladder overrides them) ----------
    #
    # The single-level catalogue has no L1 tier: these hooks keep the
    # query/warm/stats plumbing shared with
    # :class:`repro.core.lsm.ShardedLsmCatalogue` (DESIGN.md §15)
    # instead of forking the query path.

    def _l1_stack_locked(self):
        """Stacked per-shard L1 device views, or ``None``. Lock held."""
        return None

    def _l1_live_locked(self) -> int:
        """Live rows resident in the L1 tier. Lock held."""
        return 0

    def _warm_l1_variants(self):
        """L1 operands :meth:`warm` compiles tails for: ``(spec, dummy)``
        pairs, where the single-level catalogue has only the no-tier
        variant."""
        return (((), None),)

    @property
    def n_shards(self) -> int:
        """L1 shard count (0: single-level, no L1 tier)."""
        return 0

    @property
    def l1_rows(self) -> int:
        """Live rows currently resident in the per-shard L1 tier."""
        return 0

    @property
    def consecutive_fold_failures(self) -> int:
        """Current L0->L1 fold failure streak (0 on a healthy ladder)."""
        return 0

    @property
    def fold_backoff_s(self) -> float:
        """Backoff the next ordinary fold retry is waiting out."""
        return 0.0

    def _chain_pressure_locked(self) -> int:
        """Sealed segments counted against ``max_l0_segments``. The LSM
        ladder overrides this to EXCLUDE L1 runs parked in the chain by
        an in-flight promotion: back-pressure exists to bound the extra
        per-query dense scans a FAILING build accumulates, and a
        promotion scans the same rows queries were already scoring
        through the stacked L1 path — no new pressure. Lock held."""
        return len(self._frozen)

    @property
    def consecutive_build_failures(self) -> int:
        with self._lock:
            return self._consec_build_failures

    @property
    def current_backoff_s(self) -> float:
        """The backoff the NEXT automatic retry is waiting out (0 when
        the last build succeeded)."""
        with self._lock:
            return self._last_backoff_s if self._consec_build_failures \
                else 0.0

    @property
    def retry_pending(self) -> bool:
        """True while an automatic post-failure retry is scheduled."""
        with self._lock:
            return self._retry_timer is not None

    def check_watchdog(self) -> bool:
        """Flag (once per build) an in-flight build exceeding the
        watchdog threshold. Returns True while the build is overdue.

        Detection only: the thread is never killed — a stalled build may
        still finish and swap in; the counter tells the operator that
        queries are meanwhile dragging an L0 chain.
        """
        with self._lock:
            started = self._build_started_at
            if self._build_thread is None or started is None:
                return False
            if time.monotonic() - started <= self.build_watchdog_s:
                return False
            if not self._watchdog_flagged:
                self._watchdog_flagged = True
                self.stats.n_stuck_builds += 1
                obs.on_compaction(
                    "stuck", version=self._snapshot.version,
                    overdue_s=time.monotonic() - started)
            return True

    def _live_concat_locked(self, snap: Snapshot, segs
                            ) -> Tuple[np.ndarray, np.ndarray]:
        """Base live rows + each segment's live rows, concatenated.

        THE liveness fold — shared by :meth:`as_dense` (the oracle view)
        and compaction (the rows the new snapshot indexes), so the two
        can never disagree about what is alive. Lock held.
        """
        parts_r: List[np.ndarray] = [snap.targets_np[~snap.dead_np]]
        parts_g: List[np.ndarray] = [snap.gids_np[~snap.dead_np]]
        for seg in segs:
            if seg.count:
                r, g = seg.live_rows()
                parts_r.append(r)
                parts_g.append(g)
        return (np.concatenate(parts_r, axis=0),
                np.concatenate(parts_g, axis=0))

    def as_dense(self) -> Tuple[np.ndarray, np.ndarray]:
        """A consistent ``(rows [N, R], gids [N])`` view of every LIVE item.

        What a from-scratch rebuild would index — the oracle the
        exactness tests and the streaming benchmark compare against.
        """
        with self._lock:
            return self._live_concat_locked(self._snapshot,
                                            self._segments())

    # -- mutations -----------------------------------------------------------

    def _locate(self, gid: int):
        """(where, segment-or-row) for a LIVE gid; KeyError if not live."""
        if gid in self._delta._pos:
            return "delta", self._delta
        for frozen in self._frozen:
            if gid in frozen._pos:
                return "frozen", frozen
        row = self._snapshot.gid_to_row.get(gid)
        if row is not None and not self._snapshot.dead_np[row]:
            return "base", row
        raise KeyError(f"gid {gid} is not a live catalogue item")

    def _kill_located(self, located) -> None:
        """Apply a validated batch of (gid, where, seg-or-row) kills.

        Base kills are BATCHED into one ``kill_rows`` call (one device
        mask update per mutation call, not per item). Lock held.
        """
        base_rows: List[int] = []
        for gid, where, seg in located:
            if where == "base":
                base_rows.append(seg)
                if self._build_thread is not None:
                    self._pending_dead.add(gid)
            else:
                seg.kill(gid)
                if where == "frozen":
                    self._pending_dead.add(gid)
        if base_rows:
            self._snapshot.kill_rows(base_rows)

    def _note_delta_peak(self) -> None:
        self.stats.max_delta_occupancy = max(
            self.stats.max_delta_occupancy, self._delta.count)

    def _validate_rows(self, rows, what: str) -> np.ndarray:
        """Shared mutation-input validation: shape, rank, finiteness.

        A NaN/Inf row would poison every score it participates in (NaN
        propagates through the matmul and breaks the sort), so it is
        rejected up front with a clear error instead of producing silent
        garbage downstream.
        """
        R = np.atleast_2d(np.asarray(rows, np.float32))
        if R.ndim != 2:
            raise ValueError(
                f"{what} must be [R] or [N, R], got shape {R.shape}")
        if R.shape[1] != self.rank:
            raise ValueError(f"rank mismatch: {R.shape[1]} != {self.rank}")
        if not np.all(np.isfinite(R)):
            bad = int(np.flatnonzero(~np.all(np.isfinite(R), axis=1))[0])
            raise ValueError(
                f"{what} contain non-finite values (first bad row: {bad}); "
                "NaN/Inf rows would corrupt every top-K they score in")
        return R

    def add_targets(self, rows) -> np.ndarray:
        """Append rows; returns their freshly assigned global ids."""
        R = self._validate_rows(rows, "inserted rows")
        out = np.empty((R.shape[0],), np.int64)
        with self._lock:
            for i, row in enumerate(R):
                if self._delta.full or faults.fire(FAULT_DELTA_OVERFLOW):
                    self._compact_locked()
                gid = self._next_gid
                self._next_gid += 1
                self._delta.append(row, gid)
                self._note_delta_peak()
                out[i] = gid
            self.stats.n_inserts += R.shape[0]
            self._bump_epoch_locked("insert")
        self._after_mutation()
        return out

    def delete_targets(self, gids) -> None:
        """Tombstone live items (base rows stay resident until compaction).

        Validate-then-apply: every gid is located while nothing has been
        mutated, so a KeyError (unknown/dead/duplicate gid) leaves the
        catalogue untouched and the batch is safely retryable.
        """
        gids = [int(g) for g in np.atleast_1d(np.asarray(gids))]
        with self._lock:
            if len(set(gids)) != len(gids):
                raise KeyError(f"duplicate gids in delete batch: {gids}")
            located = [(gid, *self._locate(gid)) for gid in gids]
            self._kill_located(located)
            self.stats.n_deletes += len(gids)
            self._bump_epoch_locked("delete")
            self._maybe_compact_locked()
        self._after_mutation()

    def update_targets(self, gids, rows) -> None:
        """Replace live items in place: tombstone the old row, append the
        new one to the delta UNDER THE SAME GID (queries see exactly one
        copy at all times). Validate-then-apply like :meth:`delete_targets`
        (a repeated gid is allowed: the LAST row wins).
        """
        gids = [int(g) for g in np.atleast_1d(np.asarray(gids))]
        R = self._validate_rows(rows, "updated rows")
        if len(gids) != R.shape[0]:
            raise ValueError("one row per gid required")
        with self._lock:
            seen: set = set()
            located = []
            for gid in gids:
                if gid not in seen:            # later copies shadow below
                    seen.add(gid)
                    located.append((gid, *self._locate(gid)))
            self._kill_located(located)
            for gid, row in zip(gids, R):
                try:
                    loc = self._locate(gid)
                except KeyError:
                    pass                       # first append for this gid
                else:
                    # same gid earlier in THIS batch — its copy may since
                    # have been frozen (or even folded into a new base) by
                    # a mid-batch compaction; the last row wins everywhere
                    self._kill_located([(gid, *loc)])
                if self._delta.full or faults.fire(FAULT_DELTA_OVERFLOW):
                    self._compact_locked()
                self._delta.append(row, gid)
                self._note_delta_peak()
            self.stats.n_updates += len(gids)
            self._bump_epoch_locked("update")
            self._maybe_compact_locked()
        self._after_mutation()

    # -- compaction ----------------------------------------------------------

    def _maybe_compact_locked(self) -> None:
        snap = self._snapshot
        thresh = min(float(self.max_tombstones),
                     self.tombstone_compact_fraction * max(snap.num_rows, 1))
        if self._delta.full or (snap.n_dead and snap.n_dead >= thresh):
            self._compact_locked()

    def _after_mutation(self) -> None:
        """Post-mutation hooks that must run OFF the catalogue lock.

        The chain cap may JOIN an in-flight build thread — and the build
        acquires the lock to swap, so joining under it would deadlock.
        Every mutation entry point calls this after releasing the lock.
        """
        self._notify_invalidation()
        self.check_watchdog()
        self._enforce_chain_cap()

    def _enforce_chain_cap(self) -> None:
        """Force the L0 chain back under ``max_l0_segments``.

        Sustained mutation pressure against failing (or merely slow)
        builds grows the sealed chain; every extra segment is one more
        dense matmul per query, so an unbounded chain degrades latency
        unboundedly. Past the cap this BLOCKS the mutating caller: joins
        the in-flight build if there is one, otherwise runs a forced
        SYNCHRONOUS build inline (bypassing the failure backoff — the
        cap outranks it). Bounded: after ``build_retry_limit + 1``
        consecutive inline failures it gives up and returns (the chain
        stays queryable; nothing is lost).
        """
        attempts = 0
        while True:
            with self._lock:
                if self._chain_pressure_locked() <= self.max_l0_segments:
                    return
                t = self._build_thread
                if t is None:
                    if attempts > self.build_retry_limit:
                        return
                    attempts += 1
                    self.stats.n_forced_sync_compactions += 1
                    obs.on_compaction("forced_sync",
                                      chain_len=len(self._frozen),
                                      attempt=attempts)
                    self._compact_locked(force=True, force_sync=True)
                    continue
            t.join()        # off-lock: the build takes the lock to swap

    def _retry_build(self) -> None:
        """Timer target: the automatic post-failure retry (async mode)."""
        with self._lock:
            self._retry_timer = None
            if self._build_thread is not None:
                return
            if (self._frozen or self._delta.count
                    or self._snapshot.n_dead):
                # force=True: the elapsed timer IS the backoff
                self._compact_locked(force=True)

    def _compact_locked(self, force: bool = False,
                        force_sync: bool = False) -> None:
        """Freeze the active delta and rebuild (inline or on a thread).

        NEVER blocks and never releases the lock: if a background build
        is already in flight, the freshly sealed delta simply joins the
        frozen chain and this call returns — the chain keeps serving
        queries and folds wholesale at the next compaction trigger (the
        L0 behaviour of an LSM under sustained write pressure; chain
        length is bounded by ``max_l0_segments`` via the chain cap). A
        build folds the ENTIRE chain as of its freeze point; a build
        exception leaves the sealed segments in place (still queryable,
        refolded later — a failed build never loses rows) and clears the
        thread slot (``try/finally``).

        After a failed build, new attempts are GATED: they wait out an
        exponential backoff and stop entirely after
        ``build_retry_limit`` consecutive failures. ``force=True``
        (explicit :meth:`compact`, the chain cap, the retry timer)
        bypasses the gate; ``force_sync=True`` additionally runs the
        build inline even in ``compact_async`` mode (the chain-cap
        back-pressure path).
        """
        if (self._delta.count == 0 and not self._frozen
                and self._snapshot.n_dead == 0):
            return                            # nothing to fold: cheap no-op
        if self._delta.count > 0 or not self._frozen:
            sealed = self._delta
            sealed.seal()
            self._frozen.append(sealed)
            self._delta = DeltaSegment(self.delta_capacity, self.rank)
            self.stats.max_l0_chain = max(self.stats.max_l0_chain,
                                          len(self._frozen))
        if self._build_thread is not None:
            return                            # in-flight build; chain waits
        if not force and self._consec_build_failures:
            # recovering from failure: stop auto-retrying entirely past
            # the limit, and from the SECOND consecutive failure on wait
            # out the exponential backoff (the first failure retries at
            # the very next trigger — transient blips heal immediately).
            # Explicit compact() and the chain cap still force attempts.
            if (self._consec_build_failures > self.build_retry_limit
                    or (self._consec_build_failures >= 2
                        and time.monotonic() < self._retry_not_before)):
                return
        snap = self._snapshot
        folding = list(self._frozen)
        # pending_dead means "kill this gid in the snapshot CURRENTLY
        # being built, whose capture predates the kill". The capture
        # below (no build is in flight here) reflects every kill so far,
        # so entries recorded against an EARLIER (failed) build are
        # stale — and a stale entry is not merely redundant: if the gid
        # was re-appended under an update since the kill, the live new
        # copy lands in this capture and the stale entry would wrongly
        # kill it at swap. Only kills landing AFTER this point belong in
        # the set.
        self._pending_dead.clear()
        new_rows, new_gids = self._live_concat_locked(snap, folding)
        new_rows = np.ascontiguousarray(new_rows)
        if new_rows.shape[0] == 0:
            # an empty catalogue cannot be indexed: keep one dead guard
            # row so engines always have M >= 1; queries see only -inf
            new_rows = np.zeros((1, self.rank), np.float32)
            new_gids = np.full((1,), -1, np.int64)
        version = snap.version + 1

        def build():
            ok = False
            t_build = time.perf_counter()
            own_compiles = 0
            headroom_compiles = 0
            try:
                faults.fire(FAULT_STALL)      # test seam: slow/stuck build
                faults.fire(FAULT_BUILD)      # test seam: failing build
                ctx = EngineContext(new_rows, version=version,
                                    **self._ctx_kwargs)
                ctx.index                     # offline index build, off-lock
                new_snap = Snapshot(new_rows, new_gids, version, ctx)
                if new_gids[0] < 0:
                    new_snap.kill_rows([0])   # the guard row is dead
                faults.fire(FAULT_WARM)       # test seam: readiness failure
                if self._warm_spec is not None:
                    # Readiness pass over the new snapshot BEFORE the swap
                    # (at the serving k and the escalated shape): builds +
                    # uploads the padded engine args and runs each warmed
                    # engine once, so the post-swap first query touches
                    # only device-resident state. Under the argument-
                    # passing contract (DESIGN.md §10) this COMPILES
                    # nothing for a same-bucket compaction — the shared
                    # executors' traces are bucket-keyed, version-free —
                    # and only a bucket-crossing build into a never-warmed
                    # bucket traces (counted in
                    # ``stats.engine_compiles_total``, off the query hot
                    # path). The segmented tails need no re-warm either:
                    # their compiles are batch-shaped, already cached.
                    # Traces are counted from the NEW context's own
                    # attributed ``trace_counts`` — a trace a concurrent
                    # serving thread causes on the OLD snapshot during
                    # this window is its own, not this build's.
                    k, sizes, engines, headroom, budgets = self._warm_spec
                    ctx.warmup(k, batch_sizes=sizes, engines=engines,
                               budgets=budgets)
                    kb_esc = min(new_snap.num_rows,
                                 int(k) + self.overfetch_reserve)
                    if engines and kb_esc > min(new_snap.num_rows, int(k)):
                        ctx.warmup(kb_esc, batch_sizes=sizes,
                                   engines=engines, budgets=budgets)
                    own_compiles = sum(ctx.trace_counts.values())
                    nxt = 2 * ctx.m_bucket
                    if (headroom
                            and 4 * new_snap.num_rows > 3 * ctx.m_bucket
                            and nxt > self._headroom_bucket):
                        # The snapshot fills ≥75% of its bucket and the
                        # next bucket was never warmed: renew the
                        # one-doubling headroom the server's boot warmup
                        # established, so the guarantee is STANDING —
                        # the crossing this growth is heading for finds
                        # its traces waiting. Renewing here (not at
                        # bucket ENTRY) defers the pre-pay until the
                        # boundary actually threatens, and the
                        # ``_headroom_bucket`` memo makes it once per
                        # doubling — steady-state builds never rebuild
                        # oversized args. Accounted separately: an
                        # investment for the next crossing, not a cost
                        # of serving this snapshot. (If delta_capacity
                        # exceeds a quarter-bucket, one compaction can
                        # leap the 75% band and the crossing build pays
                        # its own compiles — recorded, off the query
                        # path.)
                        ctx.warmup(k, batch_sizes=sizes, engines=engines,
                                   m_buckets=(nxt,), budgets=budgets)
                        if engines and kb_esc > min(new_snap.num_rows,
                                                    int(k)):
                            ctx.warmup(kb_esc, batch_sizes=sizes,
                                       engines=engines, m_buckets=(nxt,),
                                       budgets=budgets)
                        headroom_compiles = (
                            sum(ctx.trace_counts.values()) - own_compiles)
                        with self._lock:
                            self._headroom_bucket = max(
                                self._headroom_bucket, nxt)
                with self._lock:
                    pend = [new_snap.gid_to_row[g]
                            for g in self._pending_dead
                            if g in new_snap.gid_to_row]
                    if pend:
                        new_snap.kill_rows(pend)
                    self._pending_dead.clear()
                    self._snapshot = new_snap
                    self._frozen = [s for s in self._frozen
                                    if s not in folding]
                    # the swap changes visible identity (new version,
                    # pending deletes applied): old cache tokens die here
                    self._bump_epoch_locked("swap")
                    self.stats.n_compactions += 1
                    dt = time.perf_counter() - t_build
                    self.stats.last_compaction_s = dt
                    self.stats.compaction_s_total += dt
                    self.stats.engine_compiles_total += own_compiles
                    self.stats.headroom_compiles_total += headroom_compiles
                    # recovery: a successful swap clears ALL stale failure
                    # state — the error belongs to a chain that no longer
                    # exists, and keeping it would gate future builds
                    self.last_build_error = None
                    self._consec_build_failures = 0
                    self._retry_not_before = 0.0
                    self._last_backoff_s = 0.0
                    obs.on_compaction(
                        "success", version=version, epoch=self._epoch,
                        duration_s=dt, engine_compiles=own_compiles,
                        headroom_compiles=headroom_compiles,
                        num_live=int(new_snap.num_rows - new_snap.n_dead))
                self._notify_invalidation()
            except Exception as exc:
                # the sealed segments stay in self._frozen: still
                # queryable, re-folded by the next compaction — a failed
                # build loses nothing. Failures are RECORDED, never
                # raised from here: a synchronous build runs inline in
                # the middle of a mutation batch, and raising there
                # would abort the batch after its kills but before its
                # appends (losing updated rows). ``compact(wait=True)``
                # surfaces the recorded failure to callers. Recovery: an
                # exponential backoff gates ordinary retriggers, and in
                # async mode a daemon timer schedules the retry itself so
                # a quiet catalogue (no further mutations) still heals.
                with self._lock:
                    self.last_build_error = exc
                    self.stats.n_failed_compactions += 1
                    self._consec_build_failures += 1
                    backoff = min(
                        self.build_backoff_s
                        * (2 ** (self._consec_build_failures - 1)),
                        self.build_backoff_max_s)
                    self._last_backoff_s = backoff
                    self._retry_not_before = time.monotonic() + backoff
                    obs.on_compaction(
                        "fail", version_attempted=version,
                        epoch=self._epoch, error=repr(exc),
                        consecutive_failures=self._consec_build_failures,
                        backoff_s=backoff)
                    if (self.auto_retry and self.compact_async
                            and self._consec_build_failures
                            <= self.build_retry_limit
                            and self._retry_timer is None):
                        tmr = threading.Timer(backoff, self._retry_build)
                        tmr.daemon = True
                        self._retry_timer = tmr
                        tmr.start()
                        obs.on_compaction(
                            "retry_scheduled", version_attempted=version,
                            backoff_s=backoff)
            else:
                ok = True
            finally:
                with self._lock:
                    self._build_started_at = None
                    self._watchdog_flagged = False
                    if self._build_thread is threading.current_thread():
                        self._build_thread = None
                    if ok and self.compact_async and self._frozen:
                        # segments sealed while this build ran are still
                        # waiting: fold them now (a fresh thread; this one
                        # exits). Spawned under the SAME lock hold that
                        # cleared the slot, so flush() can never observe
                        # an empty slot between build and refold.
                        self._compact_locked()

        if self._consec_build_failures:
            self.stats.n_build_retries += 1     # attempt after >=1 failure
            obs.on_compaction(
                "retry", version_from=snap.version, version_to=version,
                consecutive_failures=self._consec_build_failures)
        self._build_started_at = time.monotonic()
        self._watchdog_flagged = False
        obs.on_compaction(
            "start", version_from=snap.version, version_to=version,
            epoch=self._epoch, chain_len=len(folding),
            n_rows=int(new_rows.shape[0]),
            sync=bool(not self.compact_async or force_sync))
        if self.compact_async and not force_sync:
            t = threading.Thread(target=build, name="segcat-compact",
                                 daemon=True)
            self._build_thread = t
            t.start()
        else:
            # force_sync: chain-cap back-pressure — the mutating caller
            # pays for the fold it caused (runs under the RLock; build's
            # swap re-enters it, which an RLock permits inline)
            build()

    def compact(self, wait: bool = True) -> None:
        """Force a compaction now (folds the delta + frozen chain into
        the base). ``wait=True`` loops until the chain is fully folded —
        even when builds were already in flight — and surfaces an async
        build failure as an exception instead of spinning on it."""
        first = True
        while True:
            with self._lock:
                if not first and not self._frozen:
                    return
                # fold failures count too: on the LSM ladder a failed
                # L0->L1 fold leaves the chain in place exactly like a
                # failed build, and wait=True must surface it instead of
                # spinning against an armed fold fault
                fails_before = (self.stats.n_failed_compactions
                                + self.stats.n_failed_l1_folds)
                # force=True: an explicit compact() call outranks the
                # failure backoff gate (and wait=True would otherwise
                # spin forever against it)
                self._compact_locked(force=True)
                t = self._build_thread
                first = False
            if not wait:
                return
            if t is not None:
                t.join()
            with self._lock:
                if not self._frozen:
                    return
                if (self.stats.n_failed_compactions
                        + self.stats.n_failed_l1_folds) > fails_before:
                    raise RuntimeError(
                        "compaction build failed; sealed segments remain "
                        "queryable and will be refolded"
                    ) from self.last_build_error

    def flush(self) -> None:
        """Block until every in-flight background build (including any
        auto-refold a build kicked off for segments sealed during it)
        has swapped in.

        Deliberately PASSIVE about failures: a failed build leaves its
        sealed chain in place and flush returns with it intact (the
        recorded error in :attr:`last_build_error` is the signal) —
        :meth:`compact` ``(wait=True)`` is the "fold or raise" API."""
        while True:
            with self._lock:
                # under the lock: a finishing build clears the slot and
                # spawns its refold inside ONE lock hold, so a locked
                # read can never catch the in-between state
                t = self._build_thread
            if t is None:
                return
            t.join()

    # -- query ---------------------------------------------------------------

    def _compiled_tail(self, k: int, kb: int, bucket: int,
                       seg_buckets: Tuple[int, ...],
                       l1_spec: Tuple[int, ...] = ()):
        # no snapshot version in the key: the tail's inputs are all
        # batch-shaped, so one compile serves every snapshot. The
        # check-then-insert and the trace counter run under the lock so
        # concurrent readers neither double-compile a shape nor lose
        # counter increments (the 0-retrace warmup assertions read them).
        # ``l1_spec`` is the stacked L1 tier's (n_shards, slab-capacity)
        # — a FIXED pair per LSM catalogue, so the ladder adds exactly
        # one extra tail shape per (k, kb, bucket, segs) combination.
        key = (int(k), int(kb), int(bucket), seg_buckets, tuple(l1_spec))
        with self._lock:
            fn = self._tail_cache.get(key)
            if fn is None:
                def traced(bv, tomb, bg, U, segs, l1,
                           _k=int(k), _kb=int(kb)):
                    with self._lock:
                        self.trace_counts["segmented_tail"] = (
                            self.trace_counts.get("segmented_tail", 0) + 1)
                    return _segmented_tail(bv, tomb, bg, U, segs, l1,
                                           k=_k, kb=_kb)

                fn = jax.jit(traced)
                self._tail_cache[key] = fn
        return fn

    def query(self, engine: Engine, U, k: int,
              budget: Optional[int] = None
              ) -> Tuple[TopKResult, QueryInfo]:
        """Exact top-``k`` over every LIVE item, through ``engine``.

        Returns ``(result, info)`` — ``result.indices`` are GLOBAL ids
        (stable across compactions), ``result.n_scored`` includes the
        live delta slots scored (the authoritative run's count; a
        discarded optimistic run shows up in wall-clock, not in the
        paper's score metric), and ``info`` carries the segmented
        accounting (:class:`QueryInfo`).

        ``budget`` caps the BASE engine's scan depth (list rows; see
        ``Engine.run``). The returned ``result.upper`` then bounds every
        un-enumerated base item, so :func:`certificate_gaps` stays valid
        over the live catalogue: the delta segments are always fully
        dense-scored (never budgeted), and the tombstone escalation
        ladder is budget-independent — a certified slot is provably in
        the true live top-``k`` even when the base scan halted early.

        The whole batch is computed against ONE consistent state
        captured under the lock (snapshot + dead mask + delta views) —
        mutations landing mid-query are simply not visible to it.
        """
        with self._lock:
            snap = self._snapshot
            segs = [s for s in self._segments() if s.count > 0]
            views = tuple(s.device_view() for s in segs)
            l1 = self._l1_stack_locked()      # None: no L1 tier / empty
            n_delta_live = (sum(s.n_live for s in segs)
                            + self._l1_live_locked())
            n_dead = snap.n_dead
            dead_dev, gids_dev = snap.dead_dev, snap.gids_dev
        if not views and l1 is None and n_dead == 0 and snap.identity:
            # never-mutated fast path: byte-identical to the static server
            res = engine.run(snap.ctx, U, k, budget=budget)
            return res, QueryInfo(0, min(int(k), snap.num_rows), 0,
                                  snap.version)
        # no np.asarray: a device-resident U must not round-trip the host
        U_dev = jnp.atleast_2d(jnp.asarray(U, dtype=jnp.float32))
        b = U_dev.shape[0]
        bucket = batch_bucket(b)
        U_dev = pad_to_bucket(U_dev)          # same rule as the engine cache
        seg_buckets = tuple(int(v[0].shape[0]) for v in views)
        l1_spec = () if l1 is None else tuple(int(d) for d in
                                              l1[0].shape[:2])

        mb = snap.num_rows

        def run_at(kb):
            res = engine.run(snap.ctx, U_dev, kb, budget=budget)
            # resolve mask/gids EAGERLY (two primitive gathers): the jitted
            # tail then never sees an [M_base]-shaped array, so its compile
            # key is snapshot-version-free
            safe = jnp.clip(res.indices, 0, max(mb - 1, 0))
            tomb = jnp.logical_and(res.indices >= 0, dead_dev[safe])
            bg = jnp.where(res.indices >= 0, gids_dev[safe], -1)
            fn = self._compiled_tail(k, kb, bucket, seg_buckets, l1_spec)
            vals, gids, dropped = fn(res.values, tomb, bg, U_dev, views,
                                     l1)
            return res, vals, gids, dropped

        # Tombstone-adaptive base fetch: plain k while the snapshot has no
        # dead rows (the common, warmed key — inserts never retrace), and
        # the k + reserve rung (ALSO pre-warmed) the moment tombstones
        # exist — one engine run with enough margin that a dead row in
        # the top-k costs nothing, instead of an optimistic run that
        # would be discarded and re-run on every tombstone hit.
        k = int(k)
        kb = min(mb, k if n_dead == 0 else k + self.overfetch_reserve)
        res, vals, gids, dropped = run_at(kb)
        retried = False
        # Escalation ladder. A rung's result is exact for every query
        # whose dropped count fits the over-fetch margin (dropped <=
        # kb - k: at least k live base rows survived the drop, and any
        # live row outside the top-kb scores below all of them); a full
        # base fetch (kb == M_base) is unconditionally exact, so the
        # ladder terminates. Climbing x4 is only reachable when more
        # than `reserve` dead rows sit inside ONE query's top slice —
        # those rungs compile lazily.
        while (n_dead and kb < mb
               and bool(np.any(np.asarray(dropped) > kb - k))):
            step = max(kb - k, self.overfetch_reserve // ESCALATION_STEP, 1)
            kb = min(mb, k + ESCALATION_STEP * step)
            res, vals, gids, dropped = run_at(kb)
            retried = True
        n_scored = res.n_scored + jnp.int32(n_delta_live)
        # the base engine's upper bound covers every un-enumerated base
        # item, and the delta is fully scored — so it is ALSO a valid
        # certificate bound for the merged live result
        upper = None if res.upper is None else res.upper[:b]
        out = TopKResult(vals[:b], gids[:b], n_scored[:b], res.depth[:b],
                         upper=upper)
        return out, QueryInfo(int(n_delta_live), kb, len(views),
                              snap.version, retried)

    # -- warmup --------------------------------------------------------------

    def delta_buckets(self) -> List[int]:
        """The power-of-four delta occupancy buckets up to capacity."""
        out, d = [], 1
        while d < self.delta_capacity:
            out.append(d)
            d <<= 2
        out.append(self.delta_capacity)
        return out

    def warm(self, k: int, batch_sizes=(1, 64),
             snap: Optional[Snapshot] = None,
             engines=None, m_buckets=None,
             budgets=None) -> "SegmentedCatalogue":
        """Compile the segmented tail for every delta-capacity bucket.

        Tails are warmed at BOTH base-fetch shapes — plain ``k`` (the
        no-tombstone path) and ``k + overfetch_reserve`` (what any
        tombstoned snapshot fetches) — including the two-segment shapes
        a background build exposes. After this, the first query after
        ANY insert (delta occupancy 1..capacity) dispatches a cached
        executable — 0 new traces (asserted in tests via
        :attr:`trace_counts`); deletes are likewise retrace-free when
        ``engines`` is given, which additionally pre-compiles those
        engines at the over-fetched shape — over every M-bucket in
        ``m_buckets`` (DESIGN.md §10), so a compaction that crosses into
        a warmed bucket stays compile-free on the tombstoned path too.
        ``snap`` warms a not-yet-swapped-in snapshot (the background
        compaction readiness path). Tail compiles are snapshot-free
        twice over (batch-shaped inputs AND, since the argument-passing
        refactor, version-free engine executors), so a compaction
        re-runs only the readiness pass for the new snapshot — the
        tails compiled here serve every future snapshot as is.
        """
        snap = self._snapshot if snap is None else snap
        kb = min(snap.num_rows, int(k))
        kb_esc = min(snap.num_rows, int(k) + self.overfetch_reserve)
        r = self.rank
        kbs = [kb] if kb_esc == kb else [kb, kb_esc]

        def dummy_seg(d):
            return (jnp.zeros((d, r), jnp.float32),
                    jnp.full((d,), -1, jnp.int32),
                    jnp.zeros((d,), bool))

        for bsz in batch_sizes:
            bucket = batch_bucket(bsz)
            U = jnp.ones((bucket, r), jnp.float32)
            for kb_w in kbs:
                bv = jnp.zeros((bucket, kb_w), jnp.float32)
                tomb = jnp.zeros((bucket, kb_w), bool)
                bg = jnp.zeros((bucket, kb_w), jnp.int32)
                # every tail shape is warmed with AND without the L1
                # tier operand (one extra variant on the LSM ladder —
                # the stacked tier is a single fixed shape, so folds
                # never add tail compiles)
                for l1_spec, l1_dummy in self._warm_l1_variants():
                    # post-compaction pristine-but-nonidentity tail
                    # (no segs)
                    fn = self._compiled_tail(k, kb_w, bucket, (), l1_spec)
                    jax.block_until_ready(fn(bv, tomb, bg, U, (),
                                             l1_dummy))
                    for d in self.delta_buckets():
                        fn = self._compiled_tail(k, kb_w, bucket, (d,),
                                                 l1_spec)
                        jax.block_until_ready(
                            fn(bv, tomb, bg, U, (dummy_seg(d),),
                               l1_dummy))
                    # while a background compaction is in flight queries
                    # see TWO segments: the frozen delta (sealed views
                    # present the capacity bucket) plus the active delta
                    # at any bucket
                    frozen = dummy_seg(self.delta_capacity)
                    for d in self.delta_buckets():
                        fn = self._compiled_tail(
                            k, kb_w, bucket, (self.delta_capacity, d),
                            l1_spec)
                        jax.block_until_ready(
                            fn(bv, tomb, bg, U, (frozen, dummy_seg(d)),
                               l1_dummy))
        if engines and kb_esc > kb:
            snap.ctx.warmup(kb_esc, batch_sizes=batch_sizes,
                            engines=engines, m_buckets=m_buckets,
                            budgets=budgets)
        if m_buckets:
            with self._lock:
                self._headroom_bucket = max(
                    self._headroom_bucket,
                    *(int(b) for b in m_buckets))
        return self

    def set_warm_spec(self, k: int, batch_sizes, engines=None,
                      headroom: bool = True, budgets=None) -> None:
        """Remember what to ready on each compacted snapshot, so the
        post-swap first query hits compiled executables (the rebuild cost
        stays off the query hot path, including compiles).

        ``headroom=True`` additionally has a build whose snapshot fills
        ≥75% of its M-bucket pre-trace the NEXT bucket, once per
        doubling (DESIGN.md §10) — renewing the boot warmup's
        one-doubling headroom just before growth needs it, so that
        EVERY future bucket crossing, not just the first, compacts
        compile-free; the investment is counted in
        ``SegmentStats.headroom_compiles_total``, never in
        ``engine_compiles_total``.
        """
        self._warm_spec = (int(k), tuple(batch_sizes), engines,
                           bool(headroom),
                           None if budgets is None
                           else tuple(int(b) for b in budgets))

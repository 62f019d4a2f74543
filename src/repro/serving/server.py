"""Top-K query serving: the paper's inference engine as a service layer.

``TopKServer`` owns a SEP-LR catalogue plus a shared
:class:`repro.core.engines.EngineContext` and serves batched queries
through ANY engine in the registry (``naive`` / ``ta`` / ``bta`` /
``norm`` / ``norm_sharded`` / ``pallas`` / ``fagin`` / ``partial`` /
``auto`` — see ``repro.core.engines``), addressed by registry name; the
context also owns the catalogue LAYOUTS each engine declares
(``repro.core.layout``: contiguous list prefixes for ``ta``/``bta``, the
norm-major tile order for ``norm``/``pallas``, the round-robin-dealt
sharded norm order for ``norm_sharded``), so one server process serves a
multi-device mesh by simply passing ``method="norm_sharded"``. Requests are micro-batched; per-query pruning statistics
(scores computed, depth) are aggregated PER REGISTRY ENGINE for the
benchmark harness — matching the paper's evaluation axis (query
efficiency). ``method="auto"`` resolves per batch via
:func:`repro.core.engines.select_engine`, and its traffic is accounted to
the engine that actually ran.

``TwoStageRanker`` is the production recsys pattern from DESIGN.md §3:
exact SEP-LR top-N retrieval (where the paper's algorithms apply) followed
by full-model re-ranking of the N retrieved candidates (where they don't).

**Streaming mutations** (DESIGN.md §9): the server's catalogue is a
:class:`repro.core.segments.SegmentedCatalogue` — an immutable base
snapshot (the EngineContext every engine runs against) plus a delta
buffer and tombstones. :meth:`TopKServer.add_targets` /
:meth:`delete_targets` / :meth:`update_targets` mutate it without an
index rebuild and without giving up exactness; a threshold-triggered
compaction folds the mutations into a fresh snapshot under a new
version. A never-mutated server serves the identical code path (and the
identical compiled executables) as before the streaming layer existed.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import SepLRModel, TopKIndex
from repro.core.engines import (
    CostTable,
    Engine,
    EngineContext,
    batch_bucket,
    engine_names,
    get_engine,
    note_pruning_metrics,
    select_engine,
)
from repro.core.lsm import ShardedLsmCatalogue
from repro.core.naive import TopKResult
from repro.core.segments import SegmentedCatalogue
from repro.core.strategies import sign_bucket_label

Array = jnp.ndarray

#: Ring-buffer length for per-batch latency percentiles: enough batches
#: for stable p99 at serving rates, bounded so a long-lived server never
#: grows its stats footprint.
LATENCY_RING = 512


def _batch_hist() -> obs.Histogram:
    return obs.Histogram("serve_batch_latency_us",
                         "per-query us of one served batch",
                         buckets=obs.LATENCY_BUCKETS_US,
                         ring=LATENCY_RING)


def _request_hist() -> obs.Histogram:
    return obs.Histogram("serve_request_latency_us",
                         "enqueue->result us of one caller request",
                         buckets=obs.LATENCY_BUCKETS_US,
                         ring=LATENCY_RING)


@dataclasses.dataclass
class ServeStats:
    """Per-engine serving statistics.

    Latency is tracked three ways: the lifetime mean (``us_per_query``,
    exact over every query ever served), percentiles over a BOUNDED
    ring of recent per-batch latencies (``p50_us``/``p95_us``/``p99_us``
    — each entry is one batch's per-query microseconds, so tail entries
    reflect stragglers like a post-mutation retrace or a compaction
    swap), and percentiles over a ring of per-REQUEST latencies
    (``req_p50_us``/``req_p95_us``/``req_p99_us`` — enqueue→result wall
    time for one caller request, the number an SLO is written against).
    The per-batch and per-request views DIVERGE under micro-batching:
    a request coalesced into a shared batch waits in the queue before
    its batch dispatches, time the per-batch column never sees — which
    is exactly why both columns exist (DESIGN.md §13).
    ``delta_scored`` counts scores spent on the streaming delta
    segments, separating mutation-induced work from base-scan work.
    ``sign_batches`` counts served batches per sign bucket (the compile
    specialisation axis of the batched list scan, DESIGN.md §11) — a
    bucket label appearing here that :meth:`TopKServer.warmup` did not
    warm explains a one-off trace straggler in the latency ring.

    Since the observability layer landed (DESIGN.md §14) the two rings
    are :class:`repro.obs.Histogram` instances — the registry's shared
    primitive, with log-scale buckets for export AND the bounded raw
    ring the exact percentiles read. The public API above is a façade
    over them and is UNCHANGED: ``lat_us_ring``/``req_lat_us_ring``
    still expose the underlying deques, percentiles still match
    ``np.percentile`` over the ring. Counter updates go through a lock
    (`record_batch`) so concurrent recording threads never lose
    increments.
    """

    n_queries: int = 0
    n_scored: int = 0
    total_time_s: float = 0.0
    depth_sum: int = 0
    delta_scored: int = 0
    #: per-batch per-query-us histogram (the obs shared primitive;
    #: its bounded ring backs the exact ``p50_us``/``p95_us``/``p99_us``)
    lat_hist: obs.Histogram = dataclasses.field(
        default_factory=_batch_hist, repr=False, compare=False)
    #: per-REQUEST enqueue→result histogram (one entry per caller
    #: request; honest under coalescing, unlike the per-batch ring)
    req_lat_hist: obs.Histogram = dataclasses.field(
        default_factory=_request_hist, repr=False, compare=False)
    sign_batches: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: degradation-ladder decisions taken while serving THIS method
    #: (keyed by rung: "to_norm" / "to_budgeted" / "shed"), recorded on
    #: the REQUESTED method's stats — the ladder is an admission story,
    #: so its accounting follows what the caller asked for, while the
    #: raw serve counters above follow the engine that actually ran
    degradations: Dict[str, int] = dataclasses.field(default_factory=dict)
    #: queries whose result carried at least one UNCERTIFIED slot
    #: (certificate gap > 0 — possible under a step budget, never on the
    #: exact path); the CI degradation smoke gates on this being honest
    n_uncertified: int = 0
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    # -- legacy ring façade --------------------------------------------------

    @property
    def lat_us_ring(self):
        """The per-batch latency ring (the histogram's raw-sample
        deque) — the pre-§14 attribute, kept for callers."""
        return self.lat_hist.ring()

    @property
    def req_lat_us_ring(self):
        return self.req_lat_hist.ring()

    @property
    def scores_per_query(self) -> float:
        return self.n_scored / max(self.n_queries, 1)

    @property
    def us_per_query(self) -> float:
        return 1e6 * self.total_time_s / max(self.n_queries, 1)

    def record_batch(self, n: int, n_scored: int, depth_sum: int,
                     dt_s: float, delta_scored: int = 0,
                     sign_label: str = "") -> None:
        """Fold one served batch in (thread-safe: the async pipeline's
        harvester and the sync path may both record concurrently)."""
        with self._lock:
            self.n_queries += n
            self.n_scored += n_scored
            self.depth_sum += depth_sum
            self.total_time_s += dt_s
            self.delta_scored += delta_scored
            if sign_label:
                self.sign_batches[sign_label] = (
                    self.sign_batches.get(sign_label, 0) + 1)
        self.lat_hist.observe(1e6 * dt_s / max(n, 1))

    def bump_degradation(self, rung: str) -> None:
        with self._lock:
            self.degradations[rung] = self.degradations.get(rung, 0) + 1

    def note_uncertified(self, n: int) -> None:
        with self._lock:
            self.n_uncertified += n

    def latency_percentile(self, q: float) -> float:
        """q-th percentile (0-100) of recent per-batch latencies, in us."""
        return self.lat_hist.percentile(q)

    @property
    def p50_us(self) -> float:
        return self.latency_percentile(50.0)

    @property
    def p95_us(self) -> float:
        return self.latency_percentile(95.0)

    @property
    def p99_us(self) -> float:
        return self.latency_percentile(99.0)

    def record_request_latency(self, us: float) -> None:
        """One caller request completed ``us`` microseconds after it was
        submitted (enqueue→result, queue wait included)."""
        self.req_lat_hist.observe(float(us))

    def request_percentile(self, q: float) -> float:
        """q-th percentile (0-100) of recent per-REQUEST latencies, us."""
        return self.req_lat_hist.percentile(q)

    @property
    def req_p50_us(self) -> float:
        return self.request_percentile(50.0)

    @property
    def req_p95_us(self) -> float:
        return self.request_percentile(95.0)

    @property
    def req_p99_us(self) -> float:
        return self.request_percentile(99.0)


@dataclasses.dataclass
class AdmissionPolicy:
    """Load/deadline policy for :meth:`TopKServer.query` (DESIGN.md §12).

    When a deadline is in force, each chunk walks an explicit
    degradation ladder instead of queueing unboundedly: the PREFERRED
    engine if its predicted cost fits the remaining time, else ``norm``
    (the cheapest exact scan), else a BUDGETED ``norm`` scan whose
    result carries per-item certificates (``TopKResult.upper``), else —
    deadline already blown or the server over ``max_inflight`` — the
    chunk is SHED: sentinel values (``-inf`` scores, ``-1`` ids, ``+inf``
    certificate gaps, i.e. nothing certified), never a silent partial
    answer pretending to be exact. Every downgrade/shed decision lands
    in :attr:`ServeStats.degradations` under the requested method.
    """

    #: default per-query deadline (None = no deadline: never degrade);
    #: ``query(deadline_ms=...)`` overrides per call
    deadline_ms: Optional[float] = None
    #: concurrent chunks in flight before overload shedding kicks in
    max_inflight: int = 8
    #: scan budget (list rows) used at the "budgeted" ladder rung
    degrade_budget: int = 64
    #: shed on overload/expiry (False = serve anyway, just record it)
    shed_on_overload: bool = True


class TopKServer:
    def __init__(self, model: SepLRModel, max_batch: int = 64,
                 block_size: int = 256, delta_capacity: int = 256,
                 compact_async: bool = False,
                 policy: Optional[AdmissionPolicy] = None,
                 n_shards: int = 0,
                 l1_capacity: Optional[int] = None,
                 max_tombstones: Optional[int] = None,
                 cost_table: Optional[CostTable] = None):
        self.model = model
        # per-(engine, batch-bucket, sign-bucket) measured serve cost:
        # the serving router's table (select_engine consults it through
        # the context) and the admission ladder's fallback. Passed into
        # the catalogue's ctx_kwargs so every compaction-built context
        # SHARES it — measurements survive snapshot swaps. A caller may
        # hand in a pre-measured table (CostTable.load) so a RESTARTED
        # server routes by measured costs before its first observation.
        self.cost_table = cost_table if cost_table is not None \
            else CostTable()
        # n_shards > 0 fronts the model with the LSM ladder
        # (DESIGN.md §15): per-shard L1 runs absorb most compactions as
        # cheap folds, full base rebuilds only on tier overflow
        # max_tombstones=None keeps the catalogue default
        # (2 * delta_capacity); large catalogues want an absolute cap
        # sized to M — the §9 over-fetch costs O(n_dead) per query while
        # a tombstone-triggered rebuild costs O(M), so at M >> capacity
        # the default forces full rebuilds to clear a vanishing dead
        # fraction
        tomb = {} if max_tombstones is None \
            else {"max_tombstones": max_tombstones}
        if n_shards > 0:
            self.catalogue: SegmentedCatalogue = ShardedLsmCatalogue(
                model.targets, n_shards=n_shards, l1_capacity=l1_capacity,
                delta_capacity=delta_capacity,
                compact_async=compact_async, block_size=block_size,
                cost_table=self.cost_table, **tomb)
        else:
            self.catalogue = SegmentedCatalogue(
                model.targets, delta_capacity=delta_capacity,
                compact_async=compact_async, block_size=block_size,
                cost_table=self.cost_table, **tomb)
        self.max_batch = max_batch
        self.block_size = block_size
        self.stats: Dict[str, ServeStats] = {}
        self.policy = policy if policy is not None else AdmissionPolicy()
        # per-engine EWMA of per-query serve seconds: the ladder's FIRST
        # cost source (tests set entries directly to make admission
        # decisions deterministic); when an engine has no entry here the
        # ladder falls back to the shared :attr:`cost_table` (primed by
        # warmup), and only an engine absent from BOTH predicts the
        # optimistic 0.
        self._cost_ewma: Dict[str, float] = {}
        self._admit_lock = threading.Lock()
        self._inflight = 0
        #: micro-batch sequence numbers, the ``batch`` argument of the
        #: ``obs.stage`` events (shared with an ``AsyncTopKServer`` front)
        self._batch_seq = itertools.count(1)

    @property
    def ctx(self) -> EngineContext:
        """The CURRENT base snapshot's engine context (compaction swaps
        in a fresh one under the next version — hold :attr:`catalogue`
        if you need a stable reference across mutations)."""
        return self.catalogue.snapshot.ctx

    @property
    def index(self) -> TopKIndex:
        return self.ctx.index

    @property
    def trace_counts(self) -> Dict[str, int]:
        """Engine traces (current snapshot) + segmented-tail traces."""
        return {**self.ctx.trace_counts, **self.catalogue.trace_counts}

    @staticmethod
    def available_engines() -> List[str]:
        """Registry names accepted by :meth:`query`'s ``method=``."""
        return engine_names()

    def warmup(self, k: int, batch_sizes=None, engines=None,
               m_buckets=None, budgets=None) -> "TopKServer":
        """Populate the per-engine compiled-executable cache ahead of
        traffic (DESIGN.md §6/§10). After warmup, same-shape queries hit
        the cache with zero new traces (``self.ctx.trace_counts`` proves
        it).

        **Warmup over M-buckets** (DESIGN.md §10): argument-passing
        executors are traced per CATALOGUE bucket, so this also warms
        ``m_buckets`` — by default the current bucket plus the next one
        (one doubling of headroom). A streaming catalogue that grows
        across its next power-of-two boundary then compacts with ZERO
        engine retraces, exactly like a same-bucket compaction; pass
        more buckets for more growth headroom, or ``(ctx.m_bucket,)``
        to warm only the current size.

        Also warms the streaming layer: the segmented tail is compiled
        for EVERY delta-capacity bucket (DESIGN.md §9), so the first
        query after any insert dispatches cached executables — 0 new
        traces — and records the warm spec so compaction readies each
        replacement snapshot before swapping it in (compile-free for
        warmed buckets).

        ``budgets`` additionally warms each budget-capable engine's
        BUDGETED variants (the budget joins the executor config, so each
        distinct budget is its own cache entry — DESIGN.md §12); warmed
        budgets then stay compile-free across compactions exactly like
        the unbudgeted path, including the degradation ladder's
        ``policy.degrade_budget``.
        """
        sizes = tuple(batch_sizes) if batch_sizes else (1, self.max_batch)
        if m_buckets is None:
            mb = self.ctx.m_bucket
            m_buckets = (mb, 2 * mb)
        self.ctx.warmup(k, batch_sizes=sizes, engines=engines,
                        m_buckets=m_buckets, budgets=budgets)
        self.catalogue.warm(k, batch_sizes=sizes, engines=engines,
                            m_buckets=m_buckets, budgets=budgets)
        # compactions renew the headroom iff the boot warmup established
        # any (each build then pre-traces ITS next bucket, keeping every
        # future crossing compile-free, not just the first)
        headroom = any(int(b) > self.ctx.m_bucket for b in m_buckets)
        self.catalogue.set_warm_spec(k, sizes, engines, headroom=headroom,
                                     budgets=budgets)
        return self

    # -- streaming mutations (DESIGN.md §9) ---------------------------------

    def add_targets(self, rows) -> np.ndarray:
        """Stream new items into the catalogue; returns their global ids."""
        return self.catalogue.add_targets(rows)

    def delete_targets(self, gids) -> None:
        """Tombstone items; queries exclude them immediately and exactly."""
        self.catalogue.delete_targets(gids)

    def update_targets(self, gids, rows) -> None:
        """Replace item factors in place (same global ids)."""
        self.catalogue.update_targets(gids, rows)

    @property
    def mutation_stats(self) -> Dict[str, float]:
        """Delta/compaction counters for the bench harness and dashboards.

        The key set and types are declared ONCE, in
        :data:`repro.obs.schema.MUTATION_STATS_SCHEMA` (each key
        documented there); this property just supplies the values —
        :func:`repro.obs.build_mutation_stats` raises on any drift
        between the two, so the schema cannot silently rot.
        """
        cat = self.catalogue
        return obs.build_mutation_stats({
            "n_inserts": cat.stats.n_inserts,
            "n_deletes": cat.stats.n_deletes,
            "n_updates": cat.stats.n_updates,
            "n_compactions": cat.stats.n_compactions,
            "n_failed_compactions": cat.stats.n_failed_compactions,
            "max_delta_occupancy": cat.stats.max_delta_occupancy,
            "delta_occupancy": cat.delta_occupancy,
            "n_tombstones": cat.n_tombstones,
            "snapshot_version": cat.version,
            "num_live": cat.num_live,
            # argument-passing contract (DESIGN.md §10): engine traces
            # observed during compaction builds — 0 for compactions whose
            # M-bucket was warmed — and the builds' wall-clock
            "engine_compiles_total": cat.stats.engine_compiles_total,
            "engine_compiles_per_compaction": (
                cat.stats.engine_compiles_total
                / max(cat.stats.n_compactions, 1)),
            "headroom_compiles_total": cat.stats.headroom_compiles_total,
            "compaction_s_total": cat.stats.compaction_s_total,
            "last_compaction_s": cat.stats.last_compaction_s,
            # recovery machinery (DESIGN.md §12): retry/backoff state,
            # chain-cap pressure, and watchdog flags — all zero on a
            # healthy server
            "n_build_retries": cat.stats.n_build_retries,
            "n_forced_sync_compactions": cat.stats.n_forced_sync_compactions,
            "n_stuck_builds": cat.stats.n_stuck_builds,
            "max_l0_chain": cat.stats.max_l0_chain,
            "l0_chain_len": cat.l0_chain_len,
            "consecutive_build_failures": cat.consecutive_build_failures,
            "current_backoff_s": cat.current_backoff_s,
            "retry_pending": int(cat.retry_pending),
            # LSM ladder (DESIGN.md §15): all zero on the single-level
            # catalogue — the base-class hooks return the neutral values
            "n_shards": cat.n_shards,
            "l1_rows": cat.l1_rows,
            "n_l1_folds": cat.stats.n_l1_folds,
            "n_failed_l1_folds": cat.stats.n_failed_l1_folds,
            "n_l1_fold_retries": cat.stats.n_l1_fold_retries,
            "l1_fold_s_total": cat.stats.l1_fold_s_total,
            "consecutive_fold_failures": cat.consecutive_fold_failures,
            "fold_backoff_s": cat.fold_backoff_s,
        })

    def _record(self, method: str, res, dt: float, n: int,
                delta_scored: int = 0, sign_label: str = ""):
        s = self.stats.setdefault(method, ServeStats())
        n_scored = int(np.sum(np.asarray(res.n_scored)))
        depth_sum = int(np.sum(np.asarray(res.depth)))
        s.record_batch(n, n_scored, depth_sum, dt,
                       int(delta_scored) * n, sign_label)
        # mirror into the process-wide registry: the live
        # pruning-efficiency metrics (scored fraction vs the live M)
        # plus the exported latency histograms (DESIGN.md §14)
        note_pruning_metrics(method, n, n_scored, depth_sum,
                             self.catalogue.num_live,
                             1e6 * dt / max(n, 1), sign_label)

    def _note_certificates(self, req_stats: ServeStats, engine_name: str,
                           bud: int, res) -> None:
        """Certificate accounting for one budgeted batch: the legacy
        per-request ``n_uncertified`` counter PLUS the live registry
        metrics (certified fraction and mean uncertified gap per
        (engine, budget-bucket)) — both derived from the same
        ``upper - values`` gaps :func:`repro.core.certificate_gaps`
        defines, which tests/test_obs.py pins against."""
        upper = np.asarray(res.upper)
        vals = np.asarray(res.values)
        ids = np.asarray(res.indices)
        valid = ids >= 0
        gaps = upper[:, None] - vals
        unc = np.logical_and(gaps > 0, valid)
        n_unc_queries = int(np.sum(np.any(unc, axis=1)))
        req_stats.note_uncertified(n_unc_queries)
        n_valid = int(np.sum(valid))
        n_unc = int(np.sum(unc))
        frac = 1.0 - n_unc / max(n_valid, 1)
        mean_gap = float(gaps[unc].mean()) if n_unc else 0.0
        obs.on_uncertified(engine_name, n_unc_queries)
        obs.on_certificates(engine_name, batch_bucket(int(bud)), frac,
                            mean_gap, n_unc > 0)

    def _shed_result(self, n: int, k: int) -> TopKResult:
        """Sentinel result for a shed chunk: explicitly nothing — ``-inf``
        scores, ``-1`` ids, ``+inf`` certificate gaps (no slot certified),
        never a partial answer pretending to be exact."""
        return TopKResult(
            np.full((n, k), -np.inf, np.float32),
            np.full((n, k), -1, np.int32),
            np.zeros((n,), np.int32),
            np.zeros((n,), np.int32),
            upper=np.full((n,), np.inf, np.float32))

    def _admit(self, eng: Engine, n: int,
               remaining_s: Optional[float]):
        """Pick the degradation-ladder rung for one ``n``-query chunk.

        Returns ``(engine_or_None, budget, rung)`` — ``None`` engine
        means shed. Cost predictions come from the per-engine EWMA of
        observed per-query seconds (:attr:`_cost_ewma`), falling back to
        the measured :attr:`cost_table` at this chunk's batch bucket
        (warmup primes it, so a freshly warmed server admits from
        measurements); only an engine absent from both predicts 0
        (optimistic: admit, then learn).
        """
        pol = self.policy
        if remaining_s is None:
            return eng, None, "full"
        bucket = batch_bucket(max(n, 1))

        def cost(name: str) -> float:
            c = self._cost_ewma.get(name)
            if c is None:
                c = self.cost_table.predict(name, bucket, "")
            return (c or 0.0) * n

        if remaining_s <= 0.0:
            if pol.shed_on_overload:
                return None, None, "shed"
            return get_engine("norm"), pol.degrade_budget, "to_budgeted"
        if cost(eng.name) <= remaining_s:
            return eng, None, "full"
        if eng.name != "norm" and cost("norm") <= remaining_s:
            return get_engine("norm"), None, "to_norm"
        return get_engine("norm"), pol.degrade_budget, "to_budgeted"

    def query(self, U: Array, k: int, method: str = "bta",
              budget: Optional[int] = None,
              deadline_ms: Optional[float] = None):
        """U: [B, R] (or [R]). Returns TopKResult batched like U.

        ``method`` is any registry name (or alias) from
        :meth:`available_engines`; unknown names raise ``ValueError``.
        ``auto`` dispatch reads its sparsity/batch-size statistics from
        the incoming HOST array — engine selection never enqueues work
        on the device query stream. Batch-specialised engines also
        record each chunk's sign bucket in
        :attr:`ServeStats.sign_batches` (the DESIGN.md §11 compile
        axis), again a host-side read of input VALUES only. Once the
        catalogue has streamed mutations, results
        carry GLOBAL item ids and reflect every mutation exactly (the
        segmented query path, DESIGN.md §9); a never-mutated server runs
        the raw engine path unchanged.

        **Budgeted queries** (DESIGN.md §12): ``budget`` caps the scan
        depth (list rows) of budget-capable engines. The result's
        ``upper`` field then bounds every un-scanned item;
        :func:`repro.core.certificate_gaps` ≤ 0 marks the slots that are
        PROVABLY in the true top-``k`` (always a prefix). Exact engines
        return ``upper = -inf`` (everything certified).

        **Deadlines** (``deadline_ms``, or ``policy.deadline_ms``): each
        chunk walks the admission ladder (:class:`AdmissionPolicy`) —
        preferred engine → ``norm`` → budgeted ``norm`` → shed — based
        on the EWMA cost model and the time remaining; decisions are
        recorded in :attr:`ServeStats.degradations` under the REQUESTED
        method. Over ``policy.max_inflight`` concurrent chunks, new
        chunks shed immediately instead of queueing.

        Validation: non-positive ``k``/``budget``, negative
        ``deadline_ms``, wrong-rank or >2-D ``U``, and non-finite HOST
        query values raise ``ValueError`` (device-resident inputs skip
        the finiteness scan — reading them back would break the
        no-round-trip contract above).
        """
        seq = next(self._batch_seq)
        with obs.stage("topk.validate", batch=seq) as st:
            engine: Engine = get_engine(method)
            if int(k) <= 0:
                raise ValueError(f"k must be a positive int, got {k!r}")
            if budget is not None and int(budget) <= 0:
                raise ValueError(f"budget must be a positive int or None, "
                                 f"got {budget!r}")
            if deadline_ms is not None and float(deadline_ms) < 0:
                raise ValueError(f"deadline_ms must be >= 0 or None, got "
                                 f"{deadline_ms!r}")
            # Keep the batch wherever the caller had it: host inputs are
            # sliced and dispatched as numpy (auto's nnz statistic never
            # touches the device), device-resident inputs stay on device
            # with no round-trip (select_engine reads them back once per
            # chunk only when method="auto").
            if isinstance(U, jax.Array):
                U_all = jnp.atleast_2d(U)
            else:
                U_all = np.atleast_2d(np.asarray(U, np.float32))
            st.set(n=int(U_all.shape[0]))
            if U_all.ndim != 2:
                raise ValueError(
                    f"U must be [B, R] or [R], got shape {U_all.shape}")
            rank = self.catalogue.rank
            if U_all.shape[1] != rank:
                raise ValueError(f"query rank {U_all.shape[1]} != "
                                 f"catalogue rank {rank}")
            if isinstance(U_all, np.ndarray) \
                    and not np.all(np.isfinite(U_all)):
                bad = int(np.argwhere(~np.isfinite(U_all).all(axis=1))[0, 0])
                raise ValueError(f"query row {bad} contains NaN/Inf values")
        if deadline_ms is None:
            deadline_ms = self.policy.deadline_ms
        t_admit = time.perf_counter()
        req_stats = self.stats.setdefault(engine.name, ServeStats())
        outs = []
        for i in range(0, U_all.shape[0], self.max_batch):
            chunk = U_all[i: i + self.max_batch]
            n = chunk.shape[0]
            # the first chunk carries the call's sequence number
            cseq = seq if i == 0 else next(self._batch_seq)
            # admission: overload first (cheap counter check), then the
            # deadline ladder on the time this query has left
            with self._admit_lock:
                overloaded = (self._inflight >= self.policy.max_inflight
                              and self.policy.shed_on_overload)
                self._inflight += 1
            try:
                with obs.stage("topk.route", batch=cseq, n=n):
                    eng = (select_engine(self.ctx, chunk)
                           if engine.name == "auto" else engine)
                    if overloaded:
                        run_eng, bud, rung = None, None, "shed"
                    else:
                        remaining = None if deadline_ms is None else (
                            deadline_ms / 1e3
                            - (time.perf_counter() - t_admit))
                        run_eng, bud, rung = self._admit(eng, n, remaining)
                    # sign bucket of this chunk, for the per-bucket serve
                    # stats — only engines with batch specialisation pay
                    # the (host-side, input-value-only) read; it mirrors
                    # the bucket the dispatch itself computes for the
                    # compile key (DESIGN.md §11)
                    label = (sign_bucket_label(
                                run_eng.batch_config(self.ctx, chunk))
                             if run_eng is not None
                             and run_eng.batch_config is not None else "")
                if rung != "full":
                    req_stats.bump_degradation(rung)
                    obs.on_degradation(engine.name, rung)
                if run_eng is None:
                    res = self._shed_result(n, int(k))
                    req_stats.note_uncertified(n)
                    obs.on_uncertified(engine.name, n)
                    outs.append(res)
                    continue
                if bud is None:
                    bud = budget  # explicit caller budget, not a downgrade
                with obs.stage("topk.enqueue", batch=cseq, n=n) as enq:
                    res, info = self.catalogue.query(run_eng, chunk, k,
                                                     budget=bud)
                with obs.stage("topk.await", batch=cseq, n=n) as aw:
                    res = jax.tree_util.tree_map(np.asarray, res)
                dt = aw.end - enq.start
            finally:
                with self._admit_lock:
                    self._inflight -= 1
            with obs.stage("topk.account", batch=cseq, n=n):
                if res.upper is None:
                    # legacy/sharded paths carry no bound; they are exact,
                    # so the vacuous bound (everything certified) is the
                    # truth — and it keeps chunk results concatenable
                    res = res._replace(upper=np.full(
                        (np.asarray(res.values).shape[0],), -np.inf,
                        np.float32))
                if bud is not None:
                    self._note_certificates(req_stats, run_eng.name, bud,
                                            res)
                # cost model: learn per-query seconds per (engine,
                # budgeted?) ...
                key = (run_eng.name if bud is None
                       else f"{run_eng.name}@budget")
                prev = self._cost_ewma.get(key)
                per_q = dt / max(n, 1)
                self._cost_ewma[key] = (per_q if prev is None
                                        else 0.8 * prev + 0.2 * per_q)
                # ... and granularly per (engine, batch-bucket, sign) in
                # the shared table the serving router reads (DESIGN.md §13)
                self.cost_table.observe(key, batch_bucket(n), label, per_q)
                self._record(run_eng.name, res, dt, n,
                             info.delta_scored, sign_label=label)
            outs.append(res)
        req_us = 1e6 * (time.perf_counter() - t_admit)
        req_stats.record_request_latency(req_us)
        obs.on_request_done(engine.name, req_us)
        with obs.stage("topk.fulfil", batch=seq, n=int(U_all.shape[0])):
            return jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs, axis=0), *outs)


class TwoStageRanker:
    """Exact SEP-LR retrieval -> full-model re-rank (DESIGN.md §3).

    retrieval_model: SEP-LR over the candidate catalogue (u = query tower).
    rerank_fn(query_batch, candidate_ids) -> scores of the retrieved set.
    The retrieval engine is addressed by registry name, same as
    :meth:`TopKServer.query`.
    """

    def __init__(self, retrieval: TopKServer,
                 rerank_fn: Callable[[Dict, np.ndarray], np.ndarray],
                 retrieve_n: int = 100):
        self.retrieval = retrieval
        self.rerank_fn = rerank_fn
        self.retrieve_n = retrieve_n

    def rank(self, query_batch: Dict, U: Array, k: int,
             method: str = "bta"):
        get_engine(method)  # fail fast on unknown engine names
        res = self.retrieval.query(U, self.retrieve_n, method=method)
        cand = np.asarray(res.indices)                       # [B, N]
        rerank = self.rerank_fn(query_batch, cand)           # [B, N]
        order = np.argsort(-rerank, axis=1)[:, :k]
        return (np.take_along_axis(cand, order, axis=1),
                np.take_along_axis(rerank, order, axis=1))

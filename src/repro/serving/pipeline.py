"""Async micro-batching serving pipeline (DESIGN.md §13).

The batched-native scans (DESIGN.md §11) are 5-11x cheaper per query at
B >= 8 — but only when someone HANDS the server a large batch. This
module manufactures those batches from independent request traffic:

``AsyncTopKServer`` wraps a :class:`repro.serving.server.TopKServer`
with

* a thread-safe request queue that COALESCES arrivals into the power-
  of-two batch buckets the compile cache already keys on. A request
  waits at most its flush deadline (``flush_ms``, capped at half its
  remaining admission-deadline headroom) before its partial bucket
  dispatches — and does not wait AT ALL while the device pipeline is
  idle, so the p99 at low offered load stays a single-query scan, not
  a single-query scan plus ``flush_ms``;
* a two-stage pipeline that overlaps HOST work (queue pop, cache
  probe, sign-bucketing, batch assembly, result unpadding) with the
  DEVICE scan of the previous micro-batch: the dispatcher thread fires
  ``catalogue.query`` and moves on — jax's async dispatch returns
  device futures — while the harvester thread is the only place that
  calls ``np.asarray``/``block_until_ready``. A bounded harvest queue
  (``pipeline_depth``) back-pressures the dispatcher so at most that
  many micro-batches are ever in flight;
* MEASURED-COST dispatch: engine choice per micro-batch comes from the
  shared :class:`repro.core.engines.CostTable` (one timed run per
  warmed (engine, bucket, sign) config primes it; serving keeps it
  fresh) through :func:`repro.core.engines.select_engine` — the PR-7
  EWMA generalised from a degradation-ladder input into the primary
  router. The nnz heuristic remains only as the cold fallback;
* a head-query RESULT CACHE keyed ``(query bytes, k, cache token)``
  where the token is the catalogue's ``(snapshot version, mutation
  epoch)`` pair captured BEFORE the scan dispatches. Any visible
  mutation changes the token, so a cached entry can only ever be
  served while the catalogue contents it was computed against are
  still the visible contents — compaction/tombstone events additionally
  fire an invalidation listener that empties the cache outright.

PR-7 semantics are preserved: the admission/deadline ladder
(:class:`repro.serving.server.AdmissionPolicy`) runs at DISPATCH time
per micro-batch against the batch's tightest remaining deadline, every
served result is exact or carries its certificate (a shed batch returns
the explicit sentinel, never a silent partial answer), and queue-formed
buckets only dispatch warmed (bucket, sign, engine) configs — zero
engine compiles across compactions, pinned by tests/test_pipeline.py.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import obs
from repro.core import SepLRModel
from repro.core.engines import (
    auto_candidates,
    batch_bucket,
    get_engine,
    select_engine,
)
from repro.core.naive import TopKResult
from repro.core.strategies import sign_bucket_label
from repro.serving.server import AdmissionPolicy, ServeStats, TopKServer

#: default time a request may sit in a partial bucket before it flushes
DEFAULT_FLUSH_MS = 2.0
#: micro-batches in flight (dispatched, not yet harvested) before the
#: dispatcher blocks — stage overlap needs 2; more only adds queue delay
DEFAULT_PIPELINE_DEPTH = 2
#: result-cache entries kept (LRU); one entry is one (query, k) row
DEFAULT_CACHE_CAPACITY = 4096


class ResultCache:
    """LRU cache of per-request exact results, token-scoped.

    Keys are ``(query bytes, k, token)`` with ``token`` the catalogue's
    ``(version, epoch)`` :meth:`~repro.core.segments.SegmentedCatalogue.
    cache_token` captured before the scan that produced the value was
    dispatched. Because every visible mutation changes the token, a
    lookup under the CURRENT token can only hit entries whose contents
    are the current contents — the cache cannot serve across a snapshot
    version bump (or a delta append, which bumps the epoch half). The
    catalogue's invalidation listener additionally calls
    :meth:`invalidate` so dead-token entries do not linger in memory.

    Thread-safe; only EXACT results are inserted (a degraded or
    budgeted answer is a statement about one moment's load, not about
    the query).
    """

    def __init__(self, capacity: int = DEFAULT_CACHE_CAPACITY):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._data: "collections.OrderedDict[tuple, tuple]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.n_invalidations = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def lookup(self, key: tuple) -> Optional[tuple]:
        with self._lock:
            row = self._data.get(key)
            if row is None:
                self.misses += 1
            else:
                self._data.move_to_end(key)
                self.hits += 1
        obs.on_cache_lookup(row is not None)
        return row

    def insert(self, key: tuple, row: tuple) -> None:
        with self._lock:
            self._data[key] = row
            self._data.move_to_end(key)
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)

    def invalidate(self) -> None:
        """Drop everything. Runs as the catalogue's invalidation
        listener — possibly under the catalogue lock (synchronous
        compaction), so it must not call back into the catalogue.
        (The obs journal emission below holds only the journal's own
        lock, so it keeps that guarantee.)"""
        with self._lock:
            self._data.clear()
            self.n_invalidations += 1
        obs.on_cache_invalidated()


class _Request:
    """One submitted query riding the pipeline."""

    __slots__ = ("u", "k", "method", "budget", "deadline_s", "t_enqueue",
                 "flush_by", "event", "row", "error", "trace")

    def __init__(self, u: np.ndarray, k: int, method: str,
                 budget: Optional[int], deadline_ms: Optional[float],
                 flush_ms: float):
        now = time.perf_counter()
        self.u = u
        self.k = int(k)
        self.method = method
        self.budget = budget
        self.deadline_s = (None if deadline_ms is None
                           else now + float(deadline_ms) / 1e3)
        self.t_enqueue = now
        # a deadline halves the coalescing allowance: the request must
        # keep headroom to actually RUN after its flush fires
        wait = flush_ms / 1e3
        if deadline_ms is not None:
            wait = min(wait, 0.5 * float(deadline_ms) / 1e3)
        self.flush_by = now + wait
        self.event = threading.Event()
        self.row: Optional[tuple] = None
        self.error: Optional[BaseException] = None
        #: sampled obs trace (a :class:`repro.obs.Trace`) or None — set
        #: by submit(); stage threads stamp spans onto it as the request
        #: rides the pipeline
        self.trace = None

    def fulfill(self, row: tuple) -> None:
        self.row = row
        self.event.set()

    def fail(self, exc: BaseException) -> None:
        self.error = exc
        self.event.set()


class PendingResult:
    """Handle returned by :meth:`AsyncTopKServer.submit`."""

    def __init__(self, req: _Request):
        self._req = req

    def done(self) -> bool:
        return self._req.event.is_set()

    def result(self, timeout: Optional[float] = None) -> TopKResult:
        """Block until the request completes; returns a ``[1, k]``
        batched :class:`TopKResult` (same shape contract as
        ``TopKServer.query`` on a single query)."""
        if not self._req.event.wait(timeout):
            raise TimeoutError("result not ready within timeout")
        if self._req.error is not None:
            raise self._req.error
        vals, ids, nsc, depth, upper = self._req.row
        return TopKResult(vals[None], ids[None], nsc[None], depth[None],
                          upper=upper[None])


class PipelineStats:
    """Counters for the queue/pipeline layer (engine-level serve stats
    stay on :attr:`AsyncTopKServer.stats`, per requested method)."""

    def __init__(self) -> None:
        self.n_requests = 0
        self.n_batches = 0
        self.n_cached = 0
        self.n_shed = 0
        #: dispatched micro-batch sizes, keyed by EXACT coalesced size
        #: (the bucket it padded into is ``batch_bucket(size)``)
        self.batch_size_hist: Dict[int, int] = {}

    @property
    def mean_batch_size(self) -> float:
        n = sum(self.batch_size_hist.values())
        tot = sum(b * c for b, c in self.batch_size_hist.items())
        return tot / max(n, 1)

    def as_dict(self) -> Dict[str, object]:
        return {
            "n_requests": self.n_requests,
            "n_batches": self.n_batches,
            "n_cached": self.n_cached,
            "n_shed": self.n_shed,
            "mean_batch_size": self.mean_batch_size,
            "batch_size_hist": {str(kk): v for kk, v
                                in sorted(self.batch_size_hist.items())},
        }


class AsyncTopKServer:
    """Micro-batching front-end over :class:`TopKServer` (see module
    docstring for the design; DESIGN.md §13 for the contracts).

    Use as a context manager or call :meth:`close` — two daemon threads
    (dispatcher, harvester) run between :meth:`start` and then.

    ``method="auto"`` (the default) is the measured-cost router; any
    explicit registry name pins the engine exactly like the synchronous
    server. ``flush_ms`` bounds coalescing delay; ``pipeline_depth``
    bounds in-flight micro-batches (2 = classic double buffering).
    """

    def __init__(self, model: SepLRModel, max_batch: int = 64,
                 flush_ms: float = DEFAULT_FLUSH_MS,
                 pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
                 cache_capacity: int = DEFAULT_CACHE_CAPACITY,
                 method: str = "auto",
                 block_size: int = 256, delta_capacity: int = 256,
                 compact_async: bool = False,
                 policy: Optional[AdmissionPolicy] = None,
                 n_shards: int = 0, l1_capacity: Optional[int] = None,
                 cost_table=None):
        # n_shards > 0 fronts the async pipeline with the sharded LSM
        # ladder; cost_table accepts a pre-measured CostTable.load so a
        # restarted pipeline routes before its first observation
        self.server = TopKServer(model, max_batch=max_batch,
                                 block_size=block_size,
                                 delta_capacity=delta_capacity,
                                 compact_async=compact_async,
                                 policy=policy, n_shards=n_shards,
                                 l1_capacity=l1_capacity,
                                 cost_table=cost_table)
        self.max_batch = batch_bucket(max(int(max_batch), 1))
        self.flush_ms = float(flush_ms)
        self.pipeline_depth = max(int(pipeline_depth), 1)
        self.method = method
        get_engine(method)                    # fail fast on unknown names
        self.cache = ResultCache(cache_capacity)
        self.server.catalogue.add_invalidation_listener(
            self.cache.invalidate)
        self.pipeline_stats = PipelineStats()
        self._queue: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._harvest: "queue.Queue" = queue.Queue(
            maxsize=self.pipeline_depth)
        self._inflight_batches = 0
        self._stop = False
        self._dispatcher: Optional[threading.Thread] = None
        self._harvester: Optional[threading.Thread] = None

    # -- delegation ----------------------------------------------------------

    @property
    def catalogue(self):
        return self.server.catalogue

    @property
    def ctx(self):
        return self.server.ctx

    @property
    def stats(self) -> Dict[str, ServeStats]:
        return self.server.stats

    @property
    def cost_table(self):
        return self.server.cost_table

    @property
    def trace_counts(self) -> Dict[str, int]:
        return self.server.trace_counts

    @property
    def mutation_stats(self) -> Dict[str, float]:
        return self.server.mutation_stats

    def add_targets(self, rows) -> np.ndarray:
        return self.server.add_targets(rows)

    def delete_targets(self, gids) -> None:
        self.server.delete_targets(gids)

    def update_targets(self, gids, rows) -> None:
        self.server.update_targets(gids, rows)

    def warmup(self, k: int, batch_sizes=None, engines=None,
               m_buckets=None, budgets=None) -> "AsyncTopKServer":
        """Warm EVERY power-of-two bucket up to ``max_batch`` (plus any
        explicit ``batch_sizes``): queue-formed micro-batches land in
        whatever bucket the traffic produced — a half-full flush at
        B=13 pads into bucket 16 — so the async zero-compile guarantee
        needs the full ladder warmed, not just the endpoints the
        synchronous server warms. Each warmed (engine, bucket, sign)
        config also gets one timed run into the shared cost table
        (:meth:`repro.core.engines.EngineContext.warmup`), which is what
        arms the measured-cost router before the first real query.

        ``engines=None`` warms exactly the engines this pipeline can
        DISPATCH — the auto-router candidates, the pinned ``method``,
        and the ladder's ``norm`` fallback — not the whole registry:
        the compaction readiness pass replays this warm set on every
        new snapshot, and warming a per-context (closure-compiled)
        engine there would charge its unavoidable retrace to every
        compaction, breaking the zero-compile guarantee for engines the
        queue never dispatches anyway."""
        sizes = {1 << i for i in range(self.max_batch.bit_length())
                 if (1 << i) <= self.max_batch}
        sizes.add(self.max_batch)
        if batch_sizes:
            sizes.update(batch_bucket(int(b)) for b in batch_sizes)
        if engines is None:
            engines = sorted({*auto_candidates(), "norm"}
                             | ({self.method} - {"auto"}))
        self.server.warmup(k, batch_sizes=tuple(sorted(sizes)),
                           engines=engines, m_buckets=m_buckets,
                           budgets=budgets)
        return self

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "AsyncTopKServer":
        if self._dispatcher is not None:
            return self
        self._stop = False
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="topk-dispatch", daemon=True)
        self._harvester = threading.Thread(
            target=self._harvest_loop, name="topk-harvest", daemon=True)
        self._dispatcher.start()
        self._harvester.start()
        return self

    def close(self) -> None:
        """Drain and stop both pipeline threads (idempotent)."""
        if self._dispatcher is None:
            return
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._dispatcher.join()
        self._harvest.put(None)
        self._harvester.join()
        self._dispatcher = None
        self._harvester = None

    def __enter__(self) -> "AsyncTopKServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- submission ----------------------------------------------------------

    def submit(self, u, k: int, method: Optional[str] = None,
               budget: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> PendingResult:
        """Enqueue ONE query ``u`` ([R]); returns immediately with a
        :class:`PendingResult`. Validation failures raise here, in the
        caller's thread, not on the pipeline."""
        if self._dispatcher is None:
            raise RuntimeError("AsyncTopKServer not started "
                               "(use `with server:` or .start())")
        if int(k) <= 0:
            raise ValueError(f"k must be a positive int, got {k!r}")
        if budget is not None and int(budget) <= 0:
            raise ValueError(
                f"budget must be a positive int or None, got {budget!r}")
        if deadline_ms is not None and float(deadline_ms) < 0:
            raise ValueError(
                f"deadline_ms must be >= 0 or None, got {deadline_ms!r}")
        row = np.ascontiguousarray(np.asarray(u, np.float32)).reshape(-1)
        rank = self.catalogue.rank
        if row.shape[0] != rank:
            raise ValueError(
                f"query rank {row.shape[0]} != catalogue rank {rank}")
        if not np.all(np.isfinite(row)):
            raise ValueError("query contains NaN/Inf values")
        m = method if method is not None else self.method
        get_engine(m)
        if deadline_ms is None:
            deadline_ms = self.server.policy.deadline_ms
        req = _Request(row, int(k), m, budget, deadline_ms, self.flush_ms)
        # sampled full-span tracing (cheap counters stay always-on);
        # start is the enqueue timestamp so queue wait is span 1
        req.trace = obs.TRACER.start_trace(
            "topk.request", start=req.t_enqueue, k=int(k), method=m,
            budget=budget if budget is None else int(budget))
        with self._cond:
            self._queue.append(req)
            self.pipeline_stats.n_requests += 1
            self._cond.notify_all()
        return PendingResult(req)

    def query(self, U, k: int, method: Optional[str] = None,
              budget: Optional[int] = None,
              deadline_ms: Optional[float] = None) -> TopKResult:
        """Synchronous convenience: submit every row of ``U`` as an
        independent request and block for the batched result. Rows may
        coalesce with each other AND with concurrent submitters."""
        U2 = np.atleast_2d(np.asarray(U, np.float32))
        handles = [self.submit(U2[i], k, method=method, budget=budget,
                               deadline_ms=deadline_ms)
                   for i in range(U2.shape[0])]
        outs = [h.result() for h in handles]
        return jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs, axis=0), *outs)

    # -- stage 1: the dispatcher (host side) ---------------------------------

    def _dispatch_loop(self) -> None:
        while True:
            with self._cond:
                while not self._stop and not self._flushable_locked():
                    self._cond.wait(self._wait_s_locked())
                if self._stop and not self._queue:
                    return
                batch = self._form_batch_locked()
            if batch:
                try:
                    self._dispatch_batch(batch)
                except BaseException as exc:   # noqa: BLE001 — relayed
                    for r in batch:
                        r.fail(exc)

    def _flushable_locked(self) -> bool:
        """Head-of-queue flush test (lock held): fire when the pipeline
        is IDLE (coalescing would trade latency for nothing), when a
        full bucket is waiting, or when the oldest request's flush
        deadline has passed."""
        if not self._queue:
            return False
        if self._inflight_batches == 0:
            return True
        if len(self._queue) >= self.max_batch:
            return True
        return time.perf_counter() >= self._queue[0].flush_by

    def _wait_s_locked(self) -> Optional[float]:
        if not self._queue:
            return None
        return max(self._queue[0].flush_by - time.perf_counter(), 0.0)

    def _form_batch_locked(self) -> List[_Request]:
        """Pop the head request plus every queued COMPATIBLE request —
        same (k, method, budget), the static axes of one engine dispatch
        — preserving arrival order, up to ``max_batch``."""
        if not self._queue:
            return []
        head = self._queue[0]
        sig = (head.k, head.method, head.budget)
        batch, keep = [], collections.deque()
        while self._queue:
            r = self._queue.popleft()
            if len(batch) < self.max_batch \
                    and (r.k, r.method, r.budget) == sig:
                batch.append(r)
            else:
                keep.append(r)
        self._queue = keep
        return batch

    def _dispatch_batch(self, batch: List[_Request]) -> None:
        """Host stage for one micro-batch: cache probe, admission
        ladder, batch assembly, sign-bucketing — then fire the device
        scan WITHOUT waiting on it and hand the futures to the
        harvester. Runs concurrently with the device scan of the
        previous micro-batch. Each step is an :class:`obs.stage` of the
        micro-batch's sequence number."""
        srv = self.server
        seq = next(srv._batch_seq)
        k, method = batch[0].k, batch[0].method
        budget = batch[0].budget
        req_name = get_engine(method).name
        with obs.stage("topk.coalesce", batch=seq, n=len(batch)) as co:
            # the token is captured BEFORE the scan dispatches: a mutation
            # landing mid-scan bumps the live token, so whatever this scan
            # returns is inserted under a token no future lookup can match
            token = self.catalogue.cache_token()
            misses: List[_Request] = []
            for r in batch:
                obs.on_queue_wait(1e6 * (co.start - r.t_enqueue))
                row = (None if budget is not None
                       else self.cache.lookup((r.u.tobytes(), r.k, token)))
                if row is not None:
                    self.pipeline_stats.n_cached += 1
                    if r.trace is not None:
                        r.trace.root.set(batch=seq)
                        r.trace.span("queue_wait", start=r.t_enqueue,
                                     end=co.start)
                        r.trace.span("cache_hit", start=co.start,
                                     version=token[0], epoch=token[1])
                    self._finish_request(r, method, row)
                else:
                    misses.append(r)
            if not misses:
                return
            n = len(misses)
            obs.on_batch_formed(n)
            U = np.stack([r.u for r in misses])
        req_stats = srv.stats.setdefault(req_name, ServeStats())
        with obs.stage("topk.route", batch=seq, n=n) as rt:
            eng = (select_engine(self.ctx, U) if method == "auto"
                   else get_engine(method))
            # admission at dispatch time (PR-7 ladder, per micro-batch):
            # judged against the TIGHTEST deadline riding in the batch
            deadlines = [r.deadline_s for r in misses
                         if r.deadline_s is not None]
            remaining = (min(deadlines) - time.perf_counter()
                         if deadlines else None)
            run_eng, bud, rung = srv._admit(eng, n, remaining)
            label = (sign_bucket_label(run_eng.batch_config(self.ctx, U))
                     if run_eng is not None
                     and run_eng.batch_config is not None else "")
        if rung != "full":
            req_stats.bump_degradation(rung)
            obs.on_degradation(req_name, rung)
        if run_eng is None:
            res = srv._shed_result(n, k)
            req_stats.note_uncertified(n)
            obs.on_uncertified(req_name, n)
            self.pipeline_stats.n_shed += n
            for r in misses:
                if r.trace is not None:
                    r.trace.root.set(batch=seq)
                    r.trace.span("queue_wait", start=r.t_enqueue,
                                 end=co.start)
                    r.trace.span("route", start=rt.start, end=rt.end,
                                 rung=rung)
            self._fulfill(misses, method, res, None, seq)
            self.pipeline_stats.n_batches += 1
            self.pipeline_stats.batch_size_hist[n] = \
                self.pipeline_stats.batch_size_hist.get(n, 0) + 1
            return
        if bud is None:
            bud = budget
        # span annotations are assembled once per batch, only when at
        # least one rider is traced (sampling keeps this off the common
        # path): the cost-table entry the router consulted plus the
        # stage timestamps the harvester turns into child spans
        tinfo = None
        if any(r.trace is not None for r in misses):
            bucket = batch_bucket(n)
            key = run_eng.name if bud is None else f"{run_eng.name}@budget"
            pred = srv.cost_table.predict(key, bucket, label)
            tinfo = {
                "coalesce": (co.start, co.end), "route": (rt.start, rt.end),
                "engine": run_eng.name, "rung": rung,
                "cost_entry": f"{key}|{bucket}|{label}",
                "predicted_us": (None if pred is None else 1e6 * pred),
                "sign": label, "batch_size": n,
                "version": token[0], "epoch": token[1],
            }
        with obs.stage("topk.enqueue", batch=seq, n=n) as enq:
            res, info = self.catalogue.query(run_eng, U, k, budget=bud)
        # NO np.asarray here: the result is a device future; blocking is
        # the harvester's job. This put() back-pressures the dispatcher
        # once `pipeline_depth` micro-batches are unharvested.
        with self._cond:
            self._inflight_batches += 1
        self.pipeline_stats.n_batches += 1
        self.pipeline_stats.batch_size_hist[n] = \
            self.pipeline_stats.batch_size_hist.get(n, 0) + 1
        with obs.stage("topk.backpressure", batch=seq, n=n):
            self._harvest.put((misses, method, run_eng, bud, label, res,
                               info, enq, token, tinfo, seq))

    # -- stage 2: the harvester (device sync side) ---------------------------

    def _harvest_loop(self) -> None:
        while True:
            item = self._harvest.get()
            if item is None:
                return
            (misses, method, run_eng, bud, label, res, info, enq, token,
             tinfo, seq) = item
            n = len(misses)
            try:
                with obs.stage("topk.await", batch=seq, n=n) as aw:
                    res = jax.tree_util.tree_map(np.asarray, res)  # blocks
                with obs.stage("topk.account", batch=seq, n=n):
                    dt = aw.end - enq.start
                    if res.upper is None:
                        res = res._replace(upper=np.full(
                            (np.asarray(res.values).shape[0],), -np.inf,
                            np.float32))
                    req_stats = self.stats.setdefault(
                        get_engine(method).name, ServeStats())
                    if bud is not None:
                        self.server._note_certificates(
                            req_stats, run_eng.name, bud, res)
                    key = (run_eng.name if bud is None
                           else f"{run_eng.name}@budget")
                    per_q = dt / max(n, 1)
                    prev = self.server._cost_ewma.get(key)
                    self.server._cost_ewma[key] = (
                        per_q if prev is None else 0.8 * prev + 0.2 * per_q)
                    self.cost_table.observe(key, batch_bucket(n), label,
                                            per_q)
                    self.server._record(run_eng.name, res, dt, n,
                                        info.delta_scored, sign_label=label)
                    if tinfo is not None:
                        self._stamp_spans(misses, tinfo, enq, aw, seq)
                # only the EXACT path populates the cache (bud is the
                # effective budget: a ladder downgrade never caches)
                self._fulfill(misses, method, res,
                              None if bud is not None else token, seq)
            except BaseException as exc:       # noqa: BLE001 — relayed
                for r in misses:
                    r.fail(exc)
            finally:
                with self._cond:
                    self._inflight_batches -= 1
                    self._cond.notify_all()

    @staticmethod
    def _stamp_spans(misses: List[_Request], tinfo: dict, enq: obs.stage,
                     aw: obs.stage, seq: int) -> None:
        """The stage spans of one harvested micro-batch, onto each of its
        traced riders."""
        t_done = time.perf_counter()
        co, rt = tinfo["coalesce"], tinfo["route"]
        for r in misses:
            if r.trace is None:
                continue
            r.trace.root.set(engine=tinfo["engine"], version=tinfo["version"],
                             epoch=tinfo["epoch"], batch=seq)
            r.trace.span("queue_wait", start=r.t_enqueue, end=co[0])
            r.trace.span("coalesce", start=co[0], end=co[1],
                         batch_size=tinfo["batch_size"])
            r.trace.span("route", start=rt[0], end=rt[1],
                         engine=tinfo["engine"], rung=tinfo["rung"],
                         cost_entry=tinfo["cost_entry"],
                         predicted_us=tinfo["predicted_us"])
            r.trace.span("dispatch", start=rt[1], end=enq.start)
            r.trace.span("enqueue", start=enq.start, end=enq.end,
                         engine=tinfo["engine"], sign=tinfo["sign"],
                         version=tinfo["version"], epoch=tinfo["epoch"])
            r.trace.span("await", start=enq.end, end=aw.end)
            r.trace.span("harvest", start=aw.end, end=t_done)

    def _fulfill(self, batch: List[_Request], method: str,
                 res: TopKResult, cache_token: Optional[tuple],
                 seq: int) -> None:
        """Unpad a batched result into per-request rows, fulfil the
        futures, and (exact results only) populate the cache."""
        with obs.stage("topk.fulfil", batch=seq, n=len(batch)) as st:
            vals = np.asarray(res.values)
            ids = np.asarray(res.indices)
            nsc = np.asarray(res.n_scored)
            depth = np.asarray(res.depth)
            upper = (np.full((vals.shape[0],), -np.inf, np.float32)
                     if res.upper is None else np.asarray(res.upper))
            for i, r in enumerate(batch):
                row = (vals[i], ids[i], nsc[i], depth[i], upper[i])
                if cache_token is not None:
                    self.cache.insert((r.u.tobytes(), r.k, cache_token),
                                      row)
                if r.trace is not None:
                    r.trace.span("merge", start=st.start)
                self._finish_request(r, method, row)

    def _finish_request(self, r: _Request, method: str,
                        row: tuple) -> None:
        name = get_engine(method).name
        stats = self.stats.setdefault(name, ServeStats())
        us = 1e6 * (time.perf_counter() - r.t_enqueue)
        stats.record_request_latency(us)
        obs.on_request_done(name, us)
        if r.trace is not None:
            r.trace.finish()
            # drop the request's reference: callers hold the
            # PendingResult (hence the _Request) for as long as they
            # like, and at high sample rates retaining every span tree
            # through it is real GC pressure — finished traces live
            # only in the tracer's bounded store
            r.trace = None
        r.fulfill(row)

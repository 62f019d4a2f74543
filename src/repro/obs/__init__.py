"""Unified observability layer (DESIGN.md §14).

Three process-wide defaults, one switch:

* :data:`REGISTRY` — the metrics registry (counters / gauges /
  log-scale histograms; JSON ``snapshot()`` + Prometheus
  ``render_prom()`` exporters);
* :data:`TRACER` — per-request span trees with a sampling knob and a
  bounded store;
* :data:`JOURNAL` — the bounded structured event journal (compactions,
  faults, degradations, invalidations, epoch bumps, engine traces, XLA
  compiles).

On the profiler's clock besides: :class:`stage` marks one host stage of
the served path as a ``jax.profiler`` event, and while the layer is on
a ``gc.callbacks`` hook marks every Python garbage-collection pause
(``py.gc``) and a JAX monitoring listener names every XLA compile.

``set_enabled(False)`` turns all of it into no-op branches and removes
both hooks — the baseline the overhead benchmark
(``benchmarks/obs_overhead.py``) compares against.

The ``on_*`` helpers below are the ONLY thing production code calls:
each is one function call at the instrumentation seam, early-outs when
disabled, and owns the mapping from a domain event to instrument
updates + journal records. Keeping the mapping here (rather than at the
call sites) keeps engine/catalogue/serving code one line per seam and
makes the full instrument inventory reviewable in one file.

Label/metric naming: every metric is ``repro_``-prefixed; label axes
mirror the compile-cache axes (``engine``, ``bucket``, ``sign``) plus
the admission axes (``rung``, ``budget_bucket``) so a dashboard slices
along the same lines the system specialises along.
"""

from __future__ import annotations

import collections
import gc
import time

import jax
from jax._src.dispatch import BACKEND_COMPILE_EVENT
from jax.profiler import TraceAnnotation

from repro.obs.events import Event, EventJournal
from repro.obs.metrics import (
    Counter,
    FRACTION_BUCKETS,
    GAP_BUCKETS,
    Gauge,
    Histogram,
    LATENCY_BUCKETS_US,
    MetricsRegistry,
    SECONDS_BUCKETS,
    SIZE_BUCKETS,
    log2_buckets,
    parse_prom_text,
    validate_snapshot,
)
from repro.obs.schema import (
    MUTATION_STATS_SCHEMA,
    StatField,
    build_mutation_stats,
)
from repro.obs.trace import Span, Trace, Tracer

__all__ = [
    "REGISTRY", "JOURNAL", "TRACER", "set_enabled", "enabled", "reset",
    "stage",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "EventJournal",
    "Event", "Tracer", "Trace", "Span", "log2_buckets",
    "validate_snapshot", "parse_prom_text", "build_mutation_stats",
    "MUTATION_STATS_SCHEMA", "StatField",
    "LATENCY_BUCKETS_US", "SECONDS_BUCKETS", "FRACTION_BUCKETS",
    "GAP_BUCKETS", "SIZE_BUCKETS",
]

#: process-wide defaults — the engine/catalogue/serving seams record here.
#: The tracer keeps every 100th request's span tree (DESIGN.md §14); a
#: caller that wants every request sets ``TRACER.sample_rate = 1.0``
REGISTRY = MetricsRegistry()
JOURNAL = EventJournal(capacity=4096)
TRACER = Tracer(capacity=256, sample_rate=0.01)


def set_enabled(on: bool) -> None:
    """Master switch for the default registry, tracer and journal, the
    stage events, the GC hook and the compile listener."""
    REGISTRY.enabled = TRACER.enabled = JOURNAL.enabled = bool(on)
    _install_hooks(bool(on))


def enabled() -> bool:
    return REGISTRY.enabled


def reset() -> None:
    """Clear every default store (instrument definitions survive) —
    test/benchmark isolation."""
    REGISTRY.reset()
    JOURNAL.clear()
    TRACER.clear()


# ---------------------------------------------------------------------------
# Instrument inventory
# ---------------------------------------------------------------------------

ENGINE_TRACES = REGISTRY.counter(
    "repro_engine_traces_total",
    "Executor traces (compiles) observed at jit trace time, per engine.",
    labels=("engine",))
QUERIES = REGISTRY.counter(
    "repro_queries_total", "Queries served, per engine that ran.",
    labels=("engine",))
SCORED = REGISTRY.counter(
    "repro_scored_total",
    "Candidate scores computed (the paper's cost metric), per engine.",
    labels=("engine",))
DEPTH = REGISTRY.counter(
    "repro_depth_total", "Scan depth consumed (list rows), per engine.",
    labels=("engine",))
SCORED_FRACTION = REGISTRY.histogram(
    "repro_scored_fraction",
    "Per-batch mean fraction of the live catalogue scored — the "
    "pruning-efficiency claim, live.",
    labels=("engine",), buckets=FRACTION_BUCKETS)
BATCH_LATENCY = REGISTRY.histogram(
    "repro_batch_latency_us",
    "Per-query microseconds of one served batch (dispatch->harvest).",
    labels=("engine",), buckets=LATENCY_BUCKETS_US)
REQUEST_LATENCY = REGISTRY.histogram(
    "repro_request_latency_us",
    "Per-request enqueue->result microseconds (queue wait included).",
    labels=("engine",), buckets=LATENCY_BUCKETS_US)
QUEUE_WAIT = REGISTRY.histogram(
    "repro_queue_wait_us",
    "Microseconds a request waited in the coalescing queue before its "
    "micro-batch formed.",
    labels=(), buckets=LATENCY_BUCKETS_US)
BATCH_SIZE = REGISTRY.histogram(
    "repro_batch_size", "Coalesced micro-batch sizes (exact, pre-pad).",
    labels=(), buckets=SIZE_BUCKETS)
TOPK_SELECT = REGISTRY.counter(
    "repro_topk_select_total",
    "Dispatched batches per engine and top-K selection path "
    "(two_stage or direct, DESIGN.md §4).",
    labels=("engine", "path"))
SIGN_BATCHES = REGISTRY.counter(
    "repro_sign_batches_total",
    "Batches served per sign bucket (the DESIGN.md §11 compile axis).",
    labels=("engine", "sign"))
DEGRADATIONS = REGISTRY.counter(
    "repro_degradations_total",
    "Admission-ladder downgrades, per REQUESTED engine and rung.",
    labels=("engine", "rung"))
SHED = REGISTRY.counter(
    "repro_shed_total", "Requests shed (sentinel results).", labels=())
UNCERTIFIED = REGISTRY.counter(
    "repro_uncertified_total",
    "Queries whose result carried >= 1 uncertified slot.",
    labels=("engine",))
CERTIFIED_FRACTION = REGISTRY.histogram(
    "repro_certified_fraction",
    "Per-batch fraction of result slots provably in the true top-K "
    "(certificate gap <= 0), per engine and budget bucket.",
    labels=("engine", "budget_bucket"), buckets=FRACTION_BUCKETS)
UNCERTIFIED_GAP = REGISTRY.histogram(
    "repro_uncertified_gap",
    "Per-batch mean certificate gap over UNCERTIFIED slots (score "
    "units; how far from provable the halted scan stopped).",
    labels=("engine", "budget_bucket"), buckets=GAP_BUCKETS)
CACHE_LOOKUPS = REGISTRY.counter(
    "repro_cache_lookups_total", "Result-cache lookups by outcome.",
    labels=("outcome",))
CACHE_INVALIDATIONS = REGISTRY.counter(
    "repro_cache_invalidations_total",
    "Result-cache full invalidations (catalogue listener).", labels=())
COMPACTIONS = REGISTRY.counter(
    "repro_compaction_events_total",
    "Compaction state-machine transitions (start/success/fail/retry/"
    "retry_scheduled/forced_sync/stuck).",
    labels=("event",))
COMPACTION_SECONDS = REGISTRY.histogram(
    "repro_compaction_seconds", "Successful compaction build seconds.",
    labels=(), buckets=SECONDS_BUCKETS)
EPOCH_BUMPS = REGISTRY.counter(
    "repro_epoch_bumps_total",
    "Mutation-epoch bumps by kind (insert/update/delete/swap).",
    labels=("kind",))
FAULTS_FIRED = REGISTRY.counter(
    "repro_faults_fired_total", "Armed fault-seam triggers, per point.",
    labels=("point",))
COST_TABLE_US = REGISTRY.gauge(
    "repro_cost_table_us",
    "Measured per-query cost EWMA, per (engine, batch bucket, sign) — "
    "the serving router's table, exported live.",
    labels=("engine", "bucket", "sign"))
GC_PAUSE = REGISTRY.histogram(
    "repro_gc_pause_seconds",
    "Python garbage-collection pauses, per collected generation.",
    labels=("generation",), buckets=log2_buckets(2.0 ** -20, 64.0))
XLA_COMPILES = REGISTRY.counter(
    "repro_xla_compiles_total",
    "XLA backend compiles (persistent-cache loads included), per jitted "
    "function name.",
    labels=("fun",))


# ---------------------------------------------------------------------------
# Wiring helpers (the one-liners production seams call)
# ---------------------------------------------------------------------------

def on_engine_trace(engine: str, bcfg: tuple = ()) -> None:
    """An executor traced (compiled) — engines._note_trace seam."""
    if not REGISTRY.enabled:
        return
    ENGINE_TRACES.inc(engine=engine)
    JOURNAL.emit("engine.trace", engine=engine,
                 sign=str(bcfg) if bcfg else "")


def on_batch_served(engine: str, n: int, n_scored: int, depth_sum: int,
                    m_live: int, per_query_us: float,
                    sign_label: str = "") -> None:
    """One batch harvested: pruning-efficiency + latency metrics."""
    if not REGISTRY.enabled:
        return
    QUERIES.inc(n, engine=engine)
    SCORED.inc(n_scored, engine=engine)
    DEPTH.inc(depth_sum, engine=engine)
    if m_live > 0 and n > 0:
        SCORED_FRACTION.observe(n_scored / (n * m_live), engine=engine)
    BATCH_LATENCY.observe(per_query_us, engine=engine)
    if sign_label:
        SIGN_BATCHES.inc(engine=engine, sign=sign_label)


def on_topk_select(engine: str, path: str) -> None:
    """One batch dispatched to an executor whose top-K takes ``path``."""
    if not REGISTRY.enabled:
        return
    TOPK_SELECT.inc(engine=engine, path=path)


def on_request_done(engine: str, us: float) -> None:
    if not REGISTRY.enabled:
        return
    REQUEST_LATENCY.observe(us, engine=engine)


def on_queue_wait(us: float) -> None:
    if not REGISTRY.enabled:
        return
    QUEUE_WAIT.observe(us)


def on_batch_formed(n: int) -> None:
    if not REGISTRY.enabled:
        return
    BATCH_SIZE.observe(n)


def on_degradation(engine: str, rung: str) -> None:
    """An admission-ladder downgrade decision (recorded under the
    REQUESTED engine, same accounting as ``ServeStats.degradations``)."""
    if not REGISTRY.enabled:
        return
    DEGRADATIONS.inc(engine=engine, rung=rung)
    if rung == "shed":
        SHED.inc()
    JOURNAL.emit("admission.degrade", engine=engine, rung=rung)


def on_uncertified(engine: str, n: int) -> None:
    if not REGISTRY.enabled or n <= 0:
        return
    UNCERTIFIED.inc(n, engine=engine)


def on_certificates(engine: str, budget_bucket: int,
                    certified_fraction: float,
                    mean_uncertified_gap: float,
                    any_uncertified: bool) -> None:
    """One budgeted batch's certificate summary (pinned against
    ``certificate_gaps`` ground truth by tests/test_obs.py)."""
    if not REGISTRY.enabled:
        return
    b = str(int(budget_bucket))
    CERTIFIED_FRACTION.observe(certified_fraction, engine=engine,
                               budget_bucket=b)
    if any_uncertified:
        UNCERTIFIED_GAP.observe(mean_uncertified_gap, engine=engine,
                                budget_bucket=b)


def on_cache_lookup(hit: bool) -> None:
    if not REGISTRY.enabled:
        return
    CACHE_LOOKUPS.inc(outcome="hit" if hit else "miss")


def on_cache_invalidated() -> None:
    """Result-cache flush. May run under the catalogue lock (the
    invalidation-listener path) — journal emission is lock-safe."""
    if not REGISTRY.enabled:
        return
    CACHE_INVALIDATIONS.inc()
    JOURNAL.emit("cache.invalidate")


def on_compaction(event: str, **fields) -> None:
    """One compaction state-machine transition; ``fields`` carry the
    join keys the producer knows (version, epoch, chain_len, ...)."""
    if not REGISTRY.enabled:
        return
    COMPACTIONS.inc(event=event)
    if event == "success" and "duration_s" in fields:
        COMPACTION_SECONDS.observe(fields["duration_s"])
    JOURNAL.emit(f"compaction.{event}", **fields)


def on_epoch_bump(kind: str, version: int, epoch: int) -> None:
    """A visible mutation bumped the epoch (called under the catalogue
    lock — emission must stay reentrancy-free, which it is)."""
    if not REGISTRY.enabled:
        return
    EPOCH_BUMPS.inc(kind=kind)
    JOURNAL.emit("epoch.bump", mutation=kind, version=version,
                 epoch=epoch)


def on_fault_fired(point: str) -> None:
    if not REGISTRY.enabled:
        return
    FAULTS_FIRED.inc(point=point)
    JOURNAL.emit("fault.fired", point=point)


def on_cost_observation(engine: str, bucket: int, label: str,
                        per_query_s: float) -> None:
    """CostTable EWMA update — exported live as a gauge."""
    if not REGISTRY.enabled:
        return
    COST_TABLE_US.set(1e6 * per_query_s, engine=engine,
                      bucket=str(int(bucket)), sign=label)


# ---------------------------------------------------------------------------
# Host stages, GC pauses and compiles on the profiler's clock
# ---------------------------------------------------------------------------

class stage:
    """One host stage of the served path, recorded on two clocks.

    ``with obs.stage("topk.enqueue", batch=seq, n=n) as st:`` opens a
    ``jax.profiler.TraceAnnotation`` of that name on the working thread,
    so it lands in a profile beside the device's ops on the device
    trace's clock, and reads the ``time.perf_counter`` boundaries
    (``st.start``, ``st.end``) that the per-request trace spans and the
    server's cost model use — one measurement in both records. ``batch``
    (the server's micro-batch sequence number) and ``n`` (the real batch
    size) travel as the event's arguments; a sampled request's root span
    carries the same ``batch``, the join key from a request to its
    batch's events. With the layer disabled no event is opened; the
    clock is still read, since the server's cost model needs it.
    """

    __slots__ = ("name", "args", "start", "end", "_event")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args
        self._event = None

    def __enter__(self) -> "stage":
        if REGISTRY.enabled:
            self._event = TraceAnnotation(self.name, **self.args)
        self.start = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Add arguments known only inside the stage to its event."""
        if self._event is not None:
            self._event.set_metadata(**args)

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if self._event is not None:
            self._event.__exit__(*exc)
            self._event = None


#: the open ``py.gc`` event and its start; collections never nest
_gc_open = None
#: pauses not yet in :data:`GC_PAUSE`, oldest first
_gc_backlog: "collections.deque" = collections.deque(maxlen=4096)


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a ``py.gc`` profiler event per collection,
    and its pause in :data:`GC_PAUSE`. A collection can interrupt this
    very thread inside a read of that histogram, under its lock, so the
    pause is recorded only while the lock is free, else at a later
    pause."""
    global _gc_open
    gen = info.get("generation")
    if phase == "start":
        _gc_open = (TraceAnnotation("py.gc", generation=gen),
                    time.perf_counter())
        return
    if _gc_open is None:
        return
    event, t0 = _gc_open
    _gc_open = None
    event.__exit__(None, None, None)
    _gc_backlog.append((str(gen), time.perf_counter() - t0))
    while _gc_backlog:
        g, seconds = _gc_backlog[0]
        if not GC_PAUSE.try_observe(seconds, generation=g):
            break
        _gc_backlog.popleft()


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    """JAX monitoring listener: names the function of every backend
    compile."""
    if event != BACKEND_COMPILE_EVENT:
        return
    fun = str(kwargs.get("fun_name", ""))
    XLA_COMPILES.inc(fun=fun)
    JOURNAL.emit("xla.compile", fun=fun, seconds=float(duration_secs))


_hooked = False


def _install_hooks(on: bool) -> None:
    global _hooked, _gc_open
    if on == _hooked:
        return
    if on:
        gc.callbacks.append(_on_gc)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    else:
        gc.callbacks.remove(_on_gc)
        jax.monitoring.unregister_event_duration_listener(_on_duration)
        _gc_open = None
    _hooked = on


_install_hooks(True)

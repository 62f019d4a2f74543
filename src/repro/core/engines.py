"""Engine registry: every top-K engine behind one name-keyed interface.

The serving layer, the benchmark harness, and tests all dispatch through
this registry (DESIGN.md §1) instead of hand-rolled ``if/elif`` chains.
An :class:`Engine` bundles a batched-executable factory with capability
metadata (exact? needs the sorted-list index? batched? which backend
executes it?) so callers can enumerate, filter, and sweep engines they
have never heard of — which is how future engines (LEMP-style per-bucket
bounds, sharded variants, approximate modes) become reachable from every
layer by adding one ``register`` call.

Engines run against an :class:`EngineContext` — the catalogue plus lazily
built derived state (sorted-list index, layouts, Pallas catalogue) shared
across queries, so a server builds it once and every engine reuses it.

**Argument-passing compilation contract** (DESIGN.md §10). Engines come
in two kinds, distinguished by which :class:`Engine` fields they set:

* **Argument-passing engines** (``run_args`` + ``make_args``; ``naive``,
  ``ta``, ``bta``, ``norm``, ``norm_sharded``): the compiled function is
  a MODULE-LEVEL ``jax.jit`` executor shared by every context in the
  process. Everything snapshot-shaped — catalogue rows, index arrays,
  :mod:`repro.core.layout` pytrees — flows in as runtime ARGUMENTS
  (built once per context by ``make_args``, padded to the power-of-two
  M-bucket :func:`m_bucket`, cached by :meth:`EngineContext.engine_args`),
  together with a traced ``m_real`` scalar carrying the real catalogue
  size. The effective compile key is therefore
  ``(engine, k, batch-bucket, M-bucket, layout-shape, config)`` — NO
  snapshot version, no array identity — so a compacted snapshot of the
  same bucket re-dispatches every existing trace: compaction is
  compile-free (the streaming win this layer exists for, DESIGN.md §9).
  Pad rows follow the conventions stated in :mod:`repro.core.layout`
  and are never walked, scored, or counted (the ``m_real`` index
  arithmetic in :mod:`repro.core.strategies`), so results and the
  paper's ``n_scored``/``depth`` metrics are bit-identical to the
  unpadded scan.

* **Closure engines** (``make_batched``; ``pallas`` only): the factory
  closes over context state that cannot yet cross a jit boundary as an
  argument (the Pallas ``MIPSCatalog`` does host-side per-query block
  pre-screening and owns the kernel grid), so the executable lives in a
  per-context cache keyed ``(engine, k, batch-bucket, snapshot
  version)`` — the PR-4 contract, retained only here. A compaction
  serving ``pallas`` re-traces it. The TPU compiler refuses the kernel,
  so on a TPU backend the factory raises instead (ROADMAP A2).

Batch sizes are bucketed to the next power of two by both kinds
(:func:`batch_bucket`; queries padded by repeating the last row, results
sliced back). :meth:`EngineContext.warmup` populates the caches ahead of
traffic — optionally for LARGER M-buckets than the current catalogue's
(``m_buckets=``), so a growing streaming catalogue crosses its next
bucket boundary without a single new trace. :func:`trace_totals` exposes
the process-wide per-engine trace counters the executors bump at trace
time; :attr:`EngineContext.trace_counts` attributes deltas of those
counters to the context whose call triggered them, so tests can assert
cache hits (0 new traces after warmup, 0 across a same-bucket
compaction).

Registered engines:

================  =====  ===========  ========  ===========  ==================================
name              exact  needs_index  backend   layout       algorithm
================  =====  ===========  ========  ===========  ==================================
``naive``         yes    no           jax       row_major    full matmul + exact top-k
``ta``            yes    yes          jax       list_major   chunked TA rounds (count-faithful)
``bta``           yes    yes          jax       list_major   Block Threshold Algorithm
``norm``          yes    yes          jax       norm_major   Cauchy-Schwarz norm-block scan
``norm_sharded``  yes    yes          jax       norm_sharded shared-tile norm scan under
                                                             shard_map, cross-shard pmax bounds
``pallas``        yes    yes          pallas    norm_major   norm-block scan as a Pallas kernel
                                                             (interpret mode; refused on TPU)
``fagin``         yes    yes          numpy     row_major    Fagin's Algorithm (host oracle)
``partial``       yes    yes          numpy     row_major    Partial TA, Alg. 3 (host oracle)
``auto``          yes    yes          dispatch  —            picks per batch (see below)
================  =====  ===========  ========  ===========  ==================================

The two ``numpy`` rows are the paper-faithful host oracles: exact,
host-only, never jitted or batched (``host_only=True``, no executable —
they run as dispatch loops). Registering them makes ``list_engines()``
cover every implemented algorithm; the benchmark sweep skips
``backend="numpy"`` rows when timing.

``auto`` picks per query batch: sparse batches go to ``ta`` (zero-weight
lists are never walked, so TA's per-round work collapses to nnz(u)); dense
batches over catalogues whose norm spectrum decays go to the norm scan
``norm``; flat-spectrum dense batches go to ``bta``. ``pallas`` is never
an ``auto`` candidate: the TPU compiler refuses its kernel
(:data:`PALLAS_TPU_REFUSAL`), and elsewhere it runs in interpret mode.
The sparsity statistic is computed HOST-side from the incoming array —
dispatch never enqueues work (or a sync) on the device query stream.

Aliases accepted by :func:`get_engine`: ``threshold -> ta``,
``blocked -> bta``, ``norm_pruned -> norm``, ``topk_mips -> pallas``.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.blocked import (
    blocked_topk,
    blocked_topk_batched_native,
    chunked_ta_topk,
    chunked_ta_topk_batched_native,
    norm_pruned_topk_batched,
)
from repro.core.driver import NEG_INF
from repro.core.index import TopKIndex, build_index
from repro.core.layout import (DEFAULT_PREFIX_DEPTH,
                               LIST_LAYOUT_MIN_TARGETS,
                               build_layout, pad_rank_by_item,
                               pad_zero_rows)
from repro.core.naive import (SCORE_PRECISION, TopKResult, select_path,
                              select_topk)
from repro.core.strategies import sign_bucket, sign_bucket_label

Array = jnp.ndarray


def batch_bucket(n: int) -> int:
    """Next power of two >= n — the compile-cache batch granularity."""
    return 1 << max(0, int(n) - 1).bit_length()


def m_bucket(m: int) -> int:
    """Next power of two >= m — the compile-cache CATALOGUE granularity.

    Argument-passing engines pad every catalogue-shaped array to this
    bucket (DESIGN.md §10), so any two snapshots whose sizes share a
    bucket share every compiled executable. Same arithmetic as
    :func:`batch_bucket`, named separately because the two axes bucket
    independently.
    """
    return batch_bucket(m)


def pad_to_bucket(U: "Array") -> "Array":
    """Pad a ``[B, R]`` batch to its power-of-two bucket.

    Padding repeats the LAST query row — never zeros: an all-zero query
    deactivates every list and would drag a vmapped lockstep scan to its
    worst case. Shared by the engine compile cache and the segmented
    query path (:mod:`repro.core.segments`), so the two can never
    diverge on padding semantics.
    """
    b = U.shape[0]
    bucket = batch_bucket(b)
    if bucket == b:
        return U
    pad = jnp.broadcast_to(U[b - 1:b], (bucket - b, U.shape[1]))
    return jnp.concatenate([U, pad], axis=0)


# ---------------------------------------------------------------------------
# Process-wide trace accounting + the shared argument-passing executors
# ---------------------------------------------------------------------------

#: Process-wide trace counters, bumped by every executor AT TRACE TIME
#: (a jit cache hit adds nothing). Keyed by engine name. Contexts
#: attribute deltas of these to their own ``trace_counts``; the streaming
#: layer reads the totals around a compaction build to report
#: ``engine_compiles_per_compaction`` (DESIGN.md §10).
_TRACE_TOTALS: Dict[str, int] = {}

#: Per-sign-bucket trace counters: ``(engine, batch-cfg tuple) -> count``.
#: The batch cfg is the sign bucket for the list engines, ``()`` for
#: engines without batch specialisation — so this resolves exactly which
#: sign-specialised variants have been compiled (DESIGN.md §11).
_TRACE_DETAIL: Dict[Tuple[str, tuple], int] = {}


def _note_trace(name: str, bcfg: tuple = ()) -> None:
    _TRACE_TOTALS[name] = _TRACE_TOTALS.get(name, 0) + 1
    key = (name, bcfg)
    _TRACE_DETAIL[key] = _TRACE_DETAIL.get(key, 0) + 1
    # observability seam: a trace is always an anomaly worth journaling
    # (it only happens off the warmed path), so it carries an event as
    # well as the counter (DESIGN.md §14)
    obs.on_engine_trace(name, bcfg)


def trace_totals() -> Dict[str, int]:
    """Snapshot of the process-wide per-engine trace counters."""
    return dict(_TRACE_TOTALS)


def trace_detail() -> Dict[Tuple[str, tuple], int]:
    """Snapshot of the per-(engine, sign-bucket) trace counters."""
    return dict(_TRACE_DETAIL)


def note_pruning_metrics(engine: str, n: int, n_scored: int,
                         depth_sum: int, m_live: int,
                         per_query_us: float,
                         sign_label: str = "") -> None:
    """Record one harvested batch's pruning-efficiency metrics into the
    observability registry: ``n_scored`` and ``depth`` totals plus the
    scored FRACTION vs the live catalogue size — the paper's efficiency
    claim as a live metric instead of an offline bench column
    (DESIGN.md §14). Called by the serving layer after it materialises
    a result host-side (never from inside an executor: results on the
    dispatch path are device futures and must stay unblocked)."""
    obs.on_batch_served(engine, n, n_scored, depth_sum, m_live,
                        per_query_us, sign_label)


class CostTable:
    """Measured per-(engine, batch-bucket, sign-bucket) serve cost.

    An EWMA (default ``alpha=0.2``) of observed per-QUERY seconds, keyed
    by the same axes the compile cache specialises on — engine name,
    power-of-two batch bucket, sign-bucket label — so a router can ask
    "what does THIS engine cost for THIS batch shape" instead of
    guessing from nnz alone. Engines without batch specialisation record
    under the empty label. ``engine_cost`` aggregates across shapes (an
    EWMA over every observation for the engine) — the admission ladder's
    coarse view; :meth:`predict` is the granular one the serving router
    uses, falling back label -> engine-aggregate unless
    ``granular_only=True`` (routing must not substitute a B=64 cost for
    a B=1 decision).

    Thread-safe: the serving pipeline's harvester thread records while
    dispatchers read. Budgeted variants record under the
    ``"<engine>@budget"`` name, same convention as the PR-7 ladder.

    :meth:`EngineContext.warmup` PRIMES the table — one timed run per
    warmed (engine, bucket, sign) AFTER its compile — so the first real
    queries after a warmup are routed and admitted from measurements,
    never from the "optimistic when unseen" default.
    """

    def __init__(self, alpha: float = 0.2):
        self.alpha = float(alpha)
        self._lock = threading.Lock()
        self._ewma: Dict[Tuple[str, int, str], float] = {}
        self._engine: Dict[str, float] = {}
        self.n_observations = 0

    def observe(self, engine: str, bucket: int, label: str,
                per_query_s: float) -> None:
        """Fold one measured per-query latency into the table."""
        key = (engine, int(bucket), label)
        a = self.alpha
        with self._lock:
            prev = self._ewma.get(key)
            ewma = (per_query_s if prev is None
                    else (1 - a) * prev + a * per_query_s)
            self._ewma[key] = ewma
            prev_e = self._engine.get(engine)
            self._engine[engine] = (per_query_s if prev_e is None
                                    else (1 - a) * prev_e + a * per_query_s)
            self.n_observations += 1
        # export the folded EWMA (not the raw sample) so the gauge IS
        # the router's current belief for this (engine, bucket, sign)
        obs.on_cost_observation(engine, bucket, label, ewma)

    def predict(self, engine: str, bucket: int, label: str,
                granular_only: bool = False) -> Optional[float]:
        """Predicted per-query seconds, or None when nothing relevant was
        ever measured. Falls back (engine, bucket, label) ->
        (engine, bucket, "") -> engine aggregate unless granular_only."""
        with self._lock:
            c = self._ewma.get((engine, int(bucket), label))
            if c is None:
                c = self._ewma.get((engine, int(bucket), ""))
            if c is None and not granular_only:
                c = self._engine.get(engine)
            return c

    def engine_cost(self, engine: str) -> Optional[float]:
        """Shape-agnostic per-query seconds for ``engine`` (EWMA over
        every observation), or None if never measured."""
        with self._lock:
            return self._engine.get(engine)

    def snapshot(self) -> Dict[str, float]:
        """``"engine|bucket|label" -> seconds`` view for artifacts."""
        with self._lock:
            return {f"{e}|{b}|{lbl}": v
                    for (e, b, lbl), v in sorted(self._ewma.items())}

    def save(self, path) -> None:
        """Persist the measured state to ``path`` as JSON (ROADMAP 2b).

        Entries are stored as nested lists — ``[engine, bucket, label,
        seconds]`` — not the ``"|"``-joined display keys of
        :meth:`snapshot`, so engine names and sign labels never need
        un-parsing. A restarted server hands the loaded table to
        ``TopKServer(cost_table=...)`` and routes by these measurements
        BEFORE its first observation, instead of cold-starting on the
        heuristic.
        """
        with self._lock:
            payload = {
                "alpha": self.alpha,
                "n_observations": self.n_observations,
                "ewma": [[e, int(b), lbl, float(v)]
                         for (e, b, lbl), v in sorted(self._ewma.items())],
                "engine": {e: float(v)
                           for e, v in sorted(self._engine.items())},
            }
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")

    @classmethod
    def load(cls, path) -> "CostTable":
        """Reconstruct a table saved by :meth:`save`. The loaded EWMAs
        are live priors: new observations keep folding into them."""
        with open(path) as fh:
            payload = json.load(fh)
        table = cls(alpha=float(payload.get("alpha", 0.2)))
        with table._lock:
            for e, b, lbl, v in payload.get("ewma", []):
                table._ewma[(str(e), int(b), str(lbl))] = float(v)
            table._engine = {str(e): float(v)
                             for e, v in payload.get("engine", {}).items()}
            table.n_observations = int(payload.get("n_observations", 0))
        return table


#: engine name -> the module-level jitted executor
#: ``(args, U, *, k, cfg) -> TopKResult``. ONE executor per engine for
#: the whole process: jax's own trace cache (keyed by arg shapes/dtypes/
#: treedefs + the static ``k``/``cfg``) IS the compile cache, which is
#: what makes it snapshot- and context-free. ``cfg`` is the triple
#: ``(arg_config(ctx), batch_config(ctx, U), budget)`` — the second
#: component is the per-BATCH static bucket (the sign bucket for the
#: list engines, DESIGN.md §11), the third the per-query halting budget
#: (``None`` = run to exactness, DESIGN.md §12). Both join the compile
#: key without touching the snapshot-free arguments — budgeted variants
#: stay compile-free across compactions just like exact ones.
_ARG_EXECUTORS: Dict[str, Callable] = {}


def _make_arg_executor(name: str, run_args: Callable) -> Callable:
    def run(args, U, k, cfg):
        _note_trace(name, cfg[1])
        return run_args(args, U, k, cfg)

    return jax.jit(run, static_argnames=("k", "cfg"))


class EngineContext:
    """Catalogue + lazily built per-engine state, shared across queries.

    Args:
      targets: ``[M, R]`` catalogue factors.
      index: optional prebuilt :class:`TopKIndex` (built lazily otherwise).
      block_size: depth/block granularity handed to blocked engines.
      max_blocks: uniform halting budget (``-1`` = run to exactness).
      ta_chunk: rounds gathered per chunked-TA step (`ta` engine).
      prefix_depth: ``list_major`` layout prefix rows per dimension.
        ``None`` (default) is ADAPTIVE — the layout turns on at
        ``DEFAULT_PREFIX_DEPTH`` once ``M >= LIST_LAYOUT_MIN_TARGETS``
        and stays off below that (the cache-resident gather path is
        faster there); ``0`` disables the layout path entirely; any
        other value is honoured as given (clamped to ``M``). See
        :attr:`resolved_prefix_depth`.
      version: snapshot version of the catalogue this context was built
        from (DESIGN.md §9). Bookkeeping for the streaming layer
        (:mod:`repro.core.segments`), which builds one context per
        immutable base snapshot under a monotonically increasing
        version. Since the argument-passing refactor (DESIGN.md §10) the
        version participates ONLY in the legacy closure-engine compile
        key (``pallas``); argument-passing executors are deliberately
        version-free — that is what makes compaction compile-free.
    """

    def __init__(self, targets, index: Optional[TopKIndex] = None,
                 block_size: int = 256, max_blocks: int = -1,
                 ta_chunk: int = 32,
                 prefix_depth: Optional[int] = None, version: int = 0,
                 cost_table: Optional["CostTable"] = None):
        self.targets = jnp.asarray(targets, dtype=jnp.float32)
        # measured-cost table shared ACROSS contexts (the serving tier
        # passes one table through every compaction-built snapshot, so
        # observations survive snapshot swaps); select_engine consults
        # it when present and falls back to the cold heuristic otherwise
        self.cost_table = cost_table
        self.block_size = block_size
        self.max_blocks = max_blocks
        self.ta_chunk = ta_chunk
        # list_major prefix depth; None -> DEFAULT_PREFIX_DEPTH, 0 disables
        # the layout path entirely (list engines fall back to gathers)
        self.prefix_depth = prefix_depth
        self.version = int(version)
        self._index = index
        self._catalog = None
        self._norm_decay = None
        self._layouts: Dict[str, object] = {}
        # (engine name, M-bucket) -> the runtime-args pytree handed to the
        # shared executor. Built once per context; the arrays inside are
        # the padded snapshot state (DESIGN.md §10).
        self._engine_args: Dict[Tuple[str, int], Any] = {}
        self._padded_index: Dict[int, Dict[str, Array]] = {}
        # legacy per-context compiled cache, CLOSURE engines only
        # (pallas): (engine, k, batch-bucket, snapshot version) -> jitted
        # batched callable.
        self._compiled: Dict[Tuple[str, int, int, int], Callable] = {}
        # traces ATTRIBUTED to this context: closure engines bump it
        # directly at trace time; argument-passing calls add the delta of
        # the process-wide totals their dispatch caused (a cache hit adds
        # nothing — the compile-freeness assertions read exactly this).
        self.trace_counts: Dict[str, int] = {}

    @property
    def resolved_prefix_depth(self) -> int:
        """The list_major prefix depth this context builds (0 = disabled).

        ``prefix_depth=None`` is adaptive: the layout only turns on once
        the catalogue outgrows cache (``LIST_LAYOUT_MIN_TARGETS``) —
        below that the plain gather path is faster and the default stays
        on it. An explicit ``prefix_depth`` is always honoured.

        Compile-key note (DESIGN.md §10): the resolved depth sets the
        ``[R, P, R]`` prefix-tile shapes and is therefore the
        "layout-shape" component of the argument-passing compile key.
        At the adaptive default it is a constant (2048) for every
        catalogue ≥ 32k, so compaction never changes it; an explicit
        ``prefix_depth`` > the real size degrades gracefully (clamped,
        at the cost of one retrace per distinct clamp).
        """
        if self.prefix_depth is None:
            if self.num_targets < LIST_LAYOUT_MIN_TARGETS:
                return 0
            return int(min(self.num_targets, DEFAULT_PREFIX_DEPTH))
        return int(min(self.num_targets, self.prefix_depth))

    def layout(self, name: str):
        """The named catalogue layout, built lazily and cached per context.

        ``list_major`` resolves the context's ``prefix_depth``;
        ``norm_sharded`` deals the norm order over all visible devices on
        a 1-axis ``("data",)`` mesh (a 1-device mesh is valid — the
        sharded engine then degenerates to the single-host scan), with
        slabs sized for the M-bucket so the sharded executor's compile
        key is bucket-granular.
        """
        lay = self._layouts.get(name)
        if lay is None:
            params = {}
            if name == "list_major":
                params["prefix_depth"] = self.resolved_prefix_depth
            elif name == "norm_sharded":
                mesh = self.mesh
                params["n_shards"] = mesh.devices.size
                params["mesh"] = mesh
                params["m_total"] = self.m_bucket
            index = None if name == "row_major" else self.index
            lay = build_layout(name, self.targets, index, **params)
            self._layouts[name] = lay
        return lay

    @property
    def mesh(self):
        """1-axis ``("data",)`` mesh over all visible devices."""
        if getattr(self, "_mesh", None) is None:
            devs = np.asarray(jax.devices())
            self._mesh = jax.sharding.Mesh(devs, ("data",))
        return self._mesh

    @property
    def num_targets(self) -> int:
        return int(self.targets.shape[0])

    @property
    def m_bucket(self) -> int:
        """The catalogue's power-of-two M-bucket (DESIGN.md §10)."""
        return m_bucket(self.num_targets)

    @property
    def index(self) -> TopKIndex:
        if self._index is None:
            self._index = build_index(self.targets)
        return self._index

    @property
    def catalog(self):
        """Norm-ordered Pallas catalogue (built on first pallas query)."""
        if self._catalog is None:
            from repro.kernels.ops import MIPSCatalog
            self._catalog = MIPSCatalog(np.asarray(self.targets),
                                        block_m=self.block_size)
        return self._catalog

    @property
    def norm_decay(self) -> float:
        """Norm at the 10th-percentile depth over the head norm (<= 1).

        A catalogue constant, cached so per-batch `auto` dispatch does not
        re-transfer the norm spectrum from device on every query chunk.
        """
        if self._norm_decay is None:
            norms = np.asarray(self.index.norms_sorted)
            head = max(float(norms[0]), 1e-12)
            decayed = float(
                norms[min(len(norms) - 1, max(1, len(norms) // 10))])
            self._norm_decay = decayed / head
        return self._norm_decay

    # -- argument-passing machinery (DESIGN.md §10) --------------------------

    @property
    def m_real(self) -> Array:
        """The real catalogue size as a traced int32 scalar (the runtime
        companion of every M-bucket-padded argument array)."""
        return jnp.int32(self.num_targets)

    def padded_index_arrays(self, bucket: int) -> Dict[str, Array]:
        """The sorted-list index + catalogue, padded to ``bucket`` rows.

        The pad convention (DESIGN.md §10, shared with
        :func:`repro.core.layout.pad_rank_by_item`): pad TARGET rows are
        zero; each sorted list is extended past its real end with the
        pad ids in id order (so ``rank[r, order[r, d]] == d`` holds over
        the whole padded array); ``t_sorted_desc`` pad columns repeat
        the last real value (monotone, and unread — every bound lookup
        is ``m_real``-clamped). Cached per bucket.
        """
        arrs = self._padded_index.get(bucket)
        if arrs is None:
            idx = self.index
            m = self.num_targets
            pad = bucket - m
            if pad < 0:
                raise ValueError(
                    f"bucket {bucket} smaller than catalogue ({m})")
            if pad == 0:
                arrs = {"targets": self.targets,
                        "order_desc": idx.order_desc,
                        "t_sorted_desc": idx.t_sorted_desc,
                        "rank_desc": idx.rank_desc}
            else:
                r = int(self.targets.shape[1])
                pad_ids = jnp.arange(m, bucket, dtype=jnp.int32)
                pad_cols = jnp.broadcast_to(pad_ids[None, :], (r, pad))
                arrs = {
                    "targets": pad_zero_rows(self.targets, bucket),
                    "order_desc": jnp.concatenate(
                        [idx.order_desc, pad_cols], axis=1),
                    "t_sorted_desc": jnp.concatenate(
                        [idx.t_sorted_desc,
                         jnp.broadcast_to(idx.t_sorted_desc[:, -1:],
                                          (r, pad))], axis=1),
                    "rank_desc": jnp.concatenate(
                        [idx.rank_desc, pad_cols], axis=1),
                }
            self._padded_index[bucket] = arrs
        return arrs

    def engine_args(self, engine: "Engine", bucket: Optional[int] = None,
                    cache: bool = True):
        """The runtime-args pytree for ``engine`` at an M-bucket.

        ``bucket`` defaults to the catalogue's own :attr:`m_bucket`;
        warmup may request a LARGER bucket to pre-compile for future
        growth (``cache=False`` then avoids pinning the oversized arrays
        in this context). Cached per (engine, bucket).
        """
        bucket = self.m_bucket if bucket is None else int(bucket)
        if bucket < self.num_targets:
            raise ValueError(
                f"bucket {bucket} smaller than catalogue "
                f"({self.num_targets})")
        key = (engine.name, bucket)
        args = self._engine_args.get(key)
        if args is None:
            if engine.make_args is None:
                raise ValueError(
                    f"engine {engine.name!r} is not argument-passing")
            args = engine.make_args(self, bucket)
            if cache:
                self._engine_args[key] = args
        return args

    def _dispatch_args(self, engine: "Engine", args, U: Array,
                      k: int, budget: Optional[int] = None) -> TopKResult:
        """Run the shared executor, attributing any trace to this context.

        The static cfg is the triple ``(arg_config(ctx),
        batch_config(ctx, U), budget)``: the second component — the
        batch's sign bucket for the list engines — is computed host-side
        per dispatch (one ``np.asarray`` read of the query VALUES; for
        device-resident batches that is a transfer of an input, never a
        sync on pending device work) and joins the compile key, selecting
        the sign-specialised trace (DESIGN.md §11). ``budget`` (list-depth
        rows; ``None`` = exact) is the third static component — budgeted
        variants are ordinary compile-key entries, carrying no snapshot
        identity (DESIGN.md §12)."""
        acfg = engine.arg_config(self) if engine.arg_config is not None \
            else ()
        bcfg = engine.batch_config(self, U) \
            if engine.batch_config is not None else ()
        fn = _ARG_EXECUTORS[engine.name]
        before = _TRACE_TOTALS.get(engine.name, 0)
        bud = None if budget is None else int(budget)
        if engine.select_path is not None:
            obs.on_topk_select(engine.name, engine.select_path(args, int(k)))
        res = fn(args, U, k=int(k), cfg=(acfg, bcfg, bud))
        delta = _TRACE_TOTALS.get(engine.name, 0) - before
        if delta:
            self.trace_counts[engine.name] = (
                self.trace_counts.get(engine.name, 0) + delta)
        return res

    # -- legacy closure compilation cache (pallas only) ----------------------

    def compiled(self, engine: "Engine", k: int, batch: int) -> Callable:
        """A compiled ``U -> TopKResult`` for (engine, k, batch-bucket).

        Argument-passing engines return a thin binding of the shared
        module-level executor to this context's cached args (nothing is
        compiled per context). Closure engines (pallas) keep the PR-4
        per-context cache keyed ``(engine, k, batch-bucket, snapshot
        version)``: the factory is called EAGERLY (so lazy context state
        — index, Pallas catalogue — is constructed outside the trace)
        and the result wrapped in a ``jax.jit`` that survives across
        queries, bumping ``trace_counts[engine]`` at trace time only.
        """
        if engine.run_args is not None:
            args = self.engine_args(engine)

            def bound_fn(U, _eng=engine, _args=args, _k=int(k)):
                return self._dispatch_args(_eng, _args, U, _k)

            return bound_fn
        key = (engine.name, int(k), int(batch), self.version)
        fn = self._compiled.get(key)
        if fn is None:
            if engine.make_batched is None:
                raise ValueError(
                    f"engine {engine.name!r} is dispatch-only and has no "
                    "batched executable to compile")
            batched = engine.make_batched(self, int(k))
            name = engine.name

            def traced(U, _inner=batched, _name=name):
                self.trace_counts[_name] = self.trace_counts.get(_name, 0) + 1
                _note_trace(_name)
                return _inner(U)

            fn = jax.jit(traced)
            self._compiled[key] = fn
        return fn

    def run_engine(self, engine: "Engine", U: Array, k: int,
                   budget: Optional[int] = None) -> TopKResult:
        """Bucket the batch, pad, run the cached executable, slice back.

        Padding repeats the LAST query row (never zeros: an all-zero query
        deactivates every list and would drag a vmapped lockstep scan to
        its worst case); padded rows are dropped before returning, so
        per-query statistics are untouched. ``budget`` (list-depth rows)
        selects the halted certified variant (DESIGN.md §12); only
        argument-passing engines support it.
        """
        if not (isinstance(U, jax.Array) and U.ndim == 2
                and U.dtype == self.targets.dtype):
            U = jnp.atleast_2d(jnp.asarray(U, self.targets.dtype))
        b = U.shape[0]
        bucket = batch_bucket(b)
        if bucket != b:
            U = pad_to_bucket(U)
        if engine.run_args is not None:
            res = self._dispatch_args(engine, self.engine_args(engine),
                                      U, k, budget=budget)
        else:
            if budget is not None:
                raise ValueError(
                    f"engine {engine.name!r} is closure-compiled and does "
                    "not support budgeted queries")
            res = self.compiled(engine, k, bucket)(U)
        if bucket != b:
            res = jax.tree_util.tree_map(lambda a: a[:b], res)
        return res

    def warmup(self, k: int, batch_sizes=(1, 8, 64),
               engines: Optional[List[str]] = None,
               m_buckets=None, budgets=None,
               cost_table: Optional["CostTable"] = None
               ) -> "EngineContext":
        """Compile (engine, k, batch-bucket, M-bucket) executables ahead
        of traffic.

        Runs one representative batch per bucket through each executable
        engine so the first real query hits a compiled executable.
        ``m_buckets`` optionally lists CATALOGUE buckets to warm beyond
        the current one (values below it are clamped up): argument-
        passing traces are keyed by bucket, not by size, so warming the
        next bucket now makes the compaction that eventually crosses
        into it compile-free too (the streaming serving pattern,
        DESIGN.md §10). Oversized buckets are padded views built
        transiently — they are not pinned in this context's args cache.

        **Sign buckets** (DESIGN.md §11): engines with batch
        specialisation (``ta``/``bta`` once the list layout is on) are
        warmed with one representative batch per common sign bucket —
        nonneg-dense, nonpos-dense, mixed, and nonneg-sparse (the bucket
        ``auto``'s sparse→TA route produces) — so serving any of those
        buckets adds 0 retraces; the rare nonpos-sparse bucket pays its
        one trace lazily.

        ``budgets`` optionally lists halting budgets (list-depth rows) to
        warm BESIDES the exact ``None`` variant: each budget is one more
        static cfg entry per (engine, batch, sign) combination, so a
        server that degrades to budgeted certified scans under load never
        compiles on the hot path — and, like every other argument-passing
        variant, the budgeted traces survive compaction (DESIGN.md §12).

        ``cost_table`` (default: the context's own, if any) is PRIMED
        while warming: each warmed (engine, batch-bucket, sign) config at
        the CURRENT M-bucket gets one extra timed run AFTER its compile,
        recorded as that config's measured per-query cost — so the
        serving router and the admission ladder start from measurements
        instead of the optimistic unseen default. Returns self for
        chaining.
        """
        names = list(engines) if engines is not None \
            else executable_engines()
        r = int(self.targets.shape[1])
        own = self.m_bucket
        if m_buckets is None:
            buckets_m = [own]
        else:
            buckets_m = sorted({max(int(x), own) for x in m_buckets})
        budget_list = [None] + [int(x) for x in (budgets or ())]
        ct = cost_table if cost_table is not None else self.cost_table
        for name in names:
            eng = get_engine(name)
            if eng.run_args is not None:
                buds = budget_list if eng.supports_budget else [None]
                for mb in buckets_m:
                    args = self.engine_args(eng, mb, cache=(mb == own))
                    for b in batch_sizes:
                        bucket = batch_bucket(b)
                        for U in self._warm_batches(eng, bucket, r):
                            for bud in buds:
                                res = self._dispatch_args(eng, args, U, k,
                                                          budget=bud)
                                jax.block_until_ready(res.values)
                                if ct is not None and mb == own:
                                    self._time_into(ct, eng, args, U, k,
                                                    bud, bucket)
            else:
                for b in batch_sizes:
                    bucket = batch_bucket(b)
                    U = jnp.ones((bucket, r), self.targets.dtype)
                    fn = self.compiled(eng, int(k), bucket)
                    jax.block_until_ready(fn(U).values)
                    if ct is not None:
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(U).values)
                        ct.observe(eng.name, bucket, "",
                                   (time.perf_counter() - t0) / bucket)
        return self

    def _time_into(self, ct: "CostTable", eng: "Engine", args, U, k,
                   bud, bucket: int) -> None:
        """One timed (post-compile) run, folded into the cost table under
        the same (engine, bucket, sign-label) key serving records use —
        budgeted variants under the ladder's ``"<name>@budget"`` name."""
        t0 = time.perf_counter()
        res = self._dispatch_args(eng, args, U, k, budget=bud)
        jax.block_until_ready(res.values)
        dt = time.perf_counter() - t0
        name = eng.name if bud is None else f"{eng.name}@budget"
        ct.observe(name, bucket, cost_label(eng, self, U), dt / bucket)

    def _warm_batches(self, eng: "Engine", bucket: int, r: int) -> list:
        """Representative warm batches: one per sign bucket the engine
        specialises on, or just the all-ones batch for engines without
        batch specialisation (see :meth:`warmup`)."""
        ones = jnp.ones((bucket, r), self.targets.dtype)
        if eng.batch_config is None or not eng.batch_config(self, ones):
            return [ones]
        dt = np.dtype(self.targets.dtype)
        mixed = np.ones((bucket, r), dt)
        mixed[:, 1::2] = -1.0
        sparse = np.ones((bucket, r), dt)
        sparse[:, 1::2] = 0.0
        # buckets: (1,True), (-1,True), (0,False), (1,False)
        return [ones, -ones, jnp.asarray(mixed), jnp.asarray(sparse)]


@dataclasses.dataclass(frozen=True)
class Engine:
    """A registered engine: executable factory + capability metadata.

    Exactly one of three execution styles (DESIGN.md §10):

    * ``run_args`` + ``make_args`` (+ optional ``arg_config``) — an
      ARGUMENT-PASSING engine. ``make_args(ctx, m_bucket)`` returns the
      runtime pytree of padded snapshot state; ``run_args(args, U, k,
      cfg)`` is the pure batched body the module-level shared executor
      jits (``k`` and the hashable ``cfg`` from ``arg_config(ctx)`` are
      static). Its compile key carries no snapshot identity — every
      same-bucket snapshot shares every trace.
    * ``make_batched(ctx, k)`` — a CLOSURE engine: returns a pure
      ``U [B, R] -> TopKResult`` callable that closes over context state
      (trace-safe; any host-side setup such as index construction
      happens inside the factory, eagerly), compiled per context with
      the snapshot version in the key.
    * ``dispatch(ctx, U, k)`` — dispatch pseudo-engines (``auto``) and
      host-only reference oracles (``fagin``, ``partial``), routed per
      batch, never jitted.

    ``layout`` names the :mod:`repro.core.layout` the engine consumes
    (built via :meth:`EngineContext.layout`); ``traffic`` estimates the
    engine's memory traffic for a measured :class:`TopKResult` (per-query
    means: rows gathered, contiguous rows read, bytes moved) — the
    benchmark sweep records it so layout wins show up in the perf
    trajectory, not just wall-clock. ``select_path(args, k)`` is how an
    engine whose executor picks its top-K selection from the argument
    shapes names the path it takes (``"two_stage"`` or ``"direct"``,
    DESIGN.md §4): every dispatch of such an engine counts it, on the
    host, in ``repro_topk_select_total{engine, path}``. ``naive`` sets
    it; an engine that selects one way only leaves it ``None``.
    """

    name: str
    make_batched: Optional[
        Callable[["EngineContext", int], Callable[[Array], TopKResult]]
    ] = None
    dispatch: Optional[
        Callable[["EngineContext", Array, int], TopKResult]] = None
    make_args: Optional[Callable[["EngineContext", int], Any]] = None
    run_args: Optional[
        Callable[[Any, Array, int, tuple], TopKResult]] = None
    arg_config: Optional[Callable[["EngineContext"], tuple]] = None
    #: optional ``(ctx, U) -> tuple``: a HOST-computed static bucket of
    #: the query batch's VALUES that joins the executor compile key (the
    #: sign bucket for the list engines, DESIGN.md §11). Must be cheap,
    #: hashable, and (); for engines without batch specialisation.
    batch_config: Optional[Callable[["EngineContext", Any], tuple]] = None
    exact: bool = True
    needs_index: bool = True
    supports_batch: bool = True
    #: True for engines that honour ``run(..., budget=)`` — a list-depth
    #: halting budget joining the executor compile key, with the halted
    #: result carrying a per-item certificate bound (DESIGN.md §12).
    supports_budget: bool = False
    backend: str = "jax"
    layout: Optional[str] = None
    select_path: Optional[Callable[[Any, int], str]] = None
    host_only: bool = False
    traffic: Optional[
        Callable[["EngineContext", TopKResult], Dict[str, float]]] = None
    description: str = ""

    @property
    def has_executable(self) -> bool:
        """True for engines with a compiled batched body (everything but
        the dispatch pseudo-engines and the host oracles)."""
        return self.run_args is not None or self.make_batched is not None

    def run(self, ctx: EngineContext, U: Array, k: int,
            budget: Optional[int] = None) -> TopKResult:
        if budget is not None and not self.supports_budget:
            raise ValueError(
                f"engine {self.name!r} does not support budgeted queries; "
                "use one of "
                f"{[e.name for e in list_engines() if e.supports_budget]}")
        if self.dispatch is not None:
            if budget is not None:
                return self.dispatch(ctx, U, k, budget)
            return self.dispatch(ctx, U, k)
        return ctx.run_engine(self, U, k, budget=budget)


_REGISTRY: Dict[str, Engine] = {}
_ALIASES: Dict[str, str] = {
    "threshold": "ta",
    "blocked": "bta",
    "norm_pruned": "norm",
    "topk_mips": "pallas",
}


def register_engine(engine: Engine) -> Engine:
    _REGISTRY[engine.name] = engine
    if engine.run_args is not None:
        _ARG_EXECUTORS[engine.name] = _make_arg_executor(engine.name,
                                                         engine.run_args)
    return engine


def get_engine(name: str) -> Engine:
    key = _ALIASES.get(name, name)
    if key not in _REGISTRY:
        raise ValueError(
            f"unknown engine {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


def engine_names() -> List[str]:
    return sorted(_REGISTRY)


def list_engines(exact: Optional[bool] = None,
                 backend: Optional[str] = None,
                 needs_index: Optional[bool] = None) -> List[Engine]:
    out = []
    for name in engine_names():
        e = _REGISTRY[name]
        if exact is not None and e.exact != exact:
            continue
        if backend is not None and e.backend != backend:
            continue
        if needs_index is not None and e.needs_index != needs_index:
            continue
        out.append(e)
    return out


# ---------------------------------------------------------------------------
# Built-in engines
# ---------------------------------------------------------------------------


def _naive_args(ctx: EngineContext, bucket: int):
    return {"targets": pad_zero_rows(ctx.targets, bucket),
            "m_real": ctx.m_real}


def _naive_select_path(args, k):
    mb = args["targets"].shape[0]
    return select_path(mb, min(k, mb))


def _naive_run(args, U, k, cfg):
    """Score every row, mask the pad rows, select exactly: the two-stage
    selection where the shape engages it (DESIGN.md §4, "Exact two-stage
    selection")."""
    T, m = args["targets"], args["m_real"]
    mb = T.shape[0]
    scores = jnp.matmul(U, T.T, precision=SCORE_PRECISION)
    # pad rows are zero rows: mask them to -inf so they can never outrank
    # a real (possibly all-negative) score
    scores = jnp.where(jnp.arange(mb, dtype=jnp.int32)[None, :] < m,
                       scores, NEG_INF)
    vals, ids = select_topk(scores, min(k, mb))
    ids = jnp.where(jnp.isneginf(vals), -1, ids)
    b = U.shape[0]
    # a full scan leaves nothing unenumerated: the bound on unseen items
    # is vacuous (-inf), so every returned slot is certified
    return TopKResult(vals, ids,
                      jnp.broadcast_to(m, (b,)).astype(jnp.int32),
                      jnp.zeros((b,), jnp.int32),
                      upper=jnp.full((b,), NEG_INF, vals.dtype))


def _list_layout(ctx: EngineContext):
    """The list_major layout, or None when the context disables it."""
    return ctx.layout("list_major") if ctx.resolved_prefix_depth > 0 \
        else None


def _list_batch_cfg(ctx: EngineContext, U) -> tuple:
    """Sign bucket of the query batch, joined to the compile key.

    With the list layout off the batched-native prefix scan never runs,
    so the bucket is dropped from the key — every batch shares ONE
    traced variant, exactly the PR-5 behaviour (and the small-M trace
    count tests stay valid).
    """
    if ctx.resolved_prefix_depth <= 0:
        return ()
    return sign_bucket(U)


def _list_args(ctx: EngineContext, bucket: int):
    """Shared args for the list engines: padded index + padded layout."""
    args = dict(ctx.padded_index_arrays(bucket))
    lay = _list_layout(ctx)
    if lay is not None:
        lay = dataclasses.replace(
            lay, rank_by_item=pad_rank_by_item(lay.rank_by_item, bucket))
    args["layout"] = lay
    args["m_real"] = ctx.m_real
    return args


def _ta_cfg(ctx: EngineContext) -> tuple:
    return (ctx.ta_chunk, ctx.max_blocks)


def _ta_run(args, U, k, cfg):
    # chunked TA: block-shaped work per step, sequential-round accounting
    # (count-faithful to the paper's Algorithm 2). With the list_major
    # layout the rounds inside the prefix are gather-free (DESIGN.md §7),
    # and a sign-bucketed batch takes the batched-native prefix scan —
    # ONE shared tile enumeration for the whole batch (DESIGN.md §11).
    # TA's round unit IS list depth, so a budget caps rounds directly.
    (chunk, max_rounds), bcfg, budget = cfg
    if budget is not None:
        max_rounds = budget if max_rounds < 0 else min(max_rounds, budget)
    lay = args["layout"]

    if bcfg and lay is not None and lay.serves_sign(bcfg[0]) \
            and lay.prefix_steps(chunk) > 0:
        sign, dense = bcfg
        return chunked_ta_topk_batched_native(
            args["targets"], args["order_desc"], args["t_sorted_desc"],
            U, k, chunk=chunk, max_rounds=max_rounds, layout=lay,
            sign=sign, dense=dense, m_real=args["m_real"])

    # vmapped fallback; a single-sided layout cannot feed the per-query
    # (both-direction) prefix path, so it degrades to the gather scan
    lay_pq = lay if (lay is not None and lay.two_sided) else None

    def one(u):
        return chunked_ta_topk(args["targets"], args["order_desc"],
                               args["t_sorted_desc"], args["rank_desc"],
                               u, k, chunk=chunk, max_rounds=max_rounds,
                               layout=lay_pq, m_real=args["m_real"])

    return jax.vmap(one)(U)


def _bta_cfg(ctx: EngineContext) -> tuple:
    return (ctx.block_size, ctx.max_blocks)


def _bta_run(args, U, k, cfg):
    (block_size, max_blocks), bcfg, budget = cfg
    if budget is not None:
        # budget is list-depth rows; BTA halts at block granularity
        bb = max(1, -(-budget // block_size))
        max_blocks = bb if max_blocks < 0 else min(max_blocks, bb)
    lay = args["layout"]

    if bcfg and lay is not None and lay.serves_sign(bcfg[0]) \
            and lay.prefix_steps(block_size) > 0:
        sign, dense = bcfg
        return blocked_topk_batched_native(
            args["targets"], args["order_desc"], args["t_sorted_desc"],
            U, k, block_size=block_size, max_blocks=max_blocks,
            layout=lay, sign=sign, dense=dense, m_real=args["m_real"])

    lay_pq = lay if (lay is not None and lay.two_sided) else None

    def one(u):
        return blocked_topk(args["targets"], args["order_desc"],
                            args["t_sorted_desc"], u, k, block_size,
                            max_blocks, rank_desc=args["rank_desc"],
                            layout=lay_pq, m_real=args["m_real"])

    return jax.vmap(one)(U)


def _norm_args(ctx: EngineContext, bucket: int):
    lay = ctx.layout("norm_major")
    m = ctx.num_targets
    pad = bucket - m
    if pad == 0:
        return {"targets_by_norm": lay.targets_by_norm,
                "norm_order": lay.norm_order,
                "norms_sorted": lay.norms_sorted,
                "m_real": ctx.m_real}
    # pad rows: zero rows with norm 0 and id -1 — they sort last, so the
    # real norm-order prefix (and every Cauchy-Schwarz bound the scan can
    # reach) is untouched
    return {
        "targets_by_norm": pad_zero_rows(lay.targets_by_norm, bucket),
        "norm_order": jnp.concatenate(
            [lay.norm_order, jnp.full((pad,), -1, jnp.int32)]),
        "norms_sorted": pad_zero_rows(lay.norms_sorted, bucket),
        "m_real": ctx.m_real,
    }


def _norm_cfg(ctx: EngineContext) -> tuple:
    return (ctx.block_size, ctx.max_blocks)


def _norm_run(args, U, k, cfg):
    (block_size, max_blocks), _, budget = cfg
    if budget is not None:
        # budget is rows enumerated in norm order, i.e. blocks * block
        bb = max(1, -(-budget // block_size))
        max_blocks = bb if max_blocks < 0 else min(max_blocks, bb)
    mb = args["targets_by_norm"].shape[0]
    # batched-native scan: every query walks the SAME norm-ordered
    # prefix, so one shared tile slice + one [B,R]@[R,block] matmul
    # serves the whole batch (no per-query gathers). Tiny catalogues
    # shrink the block to the bucket so the slice stays in bounds.
    return norm_pruned_topk_batched(
        args["targets_by_norm"], args["norm_order"], args["norms_sorted"],
        U, k, min(block_size, mb), max_blocks, m_real=args["m_real"])


def _norm_sharded_args(ctx: EngineContext, bucket: int):
    if bucket == ctx.m_bucket:
        lay = ctx.layout("norm_sharded")
    else:
        mesh = ctx.mesh
        lay = build_layout("norm_sharded", ctx.targets, ctx.index,
                          n_shards=mesh.devices.size, mesh=mesh,
                          m_total=bucket)
    return {"targets_sharded": lay.targets_sharded,
            "norms_sharded": lay.norms_sharded,
            "ids_sharded": lay.ids_sharded}


def _norm_sharded_cfg(ctx: EngineContext) -> tuple:
    return (ctx.block_size, ctx.max_blocks, ctx.mesh)


def _norm_sharded_run(args, U, k, cfg):
    from repro.core.sharded import sharded_norm_topk
    # budget unsupported (supports_budget=False): cfg[2] is always None
    (block_size, max_blocks, mesh), _, _ = cfg
    scan = sharded_norm_topk(mesh, ("data",))
    return scan(args["targets_sharded"], args["norms_sharded"],
                args["ids_sharded"], U, k, block_size, max_blocks)


#: Why ``pallas`` does not run on a TPU backend, in the compiler's words
#: (``tests/test_tpu_compile.py`` compiles the kernel for a described
#: v5e and pins both refusals).
PALLAS_TPU_REFUSAL = (
    "the TPU compiler refuses the kernel. Its (1, 1, tiles) bounds block "
    "fails 'The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the "
    "overall array', and its in-kernel merge fails 'Unimplemented "
    "primitive in Pallas TPU lowering for KernelType.TC: top_k'. Use "
    "'norm', the same norm-ordered block scan in XLA")


def _pallas_batched(ctx: EngineContext, k: int):
    if jax.default_backend() == "tpu":
        raise ValueError(
            f"engine 'pallas' does not run on a TPU backend: "
            f"{PALLAS_TPU_REFUSAL}")
    cat = ctx.catalog       # built eagerly, outside the trace
    block_m = jnp.int32(cat.block_m)

    def fn(U):
        vals, ids, stats = cat.query_batch(U, k)
        # stats = (rows scored incl. block padding, blocks visited, loaded)
        # exact kernel: vacuous -inf bound => fully certified result, and
        # the pytree structure matches the argument-passing engines so
        # mixed-engine chunk results concatenate cleanly
        return TopKResult(vals, ids, stats[:, 0], stats[:, 1] * block_m,
                          upper=jnp.full((U.shape[0],), NEG_INF,
                                         vals.dtype))

    return fn


def _host_nnz_frac(U) -> float:
    """Batch sparsity, computed on the HOST.

    numpy/list inputs never touch the device; a jax Array input is read
    back once (it is an input *value*, not a pending computation, so no
    work — and no blocking reduction — is enqueued on the device query
    stream the engines are using).
    """
    arr = U if isinstance(U, np.ndarray) else np.asarray(U)
    return float(np.count_nonzero(arr)) / max(arr.size, 1)


#: COLD-START batch size at which the batched-native list scan is assumed
#: to amortise its shared tile enumeration well enough to prefer the list
#: engines (DESIGN.md §11). Once a :class:`CostTable` has measurements
#: for every auto candidate at the batch's (bucket, sign), the measured
#: costs replace this constant entirely (ROADMAP item 3c).
BATCHED_LIST_MIN_B = 8


def cost_label(eng: Engine, ctx: EngineContext, U) -> str:
    """The sign-bucket label ``eng`` would serve ``U`` under — the
    third axis of every :class:`CostTable` key, shared by warm-time
    priming and serve-time recording so the two can never disagree.
    Empty for engines without batch specialisation (and for the list
    engines while the layout is off, where every batch shares one
    trace)."""
    if eng.batch_config is None:
        return ""
    bcfg = eng.batch_config(ctx, U)
    return sign_bucket_label(bcfg) if bcfg else ""


def _select_by_cost(ctx: EngineContext, arr, bucket: int,
                    ct: CostTable) -> Optional[Engine]:
    """Measured-cost route: the cheapest auto candidate at this batch's
    (bucket, sign) — or None unless EVERY candidate has a granular
    measurement (an unmeasured engine is an unwarmed engine; dispatching
    to it on a hunch would compile on the hot path, and comparing a
    measurement against the optimistic unseen default is not a
    comparison)."""
    best, best_c = None, None
    for name in auto_candidates():
        eng = get_engine(name)
        c = ct.predict(name, bucket, cost_label(eng, ctx, arr),
                       granular_only=True)
        if c is None:
            return None
        if best_c is None or c < best_c:
            best, best_c = eng, c
    return best


def select_engine(ctx: EngineContext, U,
                  cost_table: Optional[CostTable] = None) -> Engine:
    """The ``auto`` policy: pick an engine for this query batch.

    MEASURED route first: when a :class:`CostTable` (the explicit
    argument, or the context's own) has an observed per-query cost for
    every auto candidate at this batch's (power-of-two bucket, sign
    bucket), the cheapest measured engine wins — the constant below
    never fires on a warmed serving path.

    COLD fallback: decides from three cheap HOST-side statistics — batch
    sparsity ``nnz(u)`` (sparse queries make TA's per-round cost
    collapse to the active lists), the BATCH SIZE (the batched-native
    list scan shares one prefix-tile enumeration across the batch, so
    the list engines' per-query cost collapses at
    ``B >= BATCHED_LIST_MIN_B`` — below that they pay the per-query
    lockstep scan), and the catalogue norm spectrum (a decaying spectrum
    lets the Cauchy-Schwarz scan certify after a few contiguous blocks;
    a flat spectrum makes it a full scan, so BTA wins when the batched
    list path is live).
    """
    arr = U if isinstance(U, np.ndarray) else np.asarray(U)
    b = 1 if arr.ndim < 2 else arr.shape[0]
    ct = cost_table if cost_table is not None else ctx.cost_table
    if ct is not None:
        eng = _select_by_cost(ctx, arr, batch_bucket(b), ct)
        if eng is not None:
            return eng
    batched_lists = (ctx.resolved_prefix_depth > 0
                     and batch_bucket(b) >= BATCHED_LIST_MIN_B)
    if _host_nnz_frac(arr) < 0.25 and \
            (batched_lists or ctx.resolved_prefix_depth <= 0):
        # sparse queries: TA's rounds collapse to the active lists.
        # With the layout ON but the batch too small to amortise the
        # batched scan, the per-query lockstep loop would dominate —
        # fall through to the contiguous norm scan instead.
        return get_engine("ta")
    if ctx.norm_decay < 0.5 or not batched_lists:
        return get_engine("norm")
    return get_engine("bta")


def auto_candidates():
    """Engine names :func:`select_engine` can resolve to.

    Warming exactly this set covers every dispatch ``auto`` can make
    (including the small-batch routes that prefer the shared-tile norm
    scan over the per-query list loop); warming beyond it
    (``norm_sharded`` in particular, whose layout build copies the whole
    catalogue) is wasted startup work.

    ``naive`` is a candidate for the MEASURED route only (the cold
    heuristic never picks it): the full ``[B,R]@[R,M]`` matmul batches
    through one sgemm, so past B~32 on CPU its per-query cost collapses
    ~10x from B=1 while the pruned engines' shared scans amortise only
    2-4x — the enumeration is shared but each lane's depth is driven by
    the batch's worst lane. Whether the scan's skipped scores beat the
    matmul's raw throughput at a given (bucket, sign) is exactly the
    question the cost table answers with measurements.
    """
    return ["ta", "bta", "naive", "norm"]


def executable_engines() -> List[str]:
    """Engines with a compiled batched body that THIS backend runs: every
    registered one except ``pallas`` on a TPU backend
    (:data:`PALLAS_TPU_REFUSAL`) — the default warm set."""
    tpu = jax.default_backend() == "tpu"
    return [e.name for e in list_engines() if e.has_executable
            and not (tpu and e.backend == "pallas")]


def _auto_dispatch(ctx: EngineContext, U, k: int,
                   budget: Optional[int] = None) -> TopKResult:
    eng = select_engine(ctx, U)
    if budget is not None and not eng.supports_budget:
        # every budget-capable fallback walks the same contiguous norm
        # order, so it is the natural degraded target (DESIGN.md §12)
        eng = get_engine("norm")
    return eng.run(ctx, U, k, budget=budget)


# ---------------------------------------------------------------------------
# Host-only reference oracles (paper Algorithms 1 and 3) as engines
# ---------------------------------------------------------------------------


def _host_oracle_dispatch(one_query):
    """Wrap a numpy oracle ``(T, order_desc, u, k) -> (v, i, n, d)``."""

    def dispatch(ctx: EngineContext, U, k: int) -> TopKResult:
        T = np.asarray(ctx.targets)
        od = np.asarray(ctx.index.order_desc)
        U_np = np.atleast_2d(np.asarray(U, np.float32))
        k_eff = min(int(k), T.shape[0])
        vals = np.full((U_np.shape[0], k_eff), float("-inf"), np.float32)
        ids = np.full((U_np.shape[0], k_eff), -1, np.int32)
        ns = np.zeros((U_np.shape[0],), np.int32)
        dep = np.zeros((U_np.shape[0],), np.int32)
        for b, u in enumerate(U_np):
            v, i, n, d = one_query(T, od, u, k_eff)
            vals[b, :len(v)] = v
            ids[b, :len(i)] = i
            ns[b], dep[b] = n, d
        return TopKResult(jnp.asarray(vals), jnp.asarray(ids),
                          jnp.asarray(ns), jnp.asarray(dep),
                          upper=jnp.full((U_np.shape[0],), float("-inf"),
                                         jnp.float32))

    return dispatch


def _fagin_one(T, od, u, k):
    from repro.core.fagin import fagin_topk_np
    v, i, st = fagin_topk_np(T, od, u, k)
    return v, i, st.n_scored, st.depth


def _partial_one(T, od, u, k):
    from repro.core.partial import partial_threshold_topk_np
    v, i, st = partial_threshold_topk_np(T, od, u, k)
    # n_items_touched == TA's n_scored (Theorem 4 logic: same item set)
    return v, i, st.n_items_touched, st.depth


# ---------------------------------------------------------------------------
# Memory-traffic estimators (per-query means, from measured counts)
# ---------------------------------------------------------------------------


def _traffic_dict(ctx: EngineContext, rows_gathered, rows_contiguous):
    r = int(ctx.targets.shape[1])
    total = rows_gathered + rows_contiguous
    return {
        "rows_gathered": float(rows_gathered),
        "rows_contiguous": float(rows_contiguous),
        "est_bytes_moved": float(total * r * 4),
        "gather_fraction": float(rows_gathered / total) if total else 0.0,
    }


def _naive_traffic(ctx, res):
    return _traffic_dict(ctx, 0.0, float(ctx.num_targets))


def _list_traffic(ctx, res):
    """TA/BTA: depth (list-depth units) splits at the layout prefix.

    Inside the prefix each of the R lists reads its depth range from BOTH
    direction tiles (head + tail, then a select) — contiguous, 2x rows.
    Past the prefix every candidate costs a scattered target row PLUS a
    same-shape ``rank_by_item`` row for freshness. With the layout off
    (``resolved_prefix_depth == 0``, the adaptive default below
    ``LIST_LAYOUT_MIN_TARGETS``) the engines run the plain gather path:
    ONE target row per candidate, and freshness comes from the O(R*M)
    first-occurrence key precompute — a contiguous stream of the
    ``[R, M]`` int32 rank array, M row-equivalents of bytes per query.
    """
    r = int(ctx.targets.shape[1])
    p = ctx.resolved_prefix_depth
    depth = float(np.mean(np.asarray(res.depth)))
    if p == 0:
        return _traffic_dict(ctx, depth * r, float(ctx.num_targets))
    contig = 2.0 * min(depth, p) * r
    gathered = 2.0 * max(depth - p, 0.0) * r
    return _traffic_dict(ctx, gathered, contig)


def _norm_traffic(ctx, res):
    # depth is rows enumerated in norm order — all contiguous tile reads
    return _traffic_dict(ctx, 0.0, float(np.mean(np.asarray(res.depth))))


def _host_traffic(ctx, res):
    # item-at-a-time oracles: every scored row is a random access
    return _traffic_dict(ctx, float(np.mean(np.asarray(res.n_scored))), 0.0)


register_engine(Engine(
    name="naive", make_args=_naive_args, run_args=_naive_run,
    exact=True, needs_index=False,
    supports_batch=True, supports_budget=True,  # budget ignored: one matmul
    backend="jax", layout="row_major",
    traffic=_naive_traffic, select_path=_naive_select_path,
    description="full matmul + exact two-stage top-k (strongest "
                "wall-clock baseline)"))
register_engine(Engine(
    name="ta", make_args=_list_args, run_args=_ta_run, arg_config=_ta_cfg,
    batch_config=_list_batch_cfg,
    exact=True, needs_index=True,
    supports_batch=True, supports_budget=True, backend="jax",
    layout="list_major",
    traffic=_list_traffic,
    description="Threshold Algorithm rounds (paper Alg. 2; chunked "
                "execution, sequential-round accounting, batched-native "
                "sign-specialised list-prefix tiles)"))
register_engine(Engine(
    name="bta", make_args=_list_args, run_args=_bta_run,
    arg_config=_bta_cfg, batch_config=_list_batch_cfg,
    exact=True, needs_index=True,
    supports_batch=True, supports_budget=True, backend="jax",
    layout="list_major",
    traffic=_list_traffic,
    description="Block Threshold Algorithm (MXU-shaped TA, batched-native "
                "sign-specialised list-prefix tiles)"))
register_engine(Engine(
    name="norm", make_args=_norm_args, run_args=_norm_run,
    arg_config=_norm_cfg, exact=True, needs_index=True,
    supports_batch=True, supports_budget=True, backend="jax",
    layout="norm_major",
    traffic=_norm_traffic,
    description="Cauchy-Schwarz norm-ordered block scan"))
register_engine(Engine(
    name="norm_sharded", make_args=_norm_sharded_args,
    run_args=_norm_sharded_run, arg_config=_norm_sharded_cfg, exact=True,
    needs_index=True, supports_batch=True, backend="jax",
    layout="norm_sharded", traffic=_norm_traffic,
    description="shared-tile norm scan under shard_map with cross-shard "
                "pmax threshold tightening (row-sharded catalogue)"))
register_engine(Engine(
    name="pallas", make_batched=_pallas_batched, exact=True, needs_index=True,
    supports_batch=True, backend="pallas", layout="norm_major",
    traffic=_norm_traffic,
    description="norm-ordered block scan as a Pallas kernel with "
                "two-level DMA-skipping bounds (interpret mode off-TPU; "
                "the TPU compiler refuses it; closure-compiled — the one "
                "engine whose compile key still carries the snapshot "
                "version)"))
register_engine(Engine(
    name="fagin", dispatch=_host_oracle_dispatch(_fagin_one), exact=True,
    needs_index=True, supports_batch=False, backend="numpy",
    layout="row_major", host_only=True, traffic=_host_traffic,
    description="Fagin's Algorithm (paper Alg. 1; host-only numpy "
                "reference, no jit)"))
register_engine(Engine(
    name="partial", dispatch=_host_oracle_dispatch(_partial_one), exact=True,
    needs_index=True, supports_batch=False, backend="numpy",
    layout="row_major", host_only=True, traffic=_host_traffic,
    description="Partial Threshold Algorithm (paper Alg. 3 / Eq. 4; "
                "host-only numpy reference, no jit)"))
register_engine(Engine(
    name="auto", dispatch=_auto_dispatch, exact=True, needs_index=True,
    supports_batch=True, supports_budget=True, backend="dispatch",
    description="per-batch pick from host-side nnz(u) + catalogue norm "
                "spectrum"))

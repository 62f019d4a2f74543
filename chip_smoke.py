"""Chip smoke test: the exact top-K serving path on a TPU, end to end.

Run from the root of a checkout, on a machine with a TPU::

    python chip_smoke.py             # one chip: the served path
    python chip_smoke.py --chips 4   # four chips: the sharded catalogue

One chip. A two-tower retrieval catalogue is built from ``--seed``:
M = 1,000,000 candidates (the ``retrieval_cand`` cell,
``repro/configs/base.py``) x R = 64 (dlrm-rm2's ``embed_dim``), float32,
``lowrank_spectrum`` factors. It is served through ``AsyncTopKServer``
with ``method="auto"`` and with each jitted exact engine pinned in turn
(``naive``, ``ta``, ``bta``, ``norm``); single requests are submitted
and coalesced into micro-batches as in production. Then a few inserts,
updates and deletes land, the catalogue is queried again, one compaction
is forced, and it is queried a third time. Every answer is checked
against a float64 dense reference over the live rows, and the engine
traces after warmup must stay 0, across the compaction too.

Four chips (``--chips 4``). The same catalogue is dealt over a 4-device
``("data",)`` mesh and served through ``TopKServer`` with
``method="norm_sharded"``; every answer is checked against the float64
reference, and each device must hold about M/4 of the catalogue rows.

Every phase runs in this one process, which holds the chip(s). Without a
TPU the script exits non-zero before building anything. The last line of
standard output is ``{"ok": true, "device": {...}}`` and is printed only
when every check passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: retrieval_cand candidates (``repro/configs/base.py``)
M = 1_000_000
#: dlrm-rm2 ``embed_dim`` (``repro/configs/dlrm_rm2.py``)
R = 64
K = 10
#: coalescing bound. Warmup compiles and runs every pow2 bucket up to it,
#: for ``ta``/``bta`` in 4 sign buckets at k and k + 32; at R = 64 both
#: walk nearly the whole catalogue, so a warmed batch of B costs about
#: B times 0.5-1 s of chip time and the warmup grows with the sum of the
#: buckets (16 took 1219 s on a v5e, 4 keeps the whole run inside 1200 s)
MAX_BATCH = 4
#: requests per method per phase (5 methods x 3 phases = 360 requests)
PER_METHOD = 24
METHODS = ("auto", "naive", "ta", "bta", "norm")
EPS32 = float(np.finfo(np.float32).eps)


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class DenseReference:
    """Float64 dense top-K over the live rows, mirrored from the mutations
    the server sees (the plain reference the engines are held to)."""

    def __init__(self, targets):
        self.rows = np.asarray(targets, np.float64)
        self.alive = np.ones(self.rows.shape[0], bool)

    def insert(self, gids, rows) -> None:
        _check(np.array_equal(gids, np.arange(self.rows.shape[0],
                                              self.rows.shape[0] + len(gids))),
               f"insert ids {gids[:4]}... are not the next global ids")
        self.rows = np.concatenate([self.rows, np.asarray(rows, np.float64)])
        self.alive = np.concatenate([self.alive, np.ones(len(gids), bool)])

    def update(self, gids, rows) -> None:
        self.rows[gids] = np.asarray(rows, np.float64)

    def delete(self, gids) -> None:
        self.alive[gids] = False

    def topk(self, U, k: int, chunk: int = 64):
        """Reference ``(values, ids)`` per query, each ``[B, k]``,
        values descending."""
        U64 = np.asarray(U, np.float64)
        vals = np.empty((U64.shape[0], k))
        ids = np.empty((U64.shape[0], k), np.int64)
        for i in range(0, U64.shape[0], chunk):
            S = U64[i:i + chunk] @ self.rows.T
            S[:, ~self.alive] = -np.inf
            part = np.argpartition(-S, k - 1, axis=1)[:, :k]
            pv = np.take_along_axis(S, part, axis=1)
            order = np.argsort(-pv, axis=1, kind="stable")
            ids[i:i + chunk] = np.take_along_axis(part, order, axis=1)
            vals[i:i + chunk] = np.take_along_axis(pv, order, axis=1)
        return vals, ids

    def check(self, u, vals, ids, ref_vals, ref_ids) -> int:
        """Hold one served answer to the reference; returns how many of its
        ids differ from the reference's (ties within the tolerance).

        The tolerance is the float32 rounding bound of an R-term dot
        product, ``2 * R * eps32 * sum_r |u_r t_r|``, over every row
        involved: a bf16-precision product would exceed it.
        """
        u64 = np.asarray(u, np.float64)
        vals = np.asarray(vals, np.float64)
        ids = np.asarray(ids, np.int64)
        _check(np.all(ids >= 0), f"padding id in a full answer: {ids}")
        _check(len(set(ids.tolist())) == len(ids), f"repeated ids: {ids}")
        _check(np.all(ids < self.rows.shape[0]) and np.all(self.alive[ids]),
               f"dead or unknown id served: {ids}")
        rows = np.concatenate([ids, ref_ids])
        tol = 2 * len(u64) * EPS32 * float(
            np.max(np.abs(self.rows[rows]) @ np.abs(u64)))
        _check(np.all(np.abs(vals - ref_vals) <= tol),
               f"values off the reference by "
               f"{np.max(np.abs(vals - ref_vals)):.3g} > {tol:.3g}")
        own = self.rows[ids] @ u64
        _check(np.all(np.abs(vals - own) <= tol),
               f"served values are not their ids' scores "
               f"(off by {np.max(np.abs(vals - own)):.3g} > {tol:.3g})")
        return len(set(ids.tolist()) - set(ref_ids.tolist()))


def _queries(rng, n: int, r: int) -> np.ndarray:
    """Query-tower outputs over the four warmed sign buckets: mixed dense
    (half of them), non-negative dense, non-positive dense and
    non-negative sparse."""
    spectrum = (1.0 / np.sqrt(1.0 + np.arange(r))).astype(np.float32)
    U = rng.standard_normal((n, r)).astype(np.float32) * spectrum
    kind = np.arange(n) % 6
    U[kind == 3] = np.abs(U[kind == 3])
    U[kind == 4] = -np.abs(U[kind == 4])
    sparse = np.abs(U[kind == 5])
    sparse[:, 1::2] = 0.0
    U[kind == 5] = sparse
    return U


def _engine_traces(catalogue) -> int:
    """Engine executor traces in this process plus the catalogue's
    segmented-tail traces."""
    from repro.core.engines import trace_totals
    return (sum(trace_totals().values())
            + sum(catalogue.trace_counts.values()))


def _serve_phase(srv, ref: DenseReference, rng, per_method: int,
                 label: str, log) -> None:
    """Submit ``per_method`` single requests per method in bursts of 1 to
    ``max_batch`` requests, let the pipeline coalesce each burst, and
    hold every answer to the reference."""
    r = srv.catalogue.rank
    queries = {m: _queries(rng, per_method, r) for m in METHODS}
    allq = np.concatenate([queries[m] for m in METHODS])
    ref_vals, ref_ids = ref.topk(allq, K)
    for j, method in enumerate(METHODS):
        before = _engine_traces(srv.catalogue)
        batches0 = srv.pipeline_stats.n_batches
        t0 = time.perf_counter()
        results, i = [], 0
        while i < per_method:
            n = int(rng.integers(1, srv.max_batch + 1))
            handles = [srv.submit(u, K, method=method)
                       for u in queries[method][i:i + n]]
            results += [h.result(timeout=600) for h in handles]
            i += n
        wall = time.perf_counter() - t0
        ties = 0
        for i, res in enumerate(results):
            q = j * per_method + i
            ties += ref.check(queries[method][i], res.values[0],
                              res.indices[0], ref_vals[q], ref_ids[q])
        traces = _engine_traces(srv.catalogue) - before
        log(f"{label:>9s} {method:>5s}: exact=True "
            f"requests={per_method} "
            f"batches={srv.pipeline_stats.n_batches - batches0} "
            f"tie_swapped_ids={ties} engine_traces={traces} "
            f"host_wall_s={wall:.3f}")
        _check(traces == 0,
               f"{label} {method}: {traces} engine traces after warmup")


def serve_one_chip(m: int = M, r: int = R, per_method: int = PER_METHOD,
                   seed: int = 0, max_batch: int = MAX_BATCH,
                   log=print) -> dict:
    """The one-chip phases; raises :class:`SmokeFailure` on any failed
    check. Returns the final ``mutation_stats``."""
    from repro.core import random_model
    from repro.serving.pipeline import AsyncTopKServer

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    model = random_model(rng, m, r, "lowrank_spectrum")
    ref = DenseReference(np.asarray(model.targets))
    srv = AsyncTopKServer(model, max_batch=max_batch)
    srv.ctx.index                      # offline index build
    log(f"catalogue: M={m} R={r} float32 lowrank_spectrum seed={seed}; "
        f"index built in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    srv.warmup(K, m_buckets=(srv.ctx.m_bucket,))
    warm_traces = _engine_traces(srv.catalogue)
    log(f"warmup (compile + first runs): {time.perf_counter() - t0:.1f}s, "
        f"max_batch={srv.max_batch}, {warm_traces} engine traces in "
        f"this process so far")
    with srv:
        _serve_phase(srv, ref, rng, per_method, "serve", log)

        n_ins, n_upd, n_del = 64, 8, 32
        new_rows = 2.0 * rng.standard_normal((n_ins, r)).astype(np.float32)
        gids = srv.add_targets(new_rows)
        ref.insert(np.asarray(gids), new_rows)
        upd_ids = rng.choice(m, n_upd, replace=False)
        upd_rows = rng.standard_normal((n_upd, r)).astype(np.float32)
        srv.update_targets(upd_ids, upd_rows)
        ref.update(upd_ids, upd_rows)
        # tombstones inside served answers: each probe's current best row
        probe = _queries(rng, n_del // 2, r)
        _, probe_ids = ref.topk(probe, 1)
        live = np.flatnonzero(ref.alive)
        del_ids = np.unique(np.concatenate(
            [probe_ids[:, 0], rng.choice(live, n_del // 2, replace=False)]))
        srv.delete_targets(del_ids)
        ref.delete(del_ids)
        log(f"mutations: {n_ins} inserts, {n_upd} updates, "
            f"{len(del_ids)} deletes")
        _serve_phase(srv, ref, rng, per_method, "mutated", log)

        t0 = time.perf_counter()
        srv.catalogue.compact()
        log(f"compaction: {time.perf_counter() - t0:.1f}s, snapshot "
            f"version {srv.catalogue.version}")
        _serve_phase(srv, ref, rng, per_method, "compacted", log)

    stats = srv.mutation_stats
    log("mutation_stats: " + json.dumps(stats, sort_keys=True))
    log("pipeline_stats: " + json.dumps(srv.pipeline_stats.as_dict(),
                                        sort_keys=True))
    _check(stats["n_compactions"] == 1, "the forced compaction did not run")
    _check(stats["engine_compiles_total"] == 0,
           f"{stats['engine_compiles_total']} engine compiles in compaction")
    _check(stats["num_live"] == int(ref.alive.sum()),
           f"num_live {stats['num_live']} != reference "
           f"{int(ref.alive.sum())}")
    traces = _engine_traces(srv.catalogue) - warm_traces
    log(f"engine traces after warmup: {traces}")
    _check(traces == 0, f"{traces} engine traces after warmup")
    return stats


def serve_sharded(m: int = M, r: int = R, n_queries: int = 256,
                  seed: int = 0, log=print) -> None:
    """The four-chip phase: ``norm_sharded`` over every visible device."""
    import jax

    from repro.core import random_model
    from repro.serving.server import TopKServer

    n_dev = len(jax.devices())
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    model = random_model(rng, m, r, "lowrank_spectrum")
    ref = DenseReference(np.asarray(model.targets))
    srv = TopKServer(model, max_batch=MAX_BATCH)
    lay = srv.ctx.layout("norm_sharded")
    log(f"catalogue: M={m} R={r} float32 lowrank_spectrum seed={seed}, "
        f"dealt over {lay.n_shards} devices in "
        f"{time.perf_counter() - t0:.1f}s")
    _check(lay.n_shards == n_dev, f"{lay.n_shards} shards on {n_dev} devices")
    real_rows = {sh.device.id: int(np.sum(np.asarray(sh.data) >= 0))
                 for sh in lay.ids_sharded.addressable_shards}
    slab_rows = {sh.device.id: int(sh.data.shape[0])
                 for sh in lay.targets_sharded.addressable_shards}
    log(f"catalogue rows per device: real={real_rows} slab={slab_rows}")
    _check(len(real_rows) == n_dev and len(slab_rows) == n_dev,
           "the sharded catalogue does not span every device")
    _check(all(abs(c - m / n_dev) <= 1 for c in real_rows.values()),
           f"rows are not dealt evenly: {real_rows}")

    t0 = time.perf_counter()
    # the two buckets the queries land in: singles, and full chunks
    srv.warmup(K, engines=["norm_sharded"], m_buckets=(srv.ctx.m_bucket,))
    warm_traces = _engine_traces(srv.catalogue)
    log(f"warmup (compile + first runs): {time.perf_counter() - t0:.1f}s")
    U = _queries(rng, n_queries, r)
    ref_vals, ref_ids = ref.topk(U, K)
    n_single = MAX_BATCH
    t0 = time.perf_counter()
    parts = [srv.query(U[i:i + 1], K, method="norm_sharded")
             for i in range(n_single)]
    parts.append(srv.query(U[n_single:], K, method="norm_sharded"))
    wall = time.perf_counter() - t0
    vals = np.concatenate([np.asarray(p.values) for p in parts])
    ids = np.concatenate([np.asarray(p.indices) for p in parts])
    ties = sum(ref.check(U[i], vals[i], ids[i], ref_vals[i], ref_ids[i])
               for i in range(n_queries))
    traces = _engine_traces(srv.catalogue) - warm_traces
    st = srv.stats["norm_sharded"]
    log(f"norm_sharded: exact=True queries={n_queries} "
        f"scores_per_query={st.scores_per_query:.0f} "
        f"tie_swapped_ids={ties} engine_traces={traces} "
        f"host_wall_s={wall:.3f}")
    _check(traces == 0, f"{traces} engine traces after warmup")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the served path on one chip; 4: only the "
                         "sharded catalogue over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)

    def log(msg):
        print(msg, flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        serve_sharded(seed=args.seed, log=log)
    else:
        serve_one_chip(seed=args.seed, log=log)
    log(f"total: {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

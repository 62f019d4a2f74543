"""One run of one cell: set-up, the measured window, the check.

Set-up makes the catalogue on the device from the seed, hands it to the
program's server, warms only the cell's engine at its batch buckets and
its one M bucket (``warmup(..., engines=[method],
m_buckets=(ctx.m_bucket,))``), makes the client's queries, and sends a
few requests that are not timed. The window then drives the program's
own entry: ``AsyncTopKServer.submit`` on an open-loop schedule, or
``TopKServer.query`` in a closed loop of steps. After the window the
server is closed and freed, and a sample of the window's answers, drawn
from the seed, is held to the float64 reference (``check.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import pathlib
import queue
import shutil
import sys
import threading
import time
from typing import Callable, List, Optional, Tuple

import numpy as np

from chipbench import catalogue, check, reference, spec, tracing
from chipbench import traffic as gen

#: where a traced run writes its profile (listed in .gitignore; removed
#: once it is read)
TRACE_DIR = spec.ROOT / "chipbench_out" / "trace"
#: how long after the window closes an answer may still come
LATE_S = 60.0


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read this."""

    cell: str
    config: dict
    traffic: dict
    setup_s: float
    #: length of the measured window, seconds
    window_s: float
    #: per request of the window: result on the host minus due time
    #: (open loop) or minus step start (closed loop); inf = failed
    latency_s: np.ndarray
    #: requests answered and not found wrong by the check
    n_exact: int
    #: open loop: actual send time minus due time, per request
    gen_lag_s: Optional[np.ndarray] = None
    #: closed loop: wall time of each step
    step_wall_s: Optional[np.ndarray] = None
    #: ``PipelineStats`` counters, window end minus window start
    pipeline: Optional[dict] = None
    #: XLA compiles and cache loads inside the window
    compiles: int = 0
    #: the same in set-up, after the program's own ``warmup``: what it
    #: leaves to be compiled while serving
    compiles_after_warmup: int = 0
    #: ``(real batch size, executor calls)`` inside the window
    batches: Optional[List[Tuple[int, int]]] = None
    #: traced open loop: the program's ``queue_wait`` spans, seconds
    queue_wait_s: Optional[np.ndarray] = None
    #: traced run: the reduced profiler trace (``tracing.Summary``)
    trace: object = None
    #: the chip's peaks (``peaks.json``); None off the chip
    peak: Optional[dict] = None


class CompileCounter:
    """Counts XLA executables compiled or loaded from the persistent
    cache in this process (every jit cache miss), from JAX's own
    monitoring events; ``engine_traces`` alone misses the small jitted
    helpers the program runs around its executors."""

    _n = 0
    _registered = False

    @classmethod
    def count(cls) -> int:
        if not cls._registered:
            import jax
            from jax._src.dispatch import BACKEND_COMPILE_EVENT

            def on_event(event, duration_secs, **kwargs):
                if event == BACKEND_COMPILE_EVENT:
                    cls._n += 1

            jax.monitoring.register_event_duration_secs_listener(on_event)
            cls._registered = True
        return cls._n


def engine_traces(srv) -> int:
    from repro.core.engines import trace_totals
    return (sum(trace_totals().values())
            + sum(srv.catalogue.trace_counts.values()))


def build_server(config: dict, traffic: dict, seed: int, chips: int = 1):
    """The catalogue from the seed, sharded by rows over the cell's
    ``chips`` where there are more than one, handed to the program's
    server and warmed for this cell alone."""
    import jax

    from repro.core import SepLRModel
    from repro.serving.pipeline import AsyncTopKServer
    from repro.serving.server import TopKServer

    rows = catalogue.rows(seed, catalogue.CATALOGUE, config["rows"],
                          config["rank"], config["catalogue"],
                          config["block_rows"], chips)
    jax.block_until_ready(rows)
    model = SepLRModel(targets=rows, name=config["name"])
    k, method = int(config["k"]), config["method"]
    server = dict(config.get("server", {}))
    if traffic["entry"] == "submit":
        srv = AsyncTopKServer(model, method=method, **server)
        del model, rows
        srv.warmup(k, engines=[method], m_buckets=(srv.ctx.m_bucket,))
    elif traffic["entry"] == "query":
        srv = TopKServer(model, **server)
        del model, rows
        srv.warmup(k, batch_sizes=(int(traffic["batch"]),),
                   engines=[method], m_buckets=(srv.ctx.m_bucket,))
    else:
        raise ValueError(f"unknown entry {traffic['entry']!r}")
    return srv


def warm_open(srv, config: dict, seed: int) -> int:
    """Untimed: run every exact batch size the dispatcher can hand the
    catalogue (1 to ``max_batch``; each has its own small jitted
    helpers, which the program's ``warmup`` leaves out), then start the
    pipeline threads and send a few requests. Returns the XLA compiles
    and cache loads this took: what the program would otherwise compile
    while serving."""
    import jax

    from repro.core.engines import get_engine

    k, method = int(config["k"]), config["method"]
    spare = catalogue.host_rows(seed ^ 0x5EED, catalogue.QUERIES,
                                2 * srv.max_batch, config["rank"],
                                config["queries"], config["block_rows"])
    eng = get_engine(method)
    compiles0 = CompileCounter.count()
    for n in range(1, srv.max_batch + 1):
        res, _ = srv.catalogue.query(eng, spare[:n], k)
        jax.block_until_ready(res)
    srv.start()
    for h in [srv.submit(u, k, method=method) for u in spare]:
        h.result(timeout=LATE_S)
    return CompileCounter.count() - compiles0


def drive_open(srv, rows: np.ndarray, offsets: np.ndarray, k: int,
               method: str):
    """Submit ``rows[i]`` at ``offsets[i]`` seconds after the start;
    one collector thread reads the answers back in submission order.

    Returns ``(t0, sent, done, values, ids)``: absolute send and
    result times (inf where no answer came) and every answer.
    """
    n = len(offsets)
    sent = np.empty(n)
    done = np.full(n, np.inf)
    vals = np.zeros((n, k), np.float32)
    ids = np.full((n, k), -1, np.int32)
    handles: "queue.SimpleQueue" = queue.SimpleQueue()
    deadline = [np.inf]

    def collect():
        for _ in range(n):
            i, h = handles.get()
            if h is None:
                continue
            left = deadline[0] - time.perf_counter()
            try:
                res = h.result(timeout=None if np.isinf(left)
                               else max(left, 1e-3))
            except Exception:          # noqa: BLE001 — a failed request
                continue
            done[i] = time.perf_counter()
            vals[i] = res.values[0]
            ids[i] = res.indices[0]

    collector = threading.Thread(target=collect, name="bench-collect")
    collector.start()
    t0 = time.perf_counter() + 0.005
    due = t0 + offsets
    for i in range(n):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent[i] = time.perf_counter()
        try:
            h = srv.submit(rows[i], k, method=method)
        except Exception:              # noqa: BLE001 — a failed request
            h = None
        handles.put((i, h))
    deadline[0] = due[-1] + LATE_S
    collector.join(timeout=LATE_S + 5.0)
    if collector.is_alive():
        raise RuntimeError("answers still outstanding a minute after the "
                           "window closed")
    return t0, sent, done, vals, ids


def drive_closed(srv, pool: np.ndarray, k: int, method: str,
                 seconds: float):
    """Closed loop: send a step's batch, wait for its answers on the
    host, send the next, until ``seconds`` have passed.

    Returns ``(t0, starts, ends, values, ids)`` per step."""
    starts, ends, vals, ids = [], [], [], []
    p, b = pool.shape[0], pool.shape[1]
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        ts = time.perf_counter()
        if ts >= end:
            break
        try:
            res = srv.query(pool[len(starts) % p], k, method=method)
            v, d = np.asarray(res.values), np.asarray(res.indices)
            te = time.perf_counter()
        except Exception:              # noqa: BLE001 — a failed step
            te = np.inf
            v = np.zeros((b, k), np.float32)
            d = np.full((b, k), -1, np.int32)
        starts.append(ts)
        ends.append(te)
        vals.append(v)
        ids.append(d)
    return (t0, np.asarray(starts), np.asarray(ends), np.stack(vals),
            np.stack(ids))


def _pipeline_counts(srv) -> Optional[dict]:
    st = getattr(srv, "pipeline_stats", None)
    if st is None:
        return None
    return {"n_requests": st.n_requests, "n_batches": st.n_batches,
            "n_cached": st.n_cached, "n_shed": st.n_shed,
            "batch_size_hist": dict(st.batch_size_hist)}


def _delta(after: Optional[dict], before: Optional[dict]):
    if after is None:
        return None
    out = {n: after[n] - before[n] for n in after if n != "batch_size_hist"}
    hist = {b: c - before["batch_size_hist"].get(b, 0)
            for b, c in after["batch_size_hist"].items()}
    out["batch_size_hist"] = {b: c for b, c in hist.items() if c}
    return out


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def measure(cell: str, seed: int, seconds: float, traced: bool,
            t_start: float, base: pathlib.Path = spec.BENCH_DIR,
            bench: Optional[dict] = None, peak: Optional[dict] = None,
            log: Callable[[str], None] = lambda m: print(m, file=sys.stderr,
                                                       flush=True)):
    """Set up, drive and check one run. Returns ``(run, verdict,
    memory_peak_bytes)``; ``t_start`` is the process's start on the
    ``time.perf_counter`` clock."""
    import jax

    from repro import obs

    bench = spec.load_benchmark() if bench is None else bench
    w = spec.workload(bench, cell)
    config = spec.load_config(w["config"], base)
    traffic = spec.load_traffic(w["traffic"], base)
    k, method = int(config["k"]), config["method"]

    srv = build_server(config, traffic, seed, int(w["chips"]))
    rows = gen.query_rows(traffic, config, seed, seconds)
    if traffic["loop"] == "open":
        offsets = gen.arrival_offsets(traffic, seed, seconds)
        after_warmup = warm_open(srv, config, seed)
    else:
        compiles0 = CompileCounter.count()
        jax.block_until_ready(srv.query(rows[0], k, method=method))
        after_warmup = CompileCounter.count() - compiles0
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: {config['rows']}x{config['rank']} "
        f"k={k} method={method} index_built={srv.ctx._index is not None} "
        f"compiles_after_warmup={after_warmup}")

    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        kept = obs.Tracer(capacity=len(rows) + 16)
        prev_tracer, obs.TRACER = obs.TRACER, kept
        tracing.start(str(TRACE_DIR))
    compiles0 = CompileCounter.count()
    traces0 = engine_traces(srv)
    pipe0 = _pipeline_counts(srv)
    window = jax.profiler.TraceAnnotation("bench.window")
    window.__enter__()
    try:
        if traffic["loop"] == "open":
            t0, sent, done, vals, ids = drive_open(srv, rows, offsets, k,
                                                   method)
            due = t0 + offsets
            latency = done - due
            gen_lag = sent - due
            step_wall = None
            window_s = float(seconds)
        else:
            t0, starts, ends, vals, ids = drive_closed(srv, rows, k, method,
                                                       seconds)
            step_wall = ends - starts
            b = rows.shape[1]
            latency = np.repeat(step_wall, b)
            vals = vals.reshape(-1, k)
            ids = ids.reshape(-1, k)
            gen_lag = None
            finite = ends[np.isfinite(ends)]
            window_s = (float(finite[-1]) if finite.size else t0) - t0
    finally:
        window.__exit__(None, None, None)
        if traced:
            jax.profiler.stop_trace()
            obs.TRACER = prev_tracer
    compiles = CompileCounter.count() - compiles0
    log(f"window: {compiles} XLA compiles or cache loads, "
        f"{engine_traces(srv) - traces0} engine traces")
    pipeline = _delta(_pipeline_counts(srv), pipe0)
    mem = memory_peak_bytes()
    if traffic["loop"] == "open":
        srv.close()
        batches = sorted(pipeline["batch_size_hist"].items())
    else:
        batches = [(rows.shape[1], len(step_wall))]
    del srv
    gc.collect()

    # the check: a sample of the window's answers, drawn from the seed
    n_req = len(latency)
    answered = np.flatnonzero(np.isfinite(latency) & (ids[:, 0] >= 0))
    rng = np.random.default_rng([int(seed), catalogue.SAMPLE])
    take = min(int(config["check_sample"]), answered.size)
    pick = np.sort(rng.choice(answered, take, replace=False))
    if traffic["loop"] == "open":
        U = rows[pick]
    else:
        step, row = np.divmod(pick, rows.shape[1])
        U = rows[step % rows.shape[0], row]
    t_ref = time.perf_counter()
    ref = reference.reference(seed, config, U, ids[pick], k)
    verdict = check.judge(vals[pick], ids[pick], ref, int(config["rows"]),
                          n_req - answered.size, config["limits"])
    log(f"check: {take} of {n_req} answers against float64 in "
        f"{time.perf_counter() - t_ref:.3f}s")
    latency = np.where(np.isfinite(latency) & (ids[:, 0] >= 0), latency,
                       np.inf)
    run = Run(cell=cell, config=config, traffic=traffic, setup_s=setup_s,
              window_s=window_s, latency_s=latency,
              n_exact=int(answered.size - verdict.n_wrong),
              gen_lag_s=gen_lag, step_wall_s=step_wall, pipeline=pipeline,
              compiles=int(compiles),
              compiles_after_warmup=int(after_warmup), batches=batches,
              peak=peak)
    if traced:
        run.queue_wait_s = np.asarray(
            [s.duration_s for t in kept.traces() for s in t.spans
             if s.name == "queue_wait"])
        run.trace = tracing.summarize(TRACE_DIR, window_name="bench.window")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return run, verdict, mem

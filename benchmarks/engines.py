"""Registry sweep — every registered engine over an M x B x sign grid.

The benchmark equivalent of ``TopKServer.available_engines()``: whatever
is in ``repro.core.engines`` gets measured (wall time + the paper's
score-count metric) and, when it advertises ``exact``, checked against
the naive scan. A newly registered engine shows up here with zero harness
changes — the point of the registry layer (DESIGN.md §1).

Measurement protocol (DESIGN.md §6): engines run through the registry's
compiled-executable cache (``EngineContext.warmup`` first — which also
warms the common SIGN buckets of the batched list scan, DESIGN.md §11 —
so the numbers are steady-state serving latency, not trace+compile
time), and ``us_per_query`` is the MINIMUM over ``iters`` timed batches —
the shared-host-noise-robust estimator; the median is recorded alongside.
Each row also records ``queries_per_s`` (batch throughput at this B),
``speedup_vs_naive`` (same M, same batch, same sign), and
``interpret_mode`` — Pallas rows measured off-TPU run in the Pallas
interpreter, which is orders of magnitude slower than both compiled TPU
execution and the XLA engines, and must never be read as a hardware
result (interpreter rows are measured only at the reference batch
``B = 8``; at B = 64 x 262k they are minutes per call and say nothing).

The sweep carries two axes beyond M:

* ``batch`` in {1, 8, 64} — the batched-native list scan shares ONE
  prefix-tile enumeration across the batch, so ta/bta per-QUERY latency
  must collapse as B grows (the PR-6 tentpole claim); B = 1 keeps the
  un-amortised floor visible.
* ``sign`` in {mixed, nonneg} — only for the list engines (plus naive,
  the baseline): a single-sign batch takes the sign-specialised variant
  reading ONE direction's prefix tiles with batch-SHARED freshness keys;
  mixed batches pay the per-query direction select. The other engines
  are sign-indifferent and are measured on the mixed batch only.

``sign_bucket`` records the bucket the dispatch actually specialised on
(``unbucketed`` = layout off, one unspecialised trace) and
``traces_by_sign`` snapshots the process-wide per-(engine, bucket)
compile counters (``repro.core.engines.trace_detail``) at row time — the
artifact's record that warmed buckets served without retraces.

Host-only reference oracles (``backend == "numpy"``: ``fagin``,
``partial``) are registered engines but are skipped here — item-at-a-time
python loops at M ≥ 8k are minutes-per-batch and say nothing about the
serving path.
"""
import time

import numpy as np

from benchmarks.common import csv_line, save_rows

QUICK_SWEEP = (8000,)
FULL_SWEEP = (8000, 32768, 131072, 262144)
BATCH_SWEEP = (1, 8, 64)
#: quick mode forces the list layout ON below LIST_LAYOUT_MIN_TARGETS so
#: the CI smoke sweep exercises the batched+sign-specialised path at 8k
QUICK_PREFIX_DEPTH = 512


def _catalogue(rng, m: int, r: int) -> np.ndarray:
    T = rng.standard_normal((m, r)).astype(np.float32)
    T *= (1.0 / np.sqrt(1.0 + np.arange(m, dtype=np.float32)))[:, None]
    return T


def _timed(run, U, iters: int, budget_s: float = 2.0):
    import jax

    def once():
        t0 = time.perf_counter()
        res = run(U)
        jax.tree_util.tree_map(
            lambda a: a.block_until_ready()
            if hasattr(a, "block_until_ready") else a, res)
        return res, time.perf_counter() - t0

    run(U)                       # ensure compiled
    _, est = once()              # warm estimate sizes the loop: slow calls
    iters = max(3, min(iters, int(budget_s / max(est, 1e-9))))
    ts = []
    for _ in range(iters):
        res, dt = once()
        ts.append(dt)
    return res, float(np.min(ts)), float(np.median(ts))


def run(quick: bool = True, iters: int = 30, save_as: str = "engines"):
    import jax
    import jax.numpy as jnp

    from repro.core import naive_topk
    from repro.core.engines import (
        EngineContext,
        list_engines,
        select_engine,
        trace_detail,
    )
    from repro.core.strategies import sign_bucket_label
    from repro.kernels.topk_mips import resolve_interpret

    interpret = bool(resolve_interpret(None))
    rng = np.random.default_rng(7)
    R, K = 32, 10
    rows = []
    for M in (QUICK_SWEEP if quick else FULL_SWEEP):
        T = _catalogue(rng, M, R)
        ctx = EngineContext(T, block_size=256,
                            prefix_depth=QUICK_PREFIX_DEPTH if quick
                            else None)
        ctx.warmup(K, batch_sizes=BATCH_SWEEP)
        for B in BATCH_SWEEP:
            U_mixed = rng.standard_normal((B, R)).astype(np.float32)
            U_nonneg = (np.abs(U_mixed) + 1e-3).astype(np.float32)
            for sign_name, U_np in (("mixed", U_mixed),
                                    ("nonneg", U_nonneg)):
                U = jnp.asarray(U_np)
                ref = np.sort(
                    np.asarray(naive_topk(ctx.targets, U, K).values),
                    axis=1)
                naive_us = None
                for eng in list_engines():
                    if eng.backend == "numpy":
                        continue    # host-only oracles: not a serving path
                    if sign_name == "nonneg" and eng.name != "naive" \
                            and eng.layout != "list_major":
                        continue    # sign-indifferent engines: mixed only
                    if eng.backend == "pallas" and (not interpret or B != 8):
                        # refused by the TPU compiler; the interpreter
                        # runs the reference batch only
                        continue
                    run_as = (select_engine(ctx, U_np)
                              if eng.name == "auto" else eng)
                    res, t_min, t_med = _timed(
                        lambda q, e=run_as: e.run(ctx, q, K), U, iters)
                    exact_ok = bool(np.allclose(
                        np.sort(np.asarray(res.values), axis=1), ref,
                        atol=1e-3))
                    us = t_min / B * 1e6
                    if eng.name == "naive":
                        naive_us = us
                    traffic = (run_as.traffic(ctx, res) if run_as.traffic
                               else {"rows_gathered": None,
                                     "rows_contiguous": None,
                                     "est_bytes_moved": None,
                                     "gather_fraction": None})
                    bucket = (run_as.batch_config(ctx, U_np)
                              if run_as.batch_config is not None else ())
                    traces = {sign_bucket_label(bc): n
                              for (nm, bc), n in trace_detail().items()
                              if nm == run_as.name}
                    rows.append({
                        "engine": eng.name,
                        "resolved": run_as.name,
                        "backend": eng.backend,
                        "exact": eng.exact,
                        "exact_verified": exact_ok,
                        "needs_index": eng.needs_index,
                        "layout": run_as.layout,
                        # 0 = adaptive default left the list_major layout
                        # OFF at this M (plain gather path, unbucketed)
                        "prefix_depth": (
                            ctx.resolved_prefix_depth
                            if run_as.layout == "list_major" else None),
                        # pallas rows run only in the interpreter
                        "interpret_mode": run_as.backend == "pallas",
                        "M": M, "R": R, "K": K, "batch": B,
                        "sign": sign_name,
                        "sign_bucket": sign_bucket_label(bucket),
                        "traces_by_sign": traces,
                        "avg_scores": float(
                            np.mean(np.asarray(res.n_scored))),
                        "us_per_query": us,
                        "us_per_query_median": t_med / B * 1e6,
                        "queries_per_s": B / t_min,
                        "speedup_vs_naive": None,   # filled below
                        **traffic,
                    })
                assert naive_us is not None
                for r_ in rows:
                    if (r_["M"] == M and r_["batch"] == B
                            and r_["sign"] == sign_name):
                        r_["speedup_vs_naive"] = naive_us / r_["us_per_query"]
    save_rows(save_as, rows)
    return rows


def main(quick: bool = True):
    rows = run(quick)
    bad = [r["engine"] for r in rows if r["exact"] and not r["exact_verified"]]
    m0, b0 = rows[0]["M"], 8
    derived = ";".join(
        f"{r['engine']}={r['avg_scores']:.0f}sc,{r['speedup_vs_naive']:.2f}x"
        for r in rows if r["M"] == m0 and r["batch"] == b0
        and r["sign"] == "mixed")
    derived += f";exact_failures={bad or 'none'}"
    fastest = min((r for r in rows
                   if r["M"] == m0 and r["batch"] == b0
                   and r["sign"] == "mixed"),
                  key=lambda r: r["us_per_query"])
    print(csv_line("engines", fastest["us_per_query"], derived))
    assert not bad, f"exact engines diverged from naive: {bad}"


if __name__ == "__main__":
    import sys
    main(quick="--full" not in sys.argv)

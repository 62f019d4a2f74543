"""Observability overhead gate: the PR-8 closed loop, instrumented.

DESIGN.md §14's overhead budget, measured end to end: the loadtest's
saturated closed-loop phase (open-loop arrivals at 8x the sync
baseline's rate, every result oracle-verified) runs against ONE shared
async server in interleaved A/B phases — observability DISABLED
(``repro.obs.set_enabled(False)``: every seam early-outs, and the GC
hook and compile listener are removed), then EVERYTHING on (metrics
registry recording, the event journal, the stage events and GC hook,
and span traces at ``sample_rate=1.0`` — a worse-than-production
setting; the process-wide tracer keeps 1 request in 100), in interleaved repetitions that ALTERNATE which
mode runs first. Sharing the server, interleaving, and alternating the
order is what makes this a CONTROLLED comparison: both sides see
identical compiled executables, warm cost tables and allocator state,
and slow machine-wide drift lands on both sides instead of biasing
whichever mode ran second. Both modes are burned in at the saturated
rate before timing starts (first-phase one-time costs — label-series
creation, span-store allocator growth — are warmup, not overhead).
Every phase submits unique queries, so the result cache contributes to
neither side. The gate is the ratio of best-of-N saturated completed
QPS (per-rep paired ratios ride in the summary for honesty):

* ``obs_on_qps / obs_off_qps >= 0.9`` — full observability may cost at
  most 10% of saturated throughput. This is the ``--check-perf`` gate
  the committed full-size ``results/bench/obs_overhead.json`` must
  pass on a quiet machine.
* ``--check`` (what CI runs, with ``--quick``) gates SOUNDNESS only —
  every row oracle-exact, the metrics snapshot validates against the
  checked-in schema, the Prometheus rendering parses, and a full span
  tree was captured. The ratio is REPORTED but not gated in CI:
  shared-runner clocks jitter far more than the 10% budget itself
  (observed same-mode back-to-back runs varying 10x under co-tenant
  load), so the wall-clock criterion is an artifact-generation gate,
  not a CI gate — the same split ``benchmarks/loadtest.py`` settled on.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from benchmarks.common import save_rows
from benchmarks.loadtest import _oracle_topk, run_async, run_sync


def _saturated_phase(srv, rng, T, R, k, qps, dur, method, tag):
    """One saturated closed-loop phase of UNIQUE queries (the cache
    cannot contribute; completed QPS measures the serving path alone).
    Returns the loadtest-shaped row."""
    n = min(max(int(qps * dur), 200), 20000)
    qs = rng.standard_normal((n, R)).astype(np.float32)
    return run_async(srv, qs, _oracle_topk(T, qs, k), k, qps, dur,
                     method, tag=tag, n=n)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small M / short durations (CI tier-2 smoke)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 on a soundness failure (exactness, "
                         "snapshot schema, prom parse, missing trace); "
                         "the ratio is reported, not gated — CI clocks "
                         "are too noisy")
    ap.add_argument("--check-perf", action="store_true",
                    help="additionally gate the real overhead budget: "
                         "obs-on throughput >= 0.9x obs-off (artifact "
                         "generation on a quiet machine)")
    ap.add_argument("--method", default="auto")
    args = ap.parse_args(argv)

    from repro import obs
    from repro.core import SepLRModel
    from repro.serving.pipeline import AsyncTopKServer
    from repro.serving.server import TopKServer

    M = 4096 if args.quick else 65536
    R, k, pool_n = 32, 10, 512
    dur = 1.0 if args.quick else 3.0
    max_batch = 64
    rng = np.random.default_rng(0)
    T = rng.standard_normal((M, R)).astype(np.float32)
    pool = rng.standard_normal((pool_n, R)).astype(np.float32)
    oracle = _oracle_topk(T, pool, k)
    meta = {"M": M, "R": R, "k": k, "method": args.method,
            "max_batch": max_batch}

    print(f"# obs_overhead M={M} k={k} method={args.method}", flush=True)
    # the sync baseline exists only to locate the saturating rate; it
    # runs uninstrumented so BOTH instrumented phases see the same rate
    obs.set_enabled(False)
    try:
        sync_srv = TopKServer(SepLRModel(T), max_batch=max_batch,
                              delta_capacity=64)
        sync_srv.warmup(k)
        sync_row = dict(run_sync(sync_srv, pool, oracle, k, dur,
                                 args.method), **meta)
        del sync_srv
        sync_qps = sync_row["completed_qps"]
        sat_qps = max(8.0 * sync_qps, 1.0)
        print(f"sync: {sync_qps:.0f} qps -> saturating at "
              f"{sat_qps:.0f} qps", flush=True)

        rows = [sync_row]
        obs.reset()
        obs.TRACER.sample_rate = 1.0       # worst case: trace everything
        srv = AsyncTopKServer(SepLRModel(T), max_batch=max_batch,
                              delta_capacity=64, method=args.method)
        srv.warmup(k)
        phases = {"obs_off": [], "obs_on": []}
        with srv:
            # burn in BOTH modes at the saturated rate before anything
            # is timed: the first instrumented phase otherwise pays
            # one-time costs (label-series creation, allocator growth
            # for the span store) that belong to warmup, not overhead
            for on in (False, True):
                obs.set_enabled(on)
                burn = rng.standard_normal((256, R)).astype(np.float32)
                run_async(srv, burn, _oracle_topk(T, burn, k), k,
                          sat_qps, 0.5, args.method, n=256)
            ratios = []
            for rep in range(2 if args.quick else 4):
                # alternate which mode runs first so slow machine-wide
                # drift within a rep cancels instead of always taxing
                # the same side
                order = ((("obs_off", False), ("obs_on", True))
                         if rep % 2 == 0 else
                         (("obs_on", True), ("obs_off", False)))
                pair = {}
                for mode, on in order:
                    obs.set_enabled(on)
                    row = dict(_saturated_phase(
                        srv, rng, T, R, k, sat_qps, dur, args.method,
                        f"{mode}_run{rep}"), **meta, obs_enabled=on)
                    phases[mode].append(row)
                    pair[mode] = row["completed_qps"]
                    rows.append(row)
                    print(f"{mode} run{rep}: "
                          f"{row['completed_qps']:.0f} qps", flush=True)
                ratios.append(pair["obs_on"] / max(pair["obs_off"], 1e-9))
        best = {mode: max(p["completed_qps"] for p in ps)
                for mode, ps in phases.items()}
        snapshot = obs.REGISTRY.snapshot()
        prom = obs.REGISTRY.render_prom()
        trace = obs.TRACER.slowest()
    finally:
        obs.set_enabled(True)   # never leave the process dark

    ratio = best["obs_on"] / max(best["obs_off"], 1e-9)
    summary = {
        "mode": "summary", **meta,
        "sync_qps": sync_qps,
        "offered_qps": sat_qps,
        "obs_off_qps": best["obs_off"],
        "obs_on_qps": best["obs_on"],
        "overhead_ratio": ratio,
        "per_rep_ratios": ratios,
        "exact_verified": all(r["exact_verified"] for r in rows),
        "n_prom_samples": len(obs.parse_prom_text(prom)),
        "n_traces": len(obs.TRACER.traces()),
        "slowest_trace_us": (None if trace is None
                             else trace.duration_us),
    }
    rows.append(summary)
    # the metrics snapshot of the instrumented run rides in the
    # artifact so the CI obs job can validate it against the
    # checked-in schema without rerunning the bench
    rows.append({"mode": "metrics_snapshot", "snapshot": snapshot,
                 "prom_text": prom})
    save_rows("obs_overhead", rows)
    print(f"overhead_ratio={ratio:.3f} "
          f"(obs_on {best['obs_on']:.0f} / obs_off {best['obs_off']:.0f} "
          f"qps)", flush=True)

    failures = []
    if args.check or args.check_perf:
        if not summary["exact_verified"]:
            failures.append("a served result diverged from the oracle "
                            "while instrumented")
        try:
            obs.validate_snapshot(snapshot)
        except ValueError as e:
            failures.append(f"metrics snapshot violates the checked-in "
                            f"schema: {e}")
        if summary["n_prom_samples"] < 10:
            failures.append("Prometheus rendering parsed to "
                            f"{summary['n_prom_samples']} samples")
        if trace is None or trace.find("await") is None:
            failures.append("no full span tree captured at "
                            "sample_rate=1.0")
    if args.check_perf and ratio < 0.9:
        failures.append(f"overhead ratio {ratio:.3f} < 0.9x — "
                        "observability costs more than its 10% budget")
    for f in failures:
        print(f"GATE FAILED: {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

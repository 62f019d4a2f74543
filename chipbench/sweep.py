"""Find the knee of an open-loop cell: the highest offered rate at which
the backlog does not grow through the window.

    python3 chipbench/sweep.py --workload retrieval_1m.poisson \
        --seed 5 --seconds 6

One process, one catalogue, one warmed server. Each offered rate of the
traffic file's ``sweep_rates`` runs a window of its own (fresh distinct
queries), in order; then ``--refine`` more rates bisect between the
highest rate that held and the lowest that did not. A rate holds when
every request was answered, the answers came at the offered rate (at
least 98 % of it), and the last quarter of the window's requests waited
no longer, by median, than 1.5 times the first quarter plus 1 ms. One
JSON line per rate, then the knee and 0.8 times it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise write its logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def held(r: dict) -> bool:
    return (r["unanswered"] == 0 and r["completed_per_s"]
            >= 0.98 * r["offered_per_s"]
            and r["p50_last_quarter_ms"]
            <= 1.5 * r["p50_first_quarter_ms"] + 1.0)


def one_rate(srv, config, traffic, seed, seconds, rate) -> dict:
    import numpy as np

    from chipbench import catalogue, harness, traffic as gen

    offsets = gen.arrival_offsets(traffic, seed, seconds, rate_per_s=rate)
    rows = catalogue.host_rows(seed, catalogue.QUERIES, len(offsets),
                               config["rank"], config["queries"],
                               config["block_rows"])
    b0 = srv.pipeline_stats.n_batches
    t0, sent, done, _, _ = harness.drive_open(
        srv, rows, offsets, int(config["k"]), config["method"])
    due = t0 + offsets
    lat = 1e3 * (done - due)
    ok = np.isfinite(lat)
    q = len(lat) // 4
    span = np.max(done[ok]) - due[0] if ok.any() else np.inf
    return {"offered_per_s": rate, "requests": len(lat),
            "unanswered": int(np.sum(~ok)),
            "completed_per_s": float(np.sum(ok) / span),
            "p50_ms": float(np.percentile(lat[ok], 50)),
            "p99_ms": float(np.percentile(lat[ok], 99)),
            "p50_first_quarter_ms": float(np.median(lat[:q][ok[:q]])),
            "p50_last_quarter_ms": float(np.median(lat[-q:][ok[-q:]])),
            "gen_lag_p99_ms": float(1e3 * np.percentile(sent - due, 99)),
            "batch_mean": len(lat) / max(srv.pipeline_stats.n_batches - b0,
                                         1)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--refine", type=int, default=3)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax

    from chipbench import harness, spec

    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    bench = spec.load_benchmark()
    w = spec.workload(bench, args.workload)
    config = spec.load_config(w["config"])
    traffic = spec.load_traffic(w["traffic"])
    srv = harness.build_server(config, traffic, args.seed, int(w["chips"]))
    harness.warm_open(srv, config, args.seed)
    results = []
    try:
        for i, rate in enumerate(traffic["sweep_rates"]):
            r = one_rate(srv, config, traffic, args.seed + 1 + i,
                         args.seconds, float(rate))
            r["held"] = held(r)
            results.append(r)
            print(json.dumps(r), flush=True)
        for j in range(args.refine):
            good = [r["offered_per_s"] for r in results if r["held"]]
            bad = [r["offered_per_s"] for r in results if not r["held"]
                   and r["offered_per_s"] > max(good, default=0.0)]
            if not good or not bad:
                break
            rate = 0.5 * (max(good) + min(bad))
            r = one_rate(srv, config, traffic, args.seed + 100 + j,
                         args.seconds, rate)
            r["held"] = held(r)
            results.append(r)
            print(json.dumps(r), flush=True)
    finally:
        srv.close()
    knee = max((r["offered_per_s"] for r in results if r["held"]),
               default=None)
    print(json.dumps({"knee_per_s": knee,
                      "cell_rate_per_s": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

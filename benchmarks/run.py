"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one line per benchmark) and
writes detailed rows to ``results/bench/*.json``. ``--full`` runs at
paper scale (slow on this 1-core container); default is the reduced
sweep.

**results/bench JSON schema.** Every artifact is a JSON LIST OF ROW
DICTS (one row per swept configuration), written by
:func:`benchmarks.common.save_rows`; numeric values serialise as floats.
Committed artifacts are measured on the full sweep on the development
box; CI's tier-2 job regenerates the quick sweep per commit (the gate is
exactness, wall-clock on shared runners is noise). Shared keys across
artifacts:

``M``/``R``/``K``/``batch``
    Sweep point: catalogue rows, rank, top-K size, query batch size.
``exact_verified`` (bool)
    The row's results matched the dense/oracle recomputation AFTER
    timing. CI fails on any ``false``; treat a row without it as
    unverified.
``us_per_query`` / ``us_per_query_median`` / ``us_per_query_mean``
    Wall-clock per query: min-over-iterations (noise-robust), the
    median alongside, or the lifetime mean (streaming).

``engines.json`` (``benchmarks/engines.py``) adds per engine row:
capability echoes (``engine``/``backend``/``layout``/``exact``/
``needs_index``/``resolved``/``interpret_mode`` — Pallas rows measured
off-TPU are interpreter time, never hardware results), the paper's cost
metric (``avg_scores``), ``speedup_vs_naive``, and the layout-traffic
estimators ``rows_gathered``/``rows_contiguous``/``est_bytes_moved``/
``gather_fraction`` plus ``prefix_depth`` (0 = adaptive default left the
list layout off).

``streaming.json`` (``benchmarks/streaming.py``) adds per row: the
schedule (``rounds``/``mutation_calls``/``mutated_items``/``queries``),
both sides' totals and throughput (``segmented_s``/``rebuild_s``/
``rebuild_lazy_s``/``ops_per_s_*``/``qps_segmented``/``n_rebuilds``),
``speedup_vs_rebuild[_lazy]``, latency percentiles ``p50_us``/
``p95_us``/``p99_us``, delta/compaction counters (``delta_capacity``/
``max_delta_occupancy``/``n_compactions``/``n_tombstones_final``/
``snapshot_version``/``num_live_final``/``delta_scored_per_query``),
and the compile-free-compaction acceptance fields (DESIGN.md §10):
``engine_compiles_total``/``engine_compiles_per_compaction`` (engine
traces during compaction builds; 0 = every build hit warmed M-buckets)
and ``compaction_s_total``/``compaction_s_mean`` (build wall-clock —
index/layout rebuild only, now that no engine recompiles ride along).

The figure/table artifacts (``table1_toy``/``fig1_cf``/
``fig2_multilabel``/``fig3_halted``/``table4_scaling``/``bta_tpu``)
mirror the paper's axes: per-(M, K, algorithm) rows of score counts,
depths, and per-query latency. Smoke runs write ``*_smoke.json`` names
so committed full-sweep artifacts are never clobbered by CI.
"""
import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (bta_tpu, engines, fig1_cf, fig2_multilabel,
                            fig3_halted, streaming, table1_toy,
                            table4_scaling)
    mods = {
        "table1_toy": table1_toy,
        "fig1_cf": fig1_cf,
        "fig2_multilabel": fig2_multilabel,
        "fig3_halted": fig3_halted,
        "table4_scaling": table4_scaling,
        "bta_tpu": bta_tpu,
        "engines": engines,   # sweeps every engine in the registry
        "streaming": streaming,   # interleaved mutations + queries (§9)
    }
    if args.only:
        mods = {k: v for k, v in mods.items() if k in args.only.split(",")}
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in mods.items():
        try:
            mod.main(quick=quick)
        except Exception:
            failures += 1
            print(f"{name},nan,FAILED")
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Serving launcher: exact top-K query serving over a SEP-LR catalogue.

``python -m repro.launch.serve --targets 50000 --rank 50 --k 10 -n 200``
builds a catalogue, indexes it, and serves batched queries through the
selected engine, printing the paper's efficiency metric (scores/query)
next to wall time. ``--engine all`` sweeps every exact engine in the
registry (``repro.core.engines``); any registry name or alias is accepted.
"""

from __future__ import annotations

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--targets", type=int, default=20000)
    ap.add_argument("--rank", type=int, default=50)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("-n", "--num-queries", type=int, default=100)
    ap.add_argument("--batch", type=int, default=25)
    ap.add_argument("--engine", default="bta",
                    help="registry engine name/alias, or 'all' to sweep "
                         "every exact engine")
    ap.add_argument("--distribution", default="lowrank_spectrum",
                    choices=["normal", "lognormal", "lowrank_spectrum"])
    ap.add_argument("--block-size", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import jax.numpy as jnp

    from repro.core import random_model
    from repro.core.engines import (auto_candidates, executable_engines,
                                    get_engine)
    from repro.serving.server import TopKServer

    rng = np.random.default_rng(args.seed)
    model = random_model(rng, args.targets, args.rank, args.distribution)
    print(f"catalogue: M={args.targets} R={args.rank} "
          f"dist={args.distribution}; building index...")
    srv = TopKServer(model, max_batch=args.batch, block_size=args.block_size)
    spectrum = (1.0 / np.sqrt(1.0 + np.arange(args.rank))).astype(np.float32) \
        if args.distribution == "lowrank_spectrum" else 1.0
    U = jnp.asarray(rng.standard_normal(
        (args.num_queries, args.rank)).astype(np.float32) * spectrum)

    if args.engine == "all":
        # every compiled engine this backend runs — not the host-only
        # numpy oracles: item-at-a-time python loops at serving sizes are
        # minutes per batch (they stay reachable by explicit --engine
        # fagin / partial)
        engines = executable_engines()
        # naive first: it is the ground-truth reference the others are
        # asserted against
        engines.sort(key=lambda n: n != "naive")
    else:
        engines = [get_engine(args.engine).name]
    # populate the compiled-executable cache so reported us/query is
    # steady-state serving latency, not trace+compile time (DESIGN.md §6).
    # Warm the buckets the actual chunk sequence will hit: full chunks of
    # --batch plus the remainder chunk, not just --batch.
    sizes = {min(args.batch, args.num_queries)}
    if args.num_queries % args.batch:
        sizes.add(args.num_queries % args.batch)
    # auto resolves per batch to a concrete engine — warm exactly the
    # candidates its policy can pick (host oracles have no compiled
    # executable; never warm them)
    warm = [e for e in engines
            if e != "auto" and not get_engine(e).host_only]
    if "auto" in engines:
        warm = sorted(set(warm) | set(auto_candidates()))
    if warm:
        srv.warmup(args.k, batch_sizes=sorted(sizes), engines=warm)
    ref = None
    for eng in engines:
        res = srv.query(U, args.k, method=eng)
        if ref is None:
            ref = np.sort(np.asarray(res.values), axis=1)
        else:
            assert np.allclose(np.sort(np.asarray(res.values), axis=1), ref,
                               atol=1e-4), f"{eng} mismatches naive!"
        # auto's traffic is accounted to the engine that actually ran
        # (DESIGN.md §3), so report every resolved engine it used
        resolved = sorted(srv.stats) if eng == "auto" else [eng]
        for name in resolved:
            st = srv.stats[name]
            label = f"auto->{name}" if eng == "auto" else name
            print(f"{label:>12s}: {st.scores_per_query:10.1f} scores/query "
                  f"({st.scores_per_query / args.targets:6.2%} of naive)  "
                  f"{st.us_per_query:10.1f} us/query")


if __name__ == "__main__":
    main()

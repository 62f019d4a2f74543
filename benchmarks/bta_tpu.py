"""Beyond-paper — the TPU-native engines: BTA block-size trade-off,
norm-pruned scanning, and the Pallas topk_mips kernel.

The paper's cost metric (scores computed) meets the hardware's cost metric
(MXU-shaped block work). BTA with block size B preserves exactness while
cutting rounds by ~B; the scores it wastes inside the final block are the
price of vectorisation. The norm-pruned scan exploits catalogue norm decay
(CF popularity / PLS spectra) with contiguous DMA — the layout the Pallas
kernel consumes.

Every engine here is invoked through the registry
(``repro.core.engines``) — the same dispatch path the serving layer uses —
with per-engine contexts carrying the block-size configuration.
"""
import time

import numpy as np

from benchmarks.common import csv_line, save_rows


def _timed_engine(engine_name, ctx, U, k):
    from repro.core.engines import get_engine
    eng = get_engine(engine_name)
    res = eng.run(ctx, U, k)                 # warm the jit cache
    t0 = time.perf_counter()
    res = eng.run(ctx, U, k)
    np.asarray(res.values)
    dt = time.perf_counter() - t0
    return (float(np.mean(np.asarray(res.n_scored))),
            dt / U.shape[0] * 1e6)


def run(quick: bool = True):
    import jax.numpy as jnp

    from repro.core.engines import EngineContext, executable_engines
    from repro.core.seplr import random_model

    rng = np.random.default_rng(4)
    M = 20000 if quick else 100000
    R, K = 50, 10
    model = random_model(rng, M, R, "lowrank_spectrum")
    T = np.asarray(model.targets)
    spectrum = 1.0 / np.sqrt(1.0 + np.arange(R, dtype=np.float32))
    Q = jnp.asarray(rng.standard_normal((5, R)).astype(np.float32) * spectrum)
    rows = []

    ctx = EngineContext(T, block_size=256)

    # exact TA reference counts (registry "ta" = blocked strategy, B=1)
    ta_mean, _ = _timed_engine("ta", ctx, Q, K)

    for block in (64, 256, 1024):
        ctx_b = EngineContext(T, index=ctx.index, block_size=block)
        scored, us = _timed_engine("bta", ctx_b, Q, K)
        rows.append({"engine": f"bta_b{block}", "M": M, "K": K,
                     "avg_scores": scored,
                     "vs_ta": scored / max(ta_mean, 1),
                     "us_per_query": us})

    # norm-pruned scan
    scored, us = _timed_engine("norm", ctx, Q, K)
    rows.append({"engine": "norm_pruned", "M": M, "K": K,
                 "avg_scores": scored, "vs_ta": scored / max(ta_mean, 1),
                 "us_per_query": us})

    # Pallas kernel in interpret mode (the TPU compiler refuses it)
    if "pallas" in executable_engines():
        scored, us = _timed_engine("pallas", ctx, Q, K)
        rows.append({"engine": "pallas_topk_mips(interpret)", "M": M,
                     "K": K, "avg_scores": scored,
                     "vs_ta": scored / max(ta_mean, 1),
                     "us_per_query": us})

    # naive matmul baseline
    _, us = _timed_engine("naive", ctx, Q, K)
    rows.append({"engine": "naive_matmul", "M": M, "K": K,
                 "avg_scores": M, "vs_ta": M / max(ta_mean, 1),
                 "us_per_query": us})
    rows.append({"engine": "ta_reference", "M": M, "K": K,
                 "avg_scores": ta_mean, "vs_ta": 1.0, "us_per_query": None})
    save_rows("bta_tpu", rows)
    return rows


def main(quick: bool = True):
    rows = run(quick)
    by = {r["engine"]: r for r in rows}
    ta = by["ta_reference"]["avg_scores"]
    derived = ";".join(
        f"{r['engine']}={r['avg_scores']:.0f}sc" for r in rows
        if r["engine"] != "ta_reference") + f";ta={ta:.0f}sc"
    print(csv_line("bta_tpu", by["naive_matmul"]["us_per_query"], derived))


if __name__ == "__main__":
    main()

"""The control of the check, and faults planted in the reference put in
the program's place: the readings that set the check's upper limits.

``control``
    The reference computed one precision below what the configuration
    states (float32 at ``HIGHEST``): three bf16 passes, ``hi*hi + hi*lo
    + lo*hi`` with float32 accumulation, which is what
    ``Precision.HIGH`` does on a TPU. It is written out so that it reads
    the same on any backend.
``approx_topk``
    float32 scores at ``HIGHEST``, selected by ``lax.approx_max_k`` at a
    recall of 0.95 (exact off the TPU, where it falls back to a sort).
``rows95``
    float32 scores at ``HIGHEST``, an exact top-k over the first 95 % of
    the rows only.

Each kind's answers go through the same sample and the same comparison
as a run's, at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seconds 20 \
        --kinds control approx_topk rows95 --seeds 4 5 6

prints one JSON line per reading. The program's own readings are the
``checks`` of ordinary runs (``run.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu would otherwise write its logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

KINDS = ("control", "approx_topk", "rows95")


def _split(x):
    import jax.numpy as jnp
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def three_pass_scores(U, T):
    """``U @ T.T`` from three bf16 products, float32 accumulation."""
    import jax.numpy as jnp
    (uh, ul), (th, tl) = _split(U), _split(T)

    def dot(a, b):
        return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)

    return dot(uh, th) + dot(uh, tl) + dot(ul, th)


def answers(U, T, k: int, kind: str):
    """Top-k of ``U @ T.T`` as ``kind`` computes it: ``(values, ids)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}; have {KINDS}")

    @jax.jit
    def run(U, T):
        if kind == "control":
            return jax.lax.top_k(three_pass_scores(U, T), k)
        s = jnp.matmul(U, T.T, precision=jax.lax.Precision.HIGHEST)
        if kind == "approx_topk":
            return jax.lax.approx_max_k(s, k, recall_target=0.95)
        return jax.lax.top_k(s[:, :T.shape[0] * 19 // 20], k)

    vals, ids = run(jnp.asarray(U), T)
    return np.asarray(vals), np.asarray(ids)


def reading(cell: str, seed: int, seconds: float, kind: str, base=None,
            bench=None) -> dict:
    """``kind``'s numbers on the queries a run of ``cell`` would check,
    at the cell's own size."""
    import numpy as np

    from chipbench import catalogue, check, reference, spec, traffic

    bench = spec.load_benchmark() if bench is None else bench
    base = spec.BENCH_DIR if base is None else base
    w = spec.workload(bench, cell)
    config = spec.load_config(w["config"], base)
    tr = spec.load_traffic(w["traffic"], base)
    k = int(config["k"])
    rows = traffic.query_rows(tr, config, seed, seconds)
    U = rows.reshape(-1, rows.shape[-1])
    rng = np.random.default_rng([int(seed), catalogue.SAMPLE])
    take = min(int(config["check_sample"]), U.shape[0])
    U = U[np.sort(rng.choice(U.shape[0], take, replace=False))]
    T = catalogue.rows(seed, catalogue.CATALOGUE, config["rows"],
                       config["rank"], config["catalogue"],
                       config["block_rows"])
    vals, ids = answers(U, T, k, kind)
    del T
    ref = reference.reference(seed, config, U, ids, k)
    v = check.judge(vals, ids, ref, int(config["rows"]), 0,
                    config["limits"])
    return {"kind": kind, "cell": cell, "seed": seed, "correct": v.correct,
            "checks": v.as_dict()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="the cell's run_seconds: which queries are drawn")
    ap.add_argument("--kinds", nargs="+", choices=KINDS, default=["control"])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax

    from chipbench import spec

    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    bench = spec.load_benchmark()
    for seed in args.seeds:
        for kind in args.kinds:
            print(json.dumps(reading(args.workload, seed, args.seconds, kind,
                                     bench=bench)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of ``BENCHMARK.json`` on the chip this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced),
then ``checks``: each number the check compared, with its limit. The
same numbers are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no
result.
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


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    import numpy as np

    from chipbench import harness, spec, work

    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _log(f"chipbench: no TPU (JAX found {dev.platform}); nothing run")
        return 2
    if len(devices) < int(cell["chips"]):
        _log(f"chipbench: {args.workload} needs {cell['chips']} chips, JAX "
             f"found {len(devices)}")
        return 2
    peak = work.peaks(dev.device_kind)
    _log(f"device: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)} jax={jax.__version__} "
         f"compile_cache={cache_dir}")

    traced = bool(args.trace)
    run, verdict, mem = harness.measure(args.workload, args.seed,
                                        args.seconds, traced, T_START,
                                        bench=bench, peak=peak, log=_log)
    metrics = spec.read_metrics(
        spec.cell_metrics(bench, args.workload, traced), run)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": verdict.correct,
           "attempted": int(run.latency_s.size),
           "failed": int(np.sum(~np.isfinite(run.latency_s))
                         + verdict.n_wrong),
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = verdict.as_dict()
    for name, c in out["checks"].items():
        _log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

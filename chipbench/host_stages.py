"""One traced run of a cell, with its host stages reduced by ``spans.py``.

    python3 chipbench/host_stages.py --workload <cell> --seed <n> \
        --seconds <s>

Runs ``run.py --trace 1`` in this process, which prints its own result
line. ``harness.measure`` deletes the profile once ``tracing.py`` has
reduced it, so this reduces it with ``spans.py`` on the way, and then
prints one more JSON line: the readings of the host stages, the stage
table, the share of device idle under a stage or ``py.gc`` event, the
events under the longest idle gaps, the traced run's end-to-end metrics
(the cost of tracing, against a ``--trace 0`` run), and the functions
XLA compiled after the program's own ``warmup``, by name
(``repro_xla_compiles_total``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import run  # noqa: E402  (first: its clock starts at import)


def _compiles_by_fun() -> dict:
    from repro import obs
    snap = obs.REGISTRY.snapshot()["metrics"]["repro_xla_compiles_total"]
    return {s["labels"]["fun"]: s["value"] for s in snap["series"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import harness, spans, spec, tracing

    got: dict = {}
    build_server, summarize, measure = (harness.build_server,
                                        tracing.summarize, harness.measure)

    def built(*a, **kw):
        srv = build_server(*a, **kw)
        got["compiles0"] = _compiles_by_fun()
        return srv

    def reduced(log_dir, **kw):
        got["compiles1"] = _compiles_by_fun()
        got["stages"] = spans.summarize(log_dir, **kw)
        return summarize(log_dir, **kw)

    def measured(*a, **kw):
        got["result"] = measure(*a, **kw)
        return got["result"]

    harness.build_server, tracing.summarize, harness.measure = (
        built, reduced, measured)
    try:
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", "1"])
    finally:
        harness.build_server, tracing.summarize, harness.measure = (
            build_server, summarize, measure)
    if rc != 0:
        return rc
    r, verdict, _ = got["result"]
    st = got.get("stages")
    steps = len(r.step_wall_s) if r.step_wall_s is not None else 0
    readings = {
        "dispatch_host_ms_per_batch": spans.dispatch_host_ms_per_batch(st),
        "harvest_host_ms_per_batch": spans.harvest_host_ms_per_batch(st),
        "readback_idle_ms_per_step": spans.readback_idle_ms_per_step(
            st, steps),
        "enqueue_idle_ms_per_step": spans.enqueue_idle_ms_per_step(
            st, steps),
        "gc_pause_ms_per_s": spans.gc_pause_ms_per_s(st),
    }
    bench = spec.load_benchmark()
    before, after = got.get("compiles0", {}), got.get("compiles1", {})
    compiled = {f: n - before.get(f, 0) for f, n in after.items()
                if n > before.get(f, 0)}
    out = {
        "workload": args.workload, "seed": args.seed,
        "correct": verdict.correct, "readings": readings,
        "idle_covered_share": (None if st is None or st.idle_s <= 0
                               else st.covered_idle_s / st.idle_s),
        "end_to_end": spec.read_metrics(
            spec.cell_metrics(bench, args.workload, False), r),
        "compiled_after_warmup": dict(sorted(compiled.items(),
                                             key=lambda x: -x[1])),
        "stages": None if st is None else st.as_dict(),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

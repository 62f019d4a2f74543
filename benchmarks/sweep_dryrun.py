"""Dry-run sweep driver: one subprocess per cell (bounds compiler RSS),
resume-safe (skips cells whose JSON already reports status=ok).

A CPU-only tool: each child compiles on virtual host devices and is
pinned to the CPU backend, so on a machine with a TPU it never claims
the chip."""
import json
import os
import subprocess
import sys
import time

OUT = "results/dryrun"
LM_SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
GNN_SHAPES = ["full_graph_sm", "minibatch_lg", "ogb_products", "molecule"]
RECSYS_SHAPES = ["train_batch", "serve_p99", "serve_bulk", "retrieval_cand"]

CELLS = []
for a in ["olmoe-1b-7b", "llama4-scout-17b-a16e", "deepseek-67b",
          "gemma-2b", "stablelm-3b"]:
    CELLS += [(a, s) for s in LM_SHAPES]
CELLS += [("pna", s) for s in GNN_SHAPES]
for a in ["deepfm", "dcn-v2", "dlrm-rm2", "fm"]:
    CELLS += [(a, s) for s in RECSYS_SHAPES]
assert len(CELLS) == 40, len(CELLS)


def done(arch, shape, mesh):
    f = os.path.join(OUT, f"{arch}__{shape}__{mesh}.json")
    if not os.path.exists(f):
        return False
    try:
        return json.load(open(f)).get("status") == "ok"
    except Exception:
        return False


def main():
    meshes = sys.argv[1:] or ["single", "multi"]
    t0 = time.time()
    for mesh in meshes:
        for arch, shape in CELLS:
            if done(arch, shape, mesh):
                print(f"skip {arch} x {shape} x {mesh}", flush=True)
                continue
            t = time.time()
            try:
                r = subprocess.run(
                    [sys.executable, "-m", "repro.launch.dryrun", "--arch",
                     arch, "--shape", shape, "--mesh", mesh, "--out", OUT],
                    env={**os.environ, "PYTHONPATH": "src",
                         "JAX_PLATFORMS": "cpu"},
                    capture_output=True, text=True, timeout=2400)
            except subprocess.TimeoutExpired:
                print(f"TIMEOUT {arch} x {shape} x {mesh}", flush=True)
                continue
            status = "ok" if done(arch, shape, mesh) else "FAIL"
            print(f"{status} {arch} x {shape} x {mesh} "
                  f"({time.time()-t:.0f}s)", flush=True)
            if status == "FAIL":
                print(r.stdout[-1200:], r.stderr[-1200:], flush=True)
    print(f"sweep wall: {(time.time()-t0)/60:.1f} min", flush=True)


if __name__ == "__main__":
    main()

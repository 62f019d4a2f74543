import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + \
    os.environ.get("REPRO_DRYRUN_DEVICES", "512")

"""Multi-pod dry-run: lower + compile every (arch x shape) on the
production meshes and record memory/cost/collective analysis.

The lines above MUST stay the first statements in this module — jax
locks the backend and the device count at first initialisation. This is
a CPU-only tool (virtual host devices), pinned to the CPU backend so it
never claims a TPU on a machine that has one. Do not
import this module from tests/benchmarks (they want 1 device); run it as
``PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k``.

Outputs one JSON per cell under --out (default results/dryrun/).
"""

import argparse   # noqa: E402
import gzip       # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402

from repro.configs import REGISTRY, all_cells, get_arch   # noqa: E402
from repro.launch.cells import build_cell                 # noqa: E402
from repro.launch.mesh import MESHES                      # noqa: E402
from repro.roofline.analysis import Roofline, from_compiled  # noqa: E402


def _compile_cell(cell, donate: bool = True):
    donate_args = ()
    if donate and cell.kind.endswith("_train"):
        donate_args = (0, 1)
    elif donate and cell.kind == "lm_decode":
        donate_args = (1,)
    jfn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                  out_shardings=cell.out_shardings,
                  donate_argnums=donate_args)
    lowered = jfn.lower(*cell.args)
    return lowered, lowered.compile()


def calibrated_roofline(arch_id, shape_name, mesh, n_chips, model_flops):
    """LM cells: XLA counts scan (while) bodies once, so compile the cell
    at n_layers in {1, 2} fully UNROLLED and extrapolate linearly:
    Q(L) = Q(1) + (Q(2) - Q(1)) * (L - 1). Collectives/bytes/FLOPs are all
    per-layer-affine, embed/unembed/loss live in the L-independent part."""
    qs = {}
    for L in (1, 2):
        cell = build_cell(arch_id, shape_name, mesh,
                          override={"n_layers": L, "unroll": True})
        _, compiled = _compile_cell(cell)
        r = from_compiled(compiled, compiled.as_text(), n_chips, 0.0)
        qs[L] = r
    L_full = get_arch(arch_id).make_config().n_layers
    def extrap(f):
        q1, q2 = f(qs[1]), f(qs[2])
        return q1 + (q2 - q1) * (L_full - 1)
    return Roofline(
        flops=extrap(lambda r: r.flops),
        hbm_bytes=extrap(lambda r: r.hbm_bytes),
        collective_bytes=extrap(lambda r: r.collective_bytes),
        n_chips=n_chips, model_flops=model_flops)


def run_cell(arch_id: str, shape_name: str, mesh_name: str,
             out_dir: str, donate: bool = True) -> dict:
    mesh = MESHES[mesh_name]()
    n_chips = mesh.devices.size
    record = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "n_chips": int(n_chips), "status": "unknown",
    }
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            cell = build_cell(arch_id, shape_name, mesh)
            lowered, compiled = _compile_cell(cell, donate)
            t_lower = 0.0
            t_compile = time.time() - t0

            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            roof_raw = from_compiled(compiled, hlo, n_chips, cell.model_flops)
            # the roofline table is single-pod only (brief: the multi-pod
            # pass just proves the pod axis shards) -> calibrate single-pod
            if get_arch(arch_id).family == "lm" and mesh_name != "multi":
                # de-bias the while-body-once cost analysis (DESIGN.md §8)
                roof = calibrated_roofline(arch_id, shape_name, mesh,
                                           n_chips, cell.model_flops)
            else:
                roof = roof_raw
            if mesh_name != "multi":
                os.makedirs(out_dir, exist_ok=True)
                hpath = os.path.join(
                    out_dir, f"{arch_id}__{shape_name}__{mesh_name}.hlo.gz")
                with gzip.open(hpath, "wt") as hf:
                    hf.write(hlo)
            record.update(
                status="ok",
                lower_s=round(t_lower, 2),
                compile_s=round(t_compile, 2),
                memory={
                    "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                    "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                    "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                    "peak_bytes_per_device": int(
                        getattr(mem, "argument_size_in_bytes", 0)
                        + getattr(mem, "output_size_in_bytes", 0)
                        + getattr(mem, "temp_size_in_bytes", 0)),
                },
                roofline=roof.to_dict(),
                roofline_scan_raw=roof_raw.to_dict(),
                meta=cell.meta,
                hlo_lines=len(hlo.splitlines()),
            )
            # console proof (per the brief)
            print(f"== {arch_id} x {shape_name} x {mesh_name} "
                  f"({n_chips} chips) ==")
            print(f"memory_analysis: {record['memory']}")
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            print("cost_analysis: flops=%.3e bytes=%.3e" % (
                float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0))))
            print("roofline:", json.dumps(record["roofline"], indent=None))
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
        print(f"== {arch_id} x {shape_name} x {mesh_name} FAILED: "
              f"{record['error']}")
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch_id}__{shape_name}__{mesh_name}.json".replace("/", "_")
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(record, f, indent=2, default=str)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=list(MESHES) + ["both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    ok = err = 0
    for arch_id, shape_name in cells:
        if arch_id is None or shape_name is None:
            raise SystemExit("--arch/--shape required unless --all")
        for mesh_name in meshes:
            rec = run_cell(arch_id, shape_name, mesh_name, args.out)
            ok += rec["status"] == "ok"
            err += rec["status"] != "ok"
    print(f"\nDRYRUN DONE: {ok} ok, {err} failed")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

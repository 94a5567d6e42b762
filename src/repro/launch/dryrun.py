"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh (16×16 = 256 chips/pod and 2×16×16 = 512 chips) of TPU v5e
chips and extract memory / cost / collective statistics.

``main()`` asks XLA for 512 host devices before JAX initializes (it locks
the device count then); importing this module sets nothing.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
Results: experiments/dryrun/<arch>__<shape>__<mesh>.json
"""

import argparse
import gzip
import json
import os
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, shape_applicable
from repro.launch.jaxpr_cost import trace_cost
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze
from repro.launch.specs import build_cell

# the chip the production mesh is made of (its peaks price the roofline)
TARGET_KIND = "TPU v5 lite"

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _mem_dict(stats) -> dict:
    return {k: getattr(stats, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = OUT_DIR, verbose: bool = True) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}.json")
    if not ok:
        result.update(status="skipped", reason=why)
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        if verbose:
            print(f"[skip] {arch} × {shape_name} × {mesh_name}: {why}")
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    try:
        cell = build_cell(arch, shape_name, mesh)
        with mesh:
            lowered = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings).lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            jx = trace_cost(cell.fn, *cell.args)
        hlo_dir = os.path.join(out_dir, "..", "hlo")
        os.makedirs(hlo_dir, exist_ok=True)
        with gzip.open(os.path.join(
                hlo_dir, f"{arch}__{shape_name}__{mesh_name}.hlo.gz"),
                "wt") as f:
            f.write(hlo)
        report = analyze(arch, shape, mesh_name, chips, cost,
                         _mem_dict(mem), hlo, cfg, jx,
                         device_kind=TARGET_KIND, notes=cell.notes)
        result.update(status="ok", lower_s=round(t_lower, 1),
                      compile_s=round(t_compile, 1),
                      roofline=report.to_json())
        if verbose:
            ms = result["roofline"]
            print(f"[ok]   {arch} × {shape_name} × {mesh_name} "
                  f"chips={chips} "
                  f"compute={ms['compute_s']:.3e}s "
                  f"memory={ms['memory_s']:.3e}s "
                  f"coll={ms['collective_s']:.3e}s "
                  f"bottleneck={ms['bottleneck']} "
                  f"peak_frac={ms['peak_fraction']:.2%} "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    except Exception as e:  # record failures — they are bugs to fix
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc())
        if verbose:
            print(f"[FAIL] {arch} × {shape_name} × {mesh_name}: "
                  f"{type(e).__name__}: {e}")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) cell")
    ap.add_argument("--skip-existing", action="store_true",
                    help="skip cells whose result JSON already says ok/skipped")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failed = 0
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "multi" if mp else "single"
            path = os.path.join(args.out,
                                f"{arch}__{shape}__{mesh_name}.json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("status") in ("ok", "skipped"):
                    print(f"[keep] {arch} × {shape} × {mesh_name}")
                    continue
            r = run_cell(arch, shape, mp, out_dir=args.out)
            failed += r["status"] == "error"
    if failed:
        raise SystemExit(f"{failed} cell(s) FAILED")


if __name__ == "__main__":
    main()

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run driver.

For every (architecture × input-shape) cell: build abstract args on the
production mesh, ``jax.jit(fn).lower(...).compile()``, record
memory_analysis / cost_analysis / collective schedule and the three-term
roofline (repro/roofline).  Results land in ``benchmarks/dryrun_results/
<mesh>/<arch>__<shape>.json`` — EXPERIMENTS.md §Dry-run / §Roofline are
generated from these files.

Usage:
  python -m repro.launch.dryrun --arch stablelm_12b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import json
import time
import traceback


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str) -> dict:
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh, mesh_devices
    from repro.launch.specs import build_cell, cell_is_skipped
    from repro.roofline.analysis import analyze_compiled

    mesh_name = "multi" if multi_pod else "single"
    skip = cell_is_skipped(arch, shape)
    result = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_name,
        "status": "skip" if skip else "?",
    }
    if skip:
        result["reason"] = skip
        return _save(result, out_dir)

    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_dev = mesh_devices(mesh)
        t0 = time.time()
        spec = build_cell(arch, shape, mesh)
        with mesh:
            # repro: allow[retrace-jit-per-call] -- AOT dry-run: one lower/compile per invocation is the product, the wrapper is never re-called
            lowered = jax.jit(spec.fn, donate_argnums=spec.donate).lower(*spec.args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            print(mem)
            print({k: v for k, v in list(compiled.cost_analysis().items())[:6]})
        rep = analyze_compiled(compiled, n_dev, spec.model_flops)
        result.update(
            status="ok",
            note=spec.note,
            lower_s=round(t1 - t0, 1),
            compile_s=round(t2 - t1, 1),
            devices=n_dev,
            ideal_bytes=spec.ideal_bytes,
            roofline=rep.to_json(),
        )
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        result.update(status="fail", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-4000:])
    return _save(result, out_dir)


def _save(result: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{result['arch']}__{result['shape']}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    gb = None
    if result.get("roofline"):
        gb = result["roofline"]["memory_stats"]["peak_hbm_est"] / 1e9
    print(
        f"[{result['mesh']}] {result['arch']}/{result['shape']}: {result['status']}"
        + (f" peakHBM={gb:.2f}GB bottleneck={result['roofline']['bottleneck']}" if gb else "")
        + (f" — {result.get('error', '')}" if result["status"] == "fail" else "")
    )
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/dryrun_results")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs.base import ARCH_IDS
    from repro.launch.specs import CELLS

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(CELLS) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_fail = 0
    for multi in meshes:
        out_dir = os.path.join(args.out, "multi" if multi else "single")
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, multi, out_dir)
                n_fail += r["status"] == "fail"
    print(f"dryrun done, failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

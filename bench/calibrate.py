"""Readings that the limits of ``correct`` are set from, many seeds in one
process: the program's compared numbers on ``--seeds``, the control's on
``--control-seeds``, and a planted fault's (``bench/lib/faults.py``) on
``--fault-seeds``.

    python bench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5,6 --fault half_batch --fault-seeds 7,8,9 \\
        [--out file.jsonl]

The control is one precision below the configuration's: the program's own
bfloat16 CD matmul path (``matmul_dtype="bfloat16"``).  Each reading is
judged by the harness's own comparison, as a run judges it.  The
benchmark's runs never run the control or a fault.  One JSON line per
reading goes to standard output and ``--out``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="", help="a fault of bench/lib/faults.py")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from lib import faults, harness

    cell = harness.resolve(harness.load_manifest(), args.workload)
    try:
        harness.device_check(cell.chips)
    except harness.NoChip as e:
        harness.log(f"FAIL: {e}")
        return 3
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from lib.peaks import peaks_for

    peaks = peaks_for(jax.devices()[0].device_kind)
    drv = harness.driver(cell.mix["kind"])
    out = open(args.out, "a") if args.out else None
    readings = [("program", s) for s in _seeds(args.seeds)]
    readings += [("control", s) for s in _seeds(args.control_seeds)]
    readings += [(f"fault:{args.fault}", s) for s in _seeds(args.fault_seeds)]
    first = True
    for role, seed in readings:
        t0 = time.perf_counter()
        kw = {"warmup": first}
        if role == "control":
            kw["matmul_dtype"] = "bfloat16"
        with faults.planted(args.fault if role.startswith("fault:") else None):
            run = drv.run(cell, seed=seed, seconds=args.seconds, trace=False, peaks=peaks,
                          t_start=t0, **kw)
        first = False
        rec = {"workload": cell.name, "role": role, "seed": seed,
               "correct": harness.is_correct(run.compared),
               "compared": {n: {"value": v, "limit": lim} for n, v, lim in run.compared},
               "end_to_end": run.end_to_end, "attempted": run.attempted,
               "failed": run.failed, "seconds": time.perf_counter() - t0}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

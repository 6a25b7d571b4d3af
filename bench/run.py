"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration file under
``bench/configs``, a traffic mix under ``bench/mixes`` and optional cell
parameters under ``bench/cells``; the mix's ``kind`` picks the driver in
``bench/lib``.  The driver makes its weights and inputs from ``--seed``, warms
every shape the window uses (set-up), measures for ``--seconds``, then checks
what the window produced against the plain reference.  ``--trace 1`` runs the
same window under the profiler and reports the per-layer metrics
(``bench/metrics/<name>.py``) instead of the end-to-end ones.

No TPU, or fewer chips than the cell asks for: exit code 3, no result.  The
last line of standard output is the result as one JSON object.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from lib import harness

    manifest = harness.load_manifest()
    cell = harness.resolve(manifest, args.workload)
    try:
        device = harness.device_check(cell.chips)
    except harness.NoChip as e:
        harness.log(f"FAIL: {e}")
        return 3

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)  # JAX never creates it
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    harness.log(f"compile cache: {enable_compile_cache()}")
    from lib.peaks import peaks_for

    peaks = peaks_for(device["kind"])
    drv = harness.driver(cell.mix["kind"])
    run = drv.run(cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                  peaks=peaks, t_start=T_START)
    device["memory_peak_bytes"] = run.memory_peak_bytes

    breakdown = None
    if args.trace:
        tr = run.trace
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
        metrics = {}
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": run.end_to_end[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    print(harness.result_line(harness.is_correct(run.compared), run.attempted, run.failed,
                              metrics, device, run.compared, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""PTQ launcher: quantize a trained checkpoint with any paper method.

Runs the streaming, sharded whole-model engine (core/solver.py): the
capture pass accumulates Σ = XXᵀ per linear batch-by-batch (never raw
activations), same-shape linears solve in batched vmapped calls, and with
``--shard`` both the Gram accumulation and the coordinate-descent solve
split across all local devices (single-device runs take the identical
local fallback automatically).

Flags beyond the model/method basics:

* ``--shard`` — build a 1-D ("data",) mesh over every local device;
  calibration batches data-shard with psum'd Σ accumulation and the CD
  solve shard_maps over output rows.  A no-op on one device.
* ``--stream-calib N`` — feed the capture pass at most N sequences at a
  time (0 = whole calibration batch at once).  Transient activation memory
  during capture becomes O(N·seq·p) regardless of ``--calib-batches``.
  The accumulated Σ is identical either way, per-expert Σ too: the
  capture routes every copy to its experts (dropless).
* ``--resume`` — report progress from a previous run's ``progress.jsonl``
  in the output dir before starting (block-level audit trail of what
  completed and the per-block error summary), then restart from scratch.
  The whole pipeline is deterministic for fixed flags — calibration batch
  ``i`` is a pure function of ``(seed, "calib", i)`` and the CD solve has
  no RNG — so a restart emits a **bit-identical** artifact to the
  uninterrupted run (tests/test_chaos.py pins this).
* ``--fault-plan`` — activate a seeded fault-injection plan
  (repro.faults) for chaos testing; transient faults in the calibration
  fetch are absorbed by a retry loop, a corrupted source checkpoint falls
  back to the last good step.

Resilience (DESIGN.md §Resilience): the source checkpoint loads through
``load_last_good`` (CRC-verified, damaged steps skipped with a warning),
and the calibration fetch runs under ``dist/elastic.RetryingRunner`` —
a transient storage fault restarts the (deterministic) fetch instead of
killing the run.

Progress: one line + one ``progress.jsonl`` record per quantized block
(stack, period, block index, linears solved, mean relative error,
``seconds``, ``phase_s``, ``compiles``, ``compile_s``, ``cache_loads``,
``ptq.forward_traces``).
``seconds`` is host time up to the dispatch of the block's recompute, not the
device's time for the block; ``phase_s`` holds the host seconds of each
phase span (``ptq.capture``, ``ptq.solve``, ``ptq.emit``, ``ptq.recompute``),
and ``compiles``/``compile_s`` the backend compiles the block waited on
(``cache_loads`` of them read from the persistent compilation cache);
``ptq.forward_traces`` counts the block forwards traced for the block.  A
block with routed experts adds ``moe.routed_copies``, ``moe.dropped_copies``
(0: the capture is dropless) and ``moe.expert_tokens_min``/``_max``, the
fewest and most routed copies an expert's Σ holds.

End-to-end on the reduced CPU configs (quickstart-sized, ~a minute):

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_12b \
        --reduce --steps 20 --ckpt-dir /tmp/repro_train
    PYTHONPATH=src python -m repro.launch.quantize --arch stablelm_12b \
        --reduce --ckpt-dir /tmp/repro_train --method quantease --bits 3 \
        --stream-calib 2 --shard
"""

import argparse
import json
import os
import sys

# Historical home of the torn-tail-tolerant progress parser; the shared
# implementation now lives in repro.launch.progress (tune.py and the resume
# paths import it from there) — re-exported so existing imports keep working.
from repro.launch.progress import append_record, load_progress  # noqa: F401


def main():
    ap = argparse.ArgumentParser(
        description="Whole-model PTQ with the streaming/sharded QuantEase engine."
    )
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true",
                    help="CPU-sized config (same reduction as launch/train.py)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--out-dir", default="/tmp/repro_quant")
    ap.add_argument("--method", default="quantease",
                    choices=["rtn", "gptq", "awq", "quantease", "awq_qe",
                             "spqr", "qe_outlier", "qe_outlier_struct"])
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--iterations", type=int, default=25)
    ap.add_argument("--outlier-frac", type=float, default=0.01)
    ap.add_argument("--group-size", type=int, default=0)
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-seed", type=int, default=0,
                    help="corpus seed — must match the TRAINING corpus "
                         "(launch/train.py TrainerConfig.seed, default 0)")
    ap.add_argument("--shard", action="store_true",
                    help="shard Σ accumulation + CD solve over all local devices")
    ap.add_argument("--stream-calib", type=int, default=0, metavar="N",
                    help="capture-pass chunk size in sequences (0 = whole batch)")
    ap.add_argument("--resume", action="store_true",
                    help="report a previous run's block progress before starting")
    ap.add_argument("--fault-plan", default="",
                    help="fault-injection plan: path to a JSON spec or an "
                         "inline JSON string (see repro.faults.FaultPlan)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.faults import FaultPlan, fault_plan

    plan_obj = FaultPlan.from_spec(args.fault_plan) if args.fault_plan else None
    if plan_obj is not None:
        print(f"fault plan active: seed={plan_obj.seed}, "
              f"{len(plan_obj.specs)} spec(s)")
    with fault_plan(plan_obj):
        _run(args)


def _run(args):
    import jax.numpy as jnp

    from repro import obs
    from repro.configs import get_config
    from repro.core.solver import PTQConfig, ptq_quantize_model
    from repro.data.pipeline import DataConfig, make_batch_fn
    from repro.dist import checkpoint as ckpt
    from repro.dist.elastic import RetryingRunner
    from repro.launch.mesh import make_data_mesh
    from repro.launch.train import reduced
    from repro.models import make_plan, param_shapes
    from repro.quant import GridSpec
    from repro.train.optimizer import AdamWConfig, adamw_init

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    plan = make_plan(cfg, 1)

    import jax

    progress_path = os.path.join(args.out_dir, "progress.jsonl")
    if args.resume:
        # Tolerant parse: a run killed mid-write leaves an empty file or a
        # torn last line — resume from the last *complete* record.
        lines = load_progress(progress_path)
        if lines:
            last = lines[-1]
            print(
                f"previous run: {last['done_blocks']}/{last['total_blocks']} blocks "
                f"({last['stack']}.p{last['period']}.b{last['block']}), "
                f"mean_err={last['mean_rel_error']:.4g} — restarting from scratch"
            )
        else:
            print("previous run: no complete progress records — cold start")
    # Each run owns its progress file: truncate so records never interleave
    # across runs (with or without --resume).
    if os.path.exists(progress_path):
        os.remove(progress_path)

    # Shapes, not arrays, as the template: the loader only reads shapes and
    # dtypes, and a zero-filled copy of a full-width model would not fit
    # beside the loaded one on the device.
    shapes = param_shapes(plan)
    try:  # quantized/eval checkpoints hold params only …
        state, manifest, skipped = ckpt.load_last_good(
            args.ckpt_dir, {"params": shapes}
        )
    except ckpt.CheckpointCorrupt:  # … train checkpoints also carry AdamW moments
        opt = jax.eval_shape(lambda p: adamw_init(p, AdamWConfig()), shapes)
        state, manifest, skipped = ckpt.load_last_good(
            args.ckpt_dir, {"params": shapes, "opt": opt}
        )
    for step, reason in skipped:
        print(f"WARNING: skipped damaged checkpoint step_{step}: "
              f"{reason.splitlines()[0]}", file=sys.stderr)
    params = state["params"]
    print(f"loaded checkpoint step {manifest['step']}")

    mesh = make_data_mesh() if args.shard else None
    if args.shard:
        n = len(jax.devices())
        print(f"--shard: {n} device(s)" + (" — single-device fallback" if mesh is None else ""))

    # Dedicated calib split: disjoint from the train stream (and from the
    # eval split launch/eval.py scores on) by construction — see
    # data/pipeline.py.  The corpus seed must match the trainer's
    # (TrainerConfig.seed): DataConfig.seed fixes the Markov chain itself,
    # and the old default (1234) calibrated against a *different chain*
    # than the checkpoint was trained on.
    batch_fn, _ = make_batch_fn(
        DataConfig(vocab=cfg.vocab, seed=args.data_seed), cfg,
        batch=4, seq=args.seq, split="calib",
    )
    # Retried fetch: batch i is a pure function of (seed, "calib", i), so
    # restarting from an empty list after a transient storage fault
    # reproduces the exact same calibration set.
    fetcher = RetryingRunner(
        lambda acc, i: acc + [{k: jnp.asarray(v) for k, v in batch_fn(i).items()}],
        lambda: ([], 0),
        max_retries=5,
    )
    calib, _ = fetcher.run([], 0, args.calib_batches)
    if fetcher.recoveries:
        print(f"calibration fetch recovered from {fetcher.recoveries} "
              "transient fault(s)")
    pcfg = PTQConfig(
        method=args.method,
        spec=GridSpec(bits=args.bits, group_size=args.group_size or None),
        iterations=args.iterations,
        outlier_frac=args.outlier_frac,
        stream_chunk=args.stream_calib,
        shard=args.shard,
    )

    os.makedirs(args.out_dir, exist_ok=True)

    def progress(rec: dict):
        phases = " ".join(f"{k.removeprefix('ptq.')} {v:.3f}s"
                          for k, v in rec["phase_s"].items())
        print(
            f"[{rec['stack']} p{rec['period']} b{rec['block']} "
            f"{rec['done_blocks']}/{rec['total_blocks']}] "
            f"{rec['n_linears']} linears  mean_err={rec['mean_rel_error']:.4g}  "
            f"host to recompute dispatch {rec['seconds']}s  ({phases}; "
            f"{rec['compiles']} compiles {rec['compile_s']:.3f}s, "
            f"{rec['cache_loads']} from the cache; "
            f"{rec['ptq.forward_traces']} block forwards traced)"
            + (f"  experts: {rec['moe.routed_copies']} routed copies, "
               f"{rec['moe.dropped_copies']} dropped, {rec['moe.expert_tokens_min']}.."
               f"{rec['moe.expert_tokens_max']} an expert" if "moe.routed_copies" in rec else "")
        )
        append_record(progress_path, rec)

    with obs.record():
        qparams, report = ptq_quantize_model(
            plan, params, calib, pcfg, mesh=mesh, progress_cb=progress
        )
    ckpt.save_checkpoint(
        args.out_dir, manifest["step"],
        {"params": qparams},
        meta={"method": args.method, "bits": args.bits,
              "report": {k: float(v) for k, v in report.items()}},
    )
    import numpy as np

    errs = np.array(list(report.values()))
    print(json.dumps({
        "layers": len(report),
        "mean_rel_error": float(errs.mean()),
        "max_rel_error": float(errs.max()),
        "out_dir": args.out_dir,
    }, indent=1))


if __name__ == "__main__":
    main()

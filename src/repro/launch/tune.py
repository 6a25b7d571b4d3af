"""Auto-tuning launcher: budgeted mixed-precision search over a checkpoint.

Drives :func:`repro.tune.search.tune_model` end to end: probe per-layer
sensitivity (error tables at every candidate width + λ_max(Σ)), build the
greedy budgeted allocations (tune/allocate.py) alongside the uniform
baseline at equal average bits, re-quantize every candidate through the
whole-model PTQ driver with per-layer ``layer_specs`` overrides, and score
each on the **eval** split via the eval-harness scorer on the restacked
serving artifact.  The winner (lowest perplexity; never worse than uniform
since uniform is always candidate 0) is re-quantized once more and saved as
a checkpoint next to its allocation JSON.

Resume contract (mirrors launch/quantize.py's progress.jsonl machinery):

* every finished candidate appends one ``{"candidate": ...}`` record to
  ``<out-dir>/progress.jsonl`` (probe passes also log, as ``{"probe": ...}``
  records, for the audit trail);
* ``--resume`` replays the completed candidate records as prior results and
  evaluation continues with the next unfinished candidate — probing reruns
  (probes are cheap RTN passes; only candidate evaluation is the expensive,
  resumable unit).  Torn tails are tolerated via ``load_progress``.
* in-process crash recovery wraps the candidate loop in
  ``dist/elastic.RetryingRunner``: a failed candidate evaluation rolls back
  to the persisted results and retries (nothing partial is ever persisted,
  so restore == the progress file's view).

End-to-end on the reduced CPU configs:

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_12b \
        --reduce --steps 20 --ckpt-dir /tmp/repro_train
    PYTHONPATH=src python -m repro.launch.tune --arch stablelm_12b \
        --reduce --ckpt-dir /tmp/repro_train --budget-avg-bits 3 \
        --bits-candidates 2,3,4 --iterations 4
"""

import argparse
import json
import os
import sys


def main():
    ap = argparse.ArgumentParser(
        description="Accuracy-driven per-layer mixed-precision auto-tuning."
    )
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true",
                    help="CPU-sized config (same reduction as launch/train.py)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--out-dir", default="/tmp/repro_tune")
    ap.add_argument("--budget-avg-bits", type=float, default=3.0,
                    help="global storage budget in average bits per weight "
                         "(COO outliers priced at 48 bits each)")
    ap.add_argument("--bits-candidates", default="2,3,4,8",
                    help="comma-separated ascending per-layer widths")
    ap.add_argument("--outlier-fracs", default="",
                    help="comma-separated COO outlier fractions offered as "
                         "allocator upgrades (empty = bits-only tuning)")
    ap.add_argument("--policies", default="sensitivity,error",
                    help="allocation policies to race (greedy candidates)")
    ap.add_argument("--method", default="quantease",
                    help="final-quantize CD method for candidates")
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--awq-prepass", action="store_true",
                    help="auto-alpha AWQ rescale before CD (awq_then_quantease)")
    ap.add_argument("--group-size", type=int, default=0)
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ppl-batches", type=int, default=2,
                    help="eval-split batches per candidate (the objective)")
    ap.add_argument("--data-seed", type=int, default=0,
                    help="corpus seed — must match the TRAINING corpus")
    ap.add_argument("--resume", action="store_true",
                    help="continue from <out-dir>/progress.jsonl candidate records")
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
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_batch_fn
    from repro.dist import checkpoint as ckpt
    from repro.dist.elastic import RetryingRunner
    from repro.launch.progress import append_record, load_progress
    from repro.launch.train import reduced
    from repro.models import make_plan, param_shapes
    from repro.train.optimizer import AdamWConfig, adamw_init
    from repro.tune import TuneConfig, quantize_candidate, tune_model
    from repro.tune.search import build_candidates

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    plan = make_plan(cfg, 1)

    tcfg = TuneConfig(
        budget_avg_bits=args.budget_avg_bits,
        bits_candidates=tuple(int(b) for b in args.bits_candidates.split(",")),
        outlier_frac_candidates=tuple(
            float(f) for f in args.outlier_fracs.split(",") if f
        ),
        policies=tuple(p for p in args.policies.split(",") if p),
        method=args.method,
        iterations=args.iterations,
        awq_prepass=args.awq_prepass,
        group_size=args.group_size or None,
        n_ppl_batches=args.ppl_batches,
    )

    os.makedirs(args.out_dir, exist_ok=True)
    progress_path = os.path.join(args.out_dir, "progress.jsonl")
    prior_results = []
    if args.resume:
        prior = [r for r in load_progress(progress_path) if "candidate" in r]
        prior_results = [r["candidate"] for r in prior]
        print(f"resume: {len(prior_results)} candidate(s) already evaluated")
    elif os.path.exists(progress_path):
        os.remove(progress_path)

    def log_record(rec: dict):
        append_record(progress_path, rec)

    like_params = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), param_shapes(plan)
    )
    like = {"params": like_params, "opt": adamw_init(like_params, AdamWConfig())}
    state, manifest, skipped = ckpt.load_last_good(args.ckpt_dir, like)
    for step, reason in skipped:
        print(f"WARNING: skipped damaged checkpoint step_{step}: "
              f"{reason.splitlines()[0]}", file=sys.stderr)
    params = state["params"]
    print(f"loaded checkpoint step {manifest['step']}")

    dcfg = DataConfig(vocab=cfg.vocab, seed=args.data_seed)
    calib_fn, _ = make_batch_fn(dcfg, cfg, batch=4, seq=args.seq, split="calib")
    eval_fn, _ = make_batch_fn(dcfg, cfg, batch=4, seq=args.seq, split="eval")
    # Retried fetch: calib batch i is pure in (seed, "calib", i) — a
    # transient storage fault restarts the fetch and reproduces the exact
    # same calibration set.
    fetcher = RetryingRunner(
        lambda acc, i: acc + [{k: jnp.asarray(v) for k, v in calib_fn(i).items()}],
        lambda: ([], 0),
        max_retries=5,
    )
    calib, _ = fetcher.run([], 0, args.calib_batches)
    if fetcher.recoveries:
        print(f"calibration fetch recovered from {fetcher.recoveries} "
              "transient fault(s)")

    def progress(rec: dict):
        if "probe" in rec:
            print(f"[probe] {rec['probe']}: {rec['layers']} layers")
            log_record(rec)
        else:
            print(f"[candidate] {rec['candidate']}: ppl={rec['ppl']:.4f}")

    doc = tune_model(
        plan, params, calib, eval_fn, tcfg,
        prior_results=prior_results,
        result_cb=lambda res: log_record({"candidate": res}),
        runner_factory=lambda step, restore: RetryingRunner(step, restore),
        progress_cb=progress,
    )

    # Re-quantize the winner for the saved artifact.  Candidates are
    # deterministic for fixed (stats, tcfg), so rebuilding by label is exact.
    win_label = doc["best"]["label"]
    # Stats were consumed inside tune_model; rebuild candidate descriptors
    # from the winning result instead of re-probing: uniform rebuilds from
    # its bits, mixed re-runs the (deterministic) probe + allocation.
    if doc["best"]["kind"] == "uniform":
        cand = {"label": win_label, "kind": "uniform", "bits": tcfg.uniform_bits()}
    else:
        from repro.tune import probe_layer_stats

        stats = probe_layer_stats(
            plan, params, calib,
            bits_candidates=tcfg.bits_candidates,
            outlier_cells=tuple(
                (tcfg.bits_candidates[0], f) for f in tcfg.outlier_frac_candidates
            ),
            outlier_iterations=tcfg.probe_outlier_iterations,
        )
        cand = next(
            c for c in build_candidates(stats, tcfg) if c["label"] == win_label
        )
    qp, report = quantize_candidate(plan, params, calib, cand, tcfg)
    ckpt.save_checkpoint(
        args.out_dir, manifest["step"],
        {"params": qp},
        meta={"tuned": True, "label": win_label,
              "avg_bits": doc["best"]["avg_bits"],
              "report": {k: float(v) for k, v in report.items()}},
    )
    alloc_doc = dict(doc)
    if cand["kind"] == "mixed":
        alloc = cand["allocation"]
        alloc_doc["winner_allocation"] = {
            "bits": alloc.bits,
            "outlier_frac": alloc.outlier_frac,
            "trace": alloc.trace,
        }
    with open(os.path.join(args.out_dir, "tune.json"), "w") as f:
        json.dump(alloc_doc, f, indent=1)
    print(json.dumps({
        "best": doc["best"],
        "uniform_ppl": doc["uniform"]["ppl"],
        "out_dir": args.out_dir,
    }, indent=1))


if __name__ == "__main__":
    main()

"""Eval launcher: score a (quantized) model end to end — the paper's tables.

Loads a trained checkpoint, sweeps a method × bits (× outlier budget) grid
through the whole-model PTQ driver (each cell quantizes in-process and is
scored as the restacked QuantizedTensor serving artifact), and measures on
the ``split="eval"`` stream — disjoint from the ``split="calib"`` stream by
construction (data/pipeline.py):

  * perplexity / NLL (Tables 1-3, 5 shape),
  * cloze next-token top-1/top-5 and multi-choice continuation accuracy
    (the zero-shot task family, §5.3 shape),
  * scorer-vs-serving-engine logit parity (the numbers describe what the
    engines actually execute; see repro/eval/harness.py for the tolerance).

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_12b \
        --reduce --steps 100 --ckpt-dir /tmp/repro_train
    PYTHONPATH=src python -m repro.launch.eval --arch stablelm_12b \
        --reduce --ckpt-dir /tmp/repro_train --bits 4 3 \
        --methods rtn gptq quantease --outlier-bits 3 --out /tmp/eval.json

``--smoke`` shrinks the grid and budgets to seconds (schema unchanged —
the CI smoke path; the committed ``BENCH_eval.json`` comes from
``benchmarks/bench_eval.py``, which drives the same harness on the shared
trained benchmark model).
"""

import argparse
import json


def main():
    ap = argparse.ArgumentParser(
        description="End-to-end quantized-model evaluation (ppl + tasks + parity)."
    )
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true",
                    help="CPU-sized config (same reduction as launch/train.py)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--out", default="/tmp/repro_eval/eval.json")
    ap.add_argument("--methods", nargs="+", default=["rtn", "gptq", "quantease"])
    ap.add_argument("--bits", type=int, nargs="+", default=[4, 3])
    ap.add_argument("--outlier-bits", type=int, default=0, metavar="B",
                    help="add a qe_outlier cell at B bits (0 = off)")
    ap.add_argument("--outlier-frac", type=float, default=0.01)
    ap.add_argument("--group-size", type=int, default=0)
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--calib-batches", type=int, default=4)
    ap.add_argument("--eval-batches", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-seed", type=int, default=0,
                    help="corpus seed — must match the TRAINING corpus "
                         "(launch/train.py TrainerConfig.seed, default 0): "
                         "it fixes the Markov chain itself, not just the stream")
    ap.add_argument("--emit", choices=["qt", "fake"], default="qt",
                    help="score the QuantizedTensor serving artifact (qt) or "
                         "the dequantized bf16 tree (fake)")
    ap.add_argument("--no-parity", action="store_true",
                    help="skip the serving-engine logit parity check")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale budgets, 2-cell grid (schema unchanged)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, make_batch_fn
    from repro.dist import checkpoint as ckpt
    from repro.eval import EVAL_SCHEMA, quantized_parity, run_grid, validate_doc
    from repro.eval.harness import EvalBudget
    from repro.launch.train import reduced
    from repro.models import init_params, make_plan, param_shapes

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    plan = make_plan(cfg, 1)
    like_params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                               param_shapes(plan))
    try:
        try:  # quantized/eval checkpoints hold params only …
            state, manifest = ckpt.load_checkpoint(
                args.ckpt_dir, {"params": like_params}
            )
        except ValueError:  # … train checkpoints also carry optimizer state
            from repro.train.optimizer import AdamWConfig, adamw_init

            state, manifest = ckpt.load_checkpoint(
                args.ckpt_dir,
                {"params": like_params,
                 "opt": adamw_init(like_params, AdamWConfig())},
            )
        params = state["params"]
        print(f"loaded checkpoint step {manifest['step']}")
    except FileNotFoundError:
        print("no checkpoint found — evaluating random init (smoke/demo only)")
        params = init_params(plan, jax.random.PRNGKey(0))

    dc = DataConfig(vocab=cfg.vocab, seed=args.data_seed)
    calib_fn, _ = make_batch_fn(dc, cfg, batch=4, seq=args.seq, split="calib")
    eval_fn, corpus = make_batch_fn(dc, cfg, batch=4, seq=args.seq, split="eval")
    n_calib = 1 if args.smoke else args.calib_batches
    calib = [
        {k: jnp.asarray(v) for k, v in calib_fn(i).items()} for i in range(n_calib)
    ]

    if args.smoke:
        cells = [
            {"method": "rtn", "bits": 4},
            {"method": "quantease", "bits": 3, "iterations": 2},
        ]
        budget = EvalBudget.smoke()
    else:
        cells = [
            {"method": m, "bits": b, "group_size": args.group_size or None}
            for b in args.bits for m in args.methods
        ]
        if args.outlier_bits:
            cells.append({
                "method": "qe_outlier", "bits": args.outlier_bits,
                "outlier_frac": args.outlier_frac,
            })
        budget = EvalBudget(n_ppl_batches=args.eval_batches)

    def progress(rec):
        print(f"[{rec['cell']}] ppl={rec.get('ppl', 0):.4f} "
              f"top1={rec.get('top1', 0):.3f} choice={rec.get('choice_acc', 0):.3f}")

    iterations = 2 if args.smoke else args.iterations
    doc = {
        "schema": EVAL_SCHEMA,
        "smoke": bool(args.smoke),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "arch": args.arch,
        "data": {
            "vocab": cfg.vocab, "seq": args.seq,
            "eval_split": "eval", "calib_split": "calib",
            "entropy_floor_ppl": round(float(np.exp(corpus.entropy_floor())), 4),
        },
        "iterations": iterations,
        "emit": args.emit,
    }
    doc.update(run_grid(
        plan, params, calib, eval_fn, cells,
        iterations=iterations, emit=args.emit, budget=budget,
        progress_cb=progress,
    ))
    if args.no_parity:
        doc["parity"] = None
    else:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
                   for n in (5, 13, 29)]
        doc["parity"] = quantized_parity(
            plan, params, calib, prompts,
            iterations=2 if args.smoke else 6,
        )
        print(f"parity: {doc['parity']}")

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")
    # Validation runs regardless of --no-parity: a full doc without parity
    # (or with broken orderings) should warn here exactly as
    # bench_eval.py --validate would fail on it later.
    if not doc["smoke"]:
        for p in validate_doc(doc):
            print(f"WARNING: {p}")


if __name__ == "__main__":
    main()

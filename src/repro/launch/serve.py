"""Serving launcher: load a (quantized) checkpoint and serve batched requests.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm_12b --reduce \
        --ckpt-dir /tmp/repro_quant --requests 8 --engine paged

``--engine paged`` (default for self-attention decoder archs) serves from
the paged-KV engine — shared page pool, chunked prefill, prefix caching,
SLO-aware scheduling; ``--engine contiguous`` keeps the per-slot max_seq
reservation baseline (and is the only choice for enc-dec / SSM-hybrid
archs — the fallback warns loudly, and ``--strict-engine`` turns it into a
hard error for deployments that must not silently lose paging).

SLO knobs (paged engine): ``--deadline-ms`` attaches a per-request
deadline, ``--priority`` a scheduling priority; requests finish with a
terminal status (completed / preempted_resumed / shed / deadline_missed).
``--fault-plan`` activates seeded fault injection (repro.faults) for chaos
drills.

Speculative decoding (paged engine, DESIGN.md §Speculative-serving):
``--speculate`` turns on self-speculative greedy decode — a draft stack
proposes ``--gamma`` tokens per round into draft-owned pages of the same
pool and one fused target forward verifies; output is token-identical to
non-speculative greedy.  The draft comes from ``--draft-layers K`` (the
first K periods of the served artifact — zero extra weight memory),
``--draft-bits B`` (on-the-fly RTN of the loaded dense checkpoint via
serve/qparams.rtn_quantize_for_serving), ``--draft-checkpoint DIR`` (a
separately trained/quantized stack), or combinations (bits/checkpoint
then truncated by ``--draft-layers``).  With no source given,
``--speculate`` defaults to truncating the served stack at half depth.
"""

import argparse
import sys


def _positive_int(name):
    """argparse type: strictly positive integer with a pointed error."""
    def parse(s):
        try:
            v = int(s)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"{name} expects a positive integer, got {s!r}"
            )
        if v <= 0:
            raise argparse.ArgumentTypeError(
                f"{name} must be >= 1, got {v} — 0 or negative would serve "
                "nothing (use a positive count)"
            )
        return v
    return parse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--quantized", action="store_true",
                    help="checkpoint holds fake-quant/dense params either way;"
                         " flag is informational")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=_positive_int("--max-new"), default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--engine", choices=["paged", "contiguous"], default="paged")
    ap.add_argument("--strict-engine", action="store_true",
                    help="hard-error instead of falling back to the "
                         "contiguous engine when --engine paged is "
                         "unavailable for the arch")
    ap.add_argument("--page-size", type=_positive_int("--page-size"), default=16)
    ap.add_argument("--n-pages", type=int, default=0,
                    help="KV pool size in pages (0 = ample: no preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--kv-dtype", choices=["bf16", "int8", "int4"], default="bf16",
                    help="KV cache storage; int4 packs two codes/byte and is "
                         "paged-engine only")
    ap.add_argument("--scheduler", choices=["slo", "fifo"], default="slo",
                    help="paged-engine scheduling policy (fifo = legacy "
                         "arrival order + preempt-newest)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request SLO deadline in ms (0 = none); "
                         "unmeetable requests are shed, overdue ones "
                         "finish as deadline_missed")
    ap.add_argument("--priority", type=int, default=0,
                    help="request priority (higher = more urgent; low-"
                         "priority work parks under pool pressure)")
    ap.add_argument("--fault-plan", default="",
                    help="fault-injection plan: path to a JSON spec or an "
                         "inline JSON string (see repro.faults.FaultPlan)")
    ap.add_argument("--speculate", action="store_true",
                    help="self-speculative greedy decode (paged engine only; "
                         "token-identical output)")
    ap.add_argument("--gamma", type=_positive_int("--gamma"), default=4,
                    help="draft tokens proposed per speculative round")
    ap.add_argument("--draft-layers", type=_positive_int("--draft-layers"),
                    default=None,
                    help="truncated self-draft: first K periods of the "
                         "served stack (zero extra weight memory)")
    ap.add_argument("--draft-bits", type=_positive_int("--draft-bits"),
                    default=None,
                    help="RTN-quantize the loaded dense checkpoint to this "
                         "many bits as the draft stack")
    ap.add_argument("--draft-checkpoint", default="",
                    help="serve the draft from a separate checkpoint dir "
                         "(same arch)")
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
    import numpy as np

    from repro.configs import get_config
    from repro.dist import checkpoint as ckpt
    from repro.launch.train import reduced
    from repro.models import make_plan, param_shapes
    from repro.serve.engine import PagedServingEngine, Request, ServingEngine

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    plan = make_plan(cfg, 1, kv_cache_dtype=args.kv_dtype)
    like_params = param_shapes(plan)  # shapes only: no full-width zero tree

    def load_params(ckpt_dir):
        try:  # quantized/eval checkpoints hold params only …
            state, manifest = ckpt.load_checkpoint(
                ckpt_dir, {"params": like_params}
            )
        except ValueError:  # … train checkpoints also carry optimizer state
            from repro.train.optimizer import AdamWConfig, adamw_init

            state, manifest = ckpt.load_checkpoint(
                ckpt_dir,
                {"params": like_params,
                 "opt": jax.eval_shape(
                     lambda p: adamw_init(p, AdamWConfig()), like_params
                 )},
            )
        return state["params"], manifest

    try:
        params, manifest = load_params(args.ckpt_dir)
        print(f"loaded step {manifest['step']}")
    except FileNotFoundError:
        from repro.models import init_params

        print("no checkpoint found — serving random init (demo)")
        params = init_params(plan, jax.random.PRNGKey(0))

    # Roofline-selected weight layout (serve/qparams.py): packed-4-bit
    # QuantizedTensor leaves may re-permute into the GEMM kernel's
    # tile-native order.  Dense/bf16 checkpoints pass through untouched.
    from repro.serve.qparams import prepack_params_for_serving

    params, layout_decisions = prepack_params_for_serving(plan, params)
    if layout_decisions:
        labels = sorted(set(layout_decisions.values()))
        print(f"weight pack layout ({jax.default_backend()}): "
              + ", ".join(f"{lb} ×{sum(1 for v in layout_decisions.values() if v == lb)}"
                          for lb in labels))
    else:
        print("weight pack layout: linear (no packed 4-bit weight leaves)")

    if args.kv_dtype == "int4" and args.engine != "paged":
        raise SystemExit(
            "--kv-dtype int4 requires --engine paged: int4 KV lives in packed "
            "pages (quant/pack.kv_pack_int4); the contiguous engine supports "
            "bf16/int8 only"
        )
    rng = np.random.default_rng(0)
    if args.engine == "paged":
        try:  # probe arch support only — config errors must still surface
            from repro.models import paged_cache_shapes

            paged_cache_shapes(plan, 2, args.page_size)
        except ValueError as e:  # enc-dec / SSM-hybrid / prefix archs
            if args.kv_dtype == "int4":
                # No silent downgrade: the contiguous fallback cannot hold
                # int4 pages, so the request is unsatisfiable as stated.
                raise SystemExit(
                    f"--kv-dtype int4 unavailable for {args.arch}: {e}"
                )
            if args.strict_engine:
                raise SystemExit(
                    f"--strict-engine: paged engine unavailable for arch "
                    f"{args.arch!r} ({e}) and fallback is disabled"
                )
            print(
                f"WARNING: paged engine unavailable for arch {args.arch!r} "
                f"({e}) — FALLING BACK to the contiguous engine: no paged "
                "KV pool, no prefix cache, no SLO preemption; per-slot "
                "max_seq KV is reserved up front (pass --strict-engine to "
                "make this a hard error)",
                file=sys.stderr,
            )
            args.engine = "contiguous"
    if args.speculate and args.engine != "paged":
        # No silent downgrade: draft pages live in the paged pool, so
        # speculation cannot run on the contiguous engine.
        raise SystemExit(
            "--speculate requires the paged engine (draft tokens decode "
            "into draft-owned pages of the shared pool); it is unavailable "
            f"with --engine {args.engine} for arch {args.arch!r}"
        )
    spec = None
    if args.speculate:
        from repro.serve.qparams import rtn_quantize_for_serving
        from repro.serve.spec import SpecConfig, truncate_draft

        draft_plan, draft_params = plan, params
        if args.draft_checkpoint:
            draft_params, d_manifest = load_params(args.draft_checkpoint)
            print(f"draft checkpoint: step {d_manifest['step']}")
        if args.draft_bits:
            draft_params, d_layout = rtn_quantize_for_serving(
                plan, draft_params, bits=args.draft_bits
            )
            print(f"draft: {args.draft_bits}-bit RTN [{d_layout}]")
        k = args.draft_layers
        if k is None and not args.draft_bits and not args.draft_checkpoint:
            k = max(1, cfg.n_periods // 2)
            print(f"--speculate with no draft source: truncated self-draft "
                  f"at {k}/{cfg.n_periods} periods")
        if k is not None:
            if k >= cfg.n_periods:
                raise SystemExit(
                    f"--draft-layers {k} must be < the target's "
                    f"{cfg.n_periods} periods — a full-depth draft is the "
                    "target itself and speculation would only add overhead"
                )
            draft_plan, draft_params = truncate_draft(
                draft_plan, draft_params, k
            )
        spec = SpecConfig(draft_plan=draft_plan, draft_params=draft_params,
                          gamma=args.gamma)
    if args.engine == "paged":
        eng = PagedServingEngine(
            plan, params, max_batch=args.max_batch, max_seq=512,
            page_size=args.page_size, n_pages=args.n_pages or None,
            prefill_chunk=args.prefill_chunk, scheduler=args.scheduler,
            spec=spec,
        )
    else:
        eng = ServingEngine(plan, params, max_batch=args.max_batch, max_seq=512)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab, rng.integers(4, 32)).astype(np.int32)
        eng.submit(Request(
            rid=i, prompt=prompt, max_new_tokens=args.max_new,
            deadline_ms=args.deadline_ms or None, priority=args.priority,
        ))
    finished = eng.run()
    for r in sorted(finished, key=lambda r: r.rid):
        print(f"req{r.rid} [{r.status}]: prompt[{len(r.prompt)}] -> {r.output}")
    if args.engine == "paged":
        print(f"{len(finished)} requests, {eng.n_decode_steps} decode steps, "
              f"{eng.n_prefill_chunks} prefill chunks "
              f"({eng.n_prefix_hit_tokens} prefix-cached tokens, "
              f"{eng.n_preemptions} preemptions, {eng.n_shed} shed, "
              f"{eng.n_deadline_missed} deadline-missed)")
        if args.speculate:
            acc = eng.acceptance_rate()
            print(f"speculative: {eng.n_spec_rounds} rounds, "
                  f"{eng.n_draft_accepted}/{eng.n_draft_tokens} draft tokens "
                  f"accepted (rate "
                  f"{'-' if acc is None else format(acc, '.3f')}, γ="
                  f"{args.gamma})")
    else:
        print(f"{len(finished)} requests, {eng.n_decode_steps} decode steps, "
              f"{eng.n_prefills} prefills")


if __name__ == "__main__":
    main()

"""Training launcher.

CPU / small runs:
    PYTHONPATH=src python -m repro.launch.train --arch stablelm_12b \
        --reduce --steps 100

Cluster runs (the dry-run proves the lowering; on hardware the same entry
point executes): drop ``--reduce``, set ``--mesh single|multi`` — jax
devices must match (real TPU slices; here only the dry-run exercises it).
"""

import argparse
import dataclasses


def reduced(cfg):
    kw = dict(
        **cfg.reduced_stack(),
        d_model=128,
        n_heads=4 if cfg.n_heads else 0,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads else 0,
        head_dim=32,
        d_ff=0 if cfg.d_ff == 0 else 256,
        vocab=256,
        max_seq=1024,
        n_experts=4 if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2),
        moe_d_ff=256 if cfg.n_experts else 0,
        ssm_state=16,
        ssm_headdim=16,
        n_enc_periods=2 if cfg.n_enc_periods else 0,
        n_frames=64 if cfg.family == "encdec" else 1500,
        n_prefix=16 if cfg.n_prefix else 0,
    )
    return dataclasses.replace(cfg, **kw)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduce", action="store_true", help="CPU-sized config")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--moments", default="fp32", choices=["fp32", "int8"])
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    trainer = Trainer(
        cfg,
        AdamWConfig(lr=args.lr, total_steps=args.steps, moments=args.moments),
        TrainerConfig(
            steps=args.steps,
            batch=args.batch,
            seq=args.seq,
            ckpt_dir=args.ckpt_dir,
            ckpt_every=max(args.steps // 4, 1),
            n_microbatches=args.microbatches,
        ),
    )
    out = trainer.run()
    loss = out["final_loss"]  # None when steps < the metrics-log interval
    print(f"final loss: {'n/a' if loss is None else f'{loss:.4f}'}  "
          f"recoveries: {out['recoveries']}")
    for m in out["log"]:
        print(m)


if __name__ == "__main__":
    main()

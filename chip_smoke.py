"""Chip smoke: calibrate → QuantEase-solve → pack → paged serve of Phi-3-mini
at its published widths on one TPU, in one process.

    python chip_smoke.py                # one chip: the whole main path
    python chip_smoke.py --four-chips   # four chips: sharded solve vs 1-device

Weights are random (``init_params`` from ``--seed``) and calibration batches
come from the synthetic corpus, so no download is needed.  Phases, in order:

1. device check — no TPU, no result: the script exits non-zero;
2. quantize — ``ptq_quantize_model`` (4-bit QuantEase, ``emit="qt"``) over
   all 32 blocks; prints the relative errors and the engine of each linear
   family;
3. kernel vs oracle — each main-path kernel once at the smoke's shapes
   against ``kernels/ref.py`` (or the XLA schedule), beside a stated bound;
4. serve — free the dense tree, prepack, and run ``PagedServingEngine`` over
   8 prompts of 16–512 tokens, twice (cold, then warm);
5. parity — scorer next-token logits vs the engine's first decode logits on
   the quantized artifact (bound 0.05, README "Evaluation");
6. timings of each phase, first call (compile) and steady.

Any failed check exits non-zero.  The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

# CD iterations per layer, cut from the paper's 25 to keep a cold run short
# (the shapes and kernels do not change with the count).
ITERATIONS = 8
CALIB_BATCHES, CALIB_SEQ = 4, 256  # calibration: 4 batches of 4 x 256 tokens
MAX_NEW = 16  # new tokens per served request

PHASE_TIMES: dict = {}


def fail(msg: str):
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(tag: str, msg: str):
    print(f"[{tag}] {msg}", flush=True)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


def device_check(n_chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log("device", f"platform={d.platform} kind={d.device_kind!r} count={len(devs)}")
    if d.platform != "tpu":
        fail(f"no TPU: JAX found {d.platform!r} devices only")
    if len(devs) < n_chips:
        fail(f"need {n_chips} TPU chip(s), found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def timed(fn, *args, **kw):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def calib_batches(cfg, seed: int, n: int, seq: int):
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig, make_batch_fn

    batch_fn, _ = make_batch_fn(
        DataConfig(vocab=cfg.vocab, seed=seed), cfg, batch=4, seq=seq,
        split="calib",
    )
    return [{k: jnp.asarray(v) for k, v in batch_fn(i).items()} for i in range(n)]


# ---------------------------------------------------------------------------
# Phase 2: quantize
# ---------------------------------------------------------------------------

# Linear families of a dense attention block: (q, p) of the solver's (out, in)
# view, from the config's widths.
def linear_families(cfg) -> dict:
    hd_all = cfg.n_heads * cfg.hd
    kv_all = cfg.n_kv_heads * cfg.hd
    return {
        "wq": (hd_all, cfg.d_model), "wk": (kv_all, cfg.d_model),
        "wv": (kv_all, cfg.d_model), "wo": (cfg.d_model, hd_all),
        "wg": (cfg.d_ff, cfg.d_model), "wu": (cfg.d_ff, cfg.d_model),
        "wd": (cfg.d_model, cfg.d_ff),
    }


def quantize_phase(plan, params, calib, iterations: int):
    import numpy as np

    from repro.core.quantease import QuantEaseConfig, fused_engine
    from repro.core.solver import PTQConfig, ptq_quantize_model
    from repro.quant import GridSpec
    from repro.serve.qparams import quantize_params_for_serving

    cfg = plan.cfg
    pcfg = PTQConfig(
        method="quantease", spec=GridSpec(bits=4), iterations=iterations,
        emit="qt",
    )
    qe = pcfg.qe_config()
    for name, (q, p) in linear_families(cfg).items():
        engine, why = fused_engine(
            p, QuantEaseConfig.block_size, qe.matmul_dtype, qe.use_kernel
        )
        log("quantize", f"{name} ({q}x{p}): {engine} — {why}")

    block_s = []

    def progress(rec):
        block_s.append(rec["seconds"])
        if rec["done_blocks"] in (1, 2) or rec["done_blocks"] % 8 == 0:
            log("quantize", f"block {rec['done_blocks']}/{rec['total_blocks']}: "
                f"{rec['n_linears']} linears mean_err={rec['mean_rel_error']:.6g} "
                f"{rec['seconds']}s")

    t0 = time.perf_counter()
    qparams, report = ptq_quantize_model(
        plan, params, calib, pcfg, progress_cb=progress
    )
    total = time.perf_counter() - t0
    errs = np.array(list(report.values()))
    n_blocks = cfg.n_periods * len(cfg.pattern)
    log("quantize", f"{len(report)} linears in {len(block_s)}/{n_blocks} blocks, "
        f"iterations={iterations}: mean_rel_error={errs.mean():.6g} "
        f"max_rel_error={errs.max():.6g}")
    check(len(block_s) == n_blocks, f"quantized {len(block_s)} of {n_blocks} blocks")
    check(len(report) == n_blocks * len(linear_families(cfg)),
          f"{len(report)} linears quantized")
    check(bool(np.isfinite(errs).all()), "non-finite relative error")
    PHASE_TIMES["quantize"] = {
        "total_s": total, "first_block_s": block_s[0],
        "steady_block_s": float(np.median(block_s[1:])) if len(block_s) > 1 else None,
    }
    # Per-period solver blocks → the serving scan layout.
    return quantize_params_for_serving(plan, qparams, qparams["dec"]), report


# ---------------------------------------------------------------------------
# Phase 3: kernels against their oracles
# ---------------------------------------------------------------------------

# One bound for every comparison, stated before any run: the error relative to
# the oracle's largest magnitude.  Kernel and oracle both accumulate in fp32;
# 1e-2 leaves room for MXU passes that round fp32 operands to bf16 (2^-8).
REL_BOUND = 1e-2
# Quantized CD outputs may land on the other side of a rounding boundary where
# the two schedules' fp32 sums differ in the last bits: at most this share of
# entries may change code.
CODE_FLIP_BOUND = 1e-3


def _rel(got, want):
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return math.inf, math.inf
    err = float(np.abs(got - want).max())
    return err, err / max(float(np.abs(want).max()), 1e-30)


def _compare(name, kernel_fn, oracle_fn, pick=lambda o: o):
    import jax

    got, first = timed(kernel_fn)
    got, steady = timed(kernel_fn)
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(oracle_fn())
    err, rel = _rel(pick(got), pick(want))
    log("kernels", f"{name}: max_abs={err:.3e} rel={rel:.3e} (bound {REL_BOUND:g}) "
        f"first={first:.3f}s steady={steady:.4f}s")
    check(rel <= REL_BOUND, f"{name}: rel error {rel:.3e} > {REL_BOUND:g}")
    PHASE_TIMES[f"kernel {name}"] = {"first_s": first, "steady_s": steady}


def kernel_phase(cfg, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quantease as qe
    from repro.kernels import ops, ref
    from repro.kernels.dequant_matmul import select_tile_k
    from repro.quant import GridSpec, pack_codes
    from repro.quant.pack import prepack_codes
    from repro.serve.kv_cache import NULL_PAGE

    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    bsz, n_levels = qe.QuantEaseConfig.block_size, 16

    def solver_inputs(q, p):
        w = jax.random.normal(next(keys), (q, p), jnp.float32) * 0.02
        x = jax.random.normal(next(keys), (2048, p), jnp.float32)
        with jax.default_matmul_precision("highest"):
            sigma = x.T @ x
            w, _, scale_pc, zero_pc, sig_tilde, pmat, _ = qe._prep(
                w, sigma, GridSpec(bits=4), 0.01, None
            )
            base = pmat - w @ sig_tilde
        return w, scale_pc, zero_pc, sig_tilde, base

    # Fused CD iteration vs the XLA schedule, at the wg/wu shape.
    q, p = cfg.d_ff, cfg.d_model
    w, scale_pc, zero_pc, sig_tilde, base = solver_inputs(q, p)
    delta = jnp.zeros_like(base)
    xla_step = jax.jit(
        qe._fused_xla_iteration_step(
            sig_tilde, scale_pc, zero_pc, n_levels, bsz, p // bsz, jnp.float32
        ),
        static_argnums=3,
    )
    for quantize in (False, True):
        kern = lambda quantize=quantize: ops.quantease_fused_iteration(
            base, sig_tilde, w, scale_pc, zero_pc, delta,
            n_levels=n_levels, quantize=quantize, bsz=bsz,
        )
        orac = lambda quantize=quantize: xla_step(w, base, delta, quantize)
        if not quantize:
            _compare(f"fused iteration {q}x{p} (w_new)", kern, orac, lambda o: o[0])
            continue
        got = np.asarray(jax.block_until_ready(kern())[0])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(orac()[0])
        flips = float((np.abs(got - want) > 0.5 * np.asarray(scale_pc)).mean())
        log("kernels", f"fused iteration {q}x{p} quantized: code flips "
            f"{flips:.3e} of entries (bound {CODE_FLIP_BOUND:g})")
        check(flips <= CODE_FLIP_BOUND, f"fused quantized iteration flips {flips:.3e}")
    del w, scale_pc, zero_pc, sig_tilde, base, delta

    # Outlier iteration and one block sweep vs kernels/ref.py, at the wq shape.
    q, p = cfg.n_heads * cfg.hd, cfg.d_model
    w, scale_pc, zero_pc, sig_tilde, base = solver_inputs(q, p)
    mask = jax.random.uniform(next(keys), (q, p)) < 0.01
    dh_prev = jnp.where(mask, jax.random.normal(next(keys), (q, p)) * 0.01, 0.0)
    delta = jnp.zeros_like(base)
    args = (base, sig_tilde, w, scale_pc, zero_pc, delta, dh_prev)
    kw = dict(n_levels=n_levels, quantize=False, bsz=bsz)
    _compare(f"outlier iteration {q}x{p} (residual)",
             lambda: ops.quantease_outlier_iteration(*args, **kw),
             lambda: ref.quantease_outlier_iteration_ref(*args, **kw),
             lambda o: o[3])
    blk = (base[:, :bsz], sig_tilde[:bsz, :bsz], w[:, :bsz], scale_pc[:, :bsz],
           zero_pc[:, :bsz])
    _compare(f"block sweep {q}x{bsz}",
             lambda: ops.quantease_block_sweep(*blk, n_levels=n_levels, quantize=False),
             lambda: ref.quantease_block_sweep_ref(*blk, n_levels=n_levels,
                                                   quantize=False),
             lambda o: o[0])
    del args, blk, w, scale_pc, zero_pc, sig_tilde, base, delta, dh_prev

    # Serving GEMM at the wg shape: per-channel uint8, packed int4 linear and
    # tile-native, for a decode step (m=4) and a prefill chunk (m=64).
    q, p = cfg.d_ff, cfg.d_model
    codes = jax.random.randint(next(keys), (q, p), 0, 16).astype(jnp.uint8)
    scale = jax.random.uniform(next(keys), (q, 1), jnp.float32, 0.001, 0.01)
    zero = jax.random.randint(next(keys), (q, 1), 0, 16).astype(jnp.float32)
    tk = select_tile_k(p)
    layouts = {
        "per-channel": (codes, dict(packed4=False)),
        "int4 linear": (pack_codes(codes, 4), dict(packed4=True)),
        "int4 tile": (prepack_codes(codes, 4, tk),
                      dict(packed4=True, pack_layout="tile", pack_tile=tk)),
    }
    for m in (4, 64):
        x = jax.random.normal(next(keys), (m, p), jnp.float32).astype(jnp.bfloat16)
        for label, (c, lkw) in layouts.items():
            _compare(
                f"dequant_matmul {label} m={m} {q}x{p}",
                lambda c=c, lkw=lkw, x=x: ops.dequant_matmul(
                    x, c, scale, zero, out_dtype=jnp.float32, **lkw),
                lambda x=x: ref.dequant_matmul_ref(
                    x, codes, scale, zero, out_dtype=jnp.float32),
            )
    del codes, layouts

    # Paged decode attention at phi3's heads (head_dim 96), bf16 and int8 pages.
    B, kvp, hd, psz = 4, cfg.n_kv_heads, cfg.hd, 16
    g = cfg.n_heads // cfg.n_kv_heads
    max_seq = 544
    n_pgs = max_seq // psz
    n_pages = 1 + B * n_pgs
    lengths = np.array([16, 129, 300, max_seq], np.int32)
    table = np.full((B, n_pgs), NULL_PAGE, np.int32)
    perm = np.random.default_rng(seed).permutation(np.arange(1, n_pages))
    for b in range(B):
        used = -(-int(lengths[b]) // psz)
        table[b, :used] = perm[b * n_pgs : b * n_pgs + used]
    qv = jax.random.normal(next(keys), (B, kvp, g, hd), jnp.float32).astype(jnp.bfloat16)
    shape = (n_pages, psz, kvp, hd)
    pages = {
        "bf16": tuple(jax.random.normal(next(keys), shape, jnp.float32)
                      .astype(jnp.bfloat16) for _ in range(2)) + (None, None),
        "int8": tuple(jax.random.randint(next(keys), shape, -127, 128)
                      .astype(jnp.int8) for _ in range(2))
        + tuple(jax.random.uniform(next(keys), shape[:3] + (1,), jnp.float32,
                                   0.001, 0.02) for _ in range(2)),
    }
    pt, ln = jnp.asarray(table), jnp.asarray(lengths)
    for label, (kp, vp, ks, vs) in pages.items():
        akw = dict(k_scale_pages=ks, v_scale_pages=vs)
        _compare(
            f"paged_attention {label} B={B} kv={kvp} hd={hd}",
            lambda kp=kp, vp=vp, akw=akw: ops.paged_attention(qv, kp, vp, pt, ln, **akw),
            lambda kp=kp, vp=vp, akw=akw: ref.paged_attention_ref(qv, kp, vp, pt, ln, **akw),
        )
    check(not ops.fallbacks, f"kernel checks fell back to XLA: {dict(ops.fallbacks)}")



# ---------------------------------------------------------------------------
# Phases 4-5: serve, parity
# ---------------------------------------------------------------------------

PROMPT_LENS = (16, 37, 64, 100, 200, 333, 450, 512)


def serve_phase(plan, qparams, seed: int, max_new: int):
    import numpy as np

    from repro.eval.scorer import next_token_logits
    from repro.kernels import ops
    from repro.serve.engine import TERMINAL_STATUSES, PagedServingEngine, Request
    from repro.serve.qparams import prepack_params_for_serving

    cfg = plan.cfg
    (qparams, decisions), t_pack = timed(prepack_params_for_serving, plan, qparams)
    labels = sorted(set(decisions.values()))
    log("serve", f"prepacked {len(decisions)} weight leaves in {t_pack:.2f}s: "
        + ", ".join(f"{lb} x{sum(v == lb for v in decisions.values())}" for lb in labels))
    max_seq = 544  # longest prompt + new tokens, whole 16-token pages
    eng = PagedServingEngine(
        plan, qparams, max_batch=4, max_seq=max_seq, page_size=16,
        prefill_chunk=64, record_logits=True,
    )
    rng = np.random.default_rng(seed)
    prompts = {}
    rounds = {}
    for rnd in ("cold", "warm"):
        for n in PROMPT_LENS:
            rid = len(prompts)
            prompts[rid] = rng.integers(0, cfg.vocab, n).astype(np.int32)
            eng.submit(Request(rid=rid, prompt=prompts[rid], max_new_tokens=max_new))
        steps0, chunks0 = eng.n_decode_steps, eng.n_prefill_chunks
        n_done0 = len(eng.finished)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        done = eng.finished[n_done0:]
        statuses = sorted({r.status for r in done})
        n_tok = sum(len(r.output) for r in done)
        log("serve", f"{rnd}: {len(done)} requests {statuses}, {n_tok} tokens, "
            f"{eng.n_decode_steps - steps0} decode steps, "
            f"{eng.n_prefill_chunks - chunks0} prefill chunks, {dt:.2f}s")
        check(len(done) == len(PROMPT_LENS), f"{rnd}: {len(done)} requests finished")
        check(all(r.status in TERMINAL_STATUSES for r in done),
              f"{rnd}: non-terminal status in {statuses}")
        check(all(r.status != "completed" or len(r.output) == max_new for r in done),
              f"{rnd}: a completed request is short of {max_new} tokens")
        rounds[rnd] = dt
    PHASE_TIMES["serve"] = {"first_s": rounds["cold"], "steady_s": rounds["warm"]}
    check(not ops.fallbacks, f"serving fell back to XLA: {dict(ops.fallbacks)}")

    # Parity bridge: scorer prefill logits vs the engine's first decode logits.
    tol = 0.05
    worst = 0.0
    t0 = time.perf_counter()
    for rid in (0, 3):
        want = next_token_logits(plan, qparams, prompts[rid])
        got = np.asarray(eng.logit_trace[rid][0])
        d = float(np.abs(want - got).max())
        worst = max(worst, d)
        log("parity", f"prompt {rid} ({len(prompts[rid])} tokens): max_abs_diff={d:.4g}")
    PHASE_TIMES["parity"] = {"total_s": time.perf_counter() - t0}
    log("parity", f"max_abs_diff={worst:.4g} (bound {tol})")
    check(worst <= tol, f"parity {worst:.4g} > {tol}")
    check(not ops.fallbacks, f"parity fell back to XLA: {dict(ops.fallbacks)}")


# ---------------------------------------------------------------------------
# Four chips: sharded solve vs 1-device solve
# ---------------------------------------------------------------------------


def four_chip_phase(cfg, seed: int, iterations: int, calib):
    import dataclasses

    import jax

    from repro.core.solver import PTQConfig, ptq_quantize_model
    from repro.launch.mesh import make_data_mesh
    from repro.models import init_params, make_plan
    from repro.quant import GridSpec

    cfg2 = dataclasses.replace(cfg, n_periods=2)
    plan = make_plan(cfg2, 1)
    params = init_params(plan, jax.random.PRNGKey(seed))
    mesh = make_data_mesh(4)
    base = dict(method="quantease", spec=GridSpec(bits=4), iterations=iterations)
    (_, rep_sh), t_sh = timed(ptq_quantize_model, plan, params, calib,
                              PTQConfig(**base, shard=True), mesh=mesh)
    (_, rep_1), t_1 = timed(ptq_quantize_model, plan, params, calib, PTQConfig(**base))
    check(set(rep_sh) == set(rep_1), "sharded and 1-device reports differ in layers")
    worst = max(abs(rep_sh[k] - rep_1[k]) for k in rep_1)
    for k in sorted(rep_1):
        log("4chip", f"{k}: sharded={rep_sh[k]:.8f} local={rep_1[k]:.8f}")
    log("4chip", f"{len(rep_1)} layers of 2 blocks: max |sharded - local| "
        f"= {worst:.3e} (bound 1e-4)")
    PHASE_TIMES["4chip solve"] = {"sharded_s": t_sh, "local_s": t_1}
    check(worst <= 1e-4, f"sharded vs local relative error differs by {worst:.3e}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded solve on a 4-device mesh beside "
                         "the 1-device solve of the same blocks")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and prompts")
    args = ap.parse_args()

    n_chips = 4 if args.four_chips else 1
    device = device_check(n_chips)

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log("device", f"compile cache: {enable_compile_cache()}")

    import jax

    from repro.configs import get_config
    from repro.models import init_params, make_plan

    cfg = get_config("phi3_mini_3_8b")
    log("config", f"{cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads} "
        f"kv_heads={cfg.n_kv_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} blocks={cfg.n_periods * len(cfg.pattern)}")
    calib, t_calib = timed(calib_batches, cfg, args.seed, CALIB_BATCHES, CALIB_SEQ)
    log("config", f"calibration: {CALIB_BATCHES} x 4 x {CALIB_SEQ} tokens "
        f"({t_calib:.2f}s)")

    if args.four_chips:
        four_chip_phase(cfg, args.seed, ITERATIONS, calib)
    else:
        plan = make_plan(cfg, 1)
        # One compiled program: eager init would compile per leaf shape and
        # materialize each leaf's fp32 draw before the bf16 cast.
        init = jax.jit(functools.partial(init_params, plan))
        params, t_init = timed(init, jax.random.PRNGKey(args.seed))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        log("config", f"{n_params / 1e9:.3f}B parameters from seed {args.seed} "
            f"({t_init:.2f}s)")
        qparams, _ = quantize_phase(plan, params, calib, ITERATIONS)
        del params  # the dense tree: serving holds only the artifact
        kernel_phase(cfg, args.seed)
        serve_phase(plan, qparams, args.seed, MAX_NEW)

    for name, t in PHASE_TIMES.items():
        log("time", f"{name}: " + " ".join(
            f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}" for k, v in t.items()))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

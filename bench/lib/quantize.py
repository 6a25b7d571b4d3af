"""Driver of ``kind: quantize`` mixes: QuantEase PTQ, block after block.

Set-up makes the whole dense model on the device from the seed, draws the
calibration tokens, and quantizes one block (the grouped solves compile).
The window then calls ``ptq_quantize_model`` once per block — block 0, 1,
2, … (wrapping at the last) — each call a one-block model fed the
calibration set through its embedding: capture Σ, solve all linears, emit
the codes, recompute the block's outputs.  It stops at the first block
boundary after ``seconds``.  A call's work has the shapes and sizes of that
block's step in a whole-model run; only its inputs are the embedded
calibration tokens instead of the previous block's outputs, which is what
lets the reference rebuild any block's inputs from the seed alone.

Check: for one window block drawn from the seed, the reference captures its
Σ from the same tokens and weights and solves every linear by plain
coordinate descent in float32; compared is the largest excess of the
program's layer objective ‖(W − Ŵ)X‖² over the reference's.  The share of
codes that differ is logged beside it and not compared: one early near-tie
rounding cascades along a row, so it is no steady reading (PERF.md).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from lib import flops, harness, reference, traffic, weights


def _slice_block(dec, i):
    import jax

    return jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1), dec)


def _one_block(dense: dict, i: int, slice_fn) -> dict:
    """The dense model with only block i in its stack; one compiled slice
    for every i (the index is traced), so no block compiles in the window."""
    out = {k: v for k, v in dense.items() if k != "dec"}
    out["dec"] = slice_fn(dense["dec"], np.int32(i))
    return out


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool, peaks: dict,
        t_start: float, matmul_dtype: str = "float32", warmup: bool = True
        ) -> harness.RunResult:
    """One run of the cell.  ``matmul_dtype="bfloat16"`` is the program's
    own lower-precision CD path (the control); ``warmup=False`` skips the
    warm-up block where the process has compiled it already."""
    import jax
    import jax.numpy as jnp

    from repro.core.solver import PTQConfig, ptq_quantize_model
    from repro.models import make_plan
    from repro.models.model import param_shapes
    from repro.quant import GridSpec

    cfgj, mix, spans = cell.config, cell.mix, harness.Spans()
    mcfg = harness.model_config(cfgj)
    n_layers = mcfg.n_periods
    shapes = param_shapes(make_plan(mcfg))
    plan1 = make_plan(dataclasses.replace(mcfg, n_periods=1))

    t = time.perf_counter()
    harness.log(f"setup: process and device {t - t_start:.3f}s")
    dense = jax.block_until_ready(weights.make_dense(shapes, seed))
    harness.log(f"setup: weights {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    toks = traffic.calib_tokens(mix["calib_sequences"], mix["calib_seq_len"],
                                cfgj["vocab_size"], seed)
    harness.log(f"setup: calibration tokens {time.perf_counter() - t:.3f}s")
    bsz = mix["calib_batch"]
    calib = [{"tokens": jnp.asarray(toks[i:i + bsz])} for i in range(0, len(toks), bsz)]
    pcfg = PTQConfig(method="quantease", spec=GridSpec(bits=mix["bits"]),
                     iterations=mix["iterations"], percdamp=mix["percdamp"], emit="qt",
                     matmul_dtype=matmul_dtype)

    def on_block(rec):
        with spans("bench.block_done"):
            pass

    slice_fn = jax.jit(_slice_block)

    def call(i):
        params_i = _one_block(dense, i, slice_fn)
        with spans("bench.ptq"):
            out, _ = ptq_quantize_model(plan1, params_i, calib, pcfg, progress_cb=on_block)
            qt = jax.block_until_ready(out["dec"][0]["b0"])
        return qt

    if warmup:
        t = time.perf_counter()
        call(0)  # compiles the capture ops and the grouped solves
        harness.log(f"setup: warm-up block {time.perf_counter() - t:.3f}s")
    setup_s = time.perf_counter() - t_start
    harness.log(f"setup_s {setup_s:.3f}")

    done = []  # (layer, emitted block)
    with harness.profiled(trace) as get_trace:
        with spans("bench.window"):
            t0 = time.perf_counter()
            i = 0
            while True:
                done.append((i % n_layers, call(i % n_layers)))
                t_last = time.perf_counter()
                if t_last - t0 >= seconds:
                    break
                i += 1
    n_blocks = len(done)
    block_s = (t_last - t0) / n_blocks
    harness.log(f"window: {len(done)} blocks in {t_last - t0:.3f}s, {block_s:.4f} s/block")
    mem_peak = harness.memory_peak_bytes(cell.chips)

    pick = int(traffic.rng_for(seed, "check").integers(len(done)))
    layer, qt = done[pick]
    del done, dense, calib
    t_ref = time.perf_counter()
    compared = check_block(cell, shapes, seed, layer, toks, qt)
    harness.log(f"check of block {layer}: {time.perf_counter() - t_ref:.1f}s")

    info = {"n_blocks": n_blocks, "block_s": block_s, "window_s": t_last - t0,
            "flops_per_block": flops.quantize_block_flops(
                cfgj, mix["calib_sequences"], mix["calib_seq_len"], mix["iterations"]),
            "cd_least_s_per_block": flops.cd_block_least_s(cfgj, mix["iterations"], peaks),
            "cd_kernel_calls_per_block": 3 * mix["iterations"]}
    return harness.RunResult(
        cell=cell, peaks=peaks, end_to_end={"setup_s": setup_s, "quant_block_s": block_s},
        compared=compared, attempted=info["n_blocks"], failed=0, memory_peak_bytes=mem_peak,
        spans=spans, info=info, trace=get_trace())


def check_block(cell, shapes, seed, layer, toks, qt) -> list:
    """Reference solve of one block; returns [(name, value, limit)]."""
    import jax.numpy as jnp

    cfgj, mix = cell.config, cell.mix
    dims = flops.widths(cfgj)
    linears = flops.block_linears(cfgj)
    blk = weights.dense_block(shapes, seed, layer)
    w_io = {n: blk[n].reshape(linears[n][1], -1) for n in linears}
    ref_blk = dict(w_io, **{"ln/scale": blk["ln/scale"], "ln2/scale": blk["ln2/scale"]})
    embed = weights.top_leaf(shapes, seed, "embed")
    dims_t = tuple(sorted({k: dims[k] for k in ("h", "kv", "hd")}.items()))
    t = time.perf_counter()
    sig = None
    chunk = mix["calib_batch"]
    for c0 in range(0, len(toks), chunk):
        x = embed[jnp.asarray(toks[c0:c0 + chunk])]
        inc = reference.capture_chunk(x, ref_blk, dims_t, float(cfgj["rope_theta"]))
        sig = inc if sig is None else tuple(a + b for a, b in zip(sig, inc))
    harness.log(f"  reference capture: {time.perf_counter() - t:.1f}s")
    mismatch, excess = 0.0, -np.inf
    for name in linears:
        t = time.perf_counter()
        w = w_io[name].T.astype(jnp.float32)
        s = sig[reference.SIGMA_OF[name]]
        wh_ref, scale, _ = reference.cd_solve(w, s, mix["bits"], mix["iterations"],
                                              mix["percdamp"])
        wh_prog = qt[name].dequantize()
        flips = float(jnp.mean(jnp.abs(wh_prog - wh_ref) > 0.5 * scale))
        ratio = float(reference.objective(w, wh_prog, s) / reference.objective(w, wh_ref, s))
        harness.log(f"  {name}: code mismatch {flips:.6g}, objective ratio {ratio:.8f} "
                    f"({time.perf_counter() - t:.1f}s)")
        if name in ("wq", "wk", "wv"):
            mismatch = max(mismatch, flips)  # logged, not compared
        excess = max(excess, ratio - 1.0)
        del wh_ref, wh_prog
    harness.log(f"  q/k/v code mismatch (not compared): {mismatch:.6g}")
    return [("objective_excess", excess, cell.params["limits"]["objective_excess"])]

"""Pallas TPU kernels: QuantEase coordinate-descent sweeps.

Three kernels:

* :func:`quantease_block_sweep_pallas` — the intra-block sweep of one column
  block (the original per-block kernel; the legacy engine launches one of
  these per block per iteration).
* :func:`quantease_fused_iteration_pallas` — one **whole CD iteration** as a
  single kernel launch (DESIGN.md §Fused-iteration).  Grid
  ``(q-tiles, blocks)`` with the block dimension "arbitrary" (sequential):
  each program applies the full-width rolling-Δ correction for its block —
  ``corr = Σ̃ᵀ[blk, :] @ Δ`` with the (p × TQ) Δ accumulator resident in
  VMEM scratch across block steps — then runs the sequential intra-block
  sweep, then publishes its block's fresh Δ into the accumulator for the
  blocks that follow.  The rolling buffer holds current-iteration Δ for
  processed blocks and previous-iteration Δ for the rest, so the one matmul
  per block simultaneously applies the triangular cross-block correction
  and the incremental ``base = P − P̂`` maintenance (see
  repro/core/quantease.py).
* :func:`quantease_outlier_iteration_pallas` — the outlier-aware variant
  (DESIGN.md §Outlier-aware-fused): same rolling-Δ sweep plus, in the same
  launch, (a) the Ĥ-step's lazy target move (``−dĤ_prev`` absorbed at the
  base read, ``−dĤ_prevΣ̃`` folded into the published Δ) and (b) the exact
  post-sweep residual ``R = P − ŴΣ̃`` accumulated into a VMEM-resident
  output: each block adds its β0 tile plus its pure δŴ's suffix
  contribution ``Σ̃ᵀ[:, blk] δŴ_blk`` masked to the blocks already seeded.
  One launch per *outer* Algorithm-3 iteration.

Row independence makes everything embarrassingly parallel over the q
(output-channel) dimension, so the grid tiles q.  All operands are carried
*transposed* — (B, TQ) instead of (TQ, B) — so the sequential index
addresses the sublane dimension (dynamic lane-dim slicing is slow on TPU;
sublane slicing is free).

VMEM budget per fused-iteration program (TQ=256, B=256, fp32, p=4096):
Δ accumulator p×TQ×4 B = 4 MB + Σ̃ᵀ correction rows B×p×4 B = 4 MB
(2 MB at bf16) + 7 small (B × TQ) tiles ≈ 1.8 MB — fits the ~16 MB VMEM
with double-buffering headroom up to p ≈ 4–5k; shrink ``tq`` for wider
layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES

__all__ = [
    "quantease_block_sweep_pallas",
    "quantease_fused_iteration_pallas",
    "quantease_outlier_iteration_pallas",
    "quantease_outlier_iteration_t_pallas",
]


# fp32 operands stay fp32 on the MXU.  Mosaic's default contract precision
# rounds them to bf16 (2.4e-3 relative error on a v5e): at phi3 widths that
# moved 4.7% of one quantized iteration's codes away from the fp32
# schedule's (a wrong rounding early in a row cascades along the row).
_FP32 = jax.lax.Precision.HIGHEST


def _precision(dtype):
    """fp32 contract precision for fp32 operands; bf16 operands (the bf16
    correction option) take Mosaic's default, which refuses fp32 for them.
    Explicit either way, so a caller's default_matmul_precision never
    reaches the kernel."""
    return _FP32 if dtype == jnp.float32 else jax.lax.Precision.DEFAULT


def _sweep_kernel(
    beta0_t_ref,  # (B, TQ) f32
    sig_t_ref,  # (B, B) f32 — Σ̃_blkᵀ (row i = Σ̃[:, i])
    w_old_t_ref,  # (B, TQ) f32
    scale_t_ref,  # (B, TQ) f32
    zero_t_ref,  # (B, TQ) f32
    w_new_t_ref,  # (B, TQ) f32 out
    delta_t_ref,  # (B, TQ) f32 out — old − new, doubles as the Δ accumulator
    *,
    n_levels: int,
    quantize: bool,
    bsz: int,
):
    delta_t_ref[...] = jnp.zeros_like(delta_t_ref)

    def body(i, _):
        # corr = Σ̃[:, i] · Δ  — rows ≥ i of Δ are still zero, so no mask.
        sig_row = sig_t_ref[pl.ds(i, 1), :]  # (1, B)
        corr = jnp.dot(
            sig_row, delta_t_ref[...], preferred_element_type=jnp.float32,
            precision=_FP32,
        )  # (1, TQ)
        beta = beta0_t_ref[pl.ds(i, 1), :] + corr
        if quantize:
            sc = scale_t_ref[pl.ds(i, 1), :]
            zc = zero_t_ref[pl.ds(i, 1), :]
            codes = jnp.clip(jnp.round(beta / sc) + zc, 0, n_levels - 1)
            new = (codes - zc) * sc
        else:
            new = beta
        w_new_t_ref[pl.ds(i, 1), :] = new
        delta_t_ref[pl.ds(i, 1), :] = w_old_t_ref[pl.ds(i, 1), :] - new
        return 0

    jax.lax.fori_loop(0, bsz, body, 0)


@functools.partial(
    jax.jit, static_argnames=("n_levels", "quantize", "tq", "interpret")
)
def quantease_block_sweep_pallas(
    beta0: jax.Array,  # (q, B) f32
    sig_blk: jax.Array,  # (B, B) f32
    w_old_blk: jax.Array,  # (q, B) f32
    scale_blk: jax.Array,  # (q, B) f32
    zero_blk: jax.Array,  # (q, B) f32
    *,
    n_levels: int,
    quantize: bool,
    tq: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    q, bsz = beta0.shape
    tq = min(tq, q)
    pad_q = (-q) % tq
    qp = q + pad_q

    def prep(a):  # (q, B) → (B, qp) transposed + padded
        if pad_q:
            a = jnp.pad(a, ((0, pad_q), (0, 0)))
        return a.T

    beta0_t = prep(beta0)
    w_old_t = prep(w_old_blk)
    scale_t = prep(jnp.maximum(scale_blk, 1e-12))
    zero_t = prep(zero_blk)
    sig_t = sig_blk.T

    kernel = functools.partial(
        _sweep_kernel, n_levels=n_levels, quantize=quantize, bsz=bsz
    )
    grid = (qp // tq,)
    w_new_t, delta_t = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bsz, tq), lambda i: (0, i)),
            pl.BlockSpec((bsz, bsz), lambda i: (0, 0)),
            pl.BlockSpec((bsz, tq), lambda i: (0, i)),
            pl.BlockSpec((bsz, tq), lambda i: (0, i)),
            pl.BlockSpec((bsz, tq), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((bsz, tq), lambda i: (0, i)),
            pl.BlockSpec((bsz, tq), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, qp), jnp.float32),
            jax.ShapeDtypeStruct((bsz, qp), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )(beta0_t, sig_t, w_old_t, scale_t, zero_t)
    return w_new_t.T[:q], delta_t.T[:q]


# ---------------------------------------------------------------------------
# Fused iteration: the whole blocked sweep as one kernel launch.
# ---------------------------------------------------------------------------


def _fused_iter_kernel(
    base_t_ref,  # (B, TQ) f32 — (P − P̂)ᵀ tile for this block
    sig_corr_ref,  # (B, p_pad) cdt — Σ̃ᵀ rows of this block (row i = Σ̃[:, col0+i])
    sig_diag_ref,  # (B, B) f32 — Σ̃ᵀ diagonal block (intra-block sweep)
    w_old_t_ref,  # (B, TQ) f32 — Ŵᵀ at iteration start
    scale_t_ref,  # (B, TQ) f32
    zero_t_ref,  # (B, TQ) f32
    delta_prev_t_ref,  # (p_pad, TQ) f32 — previous-iteration rolling Δᵀ
    w_new_t_ref,  # (B, TQ) f32 out
    base_out_t_ref,  # (B, TQ) f32 out — next iteration's base invariant
    delta_out_t_ref,  # (B, TQ) f32 out — this block's fresh Δ
    delta_acc,  # (p_pad, TQ) f32 VMEM scratch — rolling Δ, lives across blocks
    *,
    n_levels: int,
    quantize: bool,
    bsz: int,
    corr_dtype,
):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _seed():
        delta_acc[...] = delta_prev_t_ref[...]

    # Full-width rolling-Δ correction: rows < col0 of Δ hold *this*
    # iteration's deltas (triangular prefix), rows ≥ col0 the *previous*
    # iteration's (incremental base maintenance) — one matmul does both.
    corr = jnp.dot(
        sig_corr_ref[...],
        delta_acc[...].astype(corr_dtype),
        preferred_element_type=jnp.float32,
        precision=_precision(corr_dtype),
    )  # (B, TQ)
    beta0 = base_t_ref[...] + corr
    base_out_t_ref[...] = beta0

    # Intra-block sequential sweep (fp32 — the β/quantize path).
    delta_out_t_ref[...] = jnp.zeros_like(delta_out_t_ref)

    def body(i, _):
        sig_row = sig_diag_ref[pl.ds(i, 1), :]  # (1, B)
        c = jnp.dot(
            sig_row, delta_out_t_ref[...], preferred_element_type=jnp.float32,
            precision=_FP32,
        )  # (1, TQ)
        beta = base_out_t_ref[pl.ds(i, 1), :] + c  # β₀ row i (stored above)
        if quantize:
            sc = scale_t_ref[pl.ds(i, 1), :]
            zc = zero_t_ref[pl.ds(i, 1), :]
            codes = jnp.clip(jnp.round(beta / sc) + zc, 0, n_levels - 1)
            new = (codes - zc) * sc
        else:
            new = beta
        w_new_t_ref[pl.ds(i, 1), :] = new
        delta_out_t_ref[pl.ds(i, 1), :] = w_old_t_ref[pl.ds(i, 1), :] - new
        return 0

    jax.lax.fori_loop(0, bsz, body, 0)
    # Publish this block's Δ so later blocks' corrections see it.
    delta_acc[pl.ds(b * bsz, bsz), :] = delta_out_t_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("n_levels", "quantize", "bsz", "tq", "matmul_dtype", "interpret"),
)
def quantease_fused_iteration_pallas(
    base: jax.Array,  # (q, p_pad) f32 — P − P̂ invariant entering this iteration
    sig_tilde: jax.Array,  # (p_pad, p_pad) f32 — zero diag, column-normalized
    w_hat: jax.Array,  # (q, p_pad) f32 — iterate entering this iteration
    scale_pc: jax.Array,  # (q, p_pad) f32
    zero_pc: jax.Array,  # (q, p_pad) f32
    delta_prev: jax.Array,  # (q, p_pad) f32 — previous iteration's rolling Δ
    *,
    n_levels: int,
    quantize: bool,
    bsz: int,
    tq: int = 256,
    matmul_dtype: str = "float32",
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One full CD iteration in a single ``pallas_call``.

    Returns ``(w_new, base_new, delta_new)`` — feed them straight back in
    for the next iteration.  ``p_pad`` must be a multiple of ``bsz`` (the
    caller's column-block padding).
    """
    q, p_pad = base.shape
    assert p_pad % bsz == 0, (p_pad, bsz)
    n_blocks = p_pad // bsz
    tq = min(tq, q)
    pad_q = (-q) % tq
    qp = q + pad_q
    cdt = jnp.bfloat16 if matmul_dtype == "bfloat16" else jnp.float32

    def prep(a, fill=0.0):  # (q, p_pad) → (p_pad, qp) transposed + padded
        if pad_q:
            a = jnp.pad(a, ((0, pad_q), (0, 0)), constant_values=fill)
        return a.T

    base_t = prep(base)
    w_old_t = prep(w_hat)
    scale_t = prep(jnp.maximum(scale_pc, 1e-12), fill=1.0)
    zero_t = prep(zero_pc)
    delta_prev_t = prep(delta_prev)
    sig_t = sig_tilde.T  # row j = Σ̃[:, j]
    sig_corr = sig_t.astype(cdt)

    kernel = functools.partial(
        _fused_iter_kernel,
        n_levels=n_levels,
        quantize=quantize,
        bsz=bsz,
        corr_dtype=cdt,
    )
    grid = (qp // tq, n_blocks)
    out_spec = pl.BlockSpec((bsz, tq), lambda i, b: (b, i))
    w_new_t, base_out_t, delta_out_t = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # base
            pl.BlockSpec((bsz, p_pad), lambda i, b: (b, 0)),  # Σ̃ᵀ corr rows
            pl.BlockSpec((bsz, bsz), lambda i, b: (b, b)),  # Σ̃ᵀ diag block
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # w_old
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # scale
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # zero
            pl.BlockSpec((p_pad, tq), lambda i, b: (0, i)),  # Δ_prev (resident)
        ],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((p_pad, qp), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, qp), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, qp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p_pad, tq), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(base_t, sig_corr, sig_t, w_old_t, scale_t, zero_t, delta_prev_t)
    return w_new_t.T[:q], base_out_t.T[:q], delta_out_t.T[:q]


# ---------------------------------------------------------------------------
# Outlier-aware fused iteration: CD sweep + exact-residual accumulation in
# one launch (DESIGN.md §Outlier-aware-fused).
# ---------------------------------------------------------------------------


def _outlier_iter_kernel(
    base_t_ref,  # (B, TQ) f32 — base invariant tile for this block
    sig_corr_ref,  # (B, p_pad) cdt — Σ̃ᵀ rows of this block (full-width corr)
    sig_col_ref,  # (p_pad, B) cdt — Σ̃ᵀ columns of this block (suffix resid)
    sig_diag_ref,  # (B, B) f32 — Σ̃ᵀ diagonal block (intra-block sweep)
    w_old_t_ref,  # (B, TQ) f32 — Ŵᵀ at iteration start
    scale_t_ref,  # (B, TQ) f32
    zero_t_ref,  # (B, TQ) f32
    dh_prev_t_ref,  # (B, TQ) f32 — previous IHT step dĤᵀ tile
    delta_prev_t_ref,  # (p_pad, TQ) f32 — rolling Δᵀ entering the iteration
    w_new_t_ref,  # (B, TQ) f32 out
    base_out_t_ref,  # (B, TQ) f32 out — next iteration's base invariant
    dpure_t_ref,  # (B, TQ) f32 out — this block's *pure* δŴ
    r_t_ref,  # (p_pad, TQ) f32 out — exact residual R = P − ŴΣ̃, accumulated
    delta_acc,  # (p_pad, TQ) f32 VMEM scratch — rolling Δ across blocks
    *,
    n_levels: int,
    quantize: bool,
    bsz: int,
    corr_dtype,
):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _seed():
        delta_acc[...] = delta_prev_t_ref[...]
        r_t_ref[...] = jnp.zeros_like(r_t_ref)

    # Full-width rolling-Δ correction.  The buffer holds, for blocks < b,
    # this iteration's published (δŴ − dĤ_prev) deltas and, for blocks ≥ b,
    # the previous iteration's — so one matmul applies the triangular
    # cross-block correction, the incremental base maintenance, AND the
    # −dĤΣ̃ target move of the Ĥ-step.  The identity part of the target move
    # (−dĤ) is absorbed into the read below.
    corr = jnp.dot(
        sig_corr_ref[...],
        delta_acc[...].astype(corr_dtype),
        preferred_element_type=jnp.float32,
        precision=_precision(corr_dtype),
    )  # (B, TQ)
    beta0 = base_t_ref[...] - dh_prev_t_ref[...] + corr
    base_out_t_ref[...] = beta0
    r_t_ref[pl.ds(b * bsz, bsz), :] += beta0

    # Intra-block sequential sweep (fp32 — the β/quantize path).
    dpure_t_ref[...] = jnp.zeros_like(dpure_t_ref)

    def body(i, _):
        sig_row = sig_diag_ref[pl.ds(i, 1), :]  # (1, B)
        c = jnp.dot(
            sig_row, dpure_t_ref[...], preferred_element_type=jnp.float32,
            precision=_FP32,
        )  # (1, TQ) — rows ≥ i still zero; dĤ_prev cancels in the difference
        beta = base_out_t_ref[pl.ds(i, 1), :] + c  # β₀ row i (stored above)
        if quantize:
            sc = scale_t_ref[pl.ds(i, 1), :]
            zc = zero_t_ref[pl.ds(i, 1), :]
            codes = jnp.clip(jnp.round(beta / sc) + zc, 0, n_levels - 1)
            new = (codes - zc) * sc
        else:
            new = beta
        w_new_t_ref[pl.ds(i, 1), :] = new
        dpure_t_ref[pl.ds(i, 1), :] = w_old_t_ref[pl.ds(i, 1), :] - new
        return 0

    jax.lax.fori_loop(0, bsz, body, 0)
    # Publish δŴ − dĤ_prev so later blocks' corrections also carry the Ĥ
    # step's −dĤΣ̃ target move; the pure δŴ stays in the output (suffix
    # residual + next iteration's rolling state).
    delta_acc[pl.ds(b * bsz, bsz), :] = (
        dpure_t_ref[...] - dh_prev_t_ref[...]
    )
    # Suffix-residual contribution: this block's pure δŴ corrects R of every
    # block ≤ b (row mask) — accumulated into the resident R output.
    contrib = jnp.dot(
        sig_col_ref[...],
        dpure_t_ref[...].astype(corr_dtype),
        preferred_element_type=jnp.float32,
        precision=_precision(corr_dtype),
    )  # (p_pad, TQ)
    row = jax.lax.broadcasted_iota(jnp.int32, contrib.shape, 0)
    r_t_ref[...] += jnp.where(row < (b + 1) * bsz, contrib, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("n_levels", "quantize", "bsz", "tq", "matmul_dtype", "interpret"),
)
def quantease_outlier_iteration_pallas(
    base: jax.Array,  # (q, p_pad) f32 — base invariant entering this iteration
    sig_tilde: jax.Array,  # (p_pad, p_pad) f32 — zero diag, column-normalized
    w_old: jax.Array,  # (q, p_pad) f32 — iterate Ŵ entering this iteration
    scale_pc: jax.Array,  # (q, p_pad) f32
    zero_pc: jax.Array,  # (q, p_pad) f32
    delta_prev: jax.Array,  # (q, p_pad) f32 — rolling Δ entering the iteration
    dh_prev: jax.Array,  # (q, p_pad) f32 — previous IHT step dĤ
    *,
    n_levels: int,
    quantize: bool,
    bsz: int,
    tq: int = 256,
    matmul_dtype: str = "float32",
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One outlier-aware fused CD iteration in a single ``pallas_call``.

    Returns ``(w_new, base_new, delta_pure, r)``: the new iterate, the next
    iteration's base invariant, the pure δŴ (rolling-Δ state before the
    next dĤ fold), and the **exact residual** ``R = P − Ŵ_newΣ̃`` the IHT
    step consumes.  ``p_pad`` must be a multiple of ``bsz``.
    """
    q, p_pad = base.shape
    assert p_pad % bsz == 0, (p_pad, bsz)
    tq = min(tq, q)
    pad_q = (-q) % tq
    qp = q + pad_q

    def prep(a, fill=0.0):  # (q, p_pad) → (p_pad, qp) transposed + padded
        if pad_q:
            a = jnp.pad(a, ((0, pad_q), (0, 0)), constant_values=fill)
        return a.T

    cdt = jnp.bfloat16 if matmul_dtype == "bfloat16" else jnp.float32
    sig_t = sig_tilde.T  # row j = Σ̃[:, j]
    w_new_t, base_out_t, dpure_t, r_t = quantease_outlier_iteration_t_pallas(
        prep(base),
        sig_corr=sig_t.astype(cdt),
        sig_t=sig_t,
        w_old_t=prep(w_old),
        scale_t=prep(jnp.maximum(scale_pc, 1e-12), fill=1.0),
        zero_t=prep(zero_pc),
        dh_prev_t=prep(dh_prev),
        delta_prev_t=prep(delta_prev),
        n_levels=n_levels,
        quantize=quantize,
        bsz=bsz,
        tq=tq,
        matmul_dtype=matmul_dtype,
        interpret=interpret,
    )
    return w_new_t.T[:q], base_out_t.T[:q], dpure_t.T[:q], r_t.T[:q]


@functools.partial(
    jax.jit,
    static_argnames=("n_levels", "quantize", "bsz", "tq", "matmul_dtype", "interpret"),
)
def quantease_outlier_iteration_t_pallas(
    base_t: jax.Array,  # (p_pad, qp) f32 — transposed base invariant
    *,
    sig_corr: jax.Array,  # (p_pad, p_pad) cdt — Σ̃ᵀ cast for the matmuls
    sig_t: jax.Array,  # (p_pad, p_pad) f32 — Σ̃ᵀ (intra-block sweep)
    w_old_t: jax.Array,  # (p_pad, qp) f32
    scale_t: jax.Array,  # (p_pad, qp) f32 — clamped ≥ 1e-12, pad cols = 1
    zero_t: jax.Array,  # (p_pad, qp) f32
    dh_prev_t: jax.Array,  # (p_pad, qp) f32
    delta_prev_t: jax.Array,  # (p_pad, qp) f32
    n_levels: int,
    quantize: bool,
    bsz: int,
    tq: int,
    matmul_dtype: str = "float32",
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Transposed-native entry: one outlier-aware fused CD iteration on
    operands already in the engine's resident (p_pad, qp) layout.

    The scanned outer loop in :mod:`repro.core.outlier` carries its state
    transposed and its Σ̃/scale/zero operands are loop-invariant — calling
    this entry directly (rather than the (q, p) wrapper above) means no
    per-iteration layout transposes cross the pallas_call boundary.
    ``p_pad % bsz == 0`` and ``qp % tq == 0`` are the caller's contract.
    """
    p_pad, qp = base_t.shape
    assert p_pad % bsz == 0 and qp % tq == 0, (p_pad, bsz, qp, tq)
    n_blocks = p_pad // bsz
    cdt = jnp.bfloat16 if matmul_dtype == "bfloat16" else jnp.float32

    kernel = functools.partial(
        _outlier_iter_kernel,
        n_levels=n_levels,
        quantize=quantize,
        bsz=bsz,
        corr_dtype=cdt,
    )
    grid = (qp // tq, n_blocks)
    out_spec = pl.BlockSpec((bsz, tq), lambda i, b: (b, i))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # base
            pl.BlockSpec((bsz, p_pad), lambda i, b: (b, 0)),  # Σ̃ᵀ corr rows
            pl.BlockSpec((p_pad, bsz), lambda i, b: (0, b)),  # Σ̃ᵀ suffix cols
            pl.BlockSpec((bsz, bsz), lambda i, b: (b, b)),  # Σ̃ᵀ diag block
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # w_old
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # scale
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # zero
            pl.BlockSpec((bsz, tq), lambda i, b: (b, i)),  # dh_prev
            pl.BlockSpec((p_pad, tq), lambda i, b: (0, i)),  # Δ_prev (resident)
        ],
        out_specs=[out_spec, out_spec, out_spec,
                   pl.BlockSpec((p_pad, tq), lambda i, b: (0, i))],  # R resident
        out_shape=[
            jax.ShapeDtypeStruct((p_pad, qp), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, qp), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, qp), jnp.float32),
            jax.ShapeDtypeStruct((p_pad, qp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p_pad, tq), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(base_t, sig_corr, sig_corr, sig_t, w_old_t, scale_t, zero_t,
      dh_prev_t, delta_prev_t)

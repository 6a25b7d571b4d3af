"""Outlier-aware QuantEase (paper §4, Algorithm 3) — fused engine.

Solves  min ‖WX − (Ŵ+Ĥ)X‖²  s.t.  Ŵ on-grid, ‖Ĥ‖₀ ≤ s
by block coordinate descent:

  * Ŵ-block: one cyclic-CD sweep of QuantEase on the surrogate target
    ``W − Ĥ`` (identical math, WΣ ← (W−Ĥ)Σ),
  * Ĥ-block: one iterative-hard-thresholding (IHT) step
    ``Ĥ ← P_s(Ĥ − η ∇_H g)`` with ``η = 1/(2 λ_max(Σ))`` (Lemma 3 descent).

Two engines (DESIGN.md §Outlier-aware-fused):

* ``engine="fused"`` (default) — one ``lax.scan`` over outer iterations whose
  state is the CD engine's resident residual product.  With
  ``σ_norm = Σ/diag`` and ``Σ̃ = σ_norm − I``, the invariant
  ``base = P − ŴΣ̃`` (``P = (W−Ĥ)σ_norm``) is maintained *incrementally*:

    - the Ŵ-sweep is the rolling-Δ fused iteration (one qp² correction
      matmul, PR 2's schedule) carried natively transposed ``(p, q)`` so the
      per-iteration state never transposes,
    - the **exact** post-sweep residual ``R = P − ŴΣ̃`` — shared across the
      Ŵ/Ĥ boundary — is recovered from the same state by one block-suffix
      product ``R = base + Σ_{c≥b} Δ_c Σ̃[c, b]`` (triangular: computed as
      ``min(4, n_blocks)`` column chunks, the diagonal chunks masked, so it
      costs ~0.6·qp² instead of the dense 2(Ŵ+Ĥ−W)Σ matmul the legacy
      schedule pays),
    - the IHT gradient is then free: ``∇_H g = −2 (R − Ŵ) ⊙ diag(Σ)``, and
      the objective (opt-in) is one matmul,
    - the Ĥ-step's effect on the target, ``P ← P − ĤσΔ``, is **never** a
      dense matmul: the ``−dĤ Σ̃`` part rides the rolling Δ buffer (the
      sweep's w_old is folded to ``Ŵ − dĤ`` so every published block delta
      carries the correction to later blocks for free), and the ``−dĤ``
      identity part is one fused elementwise subtract.

  On TPU each outer iteration is a **single Pallas launch**
  (:func:`repro.kernels.ops.quantease_outlier_iteration`): the fused CD
  sweep and the suffix-residual accumulation share one kernel, with the
  rolling Δ and the R accumulator resident in VMEM across block steps.  The
  XLA fallback applies updates in the same order (iterates agree up to fp
  reassociation; the top-s support may differ only on near-ties).

* ``engine="legacy"`` — the pre-fused schedule, kept verbatim for
  equivalence tests and BENCH_solver.json: every outer iteration re-enters
  :func:`quantease_quantize` (a fresh ``_prep`` with its qp² WΣ matmul),
  pays a dense qp² matmul for the IHT gradient and (when
  ``track_objective``) another for the objective, inside an unrolled
  Python loop.

Grid-range shrink: the per-channel grids are computed once, from W with the
top-s magnitude entries excluded (§4.3) — outliers live in Ĥ, so the grid
need not cover them.

Structured variant (§4.3 "Structured Outliers"): ``P_s`` selects the
⌊s/q⌋ columns of largest ℓ2 norm instead of the s largest entries.

Initialization: Ĥ = P_s(W), Ŵ = W − Ĥ (infeasible until the first sweep,
like basic QuantEase).

**Batched:** ``w: (G, q, p)`` with ``sigma: (G, p, p)`` solves G independent
layers in one vmapped call — the whole-model solver stacks same-shape
outlier layers exactly like the base engine (``OutlierResult`` leaves and
the Grid gain a leading G dim).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.calib import damp_sigma
from repro.core.quantease import _quant_cols, fp32_matmuls, quantease_quantize
from repro.quant import GridSpec, compute_grid_excluding_outliers

__all__ = ["OutlierResult", "outlier_quantease", "top_s_mask", "power_lambda_max"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OutlierResult:
    w_hat: jax.Array  # (q, p) quantized part (on-grid, fp32)
    h: jax.Array  # (q, p) dense sparse-correction (‖H‖₀ ≤ s)
    # Per-outer-iteration damped objective — **opt-in** via
    # ``track_objective=True`` (matches the base engine's PR 2 convention);
    # None by default.
    objective: Optional[jax.Array] = None
    # Range-shrunk grid the CD sweeps quantized against — threaded to the
    # solver's emit path so codes round-trip the solve exactly.
    grid: object = None

    @property
    def w_eff(self) -> jax.Array:
        return self.w_hat + self.h


def power_lambda_max(
    sigma: jax.Array, iters: int = 64, tol: float = 0.0
) -> jax.Array:
    """Largest eigenvalue of PSD Σ by power iteration (matrix-vector only —
    the paper's point: no decompositions anywhere in the pipeline).

    ``iters`` caps the iteration count.  ``tol > 0`` additionally early-outs
    once the Rayleigh quotient is stable to that relative tolerance — an
    *optimistic* stop: quotient stagnation is necessary but not sufficient
    for convergence (a clustered top of the spectrum can plateau near a
    sub-dominant eigenvalue), and an under-estimated λ_max makes the IHT
    step ``η = 1/(2λ_max)`` exceed the Lemma-3 bound.  The default
    ``tol=0.0`` therefore always runs the full ``iters`` matvecs; opt into
    the early-out only when the calibration spectrum is known to be
    well-separated.  One matvec per iteration: λ is read off as ``v·(Σv)``
    for the *unit* v entering the step, and the same product is reused for
    the next iterate.
    """
    p = sigma.shape[0]
    v0 = jnp.ones((p,), jnp.float32) / jnp.sqrt(p)

    def cond(state):
        i, _, lam, lam_prev = state
        if tol <= 0.0:
            return i < iters
        resolved = jnp.abs(lam - lam_prev) <= tol * jnp.maximum(jnp.abs(lam), 1e-30)
        return (i < iters) & ~resolved

    def body(state):
        i, v, lam, _ = state
        sv = sigma @ v
        lam_new = v @ sv  # Rayleigh quotient of the unit vector v
        v_new = sv / jnp.clip(jnp.linalg.norm(sv), 1e-30, None)
        return i + 1, v_new, lam_new, lam

    _, v, lam, _ = jax.lax.while_loop(
        cond, body, (0, v0, jnp.float32(0.0), jnp.float32(3.4e38))
    )
    # One final exact quotient on the converged direction.
    return v @ (sigma @ v)


def top_s_mask(a: jax.Array, s: int) -> jax.Array:
    """Boolean mask of the s largest |entries| (exact, via top_k on flat)."""
    flat = jnp.abs(a).reshape(-1)
    _, idx = jax.lax.top_k(flat, s)
    mask = jnp.zeros(flat.shape, jnp.bool_).at[idx].set(True)
    return mask.reshape(a.shape)


def _project_s(a: jax.Array, s: int) -> jax.Array:
    """P_s: keep the s largest-|value| entries, zero the rest."""
    return jnp.where(top_s_mask(a, s), a, 0.0)


def _project_columns(a: jax.Array, n_cols: int) -> jax.Array:
    """Structured P_s: keep the n_cols columns of largest ℓ2 norm."""
    norms = jnp.linalg.norm(a, axis=0)
    _, idx = jax.lax.top_k(norms, n_cols)
    mask = jnp.zeros((a.shape[1],), jnp.bool_).at[idx].set(True)
    return jnp.where(mask[None, :], a, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "s", "iterations", "structured", "cd_block_size",
        "use_kernel", "matmul_dtype", "track_objective", "engine", "lam_iters",
    ),
)
@fp32_matmuls
def outlier_quantease(
    w: jax.Array,
    sigma: jax.Array,
    spec: GridSpec,
    *,
    s: int,
    iterations: int = 25,
    structured: bool = False,
    percdamp: float = 0.01,
    cd_block_size: int = 128,
    use_kernel: str = "auto",
    matmul_dtype: str = "float32",
    track_objective: bool = False,
    engine: str = "fused",
    lam_iters: int = 64,
) -> OutlierResult:
    """Algorithm 3.  ``s`` = total outlier budget (entries; for the structured
    variant ⌊s/q⌋ columns are kept).

    ``use_kernel``/``matmul_dtype`` follow the base engine's contract
    (threaded from ``PTQConfig`` by the whole-model solver): ``"auto"``
    resolves to the compiled Pallas kernel on TPU and XLA elsewhere;
    ``matmul_dtype="bfloat16"`` runs the Σ̃ correction/residual matmuls with
    bf16 operands (fp32 accumulation; β/quantize/IHT stay fp32).

    Batched: ``w: (G, q, p)`` + ``sigma: (G, p, p)`` vmaps G independent
    solves in one call.
    """
    kw = dict(
        spec=spec, s=s, iterations=iterations, structured=structured,
        percdamp=percdamp, cd_block_size=cd_block_size, use_kernel=use_kernel,
        matmul_dtype=matmul_dtype, track_objective=track_objective,
        engine=engine, lam_iters=lam_iters,
    )
    if w.ndim == 3:
        return jax.vmap(lambda wi, si: _outlier_2d(wi, si, **kw))(w, sigma)
    return _outlier_2d(w, sigma, **kw)


def _outlier_2d(
    w, sigma, *, spec, s, iterations, structured, percdamp, cd_block_size,
    use_kernel, matmul_dtype, track_objective, engine, lam_iters,
) -> OutlierResult:
    q, p = w.shape
    w32 = w.astype(jnp.float32)
    sigma_d = damp_sigma(sigma.astype(jnp.float32), percdamp)
    eta = 1.0 / (2.0 * power_lambda_max(sigma_d, iters=lam_iters))

    n_cols = max(s // q, 1)
    # Range-shrunk grids (outliers excluded from the quantization pool).
    # The exclusion mask must match the *structure* of H: entries for the
    # unstructured variant, whole columns for the structured one.
    if structured:
        _, col_idx = jax.lax.top_k(jnp.linalg.norm(w32, axis=0), n_cols)
        excl = jnp.zeros((p,), jnp.bool_).at[col_idx].set(True)
        excl = jnp.broadcast_to(excl[None, :], (q, p))
    else:
        excl = top_s_mask(w32, s)
    grid = compute_grid_excluding_outliers(w32, spec, excl)

    if engine == "legacy":
        return _outlier_legacy_2d(
            w32, sigma_d, spec, grid, excl, eta,
            s=s, iterations=iterations, structured=structured,
            cd_block_size=cd_block_size, use_kernel=use_kernel,
            track_objective=track_objective, n_cols=n_cols,
        )
    if engine != "fused":
        raise ValueError(f"unknown engine {engine!r}")
    return _outlier_fused_2d(
        w32, sigma_d, spec, grid, excl, eta,
        s=s, iterations=iterations, structured=structured,
        cd_block_size=cd_block_size, use_kernel=use_kernel,
        matmul_dtype=matmul_dtype, track_objective=track_objective,
        n_cols=n_cols,
    )


# ---------------------------------------------------------------------------
# Legacy engine: the pre-fused schedule, verbatim (bench + equivalence tests).
# ---------------------------------------------------------------------------


def _outlier_legacy_2d(
    w32, sigma_d, spec, grid, excl, eta, *,
    s, iterations, structured, cd_block_size, use_kernel, track_objective,
    n_cols,
):
    project = (
        functools.partial(_project_columns, n_cols=n_cols)
        if structured
        else functools.partial(_project_s, s=s)
    )
    # Init: Ĥ = P_s(W), Ŵ = W − Ĥ.
    h = jnp.where(excl, w32, 0.0)
    w_hat = w32 - h

    objs = []
    for _ in range(iterations):
        # Ŵ-block: one QuantEase sweep on target (W − Ĥ).
        w_hat, _ = quantease_quantize(
            w32 - h,
            sigma_d,
            spec,
            iterations=1,
            block_size=cd_block_size,
            percdamp=0.0,  # sigma_d is already damped
            unquantized_heuristic=False,
            w_init=w_hat,
            grid=grid,
            use_kernel=use_kernel,
        )
        # Ĥ-block: IHT step.  ∇_H g = 2 (Ŵ + Ĥ − W) Σ.
        grad = 2.0 * ((w_hat + h - w32) @ sigma_d)
        h = project(h - eta * grad)
        if track_objective:
            e = w32 - w_hat - h
            objs.append(jnp.einsum("ij,jk,ik->", e, sigma_d, e))
    return OutlierResult(
        w_hat=w_hat,
        h=h,
        objective=jnp.stack(objs) if track_objective else None,
        grid=grid,
    )


# ---------------------------------------------------------------------------
# Fused engine: scanned outer loop on the resident (base, Δ) state.
# ---------------------------------------------------------------------------

_SWEEP_CHUNK = 8  # columns per unrolled sweep step (static intra-chunk tiles)


def _suffix_corr(delta_t, sig_t, bsz, cdt):
    """Exact block-suffix product ``U[:, blk b] = Σ_{c≥b} Δ_c Σ̃[c, blk b]``
    in transposed layout: ``U_t = (Σ̃ ⊙ M)ᵀ Δ_t`` with ``M[r, c] = 1`` iff
    ``block(r) ≥ block(c)``.

    The mask is block-lower-triangular, so the product is computed in
    ``min(4, n_blocks)`` column chunks — diagonal chunks masked at block
    granularity, below-diagonal crosses dense — ~0.6·qp² FLOPs instead of
    the dense qp².  ``cdt`` casts the matmul operands (bf16 option; fp32
    accumulation).
    """
    p_pad, _ = delta_t.shape
    nb = p_pad // bsz
    nchunk = next(c for c in (4, 3, 2, 1) if nb % c == 0)
    cs = p_pad // nchunk
    blk = jnp.arange(cs) // bsz
    mask = blk[:, None] <= blk[None, :]  # within-chunk: row-block ≤ col-block
    outs = []
    for i in range(nchunk):
        sl = slice(i * cs, (i + 1) * cs)
        sig_diag = jnp.where(mask, sig_t[sl, sl], 0.0).astype(cdt)
        u = jnp.dot(
            sig_diag, delta_t[sl].astype(cdt), preferred_element_type=jnp.float32
        )
        for j in range(i + 1, nchunk):
            sj = slice(j * cs, (j + 1) * cs)
            u = u + jnp.dot(
                sig_t[sl, sj].astype(cdt),
                delta_t[sj].astype(cdt),
                preferred_element_type=jnp.float32,
            )
        outs.append(u)
    return outs[0] if nchunk == 1 else jnp.concatenate(outs, 0)


def _sweep_block_t(beta0, sg_diag, wo, sc, zc, n_levels, q):
    """Transposed intra-block CD sweep: scan over K-column groups, each group
    one (K, B)·(B, q) correction matmul plus statically-unrolled rank-1
    fixups for the intra-group recurrence.  Same update order as the
    per-column reference sweep — identical iterates up to fp reassociation.
    """
    bsz = beta0.shape[0]
    K = _SWEEP_CHUNK
    ng = bsz // K
    sgr = sg_diag.reshape(ng, K, bsz)
    sgi = jnp.stack([sgr[g][:, g * K : (g + 1) * K] for g in range(ng)])
    xs = (
        jnp.arange(ng), sgr, sgi, beta0.reshape(ng, K, q),
        wo.reshape(ng, K, q), sc.reshape(ng, K, q), zc.reshape(ng, K, q),
    )

    def grp(dloc, x):
        g, sg_rows_g, sg_in, b0g, wog, scg, zcg = x
        corr = sg_rows_g @ dloc  # vs groups < g of this block (rows ≥ gK are 0)
        fresh, news = [], []
        for j in range(K):
            b = b0g[j] + corr[j]
            for jj in range(j):  # intra-group recurrence, static indices
                b = b + fresh[jj] * sg_in[j, jj]
            new = _quant_cols(b, scg[j], zcg[j], n_levels)
            fresh.append(wog[j] - new)
            news.append(new)
        dloc = jax.lax.dynamic_update_slice(dloc, jnp.stack(fresh), (g * K, 0))
        return dloc, jnp.stack(news)

    dloc, new_g = jax.lax.scan(grp, jnp.zeros((bsz, q), jnp.float32), xs)
    return new_g.reshape(bsz, q), dloc


def _outlier_fused_2d(
    w32, sigma_d, spec, grid, excl, eta, *,
    s, iterations, structured, cd_block_size, use_kernel, matmul_dtype,
    track_objective, n_cols,
):
    from repro.core.quantease import _resolve_use_kernel

    use_kernel = _resolve_use_kernel(use_kernel)
    if matmul_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown matmul_dtype {matmul_dtype!r}")
    cdt = jnp.bfloat16 if matmul_dtype == "bfloat16" else jnp.float32
    q, p = w32.shape
    n_levels = spec.n_levels

    bsz = max(_SWEEP_CHUNK, min(cd_block_size, p))
    bsz = -(-bsz // _SWEEP_CHUNK) * _SWEEP_CHUNK  # multiple of the sweep chunk
    nb = -(-p // bsz)
    p_pad = nb * bsz
    pad = p_pad - p

    scale_pc, zero_pc = grid.per_column(p)
    diag = jnp.diag(sigma_d)
    sig_norm = sigma_d / diag[None, :]
    sig_tilde = sig_norm - jnp.eye(p, dtype=jnp.float32)
    if pad:
        # Padded columns: zero Σ̃ coupling, unit scale, zero diag ⇒ they
        # quantize to an isolated 0, their IHT candidates are exactly 0, and
        # they never influence real columns.
        sig_tilde = jnp.pad(sig_tilde, ((0, pad), (0, pad)))
        diag = jnp.pad(diag, (0, pad))
        scale_pc = jnp.pad(scale_pc, ((0, 0), (0, pad)), constant_values=1.0)
        zero_pc = jnp.pad(zero_pc, ((0, 0), (0, pad)))
    w_p = jnp.pad(w32, ((0, 0), (0, pad))) if pad else w32
    excl_p = jnp.pad(excl, ((0, 0), (0, pad))) if pad else excl

    # Engine selection: the single-launch Pallas kernel when requested AND
    # its VMEM budget fits; otherwise the XLA schedule — same update order,
    # same iterates (the base engine's fallback contract).
    kernel_tq = None
    if use_kernel != "xla":
        from repro.kernels import ops as kops

        kernel_tq = kops.outlier_iteration_tq(p_pad, bsz, matmul_dtype)
        if kernel_tq is None:  # reported, never silent
            warnings.warn(
                f"outlier QuantEase ({q}×{p_pad}) on the XLA schedule: the "
                f"fused kernel does not fit VMEM at bsz={bsz}, {matmul_dtype}"
            )
    use_pallas = kernel_tq is not None
    # The kernel tiles q: pad the resident state's q axis once, outside the
    # scan (the XLA path needs no q padding).
    tq = min(kernel_tq, q) if use_pallas else 0
    pad_q = (-q) % tq if use_pallas else 0
    qq = q + pad_q

    # Everything below lives natively transposed: state is (p_pad, qq).
    sig_t = sig_tilde.T  # row j = Σ̃[:, j]
    sig_rows = sig_t.reshape(nb, bsz, p_pad)
    sig_diag_t = jnp.stack(
        [sig_rows[b][:, b * bsz : (b + 1) * bsz] for b in range(nb)]
    )
    sig_rows_c = sig_rows.astype(cdt)
    diag_t = diag[:, None]

    def prep_t(a, fill=0.0):  # (q, p_pad) → (p_pad, qq), q-padded, once
        if pad_q:
            a = jnp.pad(a, ((0, pad_q), (0, 0)), constant_values=fill)
        return a.T

    scale_tp = prep_t(jnp.maximum(scale_pc, 1e-12), fill=1.0)
    zero_tp = prep_t(zero_pc)
    w_t = prep_t(w_p)
    excl_t = prep_t(excl_p)

    # Init: Ĥ = P_s(W), Ŵ = W − Ĥ.  The base invariant collapses at init:
    # base = P − Ŵ₀Σ̃ = target(σ_norm − Σ̃) = target, since Ŵ₀ = target = W − Ĥ
    # and σ_norm − Σ̃ = I — no init matmul at all.
    h_t = jnp.where(excl_t, w_t, 0.0)
    w_hat_t = w_t - h_t
    base_t = w_hat_t

    if not use_pallas:
        scale_tb = scale_tp.reshape(nb, bsz, qq)
        zero_tb = zero_tp.reshape(nb, bsz, qq)

        def iteration(w_old_t, base_in, delta_in, dh_t):
            """One fused CD iteration; returns (Ŵ_new, base_out, Δ_pure, R)."""
            xs = (
                jnp.arange(nb), sig_rows_c, sig_diag_t,
                base_in.reshape(nb, bsz, qq), w_old_t.reshape(nb, bsz, qq),
                scale_tb, zero_tb, dh_t.reshape(nb, bsz, qq),
            )

            def block(delta_buf, x):
                b, sgr, sgd, b0, wo, sc, zc, dhp = x
                corr = jnp.dot(
                    sgr, delta_buf.astype(cdt), preferred_element_type=jnp.float32
                )
                # −dhp: the identity part of the Ĥ-step's target move,
                # absorbed into the read (base carry stays un-folded).
                beta0 = b0 - dhp + corr
                new_t, dblk = _sweep_block_t(beta0, sgd, wo, sc, zc, n_levels, qq)
                # Publish δŴ − dĤ_prev: later blocks' corrections then carry
                # the −dĤΣ̃ part of the Ĥ-step's target move for free.  The
                # pure δŴ goes out for the suffix residual and the next
                # iteration's rolling state.
                delta_buf = jax.lax.dynamic_update_slice(
                    delta_buf, dblk - dhp, (b * bsz, 0)
                )
                return delta_buf, (new_t, beta0, dblk)

            _, (new_b, beta0_b, dpure_b) = jax.lax.scan(block, delta_in, xs)
            new_t = new_b.reshape(p_pad, qq)
            base_out = beta0_b.reshape(p_pad, qq)
            dpure = dpure_b.reshape(p_pad, qq)
            r_t = base_out + _suffix_corr(dpure, sig_t, bsz, cdt)
            return new_t, base_out, dpure, r_t
    else:
        interpret = use_kernel != "pallas_hw"
        sig_corr_c = sig_t.astype(cdt)

        def iteration(w_old_t, base_in, delta_in, dh_t):
            # Single kernel launch per outer iteration, straight on the
            # resident transposed state — loop-invariant Σ̃/scale/zero slabs
            # prepped once above, no per-iteration transposes.
            return kops.quantease_outlier_iteration_t(
                base_in,
                sig_corr=sig_corr_c, sig_t=sig_t,
                w_old_t=w_old_t, scale_t=scale_tp, zero_t=zero_tp,
                dh_prev_t=dh_t, delta_prev_t=delta_in,
                n_levels=n_levels, quantize=True, bsz=bsz, tq=tq,
                matmul_dtype=matmul_dtype, interpret=interpret,
            )

    delta0 = jnp.zeros((p_pad, qq), jnp.float32)

    def project_t(cand_t):
        """P_s in transposed layout.  Returns the new Ĥᵀ."""
        if structured:
            # columns of W = rows of the transposed state
            norms = jnp.sum(cand_t * cand_t, axis=1)
            _, ridx = jax.lax.top_k(norms, n_cols)
            mask = jnp.zeros((p_pad,), jnp.bool_).at[ridx].set(True)
            return jnp.where(mask[:, None], cand_t, 0.0)
        cf = cand_t.reshape(-1)
        _, idx = jax.lax.top_k(jnp.abs(cf), s)
        return jnp.zeros_like(cf).at[idx].set(cf[idx]).reshape(cand_t.shape)

    def body(state, _):
        w_cur, h_cur, base_cur, delta_cur, dh_prev = state
        new_t, base_out, dpure, r_t = iteration(w_cur, base_cur, delta_cur, dh_prev)
        # IHT step from the exact residual: ∇_H g = −2 (R − Ŵ) ⊙ diag.
        cand_t = h_cur + (2.0 * eta) * ((r_t - new_t) * diag_t)
        h_new = project_t(cand_t)
        dh = h_new - h_cur
        if track_objective:
            e_t = w_t - h_new - new_t
            obj = jnp.sum(e_t * (sigma_d_pad @ e_t))
        else:
            obj = jnp.float32(0.0)
        # The Ĥ-step moves the target by −dĤσ_norm: its −dĤΣ̃ part rides the
        # rolling Δ (dh_prev is re-subtracted at each block's publish next
        # iteration) and its −dĤ identity part is absorbed when base is read
        # (the −dhp term in beta0) — no dense matmul anywhere.
        return (new_t, h_new, base_out, dpure - dh, dh), obj

    sigma_d_pad = (
        jnp.pad(sigma_d, ((0, pad), (0, pad))) if (track_objective and pad)
        else sigma_d
    )
    state = (w_hat_t, h_t, base_t, delta0, jnp.zeros_like(h_t))
    (w_hat_t, h_t, _, _, _), objs = jax.lax.scan(
        body, state, None, length=iterations, unroll=min(2, iterations)
    )
    return OutlierResult(
        w_hat=w_hat_t.T[:q, :p],
        h=h_t.T[:q, :p],
        objective=objs if track_objective else None,
        grid=grid,
    )

"""QuantEase — cyclic coordinate descent layer-wise quantization (the paper).

Math (Lemma 1):  with Σ = XXᵀ, the optimal quantized value of coordinate
(i, j), all others fixed, is ``q_i(β̃)`` where::

    β̃ = −[ Σ_{k≠j} Σ_{j,k} Ŵ_{i,k} − (WΣ)_{i,j} ] / Σ_{j,j}

Updates are applied one *column* at a time (rows are independent given j).

Three implementations:

* :func:`quantease_reference` — Algorithm 1 verbatim (rank-1 maintenance of
  ŴΣ).  O(p²q) per iteration with p sequential HBM-bound steps; used as the
  numerical oracle in tests.
* ``engine="legacy"`` — the pre-fused production path: Algorithm 2's
  "accelerated partial updates" (Eq. 13) restructured into column blocks,
  with a full ``Ŵ @ Σ̃`` recompute per iteration plus full-width ``Δ @ Σ̃``
  cross-block corrections.  ~2·qp² matmul FLOPs per iteration (3·qp² with
  the objective history).  Kept as the baseline for BENCH_solver.json and
  the equivalence tests.
* ``engine="fused"`` (default) — the **fused-iteration engine**
  (DESIGN.md §Fused-iteration): ``base = P − P̂`` is maintained
  *incrementally* across iterations via a rolling Δ buffer, so each block's
  single full-width correction matmul simultaneously (a) applies the
  triangular prefix of the *current* iteration's Δ and (b) amortises the
  previous iteration's Δ over ``base`` — one qp² matmul per iteration
  total, a 2× FLOP cut.  The correction matmuls optionally run with bf16
  operands and fp32 accumulation (``matmul_dtype="bfloat16"``); the
  β/quantize path stays fp32.  On TPU the whole iteration is a single
  Pallas kernel (:mod:`repro.kernels.quantease_cd`), grid
  ``(q-tiles × blocks)`` with the Δ accumulator resident in VMEM scratch
  across block steps; the XLA fallback is restructured to match (same
  update order ⇒ same iterates up to fp reassociation).

All paths support the paper's "every third iteration unquantized" heuristic
(§3.2 Initialization) and initialization from any Ŵ (e.g. GPTQ's output,
§3.1 last paragraph).  The per-iteration objective history costs an extra
qp² einsum per iteration and is **opt-in** (``track_objective=True``).

The outlier-aware solver (:mod:`repro.core.outlier`, DESIGN.md
§Outlier-aware-fused) builds its Algorithm-3 loop on the same
``base = P − P̂`` / rolling-Δ invariant, sharing it across the Ŵ-block/
Ĥ-block boundary instead of re-entering :func:`quantease_quantize` per
outer iteration.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.calib import damp_sigma
from repro.quant import GridSpec, compute_grid
from repro.quant.grid import Grid

__all__ = [
    "QuantEaseConfig",
    "fused_engine",
    "quantease_quantize",
    "quantease_reference",
    "layer_objective",
    "relative_error",
]


@dataclasses.dataclass(frozen=True)
class QuantEaseConfig:
    """Hyper-parameters of the CD solver (paper defaults).

    ``use_kernel`` selects the execution engine: ``"auto"`` resolves to the
    compiled Pallas kernel on TPU and pure XLA elsewhere; ``"pallas"``
    forces Pallas interpret mode (tests), ``"pallas_hw"`` compiled Mosaic,
    ``"xla"`` the jnp fallback.  ``matmul_dtype`` applies to the Σ̃
    correction matmuls only (fp32 accumulation; the β/quantize path is
    always fp32).  The whole-model solver threads this config through
    :func:`quantease_quantize` via :meth:`solve_kwargs`.
    """

    iterations: int = 25  # paper §5.1: 25 strikes the accuracy/runtime balance
    block_size: int = 256  # column block B for the two-level sweep
    percdamp: float = 0.01  # Σ damping (same role as in GPTQ)
    unquantized_heuristic: bool = True  # every 3rd iteration keeps β̃ raw
    use_kernel: str = "auto"  # "auto" | "pallas" | "pallas_hw" | "xla"
    matmul_dtype: str = "float32"  # "float32" | "bfloat16" — Σ̃ corrections
    track_objective: bool = False  # per-iteration objective history (qp²/iter)
    engine: str = "fused"  # "fused" | "legacy"

    def solve_kwargs(self) -> dict:
        """Keyword arguments for :func:`quantease_quantize`."""
        return dict(
            iterations=self.iterations,
            block_size=self.block_size,
            percdamp=self.percdamp,
            unquantized_heuristic=self.unquantized_heuristic,
            use_kernel=self.use_kernel,
            matmul_dtype=self.matmul_dtype,
            track_objective=self.track_objective,
            engine=self.engine,
        )


def _resolve_use_kernel(use_kernel: str) -> str:
    if use_kernel == "auto":
        from repro.kernels import ops as kops

        return "pallas_hw" if kops.on_tpu() else "xla"
    if use_kernel not in ("pallas", "pallas_hw", "xla"):
        raise ValueError(f"unknown use_kernel {use_kernel!r}")
    return use_kernel


def fused_engine(
    p: int, block_size: int, matmul_dtype: str, use_kernel: str = "auto"
) -> tuple[str, str]:
    """Which engine the fused schedule runs a layer of ``p`` input columns
    on, and why: ``("kernel", ...)`` for the single-launch Pallas iteration,
    ``("xla", ...)`` for the XLA schedule (same iterates)."""
    use_kernel = _resolve_use_kernel(use_kernel)
    if use_kernel == "xla":
        return "xla", "XLA engine selected (off-chip, or use_kernel='xla')"
    from repro.kernels import ops as kops

    bsz = min(block_size, p)
    p_pad = -(-p // bsz) * bsz
    tq = kops.fused_iteration_tq(p_pad, bsz, matmul_dtype)
    if tq is None:
        return "xla", (
            f"fused kernel does not fit VMEM at p_pad={p_pad}, bsz={bsz}, "
            f"{matmul_dtype} (needs {kops.fused_iteration_bytes(p_pad, bsz, matmul_dtype, 128)} B "
            f"at tq=128)"
        )
    return "kernel", f"fits VMEM at tq={tq}"


def layer_objective(w: jax.Array, w_hat: jax.Array, sigma: jax.Array) -> jax.Array:
    """f(Ŵ) = ‖WX − ŴX‖²_F = Tr((W−Ŵ) Σ (W−Ŵ)ᵀ).

    Accepts leading batch dims (w: (..., q, p), sigma: (..., p, p)) and
    reduces per-matrix — the grouped solver scores a whole vmap batch at
    once.
    """
    e = (w - w_hat).astype(jnp.float32)
    return jnp.einsum("...ij,...jk,...ik->...", e, sigma.astype(jnp.float32), e)


def relative_error(w: jax.Array, w_hat: jax.Array, sigma: jax.Array) -> jax.Array:
    """Error(Ŵ) = ‖WX−ŴX‖²_F / ‖WX‖²_F (paper §3.4 / Fig. 2 metric).

    Batched like :func:`layer_objective`."""
    w = w.astype(jnp.float32)
    denom = jnp.einsum("...ij,...jk,...ik->...", w, sigma.astype(jnp.float32), w)
    return layer_objective(w, w_hat, sigma) / jnp.clip(denom, 1e-30, None)


def _prep(w, sigma, spec, percdamp, grid: Optional[Grid]):
    q, p = w.shape
    w = w.astype(jnp.float32)
    sigma = damp_sigma(sigma.astype(jnp.float32), percdamp)
    if grid is None:
        grid = compute_grid(w, spec)
    scale_pc, zero_pc = grid.per_column(p)  # (q, p)
    diag = jnp.diag(sigma)
    sig_norm = sigma / diag[None, :]  # column-normalized, diag = 1
    sig_tilde = sig_norm - jnp.eye(p, dtype=jnp.float32)  # zero diag
    pmat = w @ sig_norm  # P = WΣ^norm (full diag — see Alg. 2 ordering)
    return w, sigma, scale_pc, zero_pc, sig_tilde, pmat, grid


def _quant_cols(x, scale, zero, n_levels):
    codes = jnp.clip(jnp.round(x / scale) + zero, 0, n_levels - 1)
    return (codes - zero) * scale


# ---------------------------------------------------------------------------
# Reference: Algorithm 1 (rank-1 maintenance), the oracle.
# ---------------------------------------------------------------------------


def quantease_reference(
    w: jax.Array,
    sigma: jax.Array,
    spec: GridSpec,
    *,
    iterations: int = 3,
    percdamp: float = 0.01,
    unquantized_heuristic: bool = False,
    w_init: Optional[jax.Array] = None,
) -> jax.Array:
    """Algorithm 1, column-at-a-time with rank-1 ŴΣ updates.  Slow; tests only."""
    q, p = w.shape
    w32, sigma, scale_pc, zero_pc, _, _, spec_grid = _prep(
        w, sigma, spec, percdamp, None
    )
    n_levels = spec.n_levels
    w_hat = w32 if w_init is None else w_init.astype(jnp.float32)
    wsig = w32 @ sigma  # (WΣ), fixed
    what_sig = w_hat @ sigma  # maintained by rank-1 updates
    diag = jnp.diag(sigma)

    def col_update(carry, j, quantize):
        w_hat, what_sig = carry
        wcol = jax.lax.dynamic_slice(w_hat, (0, j), (q, 1))[:, 0]
        ws_col = jax.lax.dynamic_slice(what_sig, (0, j), (q, 1))[:, 0]
        wsig_col = jax.lax.dynamic_slice(wsig, (0, j), (q, 1))[:, 0]
        sjj = diag[j]
        # β̃ = −[ (ŴΣ)_{:,j} − Σ_jj Ŵ_{:,j} − (WΣ)_{:,j} ] / Σ_jj
        beta = -(ws_col - sjj * wcol - wsig_col) / sjj
        sc = jax.lax.dynamic_slice(scale_pc, (0, j), (q, 1))[:, 0]
        zc = jax.lax.dynamic_slice(zero_pc, (0, j), (q, 1))[:, 0]
        new = _quant_cols(beta, sc, zc, n_levels) if quantize else beta
        # Rank-1 update of ŴΣ (Eq. 12).
        sig_row = sigma[j]  # (p,)
        what_sig = what_sig + jnp.outer(new - wcol, sig_row)
        w_hat = jax.lax.dynamic_update_slice(w_hat, new[:, None], (0, j))
        return (w_hat, what_sig), None

    for it in range(iterations):
        quantize = not (
            unquantized_heuristic and (it + 1) % 3 == 0 and it != iterations - 1
        )
        step = functools.partial(col_update, quantize=quantize)
        (w_hat, what_sig), _ = jax.lax.scan(step, (w_hat, what_sig), jnp.arange(p))
    return w_hat


# ---------------------------------------------------------------------------
# Production: blocked Algorithm 2 (legacy + fused engines).
# ---------------------------------------------------------------------------


def _xla_block_sweep(beta0, sig_blk, w_old_blk, scale_blk, zero_blk, n_levels, quantize):
    """Sequential CD sweep inside one column block (XLA fallback).

    beta0:  (q, B) = P_blk − P̂_blk + (cross-block ΔŴ correction)
    sig_blk: (B, B) Σ̃ block (zero diag)
    Returns (w_new_blk, delta_blk) with delta = old − new.
    """
    q, bsz = beta0.shape

    def col(carry, i):
        delta_blk = carry
        # Intra-block correction: ΔŴ_blk (zero in cols ≥ i) @ Σ̃_blk[:, i].
        corr = delta_blk @ jax.lax.dynamic_slice(sig_blk, (0, i), (bsz, 1))[:, 0]
        beta = jax.lax.dynamic_slice(beta0, (0, i), (q, 1))[:, 0] + corr
        if quantize:
            sc = jax.lax.dynamic_slice(scale_blk, (0, i), (q, 1))[:, 0]
            zc = jax.lax.dynamic_slice(zero_blk, (0, i), (q, 1))[:, 0]
            new = _quant_cols(beta, sc, zc, n_levels)
        else:
            new = beta
        old = jax.lax.dynamic_slice(w_old_blk, (0, i), (q, 1))[:, 0]
        delta_blk = jax.lax.dynamic_update_slice(
            delta_blk, (old - new)[:, None], (0, i)
        )
        return delta_blk, new

    delta_blk, new_cols = jax.lax.scan(
        col, jnp.zeros((q, bsz), jnp.float32), jnp.arange(bsz)
    )
    return new_cols.T, delta_blk  # scan stacks (B, q) → transpose


def _block_sweep(beta0, sig_blk, w_old_blk, scale_blk, zero_blk, n_levels, quantize, use_kernel):
    if use_kernel == "xla":
        return _xla_block_sweep(
            beta0, sig_blk, w_old_blk, scale_blk, zero_blk, n_levels, quantize
        )
    # Pallas path (TPU, or interpret-mode on CPU when forced).
    from repro.kernels import ops as kops

    return kops.quantease_block_sweep(
        beta0,
        sig_blk,
        w_old_blk,
        scale_blk,
        zero_blk,
        n_levels=n_levels,
        quantize=quantize,
        interpret=(use_kernel != "pallas_hw"),
    )


def fp32_matmuls(fn):
    """Trace ``fn`` with fp32 contract precision for its matmuls: a TPU's
    default rounds fp32 operands to bf16, which moves CD codes off the
    nearest grid level.  bf16 ``matmul_dtype`` operands are cast
    explicitly."""

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return traced


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "iterations", "block_size", "unquantized_heuristic",
        "use_kernel", "matmul_dtype", "track_objective", "engine",
    ),
)
@fp32_matmuls
def quantease_quantize(
    w: jax.Array,
    sigma: jax.Array,
    spec: GridSpec,
    *,
    iterations: int = 25,
    block_size: int = 256,
    percdamp: float = 0.01,
    unquantized_heuristic: bool = True,
    w_init: Optional[jax.Array] = None,
    grid: Optional[Grid] = None,
    use_kernel: str = "auto",
    matmul_dtype: str = "float32",
    track_objective: bool = False,
    engine: str = "fused",
) -> tuple[jax.Array, Optional[jax.Array]]:
    """Blocked Algorithm 2.  Returns (Ŵ fp32, objective history or None).

    The objective history is **opt-in** (``track_objective=True`` — it costs
    an extra qp² einsum per iteration): length ``iterations``, evaluated
    *after* each iteration against the damped Σ; from the first
    fully-quantized iterate onward it is non-increasing on quantized
    iterations (Lemma 2) — asserted by tests/test_property.py.  With
    ``track_objective=False`` (the default) the second element is ``None``.

    ``engine="fused"`` (default) runs the fused-iteration engine — one qp²
    correction matmul per iteration via incremental ``base = P − P̂``
    maintenance; ``engine="legacy"`` keeps the pre-fused schedule (full
    ``Ŵ @ Σ̃`` recompute + full-width corrections) for benchmarking and
    equivalence tests.  Both apply updates in the same order, so iterates
    agree up to fp reassociation.

    **Batched:** ``w: (G, q, p)`` with ``sigma: (G, p, p)`` solves G
    independent layers in one vmapped call — the whole-model solver groups
    same-shape linears of a block (and all E experts of an MoE matrix) this
    way; ``_prep``/iteration and the Pallas kernels all carry the leading
    dim.  ``grid``/``w_init`` may be batched too (Grid leaves
    ``(G, q, n_groups)``) — the solver threads its precomputed grids
    through so emitted codes round-trip the solve exactly.
    """
    if w.ndim == 3:
        solve = functools.partial(
            _quantease_2d,
            spec=spec,
            iterations=iterations,
            block_size=block_size,
            percdamp=percdamp,
            unquantized_heuristic=unquantized_heuristic,
            use_kernel=use_kernel,
            matmul_dtype=matmul_dtype,
            track_objective=track_objective,
            engine=engine,
        )
        if w_init is None and grid is None:
            return jax.vmap(lambda wi, si: solve(wi, si, w_init=None, grid=None))(
                w, sigma
            )
        if w_init is None:
            return jax.vmap(lambda wi, si, gi: solve(wi, si, w_init=None, grid=gi))(
                w, sigma, grid
            )
        if grid is None:
            return jax.vmap(lambda wi, si, ii: solve(wi, si, w_init=ii, grid=None))(
                w, sigma, w_init
            )
        return jax.vmap(
            lambda wi, si, ii, gi: solve(wi, si, w_init=ii, grid=gi)
        )(w, sigma, w_init, grid)
    return _quantease_2d(
        w,
        sigma,
        spec=spec,
        iterations=iterations,
        block_size=block_size,
        percdamp=percdamp,
        unquantized_heuristic=unquantized_heuristic,
        w_init=w_init,
        grid=grid,
        use_kernel=use_kernel,
        matmul_dtype=matmul_dtype,
        track_objective=track_objective,
        engine=engine,
    )


def _quantease_2d(
    w: jax.Array,
    sigma: jax.Array,
    *,
    spec: GridSpec,
    iterations: int,
    block_size: int,
    percdamp: float,
    unquantized_heuristic: bool,
    w_init: Optional[jax.Array],
    grid: Optional[Grid],
    use_kernel: str,
    matmul_dtype: str,
    track_objective: bool,
    engine: str,
) -> tuple[jax.Array, Optional[jax.Array]]:
    use_kernel = _resolve_use_kernel(use_kernel)
    if matmul_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown matmul_dtype {matmul_dtype!r}")
    q, p = w.shape
    w32, sigma_d, scale_pc, zero_pc, sig_tilde, pmat, _ = _prep(
        w, sigma, spec, percdamp, grid
    )
    n_levels = spec.n_levels
    w_hat = w32 if w_init is None else w_init.astype(jnp.float32)

    bsz = min(block_size, p)
    n_blocks = -(-p // bsz)
    pad = n_blocks * bsz - p
    if pad:
        # Padded columns: zero Σ̃ coupling, unit scale ⇒ they quantize to an
        # isolated 0 and never influence real columns.
        w32 = jnp.pad(w32, ((0, 0), (0, pad)))
        w_hat = jnp.pad(w_hat, ((0, 0), (0, pad)))
        scale_pc = jnp.pad(scale_pc, ((0, 0), (0, pad)), constant_values=1.0)
        zero_pc = jnp.pad(zero_pc, ((0, 0), (0, pad)))
        sig_tilde = jnp.pad(sig_tilde, ((0, pad), (0, pad)))
        pmat = jnp.pad(pmat, ((0, 0), (0, pad)))
    p_pad = p + pad
    cdt = jnp.bfloat16 if matmul_dtype == "bfloat16" else jnp.float32

    quant_flags = [
        not (unquantized_heuristic and (it + 1) % 3 == 0 and it != iterations - 1)
        for it in range(iterations)
    ]

    if engine == "legacy":
        step = _legacy_iteration_step(
            sig_tilde, pmat, scale_pc, zero_pc, n_levels, bsz, n_blocks, use_kernel
        )
        w_hat, objs = _drive(step, w_hat, w32, sigma_d, pad, quant_flags, track_objective)
    elif engine == "fused":
        on_kernel, why = fused_engine(p, block_size, matmul_dtype, use_kernel)
        if on_kernel == "xla":
            # XLA schedule — also the fallback when the single-kernel
            # iteration's VMEM-resident slabs (Δ accumulator + Σ̃ᵀ rows)
            # can't fit for very wide layers.  Same update order, same
            # iterates.  A gate miss is reported, never silent.
            if use_kernel != "xla":
                warnings.warn(f"QuantEase ({q}×{p}) on the XLA schedule: {why}")
            step = _fused_xla_iteration_step(
                sig_tilde, scale_pc, zero_pc, n_levels, bsz, n_blocks, cdt
            )
        else:
            step = _fused_pallas_iteration_step(
                sig_tilde, scale_pc, zero_pc, n_levels, bsz, matmul_dtype,
                interpret=(use_kernel != "pallas_hw"),
            )
        # Incremental-state init: one qp² matmul for base = P − Ŵ₀Σ̃ (fp32
        # regardless of matmul_dtype — one-time cost), rolling Δ = 0.
        base = pmat - w_hat @ sig_tilde
        delta = jnp.zeros_like(base)

        def fused_step(w_hat_and_state, quantize):
            w_cur, base_c, delta_c = w_hat_and_state
            return step(w_cur, base_c, delta_c, quantize)

        sigma_pad = jnp.pad(sigma_d, ((0, pad), (0, pad))) if pad else sigma_d
        state = (w_hat, base, delta)
        objs = []
        for quantize in quant_flags:
            state = fused_step(state, quantize)
            if track_objective:
                e = w32 - state[0]
                objs.append(jnp.einsum("ij,jk,ik->", e, sigma_pad, e))
        w_hat = state[0]
    else:
        raise ValueError(f"unknown engine {engine!r}")

    return w_hat[:, :p], (jnp.stack(objs) if track_objective else None)


def _drive(step, w_hat, w32, sigma_d, pad, quant_flags, track_objective):
    sigma_pad = jnp.pad(sigma_d, ((0, pad), (0, pad))) if pad else sigma_d
    objs = []
    for quantize in quant_flags:
        w_hat = step(w_hat, quantize)
        if track_objective:
            e = w32 - w_hat
            objs.append(jnp.einsum("ij,jk,ik->", e, sigma_pad, e))
    return w_hat, objs


def _legacy_iteration_step(
    sig_tilde, pmat, scale_pc, zero_pc, n_levels, bsz, n_blocks, use_kernel
):
    """Pre-fused schedule: full P̂ recompute + full-width Δ corrections."""
    q = pmat.shape[0]
    p_pad = sig_tilde.shape[0]

    def iteration(w_hat, quantize):
        p_hat = w_hat @ sig_tilde  # P̂ (zero-diag Σ̃) — one qp² matmul
        base = pmat - p_hat

        def block(carry, b):
            w_new, delta = carry  # delta: (q, p_pad), old−new, zero if unprocessed
            col0 = b * bsz
            # Cross-block correction: ΔŴ @ Σ̃[:, blk].  Unprocessed columns of
            # ΔŴ are zero, so the full matmul is exact.
            sig_cols = jax.lax.dynamic_slice(sig_tilde, (0, col0), (p_pad, bsz))
            beta0 = (
                jax.lax.dynamic_slice(base, (0, col0), (q, bsz)) + delta @ sig_cols
            )
            sig_blk = jax.lax.dynamic_slice(sig_tilde, (col0, col0), (bsz, bsz))
            w_old_blk = jax.lax.dynamic_slice(w_hat, (0, col0), (q, bsz))
            s_blk = jax.lax.dynamic_slice(scale_pc, (0, col0), (q, bsz))
            z_blk = jax.lax.dynamic_slice(zero_pc, (0, col0), (q, bsz))
            new_blk, delta_blk = _block_sweep(
                beta0, sig_blk, w_old_blk, s_blk, z_blk, n_levels, quantize, use_kernel
            )
            w_new = jax.lax.dynamic_update_slice(w_new, new_blk, (0, col0))
            delta = jax.lax.dynamic_update_slice(delta, delta_blk, (0, col0))
            return (w_new, delta), None

        (w_new, _), _ = jax.lax.scan(
            block, (w_hat, jnp.zeros((q, p_pad), jnp.float32)), jnp.arange(n_blocks)
        )
        return w_new

    return iteration


def _xla_block_sweep_t(beta0_t, sig_t, w_old_t, scale_t, zero_t, n_levels, quantize):
    """Transposed, xs-fed intra-block sweep (fused-engine XLA path).

    Same update order as :func:`_xla_block_sweep` — identical iterates —
    but every per-column operand arrives as a scan ``xs`` row and the Δ
    accumulator is carried transposed (B, q), so each step is one
    contiguous-row gemv + one contiguous-row store instead of five strided
    (q, 1) column slices.  On CPU XLA this roughly halves the sequential
    sweep's per-column cost (the floor the fused engine's matmul savings
    sit on top of).
    """
    bsz, q = beta0_t.shape

    def col(delta_t, xs):
        i, sig_row, b0, ws, sc, zc = xs
        beta = b0 + sig_row @ delta_t  # Σ̃[:, i] · Δ — rows ≥ i still zero
        if quantize:
            new = (jnp.clip(jnp.round(beta / sc) + zc, 0, n_levels - 1) - zc) * sc
        else:
            new = beta
        delta_t = jax.lax.dynamic_update_slice(delta_t, (ws - new)[None], (i, 0))
        return delta_t, new

    delta_t, new_t = jax.lax.scan(
        col,
        jnp.zeros((bsz, q), jnp.float32),
        (jnp.arange(bsz), sig_t, beta0_t, w_old_t, scale_t, zero_t),
    )
    return new_t, delta_t  # both (B, q)


def _fused_xla_iteration_step(
    sig_tilde, scale_pc, zero_pc, n_levels, bsz, n_blocks, cdt
):
    """Fused engine, XLA path: rolling-Δ incremental base maintenance.

    The rolling Δ buffer holds, when block b is processed, the *current*
    iteration's Δ for blocks < b (triangular prefix) and the *previous*
    iteration's Δ for blocks ≥ b — so one full-width correction matmul per
    block both applies the triangular correction and amortises the
    incremental ``base = P − P̂`` update.  qp² FLOPs per iteration total
    (the legacy schedule pays 2·qp² plus another qp² for its always-on
    objective).  ``cdt`` casts the correction operands (bf16 Σ̃ option);
    accumulation and the sweep stay fp32.

    Per-block operands are pre-stacked once and fed through scan ``xs``;
    per-block results come back as stacked ``ys`` (blocks partition the
    columns, so reassembly is a transpose+reshape) — the only carry is the
    rolling Δ, which each block's correction genuinely reads in full.
    """
    q = scale_pc.shape[0]
    p_pad = sig_tilde.shape[0]

    def stack_cols(a):  # (q, p_pad) → (n_blocks, B, q): block-major, transposed
        return a.reshape(q, n_blocks, bsz).transpose(1, 2, 0)

    # Σ̃ᵀ split row-blocks: slab b = Σ̃[:, blk_b]ᵀ, and its cols [blk_b] are
    # the transposed diagonal block the intra-sweep needs.
    sig_rows = sig_tilde.T.reshape(n_blocks, bsz, p_pad)
    sig_rows_c = sig_rows.astype(cdt)
    sig_diag_t = jnp.stack(
        [sig_rows[b, :, b * bsz : (b + 1) * bsz] for b in range(n_blocks)]
    )  # (n_blocks, B, B), row i = Σ̃_blk[:, i]
    scale_t = stack_cols(scale_pc)
    zero_t = stack_cols(zero_pc)

    def unstack(ys_t):  # (n_blocks, B, q) → (q, p_pad)
        return ys_t.transpose(2, 0, 1).reshape(q, p_pad)

    def iteration(w_hat, base, delta, quantize):
        base_b = stack_cols(base)
        w_old_b = stack_cols(w_hat)

        def block(delta_ct, xs):
            b, sg_rows, sg_t, base_t, w_old_t, s_t, z_t = xs
            corr = jnp.dot(
                sg_rows, delta_ct.astype(cdt), preferred_element_type=jnp.float32
            )  # (B, q) — full-width rolling-Δ correction, transposed
            beta0_t = base_t + corr
            # beta0 is exactly P_blk − (Ŵ entering this block) Σ̃ — it is
            # this block's base invariant for the *next* iteration.
            new_t, delta_t = _xla_block_sweep_t(
                beta0_t, sg_t, w_old_t, s_t, z_t, n_levels, quantize
            )
            delta_ct = jax.lax.dynamic_update_slice(delta_ct, delta_t, (b * bsz, 0))
            return delta_ct, (new_t, beta0_t, delta_t)

        _, (new_b, beta0_b, delta_b) = jax.lax.scan(
            block,
            delta.T,  # rolling Δ carried transposed (p_pad, q): contiguous updates
            (jnp.arange(n_blocks), sig_rows_c, sig_diag_t, base_b, w_old_b,
             scale_t, zero_t),
        )
        return unstack(new_b), unstack(beta0_b), unstack(delta_b)

    return iteration


def _fused_pallas_iteration_step(
    sig_tilde, scale_pc, zero_pc, n_levels, bsz, matmul_dtype, interpret
):
    """Fused engine, Pallas path: one kernel launch per iteration."""
    from repro.kernels import ops as kops

    def iteration(w_hat, base, delta, quantize):
        return kops.quantease_fused_iteration(
            base,
            sig_tilde,
            w_hat,
            scale_pc,
            zero_pc,
            delta,
            n_levels=n_levels,
            quantize=quantize,
            bsz=bsz,
            matmul_dtype=matmul_dtype,
            interpret=interpret,
        )

    return iteration

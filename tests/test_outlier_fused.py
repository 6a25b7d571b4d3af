"""Fused outlier-aware CD engine (DESIGN.md §Outlier-aware-fused): engine
parity, scanned outer loop, single-launch kernel, sparse-Ĥ COO artifact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import outlier
from repro.core.outlier import outlier_quantease, power_lambda_max
from repro.core.quantease import relative_error
from repro.kernels import ops, ref
from repro.quant import GridSpec, compute_grid

SPEC3 = GridSpec(bits=3)


def _problem(seed, q, p, n):
    r = np.random.default_rng(seed)
    x = r.standard_normal((p, n)).astype(np.float32)
    w = r.standard_normal((q, p)).astype(np.float32)
    w[r.random((q, p)) < 0.003] *= 10.0
    return jnp.asarray(w), jnp.asarray(x @ x.T)


# ---------------------------------------------------------------------------
# Engine parity (fused vs legacy schedule)
# ---------------------------------------------------------------------------


def test_fused_matches_legacy_unstructured(layer_problem):
    """Same update order ⇒ same iterates: the fused engine reproduces the
    legacy schedule exactly up to fp reassociation (the top-s support can
    only differ on near-ties, absorbed by the error-level bound)."""
    w, sigma = layer_problem
    s = int(0.01 * w.size)
    kw = dict(s=s, iterations=8, use_kernel="xla")
    rl = outlier_quantease(w, sigma, SPEC3, engine="legacy", **kw)
    rf = outlier_quantease(w, sigma, SPEC3, engine="fused", **kw)
    el = float(relative_error(w, rl.w_eff, sigma))
    ef = float(relative_error(w, rf.w_eff, sigma))
    assert ef <= el * 1.01 + 1e-7
    assert int((np.asarray(rf.h) != 0).sum()) <= s
    # Generic data has no projection ties, so the iterates agree tightly.
    np.testing.assert_allclose(
        np.asarray(rl.w_hat), np.asarray(rf.w_hat), rtol=0, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(rl.h), np.asarray(rf.h), rtol=0, atol=2e-4
    )


def test_fused_matches_legacy_structured(layer_problem):
    w, sigma = layer_problem
    q = w.shape[0]
    s = int(0.02 * w.size)
    kw = dict(s=s, iterations=6, structured=True, use_kernel="xla")
    rl = outlier_quantease(w, sigma, SPEC3, engine="legacy", **kw)
    rf = outlier_quantease(w, sigma, SPEC3, engine="fused", **kw)
    el = float(relative_error(w, rl.w_eff, sigma))
    ef = float(relative_error(w, rf.w_eff, sigma))
    assert ef <= el * 1.01 + 1e-7
    nz_cols = np.nonzero(np.abs(np.asarray(rf.h)).sum(0))[0]
    assert len(nz_cols) <= max(s // q, 1)
    np.testing.assert_allclose(
        np.asarray(rl.w_hat), np.asarray(rf.w_hat), rtol=0, atol=2e-4
    )


def test_fused_bf16_within_tolerance(layer_problem):
    """bf16 Σ̃ correction/residual operands keep solution quality at the fp32
    level (the bf16-tolerance contract of tests/test_fused_engine.py)."""
    w, sigma = layer_problem
    s = int(0.01 * w.size)
    kw = dict(s=s, iterations=8, use_kernel="xla", engine="fused")
    e32 = float(relative_error(
        w, outlier_quantease(w, sigma, SPEC3, matmul_dtype="float32", **kw).w_eff,
        sigma))
    ebf = float(relative_error(
        w, outlier_quantease(w, sigma, SPEC3, matmul_dtype="bfloat16", **kw).w_eff,
        sigma))
    assert ebf <= e32 * 1.05 + 1e-6


def test_fused_padding_non_multiple_block(layer_problem):
    """p not a multiple of the sweep block: padded columns quantize to
    isolated zeros and never enter the outlier budget."""
    r = np.random.default_rng(3)
    q, p = 48, 100  # pads to 128
    w = jnp.asarray(r.standard_normal((q, p)).astype(np.float32))
    x = r.standard_normal((p, 300)).astype(np.float32)
    sigma = jnp.asarray(x @ x.T)
    s = 50
    rl = outlier_quantease(w, sigma, SPEC3, s=s, iterations=5, engine="legacy",
                           use_kernel="xla")
    rf = outlier_quantease(w, sigma, SPEC3, s=s, iterations=5, engine="fused",
                           use_kernel="xla")
    np.testing.assert_allclose(
        np.asarray(rl.w_hat), np.asarray(rf.w_hat), rtol=0, atol=2e-4
    )
    assert rf.h.shape == (q, p)
    assert int((np.asarray(rf.h) != 0).sum()) <= s


def test_objective_optin_and_matches_legacy(layer_problem):
    """Objective history is opt-in (None by default) and, when tracked, the
    fused engine's resident-state evaluation equals the legacy einsum."""
    w, sigma = layer_problem
    s = int(0.01 * w.size)
    assert outlier_quantease(w, sigma, SPEC3, s=s, iterations=2).objective is None
    kw = dict(s=s, iterations=5, use_kernel="xla", track_objective=True)
    ol = outlier_quantease(w, sigma, SPEC3, engine="legacy", **kw).objective
    of = outlier_quantease(w, sigma, SPEC3, engine="fused", **kw).objective
    assert of.shape == (5,)
    np.testing.assert_allclose(np.asarray(ol), np.asarray(of), rtol=1e-4)


# ---------------------------------------------------------------------------
# Single-launch kernel + scanned outer loop
# ---------------------------------------------------------------------------


def test_fused_kernel_matches_xla():
    w, sigma = _problem(5, 96, 128, 256)
    s = int(0.01 * w.size)
    kw = dict(s=s, iterations=4, engine="fused")
    rx = outlier_quantease(w, sigma, SPEC3, use_kernel="xla", **kw)
    rp = outlier_quantease(w, sigma, SPEC3, use_kernel="pallas", **kw)
    np.testing.assert_allclose(
        np.asarray(rx.w_hat), np.asarray(rp.w_hat), rtol=0, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(rx.h), np.asarray(rp.h), rtol=0, atol=1e-4
    )


def test_outlier_kernel_matches_ref():
    """The single-launch kernel reproduces the pure-jnp oracle: sweep,
    base/Δ bookkeeping with the lazy dĤ fold, and the exact residual."""
    r = np.random.default_rng(7)
    q, p, bsz = 32, 64, 32
    w = jnp.asarray(r.standard_normal((q, p)).astype(np.float32))
    x = r.standard_normal((p, 200)).astype(np.float32)
    from repro.core.calib import damp_sigma

    sk = damp_sigma(jnp.asarray(x @ x.T), 0.01)
    diag = jnp.diag(sk)
    st = sk / diag[None, :] - jnp.eye(p)
    g = compute_grid(w, SPEC3)
    sc, zc = g.per_column(p)
    dprev = jnp.asarray(r.standard_normal((q, p)).astype(np.float32)) * 0.01
    dhp = jnp.asarray(r.standard_normal((q, p)).astype(np.float32)) * 0.01
    kw = dict(n_levels=SPEC3.n_levels, quantize=True, bsz=bsz)
    outs_k = ops.quantease_outlier_iteration(
        w, st, w, sc, zc, dprev, dhp, interpret=True, **kw
    )
    outs_r = ref.quantease_outlier_iteration_ref(w, st, w, sc, zc, dprev, dhp, **kw)
    for a, b in zip(outs_k, outs_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_single_launch_per_outer_iteration_and_scanned_loop():
    """The fused Pallas path issues ONE kernel dispatch per scan body — and
    because the outer loop is a lax.scan, the dispatcher is *traced* exactly
    once regardless of `iterations` (the pre-PR loop traced 25 copies) and
    never falls back to per-block sweep launches."""
    w, sigma = _problem(11, 64, 128, 256)
    s = int(0.01 * w.size)
    n_outlier = n_block = 0
    orig_o = ops.quantease_outlier_iteration_t
    orig_b = ops.quantease_block_sweep

    def count_o(*a, **k):
        nonlocal n_outlier
        n_outlier += 1
        return orig_o(*a, **k)

    def count_b(*a, **k):
        nonlocal n_block
        n_block += 1
        return orig_b(*a, **k)

    ops.quantease_outlier_iteration_t = count_o
    ops.quantease_block_sweep = count_b
    try:
        # eager internal entry point: tracing happens here, uncached
        outlier._outlier_2d(
            w, sigma, spec=SPEC3, s=s, iterations=6, structured=False,
            percdamp=0.01, cd_block_size=128, use_kernel="pallas",
            matmul_dtype="float32", track_objective=False, engine="fused",
            lam_iters=64,
        )
    finally:
        ops.quantease_outlier_iteration_t = orig_o
        ops.quantease_block_sweep = orig_b
    assert n_outlier == 1  # one traced dispatch inside the scan body
    assert n_block == 0  # no per-block launches anywhere


def test_vmem_overflow_falls_back_to_xla():
    """Layers whose single-launch kernel can't fit VMEM must take the XLA
    schedule (same iterates) instead of raising — the base engine's
    fallback contract."""
    w, sigma = _problem(29, 48, 64, 128)
    s = 30
    orig = ops.outlier_iteration_tq
    ops.outlier_iteration_tq = lambda *a, **k: None  # force "doesn't fit"
    try:
        r_fb = outlier._outlier_2d(
            w, sigma, spec=SPEC3, s=s, iterations=3, structured=False,
            percdamp=0.01, cd_block_size=64, use_kernel="pallas",
            matmul_dtype="float32", track_objective=False, engine="fused",
            lam_iters=64,
        )
    finally:
        ops.outlier_iteration_tq = orig
    r_x = outlier_quantease(w, sigma, SPEC3, s=s, iterations=3,
                            use_kernel="xla")
    np.testing.assert_allclose(
        np.asarray(r_fb.w_hat), np.asarray(r_x.w_hat), atol=1e-5
    )


def test_eta_computed_once_outside_scanned_loop():
    """Regression: η = 1/(2λ_max) is computed once per solve, not per outer
    iteration (power_lambda_max must sit outside the scanned loop)."""
    w, sigma = _problem(13, 48, 64, 128)
    n_calls = 0
    orig = outlier.power_lambda_max

    def counting(*a, **k):
        nonlocal n_calls
        n_calls += 1
        return orig(*a, **k)

    outlier.power_lambda_max = counting
    try:
        for engine in ("fused", "legacy"):
            n_calls = 0
            outlier._outlier_2d(
                w, sigma, spec=SPEC3, s=30, iterations=7, structured=False,
                percdamp=0.01, cd_block_size=64, use_kernel="xla",
                matmul_dtype="float32", track_objective=False, engine=engine,
                lam_iters=64,
            )
            assert n_calls == 1, engine
    finally:
        outlier.power_lambda_max = orig


def test_power_lambda_max_iters_and_tol():
    r = np.random.default_rng(17)
    a = r.standard_normal((48, 96)).astype(np.float32)
    sigma = jnp.asarray(a @ a.T)
    lam_true = float(np.linalg.eigvalsh(np.asarray(sigma)).max())
    lam = float(power_lambda_max(sigma))
    assert abs(lam - lam_true) / lam_true < 1e-3
    # iters is configurable and a tight cap still lands in the ballpark
    lam8 = float(power_lambda_max(sigma, iters=8))
    assert abs(lam8 - lam_true) / lam_true < 0.2
    # a loose tol early-outs without leaving the ballpark
    lam_loose = float(power_lambda_max(sigma, tol=1e-2))
    assert abs(lam_loose - lam_true) / lam_true < 0.2


# ---------------------------------------------------------------------------
# Batched (vmapped) solves
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", ["xla", "pallas"])
def test_batched_vmap_matches_per_slice(use_kernel):
    G = 3
    probs = [_problem(19 + g, 48, 64, 128) for g in range(G)]
    w3 = jnp.stack([pr[0] for pr in probs])
    sig3 = jnp.stack([pr[1] for pr in probs])
    s = int(0.01 * w3[0].size)
    kw = dict(s=s, iterations=3, engine="fused", use_kernel=use_kernel)
    rb = outlier_quantease(w3, sig3, SPEC3, **kw)
    for g in range(G):
        rg = outlier_quantease(w3[g], sig3[g], SPEC3, **kw)
        np.testing.assert_allclose(
            np.asarray(rb.w_hat[g]), np.asarray(rg.w_hat), atol=1e-5
        )
        np.testing.assert_allclose(np.asarray(rb.h[g]), np.asarray(rg.h), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(jax.tree.map(lambda x: x[g], rb.grid).scale),
            np.asarray(rg.grid.scale),
        )


# ---------------------------------------------------------------------------
# Sparse-Ĥ COO artifact + serving parity
# ---------------------------------------------------------------------------


def test_emit_qt_coo_roundtrip(layer_problem):
    """emit='qt' stores Ĥ as int32 flat indices + fp16 values; dequantizing
    codes + COO reproduces Ŵ exactly and Ĥ to fp16 rounding."""
    from repro.core.solver import PTQConfig, _emit_leaf
    from repro.quant import dequantize_tensor

    w, sigma = layer_problem
    s = int(0.01 * w.size)
    res = outlier_quantease(w, sigma, SPEC3, s=s, iterations=6, use_kernel="xla")
    cfg = PTQConfig(method="qe_outlier", spec=SPEC3, outlier_frac=0.01, emit="qt")
    qt = _emit_leaf(res.w_hat, res.h, w, cfg, grid=res.grid)
    assert qt.outlier_idx.dtype == jnp.int32
    assert qt.outlier_values.dtype == jnp.float16
    assert qt.outlier_idx.shape == (s,)
    deq = dequantize_tensor(qt)
    h16 = np.asarray(res.h).astype(np.float16).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(deq), np.asarray(res.w_hat) + h16, rtol=0, atol=1e-5
    )


def test_apply_linear_coo_matches_dense_ref(layer_problem):
    """Serving: apply_linear's post-GEMM COO correction equals the dense
    (dequant + Ĥ) matmul."""
    from repro.core.solver import PTQConfig, _emit_leaf
    from repro.models.common import apply_linear
    from repro.quant import dequantize_tensor

    w, sigma = layer_problem
    q, p = w.shape
    s = int(0.01 * w.size)
    res = outlier_quantease(w, sigma, SPEC3, s=s, iterations=4, use_kernel="xla")
    cfg = PTQConfig(method="qe_outlier", spec=SPEC3, outlier_frac=0.01, emit="qt")
    qt = _emit_leaf(res.w_hat, res.h, w, cfg, grid=res.grid)
    r = np.random.default_rng(23)
    x = jnp.asarray(r.standard_normal((5, p)).astype(np.float32))
    y = apply_linear(qt, x)
    w_eff = dequantize_tensor(qt)  # codes + fp16 COO, the artifact's truth
    y_ref = x @ w_eff.T
    np.testing.assert_allclose(
        np.asarray(y.astype(jnp.float32)), np.asarray(y_ref),
        rtol=1e-4, atol=1e-3,
    )


def test_solver_groups_outlier_layers(layer_problem):
    """The grouped solver batches same-shape outlier layers through one
    vmapped fused solve and scatters per-layer grids/h back."""
    from repro.core.solver import PTQConfig, _solve_group

    w, sigma = layer_problem
    G = 2
    w3 = jnp.stack([w, w * 1.2])
    sig3 = jnp.stack([sigma, sigma])
    cfg = PTQConfig(method="qe_outlier", spec=SPEC3, iterations=3,
                    outlier_frac=0.01)
    w_hat3, hs, grid3 = _solve_group(w3, sig3, cfg, mesh=None)
    assert w_hat3.shape == w3.shape
    assert len(hs) == G and all(h is not None for h in hs)
    assert grid3.scale.shape[0] == G and grid3.zero.shape[0] == G  # one grid per layer
    s = max(int(cfg.outlier_frac * w.size), 1)
    for g in range(G):
        assert int((np.asarray(hs[g]) != 0).sum()) <= s
        e = float(relative_error(w3[g], w_hat3[g] + hs[g], sig3[g]))
        assert np.isfinite(e) and e < 1.0

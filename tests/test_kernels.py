"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.quant import pack_codes


def _sweep_problem(seed, q, bsz):
    r = np.random.default_rng(seed)
    x = r.standard_normal((bsz, 4 * bsz)).astype(np.float32)
    s = x @ x.T
    sn = s / np.diag(s)[None, :]
    np.fill_diagonal(sn, 0.0)
    return (
        jnp.asarray(r.standard_normal((q, bsz)).astype(np.float32)),
        jnp.asarray(sn.astype(np.float32)),
        jnp.asarray(r.standard_normal((q, bsz)).astype(np.float32)),
        jnp.asarray((r.random((q, bsz)) * 0.2 + 0.05).astype(np.float32)),
        jnp.asarray(r.integers(0, 15, (q, bsz)).astype(np.float32)),
    )


@pytest.mark.parametrize("q,bsz", [(8, 16), (64, 32), (130, 64), (96, 128)])
@pytest.mark.parametrize("quantize", [True, False])
@pytest.mark.parametrize("n_levels", [4, 16])
def test_cd_sweep_matches_ref(q, bsz, quantize, n_levels):
    args = _sweep_problem(q * bsz, q, bsz)
    wk, dk = ops.quantease_block_sweep(
        *args, n_levels=n_levels, quantize=quantize, interpret=True
    )
    wr, dr = ref.quantease_block_sweep_ref(*args, n_levels=n_levels, quantize=quantize)
    scale = float(jnp.max(jnp.abs(wr))) + 1e-9
    assert float(jnp.max(jnp.abs(wk - wr))) / scale < 1e-5
    assert float(jnp.max(jnp.abs(dk - dr))) / scale < 1e-5


def test_cd_sweep_batched_matches_loop():
    """Leading group dim (grouped-block solver path) == per-slice sweeps."""
    G = 3
    probs = [_sweep_problem(11 + g, 24, 16) for g in range(G)]
    stacked = [jnp.stack([p[j] for p in probs]) for j in range(5)]
    wb, db = ops.quantease_block_sweep(
        *stacked, n_levels=16, quantize=True, interpret=True
    )
    assert wb.shape == (G, 24, 16)
    for g in range(G):
        wg, dg = ops.quantease_block_sweep(
            *probs[g], n_levels=16, quantize=True, interpret=True
        )
        np.testing.assert_allclose(np.asarray(wb[g]), np.asarray(wg), atol=1e-6)
        np.testing.assert_allclose(np.asarray(db[g]), np.asarray(dg), atol=1e-6)


@pytest.mark.parametrize(
    "m,p,q,xdt",
    [
        (4, 64, 16, jnp.float32),
        (33, 130, 50, jnp.bfloat16),
        (128, 512, 128, jnp.bfloat16),
        (1, 256, 64, jnp.float32),
    ],
)
def test_dequant_matmul_matches_ref(m, p, q, xdt):
    r = np.random.default_rng(m * p + q)
    x = jnp.asarray(r.standard_normal((m, p)), xdt)
    codes = jnp.asarray(r.integers(0, 16, (q, p)).astype(np.uint8))
    scale = jnp.asarray((r.random(q) * 0.1 + 0.01).astype(np.float32))
    zero = jnp.asarray(r.integers(0, 16, q).astype(np.float32))
    y_k = ops.dequant_matmul(x, codes, scale, zero, out_dtype=jnp.float32, interpret=True)
    y_r = ref.dequant_matmul_ref(x, codes, scale, zero)
    rel = float(jnp.max(jnp.abs(y_k - y_r)) / (jnp.max(jnp.abs(y_r)) + 1e-9))
    assert rel < 2e-6


@settings(max_examples=10, deadline=None)
@given(
    m=st.integers(1, 40),
    p=st.sampled_from([32, 64, 128, 320]),
    q=st.integers(2, 70),
    seed=st.integers(0, 2**31 - 1),
)
def test_dequant_matmul_packed4_property(m, p, q, seed):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((m, p)).astype(np.float32))
    codes = jnp.asarray(r.integers(0, 16, (q, p)).astype(np.uint8))
    scale = jnp.asarray((r.random(q) * 0.1 + 0.01).astype(np.float32))
    zero = jnp.asarray(r.integers(0, 16, q).astype(np.float32))
    packed = pack_codes(codes, 4)
    y_k = ops.dequant_matmul(
        x, packed, scale, zero, packed4=True, out_dtype=jnp.float32, interpret=True
    )
    y_r = ref.dequant_matmul_ref(x, codes, scale, zero)
    rel = float(jnp.max(jnp.abs(y_k - y_r)) / (jnp.max(jnp.abs(y_r)) + 1e-9))
    assert rel < 2e-6


def test_quantease_kernel_path_equals_xla(layer_problem):
    from repro.core import quantease_quantize
    from repro.quant import GridSpec

    w, sigma = layer_problem
    wx, _ = quantease_quantize(
        w, sigma, GridSpec(bits=4), iterations=3, block_size=32, use_kernel="xla"
    )
    wp, _ = quantease_quantize(
        w, sigma, GridSpec(bits=4), iterations=3, block_size=32, use_kernel="pallas"
    )
    np.testing.assert_allclose(np.asarray(wx), np.asarray(wp), atol=1e-5)


def test_dequant_matmul_tile_layout_bit_exact(rng):
    """The tile-native prepacked GEMM returns bit-identical results to the
    linear-packed dispatch — the reorder is a pure column permutation the
    kernel (or the un-prepacking ref) undoes exactly."""
    from repro.kernels.dequant_matmul import select_tile_k
    from repro.quant.pack import prepack_codes

    m, p, q = 4, 1024, 64
    codes = rng.integers(0, 16, (q, p)).astype(np.uint8)
    scale = jnp.asarray((rng.random((q, 1)) * 0.1 + 0.01).astype(np.float32))
    zero = jnp.asarray(rng.integers(0, 16, (q, 1)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, p)).astype(np.float32))
    y_lin = ops.dequant_matmul(
        x, pack_codes(jnp.asarray(codes), 4), scale, zero,
        packed4=True, out_dtype=jnp.float32, interpret=True,
    )
    tk = select_tile_k(p, None)
    pre = prepack_codes(jnp.asarray(codes), 4, tk)
    y_tile = ops.dequant_matmul(
        x, pre, scale, zero, packed4=True, pack_layout="tile", pack_tile=tk,
        out_dtype=jnp.float32, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(y_lin), np.asarray(y_tile))


def test_dequant_matmul_tile_layout_grouped(rng):
    """Tile layout under a grouped grid (whole-groups tiling: tk snaps to a
    group multiple) still matches the linear dispatch bit-for-bit."""
    from repro.kernels.dequant_matmul import select_tile_k
    from repro.quant.pack import prepack_codes

    m, p, q, gsz = 3, 1024, 32, 256
    n_groups = p // gsz
    codes = rng.integers(0, 16, (q, p)).astype(np.uint8)
    scale = jnp.asarray((rng.random((q, n_groups)) * 0.1 + 0.01).astype(np.float32))
    zero = jnp.asarray(rng.integers(0, 16, (q, n_groups)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, p)).astype(np.float32))
    y_lin = ops.dequant_matmul(
        x, pack_codes(jnp.asarray(codes), 4), scale, zero,
        packed4=True, group_size=gsz, out_dtype=jnp.float32, interpret=True,
    )
    tk = select_tile_k(p, gsz)
    assert tk % gsz == 0  # whole-groups tiling for this shape
    pre = prepack_codes(jnp.asarray(codes), 4, tk)
    y_tile = ops.dequant_matmul(
        x, pre, scale, zero, packed4=True, group_size=gsz,
        pack_layout="tile", pack_tile=tk, out_dtype=jnp.float32, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(y_lin), np.asarray(y_tile))


def test_reference_fallback_is_counted_on_tpu(rng, monkeypatch):
    """On a TPU, a serving dispatcher that takes its XLA reference instead
    of the kernel is counted (a chip run requires zero); off the chip the
    reference is the normal path and is not counted."""
    m, p, q = 2, 96, 16  # 96 columns in groups of 40: a ragged last group
    codes = jnp.asarray(rng.integers(0, 16, (q, p)).astype(np.uint8))
    scale = jnp.asarray((rng.random((q, 3)) * 0.1 + 0.01).astype(np.float32))
    zero = jnp.asarray(rng.integers(0, 16, (q, 3)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((m, p)).astype(np.float32))
    want = ref.dequant_matmul_ref(x, codes, scale, zero, group_size=40)
    monkeypatch.setattr(ops, "fallbacks", type(ops.fallbacks)())
    ops.dequant_matmul(x, codes, scale, zero, group_size=40, out_dtype=jnp.float32)
    assert not ops.fallbacks
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    got = ops.dequant_matmul(
        x, codes, scale, zero, group_size=40, out_dtype=jnp.float32
    )
    assert dict(ops.fallbacks) == {("dequant_matmul", "ragged-groups"): 1}
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

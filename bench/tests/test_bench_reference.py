"""The plain references and the seeded weights, on sizes a CPU test holds:
the coordinate descent against a column-by-column loop written from its
definition, the grid and objective against hand values, one block's
capture against its definition, and the reference's regenerated block
against the weights the program is given."""

import bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lib import harness, reference, weights


def _naive_cd(w, sigma, bits, iterations, percdamp, every):
    """QuantEase by its definition, one column at a time, in float64."""
    w = np.asarray(w, np.float64)
    q, p = w.shape
    n = 2**bits - 1
    s = np.asarray(sigma, np.float64) + percdamp * np.mean(np.diag(sigma)) * np.eye(p)
    sn = s / np.diag(s)[None, :]
    st = sn - np.eye(p)
    pm = w @ sn
    wmin = np.minimum(w.min(1), 0.0)
    wmax = np.maximum(w.max(1), 0.0)
    scale = np.maximum((wmax - wmin) / n, 1e-12)
    zero = np.round(-wmin / scale)
    wh = w.copy()
    for it in range(iterations):
        quantize = (it + 1) % every != 0 or it == iterations - 1
        for j in range(p):
            beta = pm[:, j] - wh @ st[:, j]
            if quantize:
                beta = (np.clip(np.round(beta / scale) + zero, 0, n) - zero) * scale
            wh[:, j] = beta
    return wh


@pytest.mark.parametrize("bits, iterations, every", [(4, 4, 3), (3, 6, 3), (4, 5, 2)])
def test_cd_solve_matches_its_definition(bits, iterations, every):
    rng = np.random.default_rng(bits * 100 + iterations)
    w = rng.normal(0, 0.02, (6, 16)).astype(np.float32)
    x = rng.normal(0, 1, (64, 16)).astype(np.float32)
    sigma = x.T @ x
    wh, _, _ = reference.cd_solve(jnp.asarray(w), jnp.asarray(sigma), bits, iterations, 0.01,
                                  every, bsz=4)
    np.testing.assert_allclose(np.asarray(wh), _naive_cd(w, sigma, bits, iterations, 0.01,
                                                        every), atol=1e-5)


def test_cd_solve_beats_rounding_to_nearest():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(0, 0.02, (8, 32)), jnp.float32)
    x = rng.normal(0, 1, (128, 32))
    x[:, 1:] += 0.8 * x[:, :-1]  # correlated inputs, where CD pays
    sigma = jnp.asarray(x.T @ x, jnp.float32)
    wh, scale, zero = reference.cd_solve(w, sigma, 3, 8, 0.01, bsz=8)
    rtn = (jnp.clip(jnp.round(w / scale) + zero, 0, 7) - zero) * scale
    assert reference.objective(w, wh, sigma) < 0.9 * reference.objective(w, rtn, sigma)


def test_grid_and_objective_by_hand():
    w = jnp.asarray([[-1.0, 0.5], [0.2, 0.6]])
    scale, zero = reference.grid(w, 2)
    np.testing.assert_allclose(np.asarray(scale)[:, 0], [0.5, 0.2], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(zero)[:, 0], [2.0, 0.0])
    sigma = jnp.asarray([[2.0, 1.0], [1.0, 3.0]])
    assert float(reference.objective(w, w, sigma)) == 0.0
    # e = (1, 0) in row 0 only: eᵀΣe = Σ₀₀.
    assert float(reference.objective(w, w - jnp.asarray([[1.0, 0.0], [0.0, 0.0]]),
                                     sigma)) == pytest.approx(2.0)


def test_capture_chunk_gram_of_attention_input():
    rng = np.random.default_rng(5)
    d, ff, h = 16, 32, 2
    dims = (("h", h), ("hd", d // h), ("kv", h))
    blk = {n: jnp.asarray(rng.normal(0, 0.2, s), jnp.bfloat16) for n, s in
           {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d), "wg": (d, ff),
            "wu": (d, ff), "wd": (ff, d)}.items()}
    blk["ln/scale"] = jnp.zeros((d,), jnp.bfloat16)
    blk["ln2/scale"] = jnp.zeros((d,), jnp.bfloat16)
    x = jnp.asarray(rng.normal(0, 1, (2, 8, d)), jnp.bfloat16)
    sig = reference.capture_chunk(x, blk, dims, 10000.0)
    assert [s.shape for s in sig] == [(d, d), (d, d), (d, d), (ff, ff)]
    for s in sig:
        np.testing.assert_allclose(np.asarray(s), np.asarray(s).T, rtol=1e-6)
    xf = np.asarray(x, np.float32).reshape(-1, d)
    hn = xf / np.sqrt(np.mean(xf * xf, -1, keepdims=True) + 1e-6)
    hn = np.asarray(jnp.asarray(hn, jnp.bfloat16), np.float32)  # kept in bf16
    np.testing.assert_allclose(np.asarray(sig[0]), hn.T @ hn, rtol=2e-2, atol=1e-2)


def _shapes():
    from repro.models import make_plan
    from repro.models.model import param_shapes

    cell = bench_tiny.tiny_cell("phi3.quantize")
    return param_shapes(make_plan(harness.model_config(cell.config)))


@pytest.mark.parametrize("layer", [0, 1])
def test_reference_block_is_the_programs_block(layer):
    """The reference regenerates one block's weights from the seed alone;
    they are the weights the whole-model call gives the program."""
    shapes = _shapes()
    seed = 2**33 + 11
    dense = weights.make_dense(shapes, seed)
    blk = weights.dense_block(shapes, seed, layer)
    for name, leaf in blk.items():
        node = dense["dec"]["b0"]
        for k in name.split("/"):
            node = node[k]
        assert leaf.dtype == node.dtype
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(node[layer]), err_msg=name)


def test_top_leaf_and_seeds_past_32_bits():
    shapes = _shapes()
    a = weights.make_dense(shapes, 2**33 + 11)
    np.testing.assert_array_equal(np.asarray(weights.top_leaf(shapes, 2**33 + 11, "embed")),
                                  np.asarray(a["embed"]))
    b = weights.make_dense(shapes, 11)  # the same low 32 bits
    assert not np.array_equal(np.asarray(a["embed"]), np.asarray(b["embed"]))
    assert jax.tree.structure(a) == jax.tree.structure(b)


def test_weights_program_is_the_same_for_every_seed():
    """The seed enters the weights program as an argument, so a new seed
    finds the program in the compile cache instead of compiling it."""
    from unittest import mock

    shapes = _shapes()
    built, real = [], jax.jit
    with mock.patch.object(jax, "jit", lambda f, **kw: built.append(f) or real(f, **kw)):
        weights.make_dense(shapes, 1)
        weights.make_dense(shapes, 2**33 + 5)
    texts = [real(f).lower(weights.base_key(0)).as_text() for f in built]
    assert len(texts) == 2 and texts[0] == texts[1]

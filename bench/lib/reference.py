"""Plain references: one block's calibration capture and QuantEase
coordinate descent — straightforward ``jax.numpy`` with no kernels, caches
or batching, matmuls at ``Precision.HIGHEST``.  They import nothing of the
program.

The model is the configuration as the program runs it: RMSNorm (eps 1e-6,
weight ``1 + scale``), rotate-half RoPE, grouped-query attention with query
head h reading key/value head h // (heads / kv_heads), SwiGLU MLP,
sequential residuals.  ``PERF.md`` lists where that departs from each
published model.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def rmsnorm(x, scale, out_dtype=jnp.float32):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(out_dtype)


def rope(x, pos, theta):
    """x: (S, heads, hd) float32; pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, q_chunk=512):
    """Causal softmax attention. q: (S, H, hd), k/v: (S, KV, hd), float32."""
    S, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for c0 in range(0, S, q_chunk):
        qc = q[c0:c0 + q_chunk]
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI) / math.sqrt(hd)
        mask = (c0 + jnp.arange(qc.shape[0]))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    return jnp.concatenate(outs, 0)


# --- one block's calibration capture -------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims", "theta"))
def capture_chunk(x, blk, dims, theta):
    """Σ increments of one chunk of calibration sequences through one block,
    keeping activations in bf16 between ops as the model states them.

    x: (B, S, d) bf16 block input.  Returns the Gram matrices XᵀX of the
    four distinct linear inputs: attn-in (q/k/v), attn-out (o), mlp-in
    (g/u), mlp-hidden (d)."""
    dims = dict(dims)
    B, S, d = x.shape
    bf = jnp.bfloat16
    pos = jnp.arange(S)

    def lin(a, w):
        return jnp.einsum("...i,io->...o", a.astype(bf), w.astype(bf),
                          preferred_element_type=jnp.float32).astype(bf)

    def gram(a):
        a = a.reshape(-1, a.shape[-1]).astype(jnp.float32)
        return jnp.matmul(a.T, a, precision=HI)

    h = rmsnorm(x, blk["ln/scale"], bf)
    q = lin(h, blk["wq"]).reshape(B, S, dims["h"], dims["hd"])
    k = lin(h, blk["wk"]).reshape(B, S, dims["kv"], dims["hd"])
    v = lin(h, blk["wv"]).reshape(B, S, dims["kv"], dims["hd"])
    q = jax.vmap(lambda a: rope(a.astype(jnp.float32), pos, theta))(q).astype(bf)
    k = jax.vmap(lambda a: rope(a.astype(jnp.float32), pos, theta))(k).astype(bf)
    o = jax.vmap(lambda a, b, c: attention(a.astype(jnp.float32), b.astype(jnp.float32),
                                           c.astype(jnp.float32)))(q, k, v)
    o = o.reshape(B, S, -1).astype(bf)
    x2 = x + lin(o, blk["wo"])
    h2 = rmsnorm(x2, blk["ln2/scale"], bf)
    g = lin(h2, blk["wg"])
    hid = (jax.nn.silu(g) * lin(h2, blk["wu"])).astype(bf)
    return gram(h), gram(o), gram(h2), gram(hid)


SIGMA_OF = {"wq": 0, "wk": 0, "wv": 0, "wo": 1, "wg": 2, "wu": 2, "wd": 3}

# The paper's heuristic as the program applies it (core/quantease.py): every
# third iteration but the last keeps β unrounded.  The program has no option
# for it, so it is no parameter of the mix either.
UNQUANTIZED_EVERY = 3


# --- QuantEase coordinate descent -----------------------------------------------

def grid(w, bits):
    """Per-row asymmetric min/max grid over the (out, in) weight."""
    n = 2 ** bits - 1
    wmin = jnp.minimum(w.min(1, keepdims=True), 0.0)
    wmax = jnp.maximum(w.max(1, keepdims=True), 0.0)
    scale = jnp.maximum((wmax - wmin) / n, 1e-12)
    zero = jnp.round(-wmin / scale)
    return scale, zero


@functools.partial(jax.jit, static_argnames=("bits", "iterations", "percdamp",
                                             "unquantized_every", "bsz", "precision"))
def cd_solve(w, sigma, bits, iterations, percdamp, unquantized_every=UNQUANTIZED_EVERY,
             bsz=128, precision=HI):
    """QuantEase: cyclic coordinate descent over the columns of the (out, in)
    weight, Ŵ starting at W.  Column j takes
    β = P_j − Σ_{k≠j} Ŵ_k Σ̃_kj  with Σ damped by percdamp·mean(diag),
    Σ̃ = Σ/diag − I, P = W Σ/diag, and is rounded to the grid, except on
    every ``unquantized_every``-th iteration but the last.  Columns are
    visited in order; a block of ``bsz`` columns shares one matmul for the
    columns before it and corrects for its own updates one by one, which is
    the same sequence of updates.  Returns (Ŵ, scale, zero)."""
    q, p = w.shape
    n = 2 ** bits - 1
    s = sigma + percdamp * jnp.mean(jnp.diag(sigma)) * jnp.eye(p, dtype=jnp.float32)
    sn = s / jnp.diag(s)[None, :]
    st = sn - jnp.eye(p, dtype=jnp.float32)
    pm = jnp.matmul(w, sn, precision=precision)
    scale, zero = grid(w, bits)
    bsz = min(bsz, p)
    nb = p // bsz

    def col(carry, xs, quantize):
        delta = carry  # (q, bsz) old − new of this block's columns so far
        i, b0, st_col, old = xs
        beta = b0 + jnp.matmul(delta, st_col, precision=precision)
        qv = (jnp.clip(jnp.round(beta / scale[:, 0]) + zero[:, 0], 0, n) - zero[:, 0]) * scale[:, 0]
        new = jnp.where(quantize, qv, beta)
        delta = delta.at[:, i].set(old - new)
        return delta, new

    def block(wh, b, quantize):
        c0 = b * bsz
        st_cols = jax.lax.dynamic_slice(st, (0, c0), (p, bsz))
        beta0 = jax.lax.dynamic_slice(pm, (0, c0), (q, bsz)) - jnp.matmul(
            wh, st_cols, precision=precision)
        st_blk = jax.lax.dynamic_slice(st, (c0, c0), (bsz, bsz))
        old = jax.lax.dynamic_slice(wh, (0, c0), (q, bsz))
        _, new = jax.lax.scan(functools.partial(col, quantize=quantize),
                              jnp.zeros((q, bsz), jnp.float32),
                              (jnp.arange(bsz), beta0.T, st_blk.T, old.T))
        return jax.lax.dynamic_update_slice(wh, new.T, (0, c0))

    def iteration(it, wh):
        quantize = jnp.logical_or((it + 1) % unquantized_every != 0, it == iterations - 1)
        return jax.lax.fori_loop(0, nb, lambda b, a: block(a, b, quantize), wh)

    wh = jax.lax.fori_loop(0, iterations, iteration, w)
    return wh, scale, zero


def objective(w, wh, sigma):
    e = (w - wh).astype(jnp.float32)
    return jnp.sum(jnp.matmul(e, sigma, precision=HI) * e)

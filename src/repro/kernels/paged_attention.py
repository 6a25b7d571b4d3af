"""Paged-attention decode kernel: one query token per sequence against a
block-paged KV cache.

The serving engine (serve/engine.py PagedServingEngine) stores KV in
fixed-size pages shared by all sequences; each sequence owns a *page table*
(row of page indices).  This kernel computes single-token attention directly
against that layout — no contiguous (B, S, ...) cache is ever materialized:

  * grid ``(B, n_pages_per_seq)`` with the page dimension sequential; the
    page table and per-sequence lengths ride a
    :class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec` scalar
    prefetch, so each program's BlockSpec index map resolves
    ``page_table[b, j]`` *before* the body runs and the pipeline DMAs
    exactly the page this (sequence, step) needs from HBM,
  * online-softmax accumulators (running max / sum / weighted value) live
    in VMEM scratch across the page steps of one sequence; the output is
    written once, at the last page step,
  * pages may be **bf16, int8, or int4-packed uint8**.  Quantized pages
    carry per-(token, head) fp32 scale planes; the scales fold
    algebraically after the dot — ``q·(s·k₈) = s·(q·k₈)`` and
    ``Σ p·(s·v₈) = Σ (p·s)·v₈`` — so the dequantized bf16 page is never
    materialized and HBM reads stay 1 byte/element for int8 and **0.5**
    for int4.  int4 pages are fold-in-half packed (quant/pack.kv_pack_int4:
    byte d of a slot holds head-dim elements d and d + hd/2 in its lo/hi
    nibbles), so the in-kernel unpack is two shift/mask sign-extends and a
    concatenate along the head dim — no lane interleave,
  * all score/softmax math accumulates in fp32 (`preferred_element_type`);
    only the final output casts back to the query dtype.

Pages past a sequence's length are masked, not skipped: the padded tail of
a page table points at the reserved null page (serve/kv_cache.py), so every
DMA is in-bounds and masked contributions are exactly zero (``exp(-1e30 −
m)`` underflows).  The oracle is :func:`repro.kernels.ref.paged_attention_ref`;
dispatch (VMEM fit gate + XLA gather fallback) lives in
:func:`repro.kernels.ops.paged_attention`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES

__all__ = ["paged_attention_pallas"]


def _paged_attn_kernel(
    pt_ref,  # (B, n_pgs) i32 scalar-prefetch — page table
    len_ref,  # (B,) i32 scalar-prefetch — valid tokens per sequence
    q_ref,  # (1, KVp, G, hd) — query, pre-scaled by 1/sqrt(hd)
    k_ref,  # (1, psz, KVp, hd) — the page this program attends
    v_ref,  # (1, psz, KVp, hd)
    *rest,  # [ks_ref, vs_ref,] o_ref, m_s, l_s, acc_s
    psz: int,
    n_pgs: int,
    window: Optional[int],
    attn_softcap: Optional[float],
    quantized: bool,
    kv_packed4: bool,
):
    def _unpack(page):  # (psz, KVp, hd/2) uint8 → (psz, KVp, hd) f32
        b32 = page.astype(jnp.int32)
        lo = ((b32 & 0xF) ^ 8) - 8  # sign-extend 4-bit two's complement
        hi = ((b32 >> 4) ^ 8) - 8
        return jnp.concatenate([lo, hi], axis=-1).astype(jnp.float32)

    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest
        ks_ref = vs_ref = None

    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[b]

    # Pages entirely past the valid prefix contribute nothing; skip the MXU
    # work (their DMA still targets a real page — the null page for padded
    # table entries — so it is always in-bounds).
    @pl.when(j * psz < length)
    def _():
        qv = q_ref[0].astype(jnp.float32)  # (KVp, G, hd)
        kb = _unpack(k_ref[0]) if kv_packed4 else k_ref[0].astype(jnp.float32)
        s = jnp.einsum(
            "kgd,tkd->kgt", qv, kb, preferred_element_type=jnp.float32
        )  # (KVp, G, psz)
        if ks_ref is not None:
            ks = ks_ref[0][:, :, 0]  # (psz, KVp)
            s = s * ks.T[:, None, :]
        if attn_softcap is not None:
            s = jnp.tanh(s / attn_softcap) * attn_softcap
        pos = j * psz + jax.lax.broadcasted_iota(jnp.int32, (1, 1, psz), 2)
        valid = pos < length
        if window is not None:
            valid &= pos >= length - window
        s = jnp.where(valid, s, -1e30)

        m_new = jnp.maximum(m_s[...], s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_s[...] - m_new)
        l_s[...] = l_s[...] * corr + p.sum(-1)
        vb = _unpack(v_ref[0]) if kv_packed4 else v_ref[0].astype(jnp.float32)
        if vs_ref is not None:
            vs = vs_ref[0][:, :, 0]  # (psz, KVp)
            p = p * vs.T[:, None, :]
        acc_s[...] = acc_s[...] * corr[..., None] + jnp.einsum(
            "kgt,tkd->kgd", p, vb, preferred_element_type=jnp.float32
        )
        m_s[...] = m_new

    @pl.when(j == n_pgs - 1)
    def _():
        o_ref[0] = (
            acc_s[...] / jnp.maximum(l_s[...], 1e-30)[..., None]
        ).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,  # (B, KVp, G, hd) — one decode token per sequence
    k_pages: jax.Array,  # (n_pages, psz, KVp, hd) bf16/f32/int8, or
    #                      (n_pages, psz, KVp, hd//2) uint8 int4-packed
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, n_pgs) int32 — padded entries → null page
    lengths: jax.Array,  # (B,) int32 — valid tokens per sequence
    *,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    k_scale_pages: Optional[jax.Array] = None,  # (n_pages, psz, KVp, 1) f32
    v_scale_pages: Optional[jax.Array] = None,
    interpret: bool = False,
) -> jax.Array:
    """Single decode-token attention over paged KV.  Returns (B, KVp, G, hd)."""
    B, KVp, G, hd = q.shape
    psz = k_pages.shape[1]
    n_pgs = page_table.shape[1]
    quantized = k_scale_pages is not None
    kv_packed4 = k_pages.dtype == jnp.uint8  # fold-in-half int4 pages
    page_hd = hd // 2 if kv_packed4 else hd

    # Mirror decode_attention's cast discipline: the 1/sqrt(hd) pre-scale is
    # applied in the query dtype, scores accumulate fp32.
    qs = (q * (1.0 / math.sqrt(hd))).astype(q.dtype)

    page_spec = pl.BlockSpec(
        (1, psz, KVp, page_hd), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
    )
    in_specs = [
        pl.BlockSpec((1, KVp, G, hd), lambda b, j, pt, ln: (b, 0, 0, 0)),
        page_spec,
        page_spec,
    ]
    args = [qs, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, psz, KVp, 1), lambda b, j, pt, ln: (pt[b, j], 0, 0, 0)
        )
        in_specs += [scale_spec, scale_spec]
        args += [k_scale_pages, v_scale_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pgs),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVp, G, hd), lambda b, j, pt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVp, G), jnp.float32),  # running max
            pltpu.VMEM((KVp, G), jnp.float32),  # running sum
            pltpu.VMEM((KVp, G, hd), jnp.float32),  # weighted-value acc
        ],
    )
    kernel = functools.partial(
        _paged_attn_kernel,
        psz=psz,
        n_pgs=n_pgs,
        window=window,
        attn_softcap=attn_softcap,
        quantized=quantized,
        kv_packed4=kv_packed4,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVp, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(page_table, lengths, *args)

"""Jit'd dispatch wrappers for the Pallas kernels.

Callers (repro.core.quantease, repro.serve) use these entry points; the
``interpret`` flag routes to Pallas interpret-mode on CPU and compiled
Mosaic on a TPU, where interpret mode is never taken.  ``ref.py`` holds the
oracles; the dispatchers never change semantics, only execution engines.

On a TPU, every time a serving dispatcher gives way to its XLA reference
(fit gate, ragged layout, injected fault) it is counted in
:data:`fallbacks` — a chip run checks the count is zero, so a kernel that
silently stops being used cannot pass for one that runs.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.faults import fault_point
from repro.kernels import VMEM_LIMIT_BYTES, ref
from repro.kernels.dequant_matmul import dequant_matmul_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.quantease_cd import (
    quantease_block_sweep_pallas,
    quantease_fused_iteration_pallas,
    quantease_outlier_iteration_pallas,
    quantease_outlier_iteration_t_pallas,
)

__all__ = [
    "quantease_block_sweep",
    "quantease_fused_iteration",
    "quantease_outlier_iteration",
    "quantease_outlier_iteration_t",
    "fused_iteration_tq",
    "fused_iteration_bytes",
    "outlier_iteration_tq",
    "outlier_iteration_bytes",
    "block_sweep_tq",
    "block_sweep_bytes",
    "dequant_matmul",
    "dequant_matmul_fits_vmem",
    "dequant_matmul_bytes",
    "paged_attention",
    "paged_attention_fits_vmem",
    "on_tpu",
    "fallbacks",
]

# Budget the fit gates hold each kernel to: the scoped limit asked of Mosaic
# less headroom for its internal scratch.  The byte formulas count every
# pipelined block twice (double buffering); checked against the TPU
# compiler at phi3 widths by tests/test_tpu_compile.py.
_VMEM_BUDGET = VMEM_LIMIT_BYTES - 16 * 1024 * 1024

# (kernel, reason) → times a serving dispatcher on a TPU took the XLA
# reference instead of its kernel (counted at trace time).
fallbacks: collections.Counter = collections.Counter()


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(interpret) -> bool:
    """Interpret mode only off the chip: on a TPU the compiled kernel runs."""
    if on_tpu():
        return False
    return True if interpret is None else interpret


def block_sweep_bytes(bsz: int, tq: int) -> int:
    """VMEM working set of one block-sweep program: six (bsz × tq) fp32
    tiles (β₀, Ŵ_old, scale, zero in; Ŵ_new, Δ out) plus the (bsz × bsz)
    Σ̃ block shared by every program."""
    return 6 * bsz * tq * 4 + bsz * bsz * 4


def block_sweep_tq(q: int, bsz: int, tq: int = 256):
    """Pick a q-tile for the intra-block sweep kernel, or None if even the
    minimum tile cannot fit VMEM (only conceivable at absurd block sizes —
    the sweep's working set is tiny — but the dispatcher gates anyway so
    every pallas_call sits behind an explicit fit decision)."""
    tq = min(tq, max(q, 1))
    while tq > 128 and block_sweep_bytes(bsz, tq) > _VMEM_BUDGET:
        tq //= 2
    if block_sweep_bytes(bsz, tq) > _VMEM_BUDGET:
        return None
    return tq


def quantease_block_sweep(
    beta0, sig_blk, w_old_blk, scale_blk, zero_blk, *, n_levels, quantize, interpret=None
):
    """Intra-block CD sweep.  2-D operands: one (q, B) block; a leading
    group dim (``beta0: (G, q, B)``, ``sig_blk: (G, B, B)``, …) sweeps G
    independent layers at once — pallas_call's batching rule folds the vmap
    into an extra grid dimension, so the grouped-block solver issues a
    single kernel launch per column block."""
    interpret = _interpret(interpret)
    q, bsz = beta0.shape[-2], beta0.shape[-1]
    tq = block_sweep_tq(q, bsz)
    if tq is None:
        ref_fn = functools.partial(
            ref.quantease_block_sweep_ref, n_levels=n_levels, quantize=quantize
        )
        if beta0.ndim == 3:
            return jax.vmap(ref_fn)(beta0, sig_blk, w_old_blk, scale_blk, zero_blk)
        return ref_fn(beta0, sig_blk, w_old_blk, scale_blk, zero_blk)
    kernel = functools.partial(
        quantease_block_sweep_pallas,
        n_levels=n_levels,
        quantize=quantize,
        tq=tq,
        interpret=interpret,
    )
    if beta0.ndim == 3:
        return jax.vmap(kernel)(beta0, sig_blk, w_old_blk, scale_blk, zero_blk)
    return kernel(beta0, sig_blk, w_old_blk, scale_blk, zero_blk)


def fused_iteration_bytes(
    p_pad: int, bsz: int, matmul_dtype: str, tq: int
) -> int:
    """VMEM working set of one fused-iteration program at tile ``tq``.

    Double-buffered blocks: the resident (p_pad × tq) fp32 Δ_prev, the
    (bsz × p_pad) Σ̃ᵀ correction slab (bf16 halves it), the (bsz × bsz)
    diagonal block and 7 (bsz × tq) fp32 tiles.  Once: the (p_pad × tq)
    fp32 Δ scratch, plus its bf16 copy for a bf16 correction matmul."""
    cd = 2 if matmul_dtype == "bfloat16" else 4
    blocks = p_pad * tq * 4 + bsz * p_pad * cd + bsz * bsz * 4 + 7 * bsz * tq * 4
    return 2 * blocks + p_pad * tq * 4 + (p_pad * tq * cd if cd == 2 else 0)


def fused_iteration_tq(p_pad: int, bsz: int, matmul_dtype: str = "float32", tq: int = 256):
    """Pick a q-tile for the fused-iteration kernel, or None if it cannot
    fit VMEM.

    Only the Δ term of :func:`fused_iteration_bytes` shrinks with ``tq`` —
    the Σ̃ slab is fixed by ``bsz``, so very wide layers don't fit at any
    tq and the caller must fall back to the per-block XLA schedule (same
    iterates).
    """
    while tq > 128 and fused_iteration_bytes(p_pad, bsz, matmul_dtype, tq) > _VMEM_BUDGET:
        tq //= 2
    if fused_iteration_bytes(p_pad, bsz, matmul_dtype, tq) > _VMEM_BUDGET:
        return None
    return tq


def quantease_fused_iteration(
    base,
    sig_tilde,
    w_hat,
    scale_pc,
    zero_pc,
    delta_prev,
    *,
    n_levels,
    quantize,
    bsz,
    matmul_dtype="float32",
    interpret=None,
    tq=None,
):
    """One full CD iteration as a single fused kernel launch.

    2-D operands: one (q, p_pad) layer; a leading group dim batches G
    layers into one launch (vmap folds into the grid).  Returns
    ``(w_new, base_new, delta_new)``.  ``tq`` defaults to
    :func:`fused_iteration_tq`'s VMEM-fitted choice; callers should gate on
    that helper returning non-None before taking this path.
    """
    interpret = _interpret(interpret)
    p_pad = sig_tilde.shape[-1]
    if tq is None:
        tq = fused_iteration_tq(p_pad, bsz, matmul_dtype)
        if tq is None:
            raise ValueError(
                f"fused iteration does not fit VMEM (p_pad={p_pad}, bsz={bsz}); "
                "use the XLA engine for this layer"
            )
    elif fused_iteration_bytes(p_pad, bsz, matmul_dtype, tq) > _VMEM_BUDGET:
        raise ValueError(
            f"explicit tq={tq} overflows VMEM (p_pad={p_pad}, bsz={bsz}); "
            "pass tq=None to let fused_iteration_tq choose"
        )
    kernel = functools.partial(
        quantease_fused_iteration_pallas,
        n_levels=n_levels,
        quantize=quantize,
        bsz=bsz,
        tq=tq,
        matmul_dtype=matmul_dtype,
        interpret=interpret,
    )
    if base.ndim == 3:
        return jax.vmap(kernel)(
            base, sig_tilde, w_hat, scale_pc, zero_pc, delta_prev
        )
    return kernel(base, sig_tilde, w_hat, scale_pc, zero_pc, delta_prev)


def outlier_iteration_bytes(
    p_pad: int, bsz: int, matmul_dtype: str, tq: int
) -> int:
    """VMEM working set of one outlier-iteration program: beyond the base
    kernel's set, a second double-buffered (p_pad × tq) fp32 slab (the R
    accumulator output), a second Σ̃ slab (the suffix column block; bf16
    halves both), and the (p_pad × tq) fp32 suffix-residual temporary."""
    cd = 2 if matmul_dtype == "bfloat16" else 4
    blocks = (
        2 * p_pad * tq * 4 + 2 * bsz * p_pad * cd + bsz * bsz * 4
        + 8 * bsz * tq * 4
    )
    return 2 * blocks + 2 * p_pad * tq * 4 + (p_pad * tq * cd if cd == 2 else 0)


def outlier_iteration_tq(
    p_pad: int, bsz: int, matmul_dtype: str = "float32", tq: int = 256
):
    """Pick a q-tile for the outlier-aware fused-iteration kernel, or None
    if it cannot fit VMEM.

    As with :func:`fused_iteration_tq`, only the p_pad×tq terms of
    :func:`outlier_iteration_bytes` shrink with ``tq`` — too-wide layers
    must take the XLA schedule.
    """
    while tq > 128 and outlier_iteration_bytes(p_pad, bsz, matmul_dtype, tq) > _VMEM_BUDGET:
        tq //= 2
    if outlier_iteration_bytes(p_pad, bsz, matmul_dtype, tq) > _VMEM_BUDGET:
        return None
    return tq


def quantease_outlier_iteration(
    base,
    sig_tilde,
    w_old,
    scale_pc,
    zero_pc,
    delta_prev,
    dh_prev,
    *,
    n_levels,
    quantize,
    bsz,
    matmul_dtype="float32",
    interpret=None,
    tq=None,
):
    """One outlier-aware fused CD iteration (sweep + exact residual) as a
    single kernel launch.

    2-D operands: one (q, p_pad) layer; a leading group dim batches G layers
    into one launch (vmap folds into the grid).  Returns
    ``(w_new, base_new, delta_pure, r)`` — see
    :func:`repro.kernels.quantease_cd.quantease_outlier_iteration_pallas`.
    """
    interpret = _interpret(interpret)
    p_pad = sig_tilde.shape[-1]
    if tq is None:
        tq = outlier_iteration_tq(p_pad, bsz, matmul_dtype)
        if tq is None:
            raise ValueError(
                f"outlier fused iteration does not fit VMEM "
                f"(p_pad={p_pad}, bsz={bsz}); use the XLA engine for this layer"
            )
    elif outlier_iteration_bytes(p_pad, bsz, matmul_dtype, tq) > _VMEM_BUDGET:
        raise ValueError(
            f"explicit tq={tq} overflows VMEM (p_pad={p_pad}, bsz={bsz}); "
            "pass tq=None to let outlier_iteration_tq choose"
        )
    kernel = functools.partial(
        quantease_outlier_iteration_pallas,
        n_levels=n_levels,
        quantize=quantize,
        bsz=bsz,
        tq=tq,
        matmul_dtype=matmul_dtype,
        interpret=interpret,
    )
    if base.ndim == 3:
        return jax.vmap(kernel)(
            base, sig_tilde, w_old, scale_pc, zero_pc, delta_prev, dh_prev
        )
    return kernel(base, sig_tilde, w_old, scale_pc, zero_pc, delta_prev, dh_prev)


def quantease_outlier_iteration_t(
    base_t,
    *,
    sig_corr,
    sig_t,
    w_old_t,
    scale_t,
    zero_t,
    dh_prev_t,
    delta_prev_t,
    n_levels,
    quantize,
    bsz,
    tq,
    matmul_dtype="float32",
    interpret=None,
):
    """Transposed-native outlier fused iteration (the scanned engine's hot
    entry): operands arrive in the resident (p_pad, qp) layout, so no
    per-iteration transposes cross the kernel boundary.  Loop-invariant
    operands (``sig_corr``/``sig_t``/``scale_t``/``zero_t``) are prepped
    once by the caller.  Returns ``(w_new_t, base_new_t, delta_pure_t,
    r_t)``, all (p_pad, qp)."""
    interpret = _interpret(interpret)
    p_pad = base_t.shape[-2]
    if outlier_iteration_bytes(p_pad, bsz, matmul_dtype, tq) > _VMEM_BUDGET:
        raise ValueError(
            f"tq={tq} overflows VMEM for the transposed outlier iteration "
            f"(p_pad={p_pad}, bsz={bsz}); size it with outlier_iteration_tq"
        )
    return quantease_outlier_iteration_t_pallas(
        base_t,
        sig_corr=sig_corr,
        sig_t=sig_t,
        w_old_t=w_old_t,
        scale_t=scale_t,
        zero_t=zero_t,
        dh_prev_t=dh_prev_t,
        delta_prev_t=delta_prev_t,
        n_levels=n_levels,
        quantize=quantize,
        bsz=bsz,
        tq=tq,
        matmul_dtype=matmul_dtype,
        interpret=interpret,
    )


def paged_attention_fits_vmem(
    page_size: int, kvp: int, g: int, hd: int, *,
    kv_bytes: float = 2, quantized: bool = False,
) -> bool:
    """VMEM fit gate for the paged-attention kernel.

    Resident per program: the double-buffered k/v page blocks (the only
    term that scales with ``page_size``), their fp32 scale planes when the
    pages are quantized, and the fixed per-sequence set (query tile, fp32
    softmax accumulators, output tile).  ``kv_bytes`` is per *element*:
    2 for bf16, 1 for int8, 0.5 for packed int4 (two codes per stored
    byte).  Same budget as :func:`fused_iteration_tq`; a non-fit must take
    the XLA gather fallback — there is no smaller tile to retry, pages are
    the tile.
    """
    pages = int(2 * 2 * page_size * kvp * hd * kv_bytes)  # k+v, double-buffered
    if quantized:
        pages += 2 * 2 * page_size * kvp * 4
    fixed = kvp * g * hd * 4 * 3 + kvp * g * 4 * 2  # q + acc + out, m + l
    return pages + fixed <= _VMEM_BUDGET


def paged_attention(
    q, k_pages, v_pages, page_table, lengths, *,
    window=None, attn_softcap=None,
    k_scale_pages=None, v_scale_pages=None, interpret=None,
):
    """Paged decode attention (serving hot path).

    Dispatch mirrors :func:`dequant_matmul`: Mosaic kernel on TPU when the
    page block fits VMEM (:func:`paged_attention_fits_vmem`); the XLA
    gather-based reference elsewhere.  Pallas *interpret* mode is reserved
    for kernel tests (``interpret=True``) and never reaches lowered
    production graphs.

    Quantized pages **must** arrive with both scale planes — they are
    either folded in-kernel or consumed explicitly by the reference; raw
    codes are never forwarded un-decoded (the grouped-dispatch audit that
    fixed ``dequant_matmul`` applies here from day one).  int8 pages carry
    one code per element; **uint8 pages are int4-packed** (two signed
    codes per byte, fold-in-half layout — quant/pack.kv_pack_int4), halving
    page HBM traffic again.
    """
    quantized = k_scale_pages is not None
    if (v_scale_pages is None) != (k_scale_pages is None):
        raise ValueError("k_scale_pages and v_scale_pages must be passed together")
    if k_pages.dtype == jnp.int8 and not quantized:
        raise ValueError("int8 KV pages require scale planes (dequant-in-kernel)")
    kv_packed4 = k_pages.dtype == jnp.uint8
    if kv_packed4 and not quantized:
        raise ValueError(
            "int4-packed KV pages require scale planes (dequant-in-kernel)"
        )

    def reference(reason):
        if on_tpu():
            fallbacks[("paged_attention", reason)] += 1
        return ref.paged_attention_ref(
            q, k_pages, v_pages, page_table, lengths,
            window=window, attn_softcap=attn_softcap,
            k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        )

    # Injection point "kernel.dispatch" (DESIGN.md §Resilience): a "deny"
    # action simulates VMEM-gate pressure — the dispatcher degrades to the
    # XLA gather reference, which reads the same pages bitwise (tested), so
    # outputs are unchanged.  Fires at dispatch time (trace time under jit).
    if fault_point("kernel.dispatch") == "deny":
        return reference("fault")
    if interpret is None and not on_tpu():
        return reference("off-chip")
    interpret = _interpret(interpret)
    psz = k_pages.shape[1]
    _, kvp, g, hd = q.shape
    if not paged_attention_fits_vmem(
        psz, kvp, g, hd,
        kv_bytes=0.5 if kv_packed4 else k_pages.dtype.itemsize,
        quantized=quantized,
    ):
        return reference("vmem")
    return paged_attention_pallas(
        q, k_pages, v_pages, page_table, lengths,
        window=window, attn_softcap=attn_softcap,
        k_scale_pages=k_scale_pages, v_scale_pages=v_scale_pages,
        interpret=interpret,
    )


def _unpacked(codes, packed4, pack_layout="linear", pack_tile=None):
    if not packed4:
        return codes
    from repro.quant import unpack_codes, unprepack_codes

    p = codes.shape[-1] * 2
    if pack_layout == "tile":
        return unprepack_codes(codes, 4, p, pack_tile)
    return unpack_codes(codes, 4, p)


def dequant_matmul_bytes(
    m: int, q: int, p: int, *, tm: int = 128, tq: int = 128, tk: int = 512
) -> int:
    """VMEM working set of one serving-GEMM program: the (tm × tk) fp32
    activation tile, the (tq × tk) codes tile (1 B/code stored — packed4
    halves HBM, not the unpacked VMEM tile), the scale/zero slabs expanded
    in-VMEM to (tq × tk) fp32 worst case, and the (tm × tq) fp32
    accumulator."""
    tm, tq, tk = min(tm, m), min(tq, q), min(tk, p)
    return tm * tk * 4 + tq * tk + 2 * tq * tk * 4 + tm * tq * 4


def dequant_matmul_fits_vmem(
    m: int, q: int, p: int, *, tm: int = 128, tq: int = 128, tk: int = 512
) -> bool:
    """VMEM fit gate for the serving GEMM.  The fixed 128/128/512 tiling
    keeps the working set near 0.8 MiB regardless of problem size, so this
    effectively always passes — it exists so the dispatch decision is an
    explicit, formula-checked gate (analysis/vmem.py re-evaluates it
    against every shipped config shape) rather than an implicit property
    of the tile constants."""
    return dequant_matmul_bytes(m, q, p, tm=tm, tq=tq, tk=tk) <= _VMEM_BUDGET


def dequant_matmul(
    x, codes, scale, zero, *, packed4=False, out_dtype=jnp.bfloat16,
    interpret=None, group_size=None, pack_layout="linear", pack_tile=None,
):
    """Serving GEMM.

    Dispatch: Mosaic kernel on TPU; pure-XLA reference elsewhere (dequant +
    dot — XLA fuses the dequant into the GEMM epilogue/prologue).  Pallas
    *interpret* mode is reserved for kernel tests (``interpret=True``) — it
    must never end up in lowered production graphs: its grid loops
    materialize per-step buffers and wreck both memory and cost analysis.

    Grouped grids (``scale: (q, n_groups)``, n_groups > 1) take the Pallas
    kernel too when the groups are uniform — the kernel tiles scale/zero
    per group; ragged layouts (a narrower last group) fall back to the XLA
    reference with the true ``group_size`` (packed4 codes are unpacked
    first — the reference consumes raw uint8 planes).  Pass ``group_size``
    (QuantizedTensor carries it) whenever the grid was built with one:
    without it a ragged layout is indistinguishable from a uniform
    ceil(p/n_groups) layout and would dequantize with wrong boundaries.

    ``pack_layout="tile"`` marks codes prepacked into the kernel's
    tile-native order at pack time (quant/pack.prepack_codes with k-tile
    ``pack_tile``, chosen by the roofline decision in serve/qparams.py):
    the kernel consumes them at exactly that tk with a contiguous
    concat-unpack; every fallback path (non-TPU, ragged groups) un-prepacks
    first, so the layout is transparent to semantics.
    """
    n_groups = scale.shape[1] if scale.ndim > 1 else 1
    p = codes.shape[-1] * (2 if packed4 else 1)
    gsz = group_size if group_size else (-(-p // n_groups) if n_groups > 1 else p)
    uniform = n_groups == 1 or (p % gsz == 0 and p // gsz == n_groups)
    tiled = packed4 and pack_layout == "tile"

    def reference(reason):
        if on_tpu():
            fallbacks[("dequant_matmul", reason)] += 1
        return ref.dequant_matmul_ref(
            x, _unpacked(codes, packed4, pack_layout, pack_tile), scale, zero,
            out_dtype=out_dtype, group_size=group_size,
        )

    # Injection point "kernel.dispatch": "deny" degrades to the XLA
    # reference (same semantics; see paged_attention's note).
    if fault_point("kernel.dispatch") == "deny":
        return reference("fault")
    if interpret is None and not on_tpu():
        return reference("off-chip")
    interpret = _interpret(interpret)
    if not dequant_matmul_fits_vmem(x.shape[0], codes.shape[0], p):
        return reference("vmem")
    kw = dict(packed4=packed4, out_dtype=out_dtype, interpret=interpret)
    if tiled:
        if p % pack_tile:  # prepack left the ragged tail linear — ref only
            return reference("ragged-tile")
        kw.update(pack_layout="tile", tk=pack_tile)
    if n_groups > 1:
        if not uniform:  # ragged last group — reference path only
            return reference("ragged-groups")
        return dequant_matmul_pallas(x, codes, scale, zero, **kw)
    s = scale.reshape(-1)
    z = zero.reshape(-1)
    return dequant_matmul_pallas(x, codes, s, z, **kw)

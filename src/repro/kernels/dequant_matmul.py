"""Pallas TPU kernel: fused dequantize + matmul for quantized serving.

Computes ``y = x @ Wᵀ`` where W is stored as uint8 quantization codes plus a
per-output-channel affine grid (scale, zero).  The codes tile is dequantized
*in VMEM* and fed straight to the MXU — W never materializes in HBM at full
precision, which is the entire inference-memory story of weight-only PTQ:
HBM traffic per weight is 1 byte (or 0.5 with the packed-int4 variant) vs 2
for bf16.

Grid: (m-tiles, q-tiles, k-tiles); k is the contraction dim, declared
"arbitrary" so the accumulator lives in the output tile across k steps.

Tiling defaults (TM=128, TQ=128, TK=512):
  x tile   128×512×2 B (bf16)        = 128 KiB
  codes    128×512×1 B               =  64 KiB
  out acc  128×128×4 B (fp32)        =  64 KiB
  total ≈ 0.26 MiB/program — leaves VMEM headroom for double-buffering.

The packed-int4 variant (``packed4=True``) takes codes packed two-per-byte
(p/2 bytes per row) and unpacks with shift/mask in-kernel, halving HBM
traffic — the lever that matters when decode is HBM-bandwidth-bound.

**Grouped grids** (``scale/zero: (q, n_groups)``, group_size = p/n_groups
columns per (s, z) pair) are first-class: the k-tile width ``tk`` is
snapped so every tile covers a whole number of groups (``tk % gsz == 0``,
tile carries a (TQ, tk//gsz) scale slab expanded in-VMEM) or sits inside
one group (``gsz % tk == 0``, tile carries a (TQ, 1) slab addressed by the
k→group index map) — group metadata HBM traffic stays O(q·n_groups), never
the O(q·p) a per-column pre-expansion would cost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT_BYTES

__all__ = ["dequant_matmul_pallas", "select_tile_k"]


def select_tile_k(p: int, group_size=None, tk: int = 512):
    """The k-tile the kernel will run for a (·, p) GEMM — the same snapping
    :func:`dequant_matmul_pallas` applies, exposed so the pack-time layout
    decision (serve/qparams.py + roofline/analysis.py) can prepack codes
    into exactly the tile the kernel reads."""
    tk = min(tk, p)
    gsz = group_size if group_size else p
    if group_size and p // gsz > 1:
        if tk >= gsz:
            tk = (tk // gsz) * gsz
        elif gsz % tk:
            tk = gsz
    return tk


def _dequant_matmul_kernel(
    x_ref,  # (TM, TK) activations
    codes_ref,  # (TQ, TK) uint8 (or (TQ, TK//2) packed4)
    gid_ref,  # (1, TK) int32 — group of each tile column, within the tile
    scale_ref,  # (1, TQ, groups_per_tile) f32
    zero_ref,  # (1, TQ, groups_per_tile) f32
    *rest,  # [perm_ref (TK, TK) bf16,] o_ref (TM, TQ) f32 accumulator
    packed4: bool,
    interleave: bool,
):
    perm_ref, o_ref = rest if interleave else (None, rest[0])

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Mosaic has no uint8 → f32 cast; widen through int32.
    codes = codes_ref[...].astype(jnp.int32)
    if packed4:
        # Lo nibbles, then hi nibbles.  The tile-native prepack
        # (pack.prepack_codes) stores a tile's first TK/2 columns in the lo
        # nibbles, so this concat is already natural column order.
        codes = jnp.concatenate([codes & 0xF, codes >> 4], axis=-1)
        if interleave:
            # Linear layout: byte b holds columns (2b, 2b+1), so the concat
            # is [even | odd].  Mosaic cannot shuffle lanes, so a 0/1
            # permutation on the MXU restores natural order — exact, since
            # codes ≤ 15 are exact in bf16 and each output sums one term.
            codes = jnp.dot(
                codes.astype(jnp.bfloat16), perm_ref[...],
                preferred_element_type=jnp.float32,
            )
    codes = codes.astype(jnp.float32)
    scale = scale_ref[0]
    zero = zero_ref[0]
    n_g = scale.shape[1]
    if n_g > 1:  # k-tile covers n_g whole groups: expand by selects (exact)
        gid = gid_ref[...]
        s_full, z_full = scale[:, :1], zero[:, :1]
        for g in range(1, n_g):
            s_full = jnp.where(gid == g, scale[:, g : g + 1], s_full)
            z_full = jnp.where(gid == g, zero[:, g : g + 1], z_full)
        scale, zero = s_full, z_full
    w = (codes - zero) * scale  # (TQ, TK)
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] += jnp.dot(x, w.T, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("tm", "tq", "tk", "packed4", "pack_layout", "out_dtype",
                     "interpret"),
)
def dequant_matmul_pallas(
    x: jax.Array,  # (m, p)
    codes: jax.Array,  # (q, p) uint8, or (q, p//2) when packed4
    scale: jax.Array,  # (q,) or (q, n_groups) f32 — uniform groups (p % n_groups == 0)
    zero: jax.Array,  # same shape as scale
    *,
    tm: int = 128,
    tq: int = 128,
    tk: int = 512,
    packed4: bool = False,
    pack_layout: str = "linear",
    out_dtype=jnp.float32,
    interpret: bool = True,
) -> jax.Array:
    m, p = x.shape
    q = codes.shape[0]
    if scale.ndim == 1:
        scale = scale[:, None]
        zero = zero[:, None]
    n_groups = scale.shape[1]
    gsz = p // n_groups if n_groups > 1 else p
    if n_groups > 1 and p % n_groups:
        raise ValueError("grouped Pallas GEMM requires uniform groups")
    tm = min(tm, m)
    tq = min(tq, q)
    tile_native = pack_layout == "tile"
    if tile_native:
        # Prepacked codes are committed to the caller's k-tile: consuming
        # them at any other tk would permute columns mid-tile.  The pack
        # decision (select_tile_k) guarantees divisibility and group fit.
        if not packed4:
            raise ValueError("pack_layout='tile' requires packed4 codes")
        if p % tk or (tk % gsz and gsz % tk):
            raise ValueError(
                f"tile-native layout needs p % tk == 0 and group-compatible "
                f"tk (p={p}, tk={tk}, group_size={gsz})"
            )
    else:
        tk = min(tk, p)
        if n_groups > 1:
            # Snap tk so each k-tile covers whole groups or sits inside one.
            if tk >= gsz:
                tk = (tk // gsz) * gsz
            elif gsz % tk:
                tk = gsz

    pad_m, pad_q, pad_k = (-m) % tm, (-q) % tq, (-p) % tk
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_q or pad_k:
        kdim_pad = pad_k // 2 if packed4 else pad_k
        codes = jnp.pad(codes, ((0, pad_q), (0, kdim_pad)))
    if pad_q:
        scale = jnp.pad(scale, ((0, pad_q), (0, 0)))
        zero = jnp.pad(zero, ((0, pad_q), (0, 0)))
    if pad_k and tk % gsz == 0:
        # Whole-groups tiling addresses ceil(pp/gsz) groups; the k padding
        # may extend past the last real group — pad the metadata to match
        # (padded x columns are zero, so the values are never observed).
        pad_g = (p + pad_k) // gsz - n_groups
        if pad_g:
            scale = jnp.pad(scale, ((0, 0), (0, pad_g)), constant_values=1.0)
            zero = jnp.pad(zero, ((0, 0), (0, pad_g)))
    mp, qp, pp = m + pad_m, q + pad_q, p + pad_k
    n_k = pp // tk
    ck = tk // 2 if packed4 else tk  # codes tile width in stored bytes

    col = np.arange(tk)
    gid = jnp.asarray((col // gsz if tk % gsz == 0 else 0 * col)[None], jnp.int32)
    interleave = packed4 and not tile_native
    extra_args, extra_specs = [], []
    if interleave:  # perm[c, n]: concat column c ([even | odd]) → column n
        src = np.concatenate([col[0::2], col[1::2]])
        perm = np.zeros((tk, tk), np.float32)
        perm[np.arange(tk), src] = 1.0
        extra_args = [jnp.asarray(perm, jnp.bfloat16)]
        extra_specs = [pl.BlockSpec((tk, tk), lambda i, j, k: (0, 0))]

    # Scale/zero ride as (n_slabs, qp, groups_per_tile) so each k-step's
    # block is (1, TQ, groups_per_tile): its last dim is the array's whole
    # last dim, which the TPU (8, 128) tiling rule accepts at any width.
    if tk % gsz == 0:  # k-tile covers whole groups → one slab per k-tile
        g_tile = tk // gsz
        slabs = lambda a: a.reshape(qp, n_k, g_tile).transpose(1, 0, 2)
        scale_spec = pl.BlockSpec((1, tq, g_tile), lambda i, j, k: (k, j, 0))
    else:  # k-tile inside one group (gsz % tk == 0): slab = the k-tile's group
        slabs = lambda a: a.T[:, :, None]
        scale_spec = pl.BlockSpec(
            (1, tq, 1), lambda i, j, k: ((k * tk) // gsz, j, 0)
        )
    scale, zero = slabs(scale), slabs(zero)

    kernel = functools.partial(
        _dequant_matmul_kernel, packed4=packed4, interleave=interleave
    )
    out = pl.pallas_call(
        kernel,
        grid=(mp // tm, qp // tq, n_k),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k: (i, k)),
            pl.BlockSpec((tq, ck), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, tk), lambda i, j, k: (0, 0)),
            scale_spec,
            scale_spec,
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((tm, tq), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, qp), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
    )(x, codes, gid, scale, zero, *extra_args)
    return out[:m, :q].astype(out_dtype)

"""Shared model components: norms, RoPE, blockwise attention, linears.

TPU-adaptation conventions (DESIGN.md §3/§4):

* **Grouped head layout.**  Attention heads are carried as
  ``(kv_heads_padded, q_per_kv, head_dim)`` so that sharding the leading
  kv-slot axis over the "model" mesh axis keeps *all* attention math local.
  ``HeadPlan`` computes the padding: KV heads are *duplicated* (GQA, exact)
  and/or q-head slots zero-padded (MHA / ragged groups) up to divisibility
  by the model-axis size.  With no mesh (CPU tests) every pad degenerates
  to the true architecture.
* **Blockwise (flash) attention.**  Scores never materialize at (S, S);
  a kv-chunk scan carries running (max, sum, acc).  Sliding windows and
  softcaps are applied inside the chunk mask.
* **Quantized linears.**  Any weight leaf may be a
  :class:`repro.quant.QuantizedTensor` (serve path); `apply_linear`
  dispatches to the fused dequant-matmul.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import logical_constraint
from repro.quant import QuantizedTensor

__all__ = [
    "HeadPlan",
    "make_head_plan",
    "rmsnorm",
    "layernorm",
    "apply_norm",
    "rope",
    "softcap",
    "apply_linear",
    "HoistedDequant",
    "hoist_dequant",
    "flash_attention",
    "decode_attention",
    "activation",
]


# --------------------------------------------------------------------------
# Head padding plan
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeadPlan:
    """Padded grouped-head layout for one (config, mesh-axis) pair.

    true q heads H, true kv heads KV  →  layout (kv_pad, g_pad, head_dim):
      * ``dup``: each true kv head duplicated ``dup`` times (exact for GQA),
      * ``kv_pad = KV * dup`` (multiple of the model-axis size),
      * ``g_pad = ceil(H / (KV*dup))``; q slots beyond H are structural pads.
    """

    n_heads: int
    n_kv: int
    head_dim: int
    axis_n: int
    dup: int
    kv_pad: int
    g_pad: int

    @property
    def h_pad(self) -> int:
        return self.kv_pad * self.g_pad


def make_head_plan(n_heads: int, n_kv: int, head_dim: int, axis_n: int = 1) -> HeadPlan:
    if axis_n <= 1 or n_kv == 0:
        g = max(n_heads // max(n_kv, 1), 1)
        return HeadPlan(n_heads, n_kv, head_dim, 1, 1, max(n_kv, 1), g)
    if n_kv == n_heads:
        # MHA: zero-pad kv slots to the axis multiple (padded q slots have
        # zero wq/wo ⇒ exact).  Duplication would pay lcm(kv,16)/kv ×; e.g.
        # qwen's 40 heads would balloon to 80 slots instead of 48.
        kv_pad = -(-n_kv // axis_n) * axis_n
        return HeadPlan(n_heads, n_kv, head_dim, axis_n, 1, kv_pad, 1)
    dup = math.lcm(n_kv, axis_n) // n_kv
    kv_pad = n_kv * dup
    g_pad = -(-n_heads // kv_pad)
    return HeadPlan(n_heads, n_kv, head_dim, axis_n, dup, kv_pad, g_pad)


# --------------------------------------------------------------------------
# Norms / activations / positional
# --------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(p: dict, x: jax.Array, kind: str) -> jax.Array:
    if kind == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def activation(x: jax.Array, kind: str) -> jax.Array:
    if kind == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, ..., head_dim); positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None]  # (1, S) broadcasting over batch
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, half)
    while ang.ndim < x.ndim:
        ang = ang[..., None, :]  # broadcast over head dims
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(
        x.dtype
    )


# --------------------------------------------------------------------------
# Linears (dense or quantized) + PTQ calibration capture
# --------------------------------------------------------------------------

import contextlib
import threading

_capture_state = threading.local()


@contextlib.contextmanager
def capture_scope(name: str):
    """Inside a capture context, tags subsequent apply_linear calls."""
    prev = getattr(_capture_state, "scope", None)
    _capture_state.scope = name
    try:
        yield
    finally:
        _capture_state.scope = prev


@contextlib.contextmanager
def capture_linear_inputs(records: dict):
    """Collect {scope/name: [x2d, ...]} for every linear applied within —
    RAW activations, O(n·p) memory per layer.  Kept as the numerical oracle
    for the streaming path (tests); the whole-model solver uses
    :func:`capture_gram_stats` instead and never materializes these lists.
    Eager-only; never active under jit."""
    prev = getattr(_capture_state, "records", None)
    _capture_state.records = records
    try:
        yield records
    finally:
        _capture_state.records = prev


@contextlib.contextmanager
def capture_gram_stats(stats: dict, mesh=None):
    """Accumulate {scope/name: CalibStats} streaming for every linear applied
    within: each call folds its activations into the layer's Σ = XXᵀ on the
    spot (``p²`` fp32 per linear, DESIGN.md §Streaming-solver) — raw
    activations are never retained.  Under a mesh, row contraction happens
    shard-locally with a psum (calib.sharded_gram).  The dict may also be
    opened inside a trace that returns it: the PTQ driver's compiled capture
    (core/solver._capture_program) does, once per chunk."""
    prev = getattr(_capture_state, "stats", None)
    prev_mesh = getattr(_capture_state, "stats_mesh", None)
    _capture_state.stats = stats
    _capture_state.stats_mesh = mesh
    try:
        yield stats
    finally:
        _capture_state.stats = prev
        _capture_state.stats_mesh = prev_mesh


def _record_linear(name, x, expert_rows=None):
    """Fold a linear's input into the open capture (records or Σ).

    ``expert_rows = (group_sizes, kept, routed)`` marks routed-expert input:
    ``x`` is (R, p) rows grouped by expert — group e is ``group_sizes[e]``
    consecutive rows, ``kept[e]`` of them routed copies (the rest empty
    capacity slots, zero rows) — out of ``routed`` (static) copies the
    router sent.  It gets one Σ per expert (``CalibStats`` with ``rows``)."""
    if name is None:
        return
    records = getattr(_capture_state, "records", None)
    stats = getattr(_capture_state, "stats", None)
    if records is None and stats is None:
        return
    scope = getattr(_capture_state, "scope", None)
    key = f"{scope}/{name}" if scope else name
    if records is not None:
        if expert_rows is None:
            records.setdefault(key, []).append(x.reshape(-1, x.shape[-1]))
        else:  # eager only: per-expert row blocks
            ends = np.cumsum(np.asarray(expert_rows[0]))
            records.setdefault(key, []).append(jnp.split(x, ends[:-1]))
    if stats is not None:
        from repro.core.calib import CalibStats

        p = x.shape[-1]
        if expert_rows is not None:
            sizes, kept, routed = expert_rows
            if key not in stats:
                stats[key] = CalibStats.zeros(p, experts=sizes.shape[0])
            stats[key] = stats[key].update_expert_rows(x, sizes, kept, routed)
        else:
            if key not in stats:
                stats[key] = CalibStats.zeros(p)
            stats[key] = stats[key].update_tokens(
                x, mesh=getattr(_capture_state, "stats_mesh", None)
            )


def apply_linear(w, x: jax.Array, out_shape: tuple = (), name: str = None) -> jax.Array:
    """y = x @ W, where W is (d_in, *out_dims) dense or a QuantizedTensor
    with codes (prod(out_dims), d_in).  x: (..., d_in)."""
    _record_linear(name, x)
    if isinstance(w, QuantizedTensor):
        from repro.kernels import ops as kops

        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y2 = kops.dequant_matmul(
            x2, w.codes, w.scale, w.zero, packed4=w.packed and w.bits == 4,
            out_dtype=x.dtype, interpret=None, group_size=w.group_size,
            pack_layout=w.pack_layout, pack_tile=w.pack_tile,
        )
        if w.outlier_values is not None:
            # Rank-s unstructured COO correction (fp16 values, flat int32
            # indices): y += x[:, cols] ⋅ vals → rows, after the dequant-GEMM.
            p_in = w.shape[1]
            rows = w.outlier_idx // p_in
            cols = w.outlier_idx % p_in
            contrib = x2[:, cols].astype(jnp.float32) * w.outlier_values.astype(
                jnp.float32
            )
            y2 = (
                y2.astype(jnp.float32)
                .at[:, rows]
                .add(contrib)
                .astype(x.dtype)
            )
        if w.outlier_col_idx is not None:
            y2 = (
                y2.astype(jnp.float32)
                + x2[:, w.outlier_col_idx].astype(jnp.float32)
                @ w.outlier_col_vals.T
            ).astype(x.dtype)
        out = out_shape or (w.shape[0],)
        return y2.reshape(*lead, *out)
    if isinstance(w, HoistedDequant):
        # Pre-dequantized QT (see HoistedDequant): same contraction shape,
        # same fp32 weight bytes, same post-GEMM outlier adds as the
        # QuantizedTensor reference path — bitwise-equal results.
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y2 = (x2.astype(jnp.float32) @ w.w.T).astype(x.dtype)
        if w.outlier_values is not None:
            p_in = w.shape[1]
            rows = w.outlier_idx // p_in
            cols = w.outlier_idx % p_in
            contrib = x2[:, cols].astype(jnp.float32) * w.outlier_values.astype(
                jnp.float32
            )
            y2 = y2.astype(jnp.float32).at[:, rows].add(contrib).astype(x.dtype)
        if w.outlier_col_idx is not None:
            y2 = (
                y2.astype(jnp.float32)
                + x2[:, w.outlier_col_idx].astype(jnp.float32)
                @ w.outlier_col_vals.T
            ).astype(x.dtype)
        out = out_shape or (w.shape[0],)
        return y2.reshape(*lead, *out)
    d_in = x.shape[-1]
    w2 = w.reshape(d_in, -1)
    y = jnp.einsum("...d,df->...f", x, w2)
    if out_shape:
        y = y.reshape(*y.shape[:-1], *out_shape)
    elif w.ndim > 2 and w.shape[0] == d_in:
        # (d_in, *out_dims) weights unfold naturally; weights whose *input*
        # spans several leading dims (e.g. mamba out_proj (nh, hd, d)) keep
        # the flat output.
        y = y.reshape(*y.shape[:-1], *w.shape[1:])
    return y


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HoistedDequant:
    """A QuantizedTensor whose dequantization has been hoisted out of the
    consuming computation: ``w`` holds byte-for-byte the fp32 matrix the
    XLA reference GEMM (kernels/ref.dequant_matmul_ref) would rebuild on
    every call — ``(codes - zero) * scale`` over the unpacked codes —
    alongside the original outlier planes, which stay *post-GEMM*
    corrections exactly as in the QuantizedTensor path.

    Purpose (DESIGN.md §Speculative-serving): inside a multi-position
    ``lax.scan`` (speculative verify / draft rollout) XLA re-dequantizes
    loop-invariant quantized weights at every scan position, which on the
    CPU reference path makes a γ+1-position verify cost γ+1 dequants.
    Hoisting pays the dequant once per *call* instead of once per
    *position*; because the per-position dot then consumes bit-identical
    weight bytes through the same ``x_f32 @ w.T → out_dtype`` contraction
    and the same post-GEMM outlier adds, results stay bitwise equal to
    the un-hoisted path — the token-identity invariant survives.  Only
    meaningful where dequant_matmul would take the XLA reference anyway
    (off-TPU); the Pallas kernel already fuses dequant in-kernel.

    Leaves may carry a leading period-stack axis like every other ``dec``
    leaf — slicing through jax.tree.map yields per-period views."""

    w: jax.Array  # (..., q, p) fp32 — exact reference dequant bytes
    outlier_values: Optional[jax.Array] = None  # (..., s) fp16
    outlier_idx: Optional[jax.Array] = None  # (..., s) int32, row·p + col
    outlier_col_idx: Optional[jax.Array] = None  # (..., c) int32
    outlier_col_vals: Optional[jax.Array] = None  # (..., q, c) fp32

    @property
    def shape(self):
        return self.w.shape


def hoist_dequant(tree):
    """Map a params tree, replacing every QuantizedTensor leaf with a
    :class:`HoistedDequant` holding the reference-path dequantized fp32
    matrix (packed codes are unpacked with the same helper the GEMM
    dispatch uses, so tile-prepacked layouts are transparent).  Dense
    leaves pass through untouched.  Roughly ``32 / bits`` × the quantized
    footprint in extra memory — a serve-time scratch copy the speculative
    engine holds only when hoisting is enabled."""
    from repro.kernels.ops import _unpacked

    def _one(leaf):
        if not isinstance(leaf, QuantizedTensor):
            return leaf
        codes = _unpacked(
            leaf.codes, leaf.packed and leaf.bits == 4,
            leaf.pack_layout, leaf.pack_tile,
        )
        p = codes.shape[-1]
        scale, zero = leaf.scale, leaf.zero
        if scale.ndim == codes.ndim - 1:  # per-channel grid stored flat
            scale, zero = scale[..., None], zero[..., None]
        n_groups = scale.shape[-1]
        gsz = leaf.group_size or -(-p // n_groups)
        idx = jnp.arange(p) // gsz
        w = (codes.astype(jnp.float32) - jnp.take(zero, idx, axis=-1)) * jnp.take(
            scale, idx, axis=-1
        )
        return HoistedDequant(
            w=w,
            outlier_values=leaf.outlier_values,
            outlier_idx=leaf.outlier_idx,
            outlier_col_idx=leaf.outlier_col_idx,
            outlier_col_vals=leaf.outlier_col_vals,
        )

    return jax.tree.map(
        _one, tree, is_leaf=lambda l: isinstance(l, QuantizedTensor)
    )


# --------------------------------------------------------------------------
# Blockwise (flash) attention — pure XLA, TPU-fusable
# --------------------------------------------------------------------------


def _chunk_mask(
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Sk,)
    causal: bool,
    window: Optional[int],
) -> jax.Array:
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), jnp.bool_)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def flash_attention(
    q: jax.Array,  # (B, Sq, KVp, G, hd)
    k: jax.Array,  # (B, Sk, KVp, hd)
    v: jax.Array,  # (B, Sk, KVp, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    q_offset: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Online-softmax blockwise attention in grouped-head layout.

    Returns (B, Sq, KVp, G, hd).  ``q_offset`` shifts query positions
    (used when queries are a suffix of the kv sequence).
    For *local* (windowed) layers only the kv chunks intersecting the window
    of each q chunk are visited (static slice — the sub-quadratic path that
    makes gemma2/mixtral long-context layers affordable).
    """
    B, Sq, KVp, G, hd = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    n_q = -(-Sq // q_chunk)
    n_kv = -(-Sk // kv_chunk)
    pad_q = n_q * q_chunk - Sq
    pad_kv = n_kv * kv_chunk - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))

    # Windowed layers: only kv chunks within [q_start − window, q_end] matter.
    if window is not None and causal:
        kv_band = min(n_kv, (window + q_chunk) // kv_chunk + 2)
    else:
        kv_band = n_kv

    q = q.reshape(B, n_q, q_chunk, KVp, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def q_block(qi, q_blk):
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
        qb = (q_blk * scale).astype(q.dtype)

        # First kv chunk index to visit (static band for windowed layers).
        if kv_band == n_kv:
            kv_start = 0
        else:
            # q chunk [qi*qc, qi*qc+qc); window reaches back `window` tokens.
            kv_start = jnp.maximum(
                0, (q_offset + qi * q_chunk - (window or 0)) // kv_chunk
            )
            kv_start = jnp.minimum(kv_start, n_kv - kv_band)

        def kv_step(carry, j):
            m_run, l_run, acc = carry
            kj = kv_start + j
            k_blk = jax.lax.dynamic_slice(
                k, (0, kj * kv_chunk, 0, 0), (B, kv_chunk, KVp, hd)
            )
            v_blk = jax.lax.dynamic_slice(
                v, (0, kj * kv_chunk, 0, 0), (B, kv_chunk, KVp, hd)
            )
            k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum(
                "bqkgd,btkd->bkgqt", qb, k_blk, preferred_element_type=jnp.float32
            )
            s = softcap(s, attn_softcap)
            mask = _chunk_mask(q_pos, k_pos, causal, window)
            mask &= (k_pos < Sk)[None, :]
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m_run, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_run = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_run, acc), None

        init = (
            jnp.full((B, KVp, G, q_chunk), -jnp.inf, jnp.float32),
            jnp.zeros((B, KVp, G, q_chunk), jnp.float32),
            jnp.zeros((B, KVp, G, q_chunk, hd), jnp.float32),
        )
        (m_run, l_run, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(kv_band))
        out = acc / jnp.maximum(l_run, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)  # (B, q_chunk, KVp, G, hd)

    out = jax.lax.map(lambda args: q_block(*args), (jnp.arange(n_q), q))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, n_q * q_chunk, KVp, G, hd)
    return out[:, :Sq].astype(k.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, KVp, G, hd)
    k_cache: jax.Array,  # (B, S, KVp, hd) bf16 or int8
    v_cache: jax.Array,  # (B, S, KVp, hd)
    cache_len: jax.Array,  # (B,) or scalar — valid prefix length
    *,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    k_scale: Optional[jax.Array] = None,  # (B, S, KVp, 1) fp32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-token attention against a (possibly ring-buffered) KV cache.

    int8 caches: per-(token, head) scales fold algebraically —
    q·(s·k₈) = s·(q·k₈) and Σ p·(s·v₈) = Σ (p·s)·v₈ — so the bf16 cache is
    never materialized; HBM reads stay 1 byte/element (§Perf H1).
    """
    B, S, KVp, hd = k_cache.shape
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum(
        "bokgd,btkd->bkgot", (q * scale).astype(q.dtype),
        k_cache.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    if k_scale is not None:
        # (B,S,KVp,1) → (B,KVp,1,1,S) broadcast over (B,KVp,G,o,S)
        s = s * k_scale[:, :, :, 0].transpose(0, 2, 1)[:, :, None, None, :]
    s = softcap(s, attn_softcap)
    pos = jnp.arange(S)[None, :]  # (1, S)
    clen = jnp.asarray(cache_len).reshape(-1, 1)  # (B or 1, 1)
    valid = pos < clen
    if window is not None:
        valid &= pos >= (clen - window)
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale[:, :, :, 0].transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum(
        "bkgot,btkd->bokgd", p.astype(q.dtype), v_cache.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)

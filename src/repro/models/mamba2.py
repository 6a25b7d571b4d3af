"""Mamba-2 SSD (state-space duality) block — chunked, MXU-friendly.

TPU adaptation (DESIGN.md §3): we implement the *SSD chunked* formulation
(arXiv:2405.21060 §6) rather than Mamba-1's sequential selective scan — the
chunked form is a handful of batched matmuls (intra-chunk "attention-like"
term + inter-chunk state recurrence over L/Q steps) which map onto the MXU,
with only an O(L/Q)-step `lax.scan` of (B, nh, hd, N) states.

Layout: SSD heads shard over the "model" mesh axis (nh % 16 == 0 for all
assigned archs); B/C group projections are replicated (G=1).  The depthwise
conv is split into an x-part (head-sharded) and a BC-part (replicated) so
its channels never straddle shards.

Decode is the O(1) recurrence: S ← exp(dtA)·S + dt·(B ⊗ x), y = C·S + D·x,
with a rolling (conv_w−1)-deep conv state.

The gated short-convolution block (LFM2's conv layers, ``kind="conv"``)
reuses the depthwise causal conv and its rolling decode buffer:
``B, C, x = split3(conv_in(h))``, ``conv_out(C ⊙ dwconv(B ⊙ x))``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import logical_constraint
from repro.models.common import apply_linear, rmsnorm

__all__ = [
    "MambaCache", "mamba_params_shape", "mamba_apply", "mamba_decode",
    "shortconv_apply", "shortconv_decode",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MambaCache:
    conv_x: jax.Array  # (B, convw-1, nh, hd)
    conv_bc: jax.Array  # (B, convw-1, 2*G*N)
    ssm: jax.Array  # (B, nh, hd, N) fp32


def _dw_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None) -> jax.Array:
    """Causal depthwise conv along axis 1.  x: (B, L, *ch), w: (*ch, K)."""
    k = w.shape[-1]
    x = jnp.pad(x, [(0, 0), (k - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    out = sum(
        x[:, i : i + x.shape[1] - k + 1] * w[..., i] for i in range(k)
    )
    return out if b is None else out + b


def _ssd_chunked(
    x: jax.Array,  # (B, L, nh, hd)
    dt: jax.Array,  # (B, L, nh) — post-softplus
    a: jax.Array,  # (nh,) negative
    b: jax.Array,  # (B, L, G, N)
    c: jax.Array,  # (B, L, G, N)
    *,
    chunk: int = 128,
    h0: Optional[jax.Array] = None,  # (B, nh, hd, N) initial state
):
    """Returns (y: (B, L, nh, hd), final state (B, nh, hd, N))."""
    B, L, nh, hd = x.shape
    G, N = b.shape[2], b.shape[3]
    hpg = nh // G  # heads per group
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (L + pad) // Q

    xc = x.reshape(B, nc, Q, nh, hd)
    dtc = dt.reshape(B, nc, Q, nh).astype(jnp.float32)
    bc = b.reshape(B, nc, Q, G, N)
    cc = c.reshape(B, nc, Q, G, N)

    da = dtc * a.astype(jnp.float32)[None, None, None, :]  # (B,nc,Q,nh) ≤ 0
    da_cs = jnp.cumsum(da, axis=2)  # inclusive cumsum
    da_tot = da_cs[:, :, -1]  # (B,nc,nh)

    # ---- intra-chunk (quadratic in Q, attention-like) ----
    cb = jnp.einsum("bcqgn,bckgn->bcgqk", cc, bc, preferred_element_type=jnp.float32)
    # decay L[h, i, j] = exp(da_cs[i] − da_cs[j]) for i ≥ j
    seg = da_cs[:, :, :, None, :] - da_cs[:, :, None, :, :]  # (B,nc,Q,Q,nh) i,j
    iq = jnp.arange(Q)
    causal = (iq[:, None] >= iq[None, :])[None, None, :, :, None]
    # Mask INSIDE the exp: exp(seg) overflows for i<j (positive seg) and a
    # where() around an inf poisons the backward pass (0·inf = NaN).
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # (B,nc,Q,Q,nh)
    scores = (
        cb.reshape(B, nc, G, 1, Q, Q)
        .repeat(hpg, axis=3)
        .reshape(B, nc, nh, Q, Q)
        .transpose(0, 1, 3, 4, 2)
        * decay
        * dtc[:, :, None, :, :]  # dt_j on the source index
    )  # (B,nc,Q,Q,nh)
    y_intra = jnp.einsum(
        "bcijh,bcjhd->bcihd", scores, xc.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    # ---- chunk summary states ----
    # S_c = Σ_j exp(da_tot − da_cs[j]) dt_j B_j ⊗ x_j   (B,nc,nh,hd,N)
    w_state = jnp.exp(da_tot[:, :, None, :] - da_cs) * dtc  # (B,nc,Q,nh)
    if G == 1:
        bx = jnp.einsum(
            "bcqgn,bcqhd,bcqh->bchdn",
            bc,
            xc.astype(jnp.float32),
            w_state,
            preferred_element_type=jnp.float32,
        )
    else:
        bx = jnp.einsum(
            "bcqgn,bcqghd,bcqgh->bcghdn",
            bc,
            xc.astype(jnp.float32).reshape(B, nc, Q, G, hpg, hd),
            w_state.reshape(B, nc, Q, G, hpg),
            preferred_element_type=jnp.float32,
        ).reshape(B, nc, nh, hd, N)

    # ---- inter-chunk recurrence over nc steps ----
    def step(h, inputs):
        bx_c, da_tot_c = inputs  # (B,nh,hd,N), (B,nh)
        h_new = h * jnp.exp(da_tot_c)[:, :, None, None] + bx_c
        return h_new, h  # emit state *before* the chunk

    if h0 is None:
        h0 = jnp.zeros((B, nh, hd, N), jnp.float32)
    h_final, h_before = jax.lax.scan(
        step,
        h0,
        (bx.transpose(1, 0, 2, 3, 4), da_tot.transpose(1, 0, 2)),
    )
    h_before = h_before.transpose(1, 0, 2, 3, 4)  # (B,nc,nh,hd,N)

    # ---- inter-chunk contribution: y_i += exp(da_cs[i]) C_i · H_before ----
    cfac = jnp.exp(da_cs)  # (B,nc,Q,nh)
    if G == 1:
        y_inter = jnp.einsum(
            "bcqgn,bchdn,bcqh->bcqhd", cc, h_before, cfac,
            preferred_element_type=jnp.float32,
        )
    else:
        y_inter = jnp.einsum(
            "bcqgn,bcghdn,bcqgh->bcqghd",
            cc,
            h_before.reshape(B, nc, G, hpg, hd, N),
            cfac.reshape(B, nc, Q, G, hpg),
            preferred_element_type=jnp.float32,
        ).reshape(B, nc, Q, nh, hd)

    y = (y_intra + y_inter).reshape(B, nc * Q, nh, hd)
    return y[:, :L], h_final


def mamba_apply(
    p: dict,
    x: jax.Array,  # (B, L, D) — post-norm input
    cfg,
    *,
    chunk: int = 128,
    cache: Optional[MambaCache] = None,
    return_cache: bool = False,
    valid_len=None,
):
    """Full-sequence SSD block (train / prefill).  ``valid_len`` (prefill):
    positions from it on are padding, with Δ = 0 there, so they leave the
    state as the last real token left it."""
    B, L, D = x.shape
    nh, hd = cfg.ssm_nheads, cfg.ssm_headdim
    G, N = cfg.ssm_ngroups, cfg.ssm_state

    z = apply_linear(p["wz"], x, out_shape=(nh, hd), name="wz")  # gate
    xin_pre = apply_linear(p["wx"], x, out_shape=(nh, hd), name="wx")  # pre-conv
    bc_pre = apply_linear(p["wbc"], x, name="wbc")  # (B,L,2GN)
    dt_raw = apply_linear(p["wdt"], x, name="wdt")  # (B,L,nh)

    xin_pre = logical_constraint(xin_pre, ("batch", None, "ssm_heads", None))
    xin = jax.nn.silu(_dw_conv(xin_pre, p["conv_x_w"], p["conv_x_b"]))
    bcv = jax.nn.silu(_dw_conv(bc_pre, p["conv_bc_w"], p["conv_bc_b"]))
    b, c = jnp.split(bcv.reshape(B, L, 2 * G, N), 2, axis=2)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if valid_len is not None:
        dt = jnp.where((jnp.arange(L) < valid_len)[None, :, None], dt, 0.0)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    y, h_final = _ssd_chunked(xin, dt, a, b, c, chunk=chunk)
    y = y + xin.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = (y.astype(x.dtype) * jax.nn.silu(z)).reshape(B, L, nh * hd)
    y = rmsnorm(y, p["norm_scale"].reshape(-1))
    out = apply_linear(p["out_proj"], y, name="out_proj")
    if not return_cache:
        return out, None
    k = cfg.ssm_conv
    new_cache = MambaCache(
        conv_x=_last_k(xin_pre, k - 1, valid_len),
        conv_bc=_last_k(bc_pre, k - 1, valid_len),
        ssm=h_final,
    )
    return out, new_cache


def _last_k(x: jax.Array, k: int, end=None) -> jax.Array:
    """The k positions before ``end`` (default: the last k), zeros before 0."""
    if end is None:
        return x[:, x.shape[1] - k :]
    x = jnp.pad(x, [(0, 0), (k, 0)] + [(0, 0)] * (x.ndim - 2))
    return jax.lax.dynamic_slice_in_dim(x, end, k, axis=1)


def mamba_decode(p: dict, x: jax.Array, cfg, cache: MambaCache):
    """One-token recurrent step.  x: (B, 1, D)."""
    B, _, D = x.shape
    nh, hd = cfg.ssm_nheads, cfg.ssm_headdim
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    xt = x[:, 0]

    z = apply_linear(p["wz"], xt, out_shape=(nh, hd), name="wz")
    xin_new = apply_linear(p["wx"], xt, out_shape=(nh, hd), name="wx")  # pre-conv
    bc_new = apply_linear(p["wbc"], xt, name="wbc")
    dt_raw = apply_linear(p["wdt"], xt, name="wdt")

    # Depthwise conv via rolling buffers (width k: k−1 past + current).
    k = cfg.ssm_conv
    conv_x_hist = jnp.concatenate([cache.conv_x, xin_new[:, None]], axis=1)
    conv_bc_hist = jnp.concatenate([cache.conv_bc, bc_new[:, None]], axis=1)
    xin = jax.nn.silu(
        jnp.einsum("bthd,hdt->bhd", conv_x_hist, p["conv_x_w"]) + p["conv_x_b"]
    )
    bc = jax.nn.silu(
        jnp.einsum("btn,nt->bn", conv_bc_hist, p["conv_bc_w"]) + p["conv_bc_b"]
    )
    b, c = jnp.split(bc.reshape(B, 2 * G, N), 2, axis=1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    da = jnp.exp(dt * a[None, :])  # (B, nh)

    xin32 = xin.astype(jnp.float32)
    bh = b.reshape(B, G, N).repeat(nh // G, axis=1)  # (B, nh, N)
    ch = c.reshape(B, G, N).repeat(nh // G, axis=1)
    ssm = cache.ssm * da[:, :, None, None] + (
        dt[:, :, None, None] * xin32[:, :, :, None] * bh[:, :, None, :]
    )
    y = jnp.einsum("bhdn,bhn->bhd", ssm, ch)
    y = y + xin32 * p["d_skip"].astype(jnp.float32)[None, :, None]
    y = (y.astype(x.dtype) * jax.nn.silu(z)).reshape(B, nh * hd)
    y = rmsnorm(y, p["norm_scale"].reshape(-1))
    out = apply_linear(p["out_proj"], y, name="out_proj")[:, None]
    new_cache = MambaCache(
        conv_x=conv_x_hist[:, 1:], conv_bc=conv_bc_hist[:, 1:], ssm=ssm
    )
    return out, new_cache


def _shortconv_gate(p: dict, x: jax.Array):
    """conv_in and the input gate: x (..., D) → (B ⊙ x, C)."""
    bcx = apply_linear(p["conv_in"], x, name="conv_in")
    b, c, xv = jnp.split(bcx, 3, axis=-1)
    return b * xv, c


def shortconv_apply(p: dict, x: jax.Array, *, return_cache: bool = False, valid_len=None):
    """Gated short conv over a sequence (train / prefill).  x: (B, L, D)
    post-norm input.  The conv runs in fp32 on the bf16 gated input; the
    cache is the ``K − 1`` gated inputs before ``valid_len`` (default: the
    last)."""
    bx, c = _shortconv_gate(p, x)
    conv = _dw_conv(bx.astype(jnp.float32), p["conv_w"].astype(jnp.float32))
    out = apply_linear(p["conv_out"], c * conv.astype(x.dtype), name="conv_out")
    if not return_cache:
        return out, None
    return out, {"conv": _last_k(bx, p["conv_w"].shape[-1] - 1, valid_len)}


def shortconv_decode(p: dict, x: jax.Array, cache: dict):
    """One-token step.  x: (B, 1, D); cache["conv"]: (B, K − 1, D)."""
    bx, c = _shortconv_gate(p, x[:, 0])
    hist = jnp.concatenate([cache["conv"], bx[:, None].astype(cache["conv"].dtype)], axis=1)
    conv = jnp.einsum("btd,dt->bd", hist.astype(jnp.float32), p["conv_w"].astype(jnp.float32))
    out = apply_linear(p["conv_out"], c * conv.astype(x.dtype), name="conv_out")
    return out[:, None], {"conv": hist[:, 1:]}

"""Model assembly: params, train loss, prefill and decode for all families.

One generic stack covers the assigned architectures (configs/base.py):
``lax.scan`` over *periods* of blocks (attn / mamba / short conv × dense /
MoE / none),
optional encoder stack (whisper), optional embedding prefix stub (llava).

Param tree (all leaves bf16 unless noted):

  embed        (vocab_pad, d)
  pos_emb      (max_seq, d)            [pos == "learned"]
  enc_pos_emb  (n_frames, d)           [encdec]
  lm_head      (d, vocab_pad)          [unless tied]
  final_norm   {scale[, bias]}
  dec / enc    per-period stacks: {"b0": {...}, "b1": {...}, ...}
               every leaf has leading dim n_periods (scan axis)

Each leaf carries *logical axes* (see dist/sharding.py) via the parallel
tree from :func:`param_axes`; the dry-run and trainer map these to mesh
PartitionSpecs.  Quantized serving swaps linear leaves for
:class:`QuantizedTensor`s (serve/quantize_model.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import BlockDef, ModelConfig
from repro.dist.sharding import logical_constraint
from repro.models import mamba2
from repro.models.common import (
    HeadPlan,
    HoistedDequant,
    activation,
    apply_linear,
    apply_norm,
    decode_attention,
    flash_attention,
    make_head_plan,
    rope,
    softcap,
)
from repro.models.mamba2 import MambaCache
from repro.models.moe import MOE_TRAIN_CAPACITY, moe_apply, router_aux_loss

__all__ = [
    "ModelPlan",
    "make_plan",
    "param_shapes",
    "param_axes",
    "init_params",
    "init_cache",
    "cache_axes",
    "train_loss",
    "prefill",
    "decode_step",
    "paged_cache_shapes",
    "init_paged_cache",
    "paged_prefill_chunk",
    "paged_decode_step",
    "paged_verify_tokens",
    "paged_draft_tokens",
]


@dataclasses.dataclass(frozen=True)
class ModelPlan:
    """Static lowering plan: config + mesh-derived paddings."""

    cfg: ModelConfig
    axis_n: int  # model-axis size (1 on CPU)
    heads: HeadPlan
    vocab_pad: int
    # "bf16" | "int8" | "int4" (§Perf H1 lever).  int4 is paged-engine only:
    # pages store two codes/byte (quant/pack.kv_pack_int4 fold-in-half) and
    # the contiguous cache path rejects it.
    kv_cache_dtype: str = "bf16"
    dispatch_groups: int = 1  # MoE data-local dispatch groups (§Perf H2)
    # Optional per-period param transform (e.g. int8-quantized FSDP gather,
    # dist/qgather.py — §Perf H3); applied inside the scan body so gathered
    # weights stay transient.  compare=False keeps the plan hashable-free.
    param_transform: Optional[Any] = dataclasses.field(default=None, compare=False)

    @property
    def dtype(self):
        return self.cfg.dtype


def make_plan(
    cfg: ModelConfig,
    axis_n: int = 1,
    kv_cache_dtype: str = "bf16",
    dispatch_groups: int = 1,
    param_transform=None,
) -> ModelPlan:
    plan_heads = make_head_plan(cfg.n_heads, cfg.n_kv_heads, cfg.hd, axis_n)
    vocab_pad = -(-cfg.vocab // max(axis_n, 1)) * max(axis_n, 1)
    return ModelPlan(
        cfg=cfg, axis_n=axis_n, heads=plan_heads, vocab_pad=vocab_pad,
        kv_cache_dtype=kv_cache_dtype, dispatch_groups=dispatch_groups,
        param_transform=param_transform,
    )


# ---------------------------------------------------------------------------
# Parameter definitions: (shape, logical axes, init scale) per leaf.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _P:
    shape: tuple
    axes: tuple
    init: str = "normal"  # normal | zeros | zeros32 | ones | small_normal | conv | dt | alog

    @property
    def dtype_override(self):
        # SSM dynamics params are numerically sensitive → fp32 (DESIGN.md §5),
        # as is the router's selection bias (a bf16 bias would decide ties).
        return jnp.float32 if self.init in ("dt", "alog", "zeros32") else None


def _norm_def(cfg, d) -> dict:
    if cfg.norm == "layernorm":
        return {"scale": _P((d,), (None,), "ones"), "bias": _P((d,), (None,), "zeros")}
    return {"scale": _P((d,), (None,), "zeros")}  # (1+scale) convention


def _attn_defs(cfg: ModelConfig, hp: HeadPlan, suffix="") -> dict:
    d, hd = cfg.d_model, cfg.hd
    defs = {
        f"wq{suffix}": _P((d, hp.kv_pad, hp.g_pad, hd), ("embed", "heads", None, None)),
        f"wk{suffix}": _P((d, hp.n_kv, hd), ("embed", "kv_heads", "head_dim")),
        f"wv{suffix}": _P((d, hp.n_kv, hd), ("embed", "kv_heads", "head_dim")),
        f"wo{suffix}": _P((hp.kv_pad, hp.g_pad, hd, d), ("heads", None, None, "embed")),
    }
    if cfg.qk_norm and not suffix:
        defs["q_norm"] = _norm_def(cfg, hd)
        defs["k_norm"] = _norm_def(cfg, hd)
    if cfg.qkv_bias and not suffix:
        defs["bq"] = _P((hp.kv_pad, hp.g_pad, hd), ("heads", None, None), "zeros")
        defs["bk"] = _P((hp.n_kv, hd), ("kv_heads", "head_dim"), "zeros")
        defs["bv"] = _P((hp.n_kv, hd), ("kv_heads", "head_dim"), "zeros")
    return defs


def _mlp_defs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    defs = {
        "wg": _P((d, f), ("embed", "ffn")),
        "wd": _P((f, d), ("ffn", "embed"), "small_normal"),
    }
    if cfg.gated_mlp:
        defs["wu"] = _P((d, f), ("embed", "ffn"))
    return defs


def _moe_defs(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_ff, cfg.n_experts
    defs = {
        "router": _P((d, e), (None, None)),
        "w_gate": _P((e, d, f), ("experts", "embed", "expert_ffn")),
        "w_down": _P((e, f, d), ("experts", "expert_ffn", "embed"), "small_normal"),
    }
    if cfg.gated_mlp:
        defs["w_up"] = _P((e, d, f), ("experts", "embed", "expert_ffn"))
    if cfg.router_bias:
        defs["expert_bias"] = _P((e,), (None,), "zeros32")
    return defs


def _conv_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    return {
        "conv_in": _P((d, 3 * d), ("embed", None)),
        "conv_w": _P((d, cfg.ssm_conv), (None, None), "conv"),
        "conv_out": _P((d, d), (None, "embed"), "small_normal"),
    }


def _mamba_defs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    nh, hd = cfg.ssm_nheads, cfg.ssm_headdim
    gn2 = 2 * cfg.ssm_ngroups * cfg.ssm_state
    k = cfg.ssm_conv
    return {
        "wz": _P((d, nh, hd), ("embed", "ssm_heads", None)),
        "wx": _P((d, nh, hd), ("embed", "ssm_heads", None)),
        "wbc": _P((d, gn2), ("embed", None)),
        "wdt": _P((d, nh), ("embed", "ssm_heads"), "small_normal"),
        "conv_x_w": _P((nh, hd, k), ("ssm_heads", None, None), "conv"),
        "conv_x_b": _P((nh, hd), ("ssm_heads", None), "zeros"),
        "conv_bc_w": _P((gn2, k), (None, None), "conv"),
        "conv_bc_b": _P((gn2,), (None,), "zeros"),
        "a_log": _P((nh,), (None,), "alog"),
        "d_skip": _P((nh,), (None,), "ones"),
        "dt_bias": _P((nh,), (None,), "dt"),
        "norm_scale": _P((nh, hd), ("ssm_heads", None), "zeros"),
        "out_proj": _P((nh, hd, d), ("ssm_heads", None, "embed"), "small_normal"),
    }


def _block_defs(cfg: ModelConfig, hp: HeadPlan, b: BlockDef) -> dict:
    d = cfg.d_model
    defs: dict = {"ln": _norm_def(cfg, d)}
    if b.kind == "attn":
        defs.update(_attn_defs(cfg, hp))
        if b.cross:
            defs["ln_c"] = _norm_def(cfg, d)
            defs.update(_attn_defs(cfg, hp, suffix="_c"))
        if cfg.post_norms:
            defs["post_ln"] = _norm_def(cfg, d)
    elif b.kind == "conv":
        defs.update(_conv_defs(cfg))
    else:
        defs.update(_mamba_defs(cfg))
    if b.mlp != "none":
        defs["ln2"] = _norm_def(cfg, d)
        defs.update(_moe_defs(cfg) if b.mlp == "moe" else _mlp_defs(cfg))
        if cfg.post_norms:
            defs["post_ln2"] = _norm_def(cfg, d)
    return defs


def _stack_defs(cfg: ModelConfig, hp: HeadPlan, pattern, n_periods) -> dict:
    out = {}
    for i, b in enumerate(pattern):
        blk = _block_defs(cfg, hp, b)
        out[f"b{i}"] = jax.tree.map(
            lambda pd: _P((n_periods, *pd.shape), ("layers", *pd.axes), pd.init),
            blk,
            is_leaf=lambda x: isinstance(x, _P),
        )
    return out


def model_defs(plan: ModelPlan) -> dict:
    cfg, hp = plan.cfg, plan.heads
    d = cfg.d_model
    defs: dict = {
        "embed": _P((plan.vocab_pad, d), ("vocab", "embed")),
        "final_norm": _norm_def(cfg, d),
        "dec": _stack_defs(cfg, hp, cfg.pattern, cfg.n_periods),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = _P((d, plan.vocab_pad), ("embed", "vocab"))
    if cfg.pos == "learned":
        defs["pos_emb"] = _P((cfg.max_seq, d), (None, "embed"), "small_normal")
    if cfg.family == "encdec":
        defs["enc"] = _stack_defs(cfg, hp, cfg.enc_pattern, cfg.n_enc_periods)
        defs["enc_pos_emb"] = _P((cfg.n_frames, d), (None, "embed"), "small_normal")
        defs["enc_final_norm"] = _norm_def(cfg, d)
    if cfg.n_prefix:
        # llava stub: learned projection bias marker (patches arrive projected).
        defs["prefix_ln"] = _norm_def(cfg, d)
    return defs


def _is_pdef(x):
    return isinstance(x, _P)


def param_shapes(plan: ModelPlan) -> Any:
    return jax.tree.map(
        lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype_override or plan.dtype),
        model_defs(plan),
        is_leaf=_is_pdef,
    )


def param_axes(plan: ModelPlan) -> Any:
    return jax.tree.map(lambda pd: pd.axes, model_defs(plan), is_leaf=_is_pdef)


def _init_leaf(key, pd: _P, dtype, n_layers_total: int):
    shape = pd.shape
    if pd.init == "zeros":
        return jnp.zeros(shape, dtype)
    if pd.init == "zeros32":
        return jnp.zeros(shape, jnp.float32)
    if pd.init == "ones":
        return jnp.ones(shape, dtype)
    if pd.init == "normal":
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
    if pd.init == "small_normal":
        s = 0.02 / math.sqrt(max(2 * n_layers_total, 1))
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)
    if pd.init == "conv":
        fan = shape[-1]
        return (
            jax.random.uniform(key, shape, jnp.float32, -1, 1) / math.sqrt(fan)
        ).astype(dtype)
    if pd.init == "dt":
        u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
        return jnp.log(jnp.expm1(u)).astype(jnp.float32)  # fp32 (sensitive)
    if pd.init == "alog":
        u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(jnp.float32)
    raise ValueError(pd.init)


def init_params(plan: ModelPlan, key: jax.Array) -> Any:
    defs = model_defs(plan)
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_pdef)
    keys = jax.random.split(key, len(leaves))
    n_layers = plan.cfg.n_layers + plan.cfg.n_enc_periods * len(plan.cfg.enc_pattern)
    out = [
        _init_leaf(k, pd, plan.dtype, n_layers) for k, pd in zip(keys, leaves)
    ]
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Forward blocks
# ---------------------------------------------------------------------------


def _qkv(cfg, hp: HeadPlan, p, h, suffix=""):
    # out_shape matters on the QuantizedTensor path (codes are 2-D fused).
    q = apply_linear(
        p[f"wq{suffix}"], h, out_shape=(hp.kv_pad, hp.g_pad, hp.head_dim),
        name=f"wq{suffix}",
    )  # (B,S,KVp,Gp,hd)
    k = apply_linear(
        p[f"wk{suffix}"], h, out_shape=(hp.n_kv, hp.head_dim), name=f"wk{suffix}"
    )  # (B,S,KV,hd)
    v = apply_linear(
        p[f"wv{suffix}"], h, out_shape=(hp.n_kv, hp.head_dim), name=f"wv{suffix}"
    )
    if cfg.qkv_bias and not suffix:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm and not suffix:  # per head, before RoPE
        q = apply_norm(p["q_norm"], q, cfg.norm)
        k = apply_norm(p["k_norm"], k, cfg.norm)
    return q, _expand_kv(hp, k), _expand_kv(hp, v)


def _expand_kv(hp, k):
    if hp.dup > 1:  # GQA: duplicate true kv heads into padded slots (exact)
        # take-with-iota instead of repeat: GSPMD turns the repeat's
        # split-dim reshape into an "involuntary full rematerialization";
        # a constant gather from a replicated operand slices locally.
        return jnp.take(k, jnp.arange(hp.kv_pad) // hp.dup, axis=2)
    if hp.kv_pad > hp.n_kv:  # MHA: zero-pad (padded q slots have wo ≡ 0)
        pad = [(0, 0)] * k.ndim
        pad[2] = (0, hp.kv_pad - hp.n_kv)
        return jnp.pad(k, pad)
    return k


def _kv_quantize(x: jax.Array):
    """Per-(token, head) symmetric int8: (…, hd) → codes int8, scale fp32."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), -1, keepdims=True) / 127.0 + 1e-12
    codes = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def _kv_quantize4(x: jax.Array):
    """Per-(token, head) symmetric int4, fold-in-half packed: (…, hd) →
    packed uint8 (…, hd/2), scale fp32 (…, 1).  Codes live in [-7, 7] so the
    4-bit two's-complement range is symmetric (−8 unused)."""
    from repro.quant.pack import kv_pack_int4

    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), -1, keepdims=True) / 7.0 + 1e-12
    codes = jnp.clip(jnp.round(x32 / scale), -7, 7).astype(jnp.int8)
    return kv_pack_int4(codes), scale


def _attn_sublayer(
    cfg,
    hp,
    b: BlockDef,
    p,
    x,
    *,
    pos_ids,
    mode: str,
    cache=None,
    enc_out=None,
    decode_pos=None,
    kv_dtype: str = "bf16",
    page_table=None,
    page_write=None,
):
    """Self-attention (+ optional cross) sublayer.  Returns (x, new_cache).

    With ``page_table`` set the KV cache is block-paged (DESIGN.md
    §Paged-serving): decode writes the new token into
    ``(page_write, pos % page_size)`` and attends through
    ``ops.paged_attention``; prefill scatter-writes the chunk into its
    pages and attends the gathered context with ``flash_attention`` at
    ``q_offset = pos_ids[0]`` (chunked prefill).
    """
    h = apply_norm(p["ln"], x, cfg.norm)
    q, k, v = _qkv(cfg, hp, p, h)
    if cfg.pos == "rope":
        q = rope(q, pos_ids, cfg.rope_theta)
        k = rope(k, pos_ids, cfg.rope_theta)
    q = logical_constraint(q, ("batch", None, "heads", None, None))
    k = logical_constraint(k, ("batch", None, "heads", None))
    v = logical_constraint(v, ("batch", None, "heads", None))

    new_cache = {}
    if page_table is not None:
        if b.cross:
            raise ValueError("paged KV serving does not support cross-attention")
        from repro.kernels import ops as kops

        kc, vc = cache["k"], cache["v"]  # (n_pages, psz, KVp, hd)
        psz = kc.shape[1]
        if mode == "decode":
            B = q.shape[0]
            pos_b = jnp.broadcast_to(jnp.asarray(decode_pos, jnp.int32), (B,))
            slot = pos_b % psz
            if kv_dtype in ("int8", "int4"):
                quantize = _kv_quantize4 if kv_dtype == "int4" else _kv_quantize
                k8, ks_new = quantize(k[:, 0])
                v8, vs_new = quantize(v[:, 0])
                kc = kc.at[page_write, slot].set(k8)
                vc = vc.at[page_write, slot].set(v8)
                ksc = cache["ks"].at[page_write, slot].set(ks_new)
                vsc = cache["vs"].at[page_write, slot].set(vs_new)
                new_cache = {"k": kc, "v": vc, "ks": ksc, "vs": vsc}
                ksp, vsp = ksc, vsc
            else:
                kc = kc.at[page_write, slot].set(k[:, 0].astype(kc.dtype))
                vc = vc.at[page_write, slot].set(v[:, 0].astype(vc.dtype))
                new_cache = {"k": kc, "v": vc}
                ksp = vsp = None
            o = kops.paged_attention(
                q[:, 0], kc, vc, page_table, pos_b + 1,
                window=b.window, attn_softcap=cfg.attn_softcap,
                k_scale_pages=ksp, v_scale_pages=vsp,
            )[:, None]
        else:  # chunked paged prefill, one sequence at a time (B == 1)
            S = k.shape[1]
            pos = jnp.asarray(pos_ids, jnp.int32).reshape(-1)  # (S,) absolute
            row = page_table[0]  # (n_pgs,)
            # Pad positions beyond the table must hit the null page (page 0)
            # explicitly — the default gather clamp would alias them onto the
            # last real page and clobber valid prompt KV.
            pg = pos // psz
            pidx = jnp.where(
                pg < row.shape[0], row[jnp.minimum(pg, row.shape[0] - 1)], 0
            )
            slot = pos % psz
            if kv_dtype in ("int8", "int4"):
                quantize = _kv_quantize4 if kv_dtype == "int4" else _kv_quantize
                k8, ks_new = quantize(k[0])
                v8, vs_new = quantize(v[0])
                kc = kc.at[pidx, slot].set(k8)
                vc = vc.at[pidx, slot].set(v8)
                ksc = cache["ks"].at[pidx, slot].set(ks_new)
                vsc = cache["vs"].at[pidx, slot].set(vs_new)
                new_cache = {"k": kc, "v": vc, "ks": ksc, "vs": vsc}
            else:
                kc = kc.at[pidx, slot].set(k[0].astype(kc.dtype))
                vc = vc.at[pidx, slot].set(v[0].astype(vc.dtype))
                new_cache = {"k": kc, "v": vc}
            n_ctx = row.shape[0] * psz
            kctx = kc[row].reshape(1, n_ctx, *kc.shape[2:])
            vctx = vc[row].reshape(1, n_ctx, *vc.shape[2:])
            if kv_dtype == "int4":
                from repro.quant.pack import kv_unpack_int4

                kctx = kv_unpack_int4(kctx)
                vctx = kv_unpack_int4(vctx)
            if kv_dtype in ("int8", "int4"):
                ksg = new_cache["ks"][row].reshape(1, n_ctx, -1, 1)
                vsg = new_cache["vs"][row].reshape(1, n_ctx, -1, 1)
                kctx = (kctx.astype(jnp.float32) * ksg).astype(q.dtype)
                vctx = (vctx.astype(jnp.float32) * vsg).astype(q.dtype)
            o = flash_attention(
                q, kctx, vctx,
                causal=True, window=b.window, attn_softcap=cfg.attn_softcap,
                q_offset=pos[0],
            )
        out = _apply_out_proj(p["wo"], o, name="wo")
        if cfg.post_norms:
            out = apply_norm(p["post_ln"], out, cfg.norm)
        return x + out, new_cache
    if mode == "decode":
        kc, vc = cache["k"], cache["v"]
        B = kc.shape[0]
        w = b.window
        pos_b = jnp.broadcast_to(jnp.asarray(decode_pos, jnp.int32), (B,))
        slot = pos_b % kc.shape[1] if w is not None else pos_b
        bidx = jnp.arange(B)
        if kv_dtype == "int8":
            k8, ks = _kv_quantize(k[:, 0])
            v8, vs = _kv_quantize(v[:, 0])
            kc = kc.at[bidx, slot].set(k8)
            vc = vc.at[bidx, slot].set(v8)
            ksc = cache["ks"].at[bidx, slot].set(ks)
            vsc = cache["vs"].at[bidx, slot].set(vs)
            new_cache = {"k": kc, "v": vc, "ks": ksc, "vs": vsc}
        else:
            kc = kc.at[bidx, slot].set(k[:, 0].astype(kc.dtype))
            vc = vc.at[bidx, slot].set(v[:, 0].astype(vc.dtype))
            ksc = vsc = None
            new_cache = {"k": kc, "v": vc}
        # Ring buffers make the window implicit; valid prefix is per-slot.
        valid_len = jnp.minimum(pos_b + 1, kc.shape[1])
        o = decode_attention(q, kc, vc, valid_len, window=None,
                             attn_softcap=cfg.attn_softcap,
                             k_scale=ksc, v_scale=vsc)
    else:
        o = flash_attention(
            q, k, v,
            causal=b.causal,
            window=b.window,
            attn_softcap=cfg.attn_softcap,
        )
        if mode == "prefill":
            new_cache = _fill_cache(cache, k, v, b.window, pos_ids, kv_dtype)

    out = _apply_out_proj(p["wo"], o, name="wo")
    if cfg.post_norms:
        out = apply_norm(p["post_ln"], out, cfg.norm)
    x = x + out

    if b.cross:
        h = apply_norm(p["ln_c"], x, cfg.norm)
        qc = apply_linear(
            p["wq_c"], h, out_shape=(hp.kv_pad, hp.g_pad, hp.head_dim), name="wq_c"
        )
        if mode == "decode":
            kcx, vcx = cache["ck"], cache["cv"]
            new_cache.update({"ck": kcx, "cv": vcx})
        else:
            kcx = _expand_kv(hp, apply_linear(
                p["wk_c"], enc_out, out_shape=(hp.n_kv, hp.head_dim), name="wk_c"
            ))
            vcx = _expand_kv(hp, apply_linear(
                p["wv_c"], enc_out, out_shape=(hp.n_kv, hp.head_dim), name="wv_c"
            ))
            if mode == "prefill":
                new_cache.update({"ck": kcx.astype(jnp.bfloat16),
                                  "cv": vcx.astype(jnp.bfloat16)})
        if mode == "decode":
            oc = decode_attention(qc, kcx, vcx, kcx.shape[1], window=None)
        else:
            oc = flash_attention(qc, kcx, vcx, causal=False)
        x = x + _apply_out_proj(p["wo_c"], oc, name="wo_c")
    return x, new_cache


def _apply_out_proj(w, o, name=None):
    """o: (B, S, KVp, Gp, hd) → (B, S, d); dense 4-D weight or QuantizedTensor
    with codes (d, KVp·Gp·hd)."""
    if hasattr(w, "codes") or isinstance(w, HoistedDequant):
        return apply_linear(w, o.reshape(*o.shape[:2], -1), name=name)
    from repro.models.common import _record_linear

    _record_linear(name, o.reshape(*o.shape[:2], -1))
    return jnp.einsum("bskgd,kgdm->bsm", o, w)


def _fill_cache(cache, k, v, window, pos_ids, kv_dtype="bf16"):
    """Prefill: write the (ring-buffered for windowed layers) cache."""
    if cache is None:
        return {}
    kc, vc = cache["k"], cache["v"]
    cap = kc.shape[1]
    S = k.shape[1]
    if kv_dtype == "int8":
        k, ks = _kv_quantize(k)
        v, vs = _kv_quantize(v)
    if window is not None and cap < S:
        # keep last `cap` positions at slots pos % cap
        slots = (jnp.arange(S - cap, S)) % cap
        kc = kc.at[:, slots].set(k[:, S - cap :].astype(kc.dtype))
        vc = vc.at[:, slots].set(v[:, S - cap :].astype(vc.dtype))
        out = {"k": kc, "v": vc}
        if kv_dtype == "int8":
            out["ks"] = cache["ks"].at[:, slots].set(ks[:, S - cap :])
            out["vs"] = cache["vs"].at[:, slots].set(vs[:, S - cap :])
    else:
        zi = (0, 0, 0, 0)
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), zi)
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), zi)
        out = {"k": kc, "v": vc}
        if kv_dtype == "int8":
            out["ks"] = jax.lax.dynamic_update_slice(cache["ks"], ks, zi)
            out["vs"] = jax.lax.dynamic_update_slice(cache["vs"], vs, zi)
    return out


def _mlp_sublayer(cfg, b: BlockDef, p, x, aux, dispatch_groups=1, moe_capacity=None):
    if b.mlp == "none":
        return x, aux
    h = apply_norm(p["ln2"], x, cfg.norm)
    if b.mlp == "moe":
        y, probs = moe_apply(
            p,
            h,
            n_experts=cfg.n_experts,
            top_k=cfg.top_k,
            act=cfg.act,
            gated=cfg.gated_mlp,
            norm_topk=cfg.router_norm_topk,
            score=cfg.router_score,
            capacity_factor=moe_capacity,
            return_aux=aux is not None,
            dispatch_groups=dispatch_groups,
        )
        if aux is not None and probs is not None:
            aux = aux + router_aux_loss(probs)
    else:
        g = apply_linear(p["wg"], h, name="wg")
        u = activation(g, cfg.act)
        if cfg.gated_mlp:
            u = u * apply_linear(p["wu"], h, name="wu")
        u = logical_constraint(u, ("batch", None, "ffn"))
        y = apply_linear(p["wd"], u, name="wd")
    if cfg.post_norms:
        y = apply_norm(p["post_ln2"], y, cfg.norm)
    return x + y, aux


def _block_apply(cfg, hp, b, p, x, *, mode, pos_ids, cache=None, enc_out=None,
                 decode_pos=None, aux=None, kv_dtype="bf16", dispatch_groups=1,
                 page_table=None, page_write=None, moe_capacity=None, valid_len=None):
    """One block.  ``moe_capacity``: the MoE capacity factor (training), or
    None for dropless routing.  ``valid_len`` (prefill): the real tokens;
    later ones are padding that recurrent state does not fold in."""
    if b.kind == "attn":
        x, new_cache = _attn_sublayer(
            cfg, hp, b, p, x,
            pos_ids=pos_ids, mode=mode, cache=cache, enc_out=enc_out,
            decode_pos=decode_pos, kv_dtype=kv_dtype,
            page_table=page_table, page_write=page_write,
        )
    elif b.kind == "conv":
        h = apply_norm(p["ln"], x, cfg.norm)
        if mode == "decode":
            y, new_cache = mamba2.shortconv_decode(p, h, cache)
        else:
            y, new_cache = mamba2.shortconv_apply(
                p, h, return_cache=(mode == "prefill"), valid_len=valid_len
            )
        x = x + y
    else:
        h = apply_norm(p["ln"], x, cfg.norm)
        if mode == "decode":
            y, new_cache = mamba2.mamba_decode(p, h, cfg, cache)
        else:
            y, new_cache = mamba2.mamba_apply(
                p, h, cfg, cache=cache, return_cache=(mode == "prefill"),
                valid_len=valid_len,
            )
        x = x + y
    x, aux = _mlp_sublayer(cfg, b, p, x, aux, dispatch_groups, moe_capacity)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Stacks (scan over periods)
# ---------------------------------------------------------------------------


def _run_stack(
    plan: ModelPlan,
    stack_params: dict,
    pattern,
    x,
    *,
    mode: str,
    pos_ids,
    caches=None,
    enc_out=None,
    decode_pos=None,
    aux=None,
    remat: bool = True,
    page_table=None,
    page_write=None,
    moe_capacity=None,
    valid_len=None,
):
    """Scan over periods.  caches: pytree stacked on leading period axis.
    ``page_table``/``page_write`` (shared across periods) switch attention
    layers to the paged KV path; ``moe_capacity`` (training) caps each
    expert's routed copies, None routes dropless; ``valid_len`` as in
    :func:`_block_apply`."""
    cfg, hp = plan.cfg, plan.heads
    have_aux = aux is not None

    def period_fn(carry, xs):
        x, aux = carry
        p_period, cache_period = xs
        if plan.param_transform is not None and mode == "train":
            p_period = plan.param_transform(p_period)
        new_caches = {}
        for i, b in enumerate(pattern):
            c_i = cache_period.get(f"b{i}") if cache_period else None
            x, nc, aux = _block_apply(
                cfg, hp, b, p_period[f"b{i}"], x,
                mode=mode, pos_ids=pos_ids, cache=c_i, enc_out=enc_out,
                decode_pos=decode_pos, aux=aux, kv_dtype=plan.kv_cache_dtype,
                dispatch_groups=plan.dispatch_groups,
                page_table=page_table, page_write=page_write,
                moe_capacity=moe_capacity, valid_len=valid_len,
            )
            new_caches[f"b{i}"] = nc
        return (x, aux), new_caches

    body = period_fn
    if remat and mode == "train":
        body = jax.checkpoint(period_fn, prevent_cse=False)

    if aux is None:
        aux = jnp.zeros((), jnp.float32)
    xs = (stack_params, caches if caches is not None else _empty_caches(pattern, plan))
    (x, aux), new_caches = jax.lax.scan(body, (x, aux), xs)
    return x, new_caches, (aux if have_aux else None)


def _empty_caches(pattern, plan):
    n = plan.cfg.n_periods
    return {f"b{i}": None for i in range(len(pattern))} if False else {
        f"b{i}": jnp.zeros((n, 0), jnp.float32) for i in range(len(pattern))
    }


# ---------------------------------------------------------------------------
# Losses / entry points
# ---------------------------------------------------------------------------


def chunked_cross_entropy(
    x: jax.Array,  # (B, S, d)
    head,  # (d, vocab_pad) dense / QuantizedTensor, or ("tied", embed)
    labels: jax.Array,  # (B, S) int32
    mask: jax.Array,  # (B, S) bool/float
    *,
    real_vocab: int,
    chunk: int = 512,
    logit_softcap: Optional[float] = None,
):
    """LM cross-entropy without materializing (B, S, V) logits: scan over
    sequence chunks (beyond-paper memory optimization, DESIGN.md §4)."""
    B, S, d = x.shape
    chunk = min(chunk, S)
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    xs = x.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, chunk).transpose(1, 0, 2)
    ms = mask.reshape(B, n, chunk).transpose(1, 0, 2)

    def step(acc, inp):
        xc, lc, mc = inp
        logits = _head_logits(xc, head)  # (B, C, Vp) fp32
        logits = softcap(logits, logit_softcap)
        logits = logical_constraint(logits, ("batch", None, "vocab"))
        vp = logits.shape[-1]
        if vp > real_vocab:
            bias = jnp.where(jnp.arange(vp) < real_vocab, 0.0, -1e30)
            logits = logits + bias
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * mc
        return (acc[0] + nll.sum(), acc[1] + mc.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros((), jnp.float32),) * 2, (xs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def _head_logits(xc, head):
    if isinstance(head, tuple) and head[0] == "tied":
        return jnp.einsum(
            "bcd,vd->bcv", xc, head[1], preferred_element_type=jnp.float32
        )
    if hasattr(head, "codes") or isinstance(head, HoistedDequant):
        # QuantizedTensor (or its hoisted-dequant serving view)
        y = apply_linear(head, xc)
        return y.astype(jnp.float32)
    return jnp.einsum("bcd,dv->bcv", xc, head, preferred_element_type=jnp.float32)


def _logit_head(plan, params):
    if plan.cfg.tie_embeddings:
        return ("tied", params["embed"])
    return params["lm_head"]


def _embed_tokens(plan, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(plan.dtype)
    if plan.cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(plan.cfg.d_model), plan.dtype)
    return x


def _encoder(plan, params, frames):
    cfg = plan.cfg
    x = frames.astype(plan.dtype) + params["enc_pos_emb"][None].astype(plan.dtype)
    pos = jnp.arange(frames.shape[1])
    x, _, _ = _run_stack(
        plan, params["enc"], cfg.enc_pattern, x, mode="train", pos_ids=pos
    )
    return apply_norm(params["enc_final_norm"], x, cfg.norm)


def train_loss(plan: ModelPlan, params, batch: dict) -> jax.Array:
    """batch: tokens (B,S) [+ frames (B,F,d) | patches (B,P,d)] → scalar loss."""
    cfg = plan.cfg
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed_tokens(plan, params, tokens)
    loss_mask = jnp.ones((B, S), jnp.float32)

    enc_out = None
    if cfg.family == "encdec":
        enc_out = _encoder(plan, params, batch["frames"])
    if cfg.n_prefix:
        pre = batch["patches"].astype(plan.dtype)
        pre = apply_norm(params["prefix_ln"], pre, cfg.norm)
        x = jnp.concatenate([pre, x], axis=1)
        loss_mask = jnp.concatenate(
            [jnp.zeros((B, cfg.n_prefix), jnp.float32), loss_mask], axis=1
        )
        tokens = jnp.concatenate(
            [jnp.zeros((B, cfg.n_prefix), tokens.dtype), tokens], axis=1
        )
        S = S + cfg.n_prefix

    x = logical_constraint(x, ("batch", "seq_sp", None))
    pos = jnp.arange(S)
    if cfg.pos == "learned":
        x = x + jax.lax.dynamic_slice(
            params["pos_emb"], (0, 0), (S, cfg.d_model)
        )[None].astype(plan.dtype)

    aux0 = jnp.zeros((), jnp.float32) if _has_moe(cfg) else None
    x, _, aux = _run_stack(
        plan, params["dec"], cfg.pattern, x,
        mode="train", pos_ids=pos, enc_out=enc_out, aux=aux0,
        moe_capacity=MOE_TRAIN_CAPACITY,
    )
    x = apply_norm(params["final_norm"], x, cfg.norm)

    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    loss_mask = loss_mask.at[:, -1].set(0.0)
    loss = chunked_cross_entropy(
        x,
        _logit_head(plan, params),
        labels,
        loss_mask,
        real_vocab=cfg.vocab,
        logit_softcap=cfg.logit_softcap,
    )
    if aux is not None:
        loss = loss + 0.01 * aux / max(cfg.n_layers, 1)
    return loss


def _has_moe(cfg) -> bool:
    return any(b.mlp == "moe" for b in cfg.pattern)


# ---------------------------------------------------------------------------
# Serving: cache init, prefill, decode
# ---------------------------------------------------------------------------


def _block_cache_shape(plan: ModelPlan, b: BlockDef, B: int, cap: int):
    cfg, hp = plan.cfg, plan.heads
    if b.kind == "attn":
        c = min(cap, b.window) if b.window is not None else cap
        if plan.kv_cache_dtype == "int4":
            raise ValueError(
                "kv_cache_dtype='int4' is paged-engine only (packed pages, "
                "quant/pack.kv_pack_int4); the contiguous cache supports "
                "bf16 and int8 — use --engine paged or drop to int8"
            )
        if plan.kv_cache_dtype == "int8":
            sh = {
                "k": jax.ShapeDtypeStruct((B, c, hp.kv_pad, hp.head_dim), jnp.int8),
                "v": jax.ShapeDtypeStruct((B, c, hp.kv_pad, hp.head_dim), jnp.int8),
                "ks": jax.ShapeDtypeStruct((B, c, hp.kv_pad, 1), jnp.float32),
                "vs": jax.ShapeDtypeStruct((B, c, hp.kv_pad, 1), jnp.float32),
            }
        else:
            sh = {
                "k": jax.ShapeDtypeStruct((B, c, hp.kv_pad, hp.head_dim), jnp.bfloat16),
                "v": jax.ShapeDtypeStruct((B, c, hp.kv_pad, hp.head_dim), jnp.bfloat16),
            }
        if b.cross:
            sh["ck"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frames, hp.kv_pad, hp.head_dim), jnp.bfloat16
            )
            sh["cv"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frames, hp.kv_pad, hp.head_dim), jnp.bfloat16
            )
        return sh
    k = cfg.ssm_conv
    if b.kind == "conv":
        return {"conv": jax.ShapeDtypeStruct((B, k - 1, cfg.d_model), jnp.bfloat16)}
    return MambaCache(
        conv_x=jax.ShapeDtypeStruct(
            (B, k - 1, cfg.ssm_nheads, cfg.ssm_headdim), jnp.bfloat16
        ),
        conv_bc=jax.ShapeDtypeStruct(
            (B, k - 1, 2 * cfg.ssm_ngroups * cfg.ssm_state), jnp.bfloat16
        ),
        ssm=jax.ShapeDtypeStruct(
            (B, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32
        ),
    )


def cache_shapes(plan: ModelPlan, B: int, cap: int):
    """ShapeDtypeStruct pytree of the decode cache (stacked over periods)."""
    cfg = plan.cfg

    def stack(sds):
        return jax.ShapeDtypeStruct((cfg.n_periods, *sds.shape), sds.dtype)

    out = {}
    for i, b in enumerate(cfg.pattern):
        out[f"b{i}"] = jax.tree.map(stack, _block_cache_shape(plan, b, B, cap))
    return out


def cache_axes(plan: ModelPlan, seq_shard: bool = False):
    """Logical axes mirroring cache_shapes."""
    cfg = plan.cfg
    seq_ax = "cache_seq" if seq_shard else None

    def attn_axes(b):
        ax = {
            "k": ("layers", "batch", seq_ax, "heads", None),
            "v": ("layers", "batch", seq_ax, "heads", None),
        }
        if plan.kv_cache_dtype == "int8":
            ax["ks"] = ("layers", "batch", seq_ax, "heads", None)
            ax["vs"] = ("layers", "batch", seq_ax, "heads", None)
        if b.cross:
            ax["ck"] = ("layers", "batch", None, "heads", None)
            ax["cv"] = ("layers", "batch", None, "heads", None)
        return ax

    out = {}
    for i, b in enumerate(cfg.pattern):
        if b.kind == "attn":
            out[f"b{i}"] = attn_axes(b)
        elif b.kind == "conv":
            out[f"b{i}"] = {"conv": ("layers", "batch", None, None)}
        else:
            out[f"b{i}"] = MambaCache(
                conv_x=("layers", "batch", None, "ssm_heads", None),
                conv_bc=("layers", "batch", None, None),
                ssm=("layers", "batch", "ssm_heads", None, None),
            )
    return out


def init_cache(plan: ModelPlan, B: int, cap: int):
    return jax.tree.map(
        lambda sds: jnp.zeros(sds.shape, sds.dtype), cache_shapes(plan, B, cap)
    )


def paged_cache_shapes(plan: ModelPlan, n_pages: int, page_size: int):
    """ShapeDtypeStruct pytree of the block-paged decode cache.

    Per attention layer: ``k``/``v`` pages ``(n_pages, page_size, KVp, hd)``
    (int8 adds fp32 ``ks``/``vs`` scale planes; int4 packs two codes/byte —
    uint8 pages of width ``hd/2`` plus the same scale planes) with a leading
    period axis,
    exactly like :func:`cache_shapes` — page id ``p`` addresses slot ``p``
    of every layer's array, so page accounting is in shared token slots.
    There is no batch axis: the pool is shared by all sequences; ownership
    lives in the page tables (serve/kv_cache.py).  Windowed layers keep
    full pages and mask in attention (no ring buffer).  Only
    self-attention decoder stacks page — cross-attention and Mamba state
    stay on the contiguous engine.
    """
    cfg, hp = plan.cfg, plan.heads
    for b in cfg.pattern:
        if b.kind != "attn" or b.cross:
            raise ValueError(
                "paged KV serving supports self-attention decoder stacks only"
            )
    if cfg.family == "encdec" or cfg.n_prefix:
        raise ValueError("paged KV serving: decoder-only models only")
    kv_dt = plan.kv_cache_dtype
    if kv_dt == "int4":
        if hp.head_dim % 2:
            raise ValueError(
                f"int4 KV pages need an even head dim (fold-in-half packing), "
                f"got hd={hp.head_dim}"
            )
        kdt, page_hd = jnp.uint8, hp.head_dim // 2
    elif kv_dt == "int8":
        kdt, page_hd = jnp.int8, hp.head_dim
    else:
        kdt, page_hd = jnp.bfloat16, hp.head_dim
    page = jax.ShapeDtypeStruct((n_pages, page_size, hp.kv_pad, page_hd), kdt)
    sh = {"k": page, "v": page}
    if kv_dt in ("int8", "int4"):
        sp = jax.ShapeDtypeStruct((n_pages, page_size, hp.kv_pad, 1), jnp.float32)
        sh["ks"] = sp
        sh["vs"] = sp

    def stack(sds):
        return jax.ShapeDtypeStruct((cfg.n_periods, *sds.shape), sds.dtype)

    return {f"b{i}": jax.tree.map(stack, sh) for i in range(len(cfg.pattern))}


def init_paged_cache(plan: ModelPlan, n_pages: int, page_size: int):
    return jax.tree.map(
        lambda sds: jnp.zeros(sds.shape, sds.dtype),
        paged_cache_shapes(plan, n_pages, page_size),
    )


def prefill(plan: ModelPlan, params, batch: dict, cache):
    """Full-sequence forward filling `cache`; returns (last_logits, cache).
    ``batch["length"]`` (optional, traced): the real tokens of a
    right-padded batch — recurrent state (mamba, short conv) stops there;
    attention caches hold the pads, which decode's length mask hides."""
    cfg = plan.cfg
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed_tokens(plan, params, tokens)
    enc_out = _encoder(plan, params, batch["frames"]) if cfg.family == "encdec" else None
    if cfg.n_prefix:
        pre = apply_norm(params["prefix_ln"], batch["patches"].astype(plan.dtype), cfg.norm)
        x = jnp.concatenate([pre, x], axis=1)
        S = S + cfg.n_prefix
    pos = jnp.arange(S)
    if cfg.pos == "learned":
        x = x + jax.lax.dynamic_slice(params["pos_emb"], (0, 0), (S, cfg.d_model))[
            None
        ].astype(plan.dtype)
    x, new_cache, _ = _run_stack(
        plan, params["dec"], cfg.pattern, x,
        mode="prefill", pos_ids=pos, caches=cache, enc_out=enc_out,
        valid_len=batch.get("length"),
    )
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _head_logits(x[:, -1:], _logit_head(plan, params))[:, 0]
    logits = softcap(logits, cfg.logit_softcap)
    return logits, new_cache


def decode_step(plan: ModelPlan, params, tokens: jax.Array, cache, pos):
    """One decode step.  tokens: (B, 1); pos: scalar or (B,) int32 position
    (per-slot positions enable continuous batching — serve/engine.py)."""
    cfg = plan.cfg
    B = tokens.shape[0]
    x = _embed_tokens(plan, params, tokens)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    pos_ids = pos_b[:, None]  # (B, 1)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_emb"], pos_b, axis=0)[:, None].astype(
            plan.dtype
        )
    x, new_cache, _ = _run_stack(
        plan, params["dec"], cfg.pattern, x,
        mode="decode", pos_ids=pos_ids, caches=cache, decode_pos=pos,
    )
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _head_logits(x, _logit_head(plan, params))[:, 0]
    logits = softcap(logits, cfg.logit_softcap)
    return logits, new_cache


def paged_prefill_chunk(
    plan: ModelPlan, params, tokens: jax.Array, cache, page_table, offset
):
    """One chunked-prefill step for a single sequence (DESIGN.md
    §Paged-serving).

    ``tokens``: (1, C) — chunk ``[offset, offset + C)`` of the prompt
    (right-padded; pad positions scatter into the null page or into
    not-yet-valid slots that decode overwrites before they enter any
    length mask, so no masking of the writes is needed).  ``page_table``:
    (1, n_pgs) — the sequence's page row; ``offset``: traced scalar, the
    absolute position of ``tokens[:, 0]``.  Writes the chunk's KV into its
    pages and attends queries against the gathered context
    ``[0, offset + C)``, so long prompts stream through in O(C) steps
    without ever holding a contiguous cache.  Returns the updated cache
    (no logits — the engine replays the last prompt token as the first
    decode, exactly like the contiguous engine).
    """
    cfg = plan.cfg
    B, S = tokens.shape
    if B != 1:
        raise ValueError("paged prefill processes one sequence per call")
    x = _embed_tokens(plan, params, tokens)
    pos = jnp.asarray(offset, jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_emb"], pos, axis=0)[None].astype(plan.dtype)
    _, new_cache, _ = _run_stack(
        plan, params["dec"], cfg.pattern, x,
        mode="prefill", pos_ids=pos, caches=cache, page_table=page_table,
    )
    return new_cache


def paged_decode_step(
    plan: ModelPlan, params, tokens: jax.Array, cache, pos, page_table,
    page_write,
):
    """One decode step over the paged KV pool.

    ``tokens``: (B, 1); ``pos``: (B,) int32 positions; ``page_table``:
    (B, n_pgs) int32 (padded entries → null page); ``page_write``: (B,)
    int32 — the page holding position ``pos[b]`` (the host scheduler knows
    the page tables, so the write target arrives precomputed; inactive
    lanes point at the null page).  Writes each lane's new KV into
    ``(page_write, pos % page_size)`` and attends via
    ``ops.paged_attention`` with per-lane lengths ``pos + 1``.
    """
    cfg = plan.cfg
    B = tokens.shape[0]
    x = _embed_tokens(plan, params, tokens)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    pos_ids = pos_b[:, None]  # (B, 1)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_emb"], pos_b, axis=0)[:, None].astype(
            plan.dtype
        )
    x, new_cache, _ = _run_stack(
        plan, params["dec"], cfg.pattern, x,
        mode="decode", pos_ids=pos_ids, caches=cache, decode_pos=pos,
        page_table=page_table, page_write=page_write,
    )
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _head_logits(x, _logit_head(plan, params))[:, 0]
    logits = softcap(logits, cfg.logit_softcap)
    return logits, new_cache


def paged_verify_tokens(
    plan: ModelPlan, params, tokens: jax.Array, cache, pos0, page_table,
    write_pages,
):
    """Multi-token speculative *verify* forward (DESIGN.md
    §Speculative-serving).

    ``tokens``: (B, L) — per lane, the replayed last committed token
    followed by the draft proposal (right-padded for lanes with shorter
    proposals); ``pos0``: (B,) int32 position of ``tokens[:, 0]``;
    ``write_pages``: (B, L) int32 — the page holding position
    ``pos0[b] + j`` (null page for pad columns and inactive lanes).
    Returns ``(logits (B, L, V), cache)`` where ``logits[:, j]`` scores
    the token *after* ``tokens[:, j]``.

    Deliberately **not** the flash-attention chunk path: the chunk path
    writes KV through prefill-path quantize/round code whose bytes
    differ from the decode path by ~1 ulp — enough to flip a near-tie
    argmax.  Instead the L positions run through **one**
    :func:`paged_decode_step` call as ``B·L`` *virtual lanes*: lane
    ``(b, j)`` decodes token ``tokens[b, j]`` at position ``pos0[b] +
    j`` against lane b's page table.  Inside every layer the decode step
    scatters all lanes' K/V into the pages *before* the attention
    gather, so virtual lane ``(b, j)`` reads the in-flight keys of
    ``(b, 0..j-1)`` from the pages it shares with them, and its causal
    length mask (``pos + 1``) hides ``(b, j+1..)`` — sequencing by
    masking instead of by a ``lax.scan``.  Every position therefore goes
    through *the same arithmetic* the non-speculative loop would have
    used, and the per-position GEMMs are row-blocks of one batched
    GEMM; tests pin that logits and KV bytes match L separate decode
    calls exactly, which is what makes the engine's token-identity
    invariant (speculative greedy ≡ plain greedy) bitwise rather than
    tolerance-based.  Unlike a scan of decode bodies, the weights — and
    on the quantized serving path, their dequantization
    (models/common.HoistedDequant) — are read once for all L positions:
    that amortization is what speculative decoding buys on the serving
    hot path.  Pad columns write to the null scratch page and their
    logits are ignored by the engine's commit rule.
    """
    tokens = jnp.asarray(tokens, jnp.int32)
    B, L = tokens.shape
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (B,))
    pos = (pos0[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]).reshape(-1)
    logits, cache = paged_decode_step(
        plan, params, tokens.reshape(B * L, 1), cache, pos,
        jnp.repeat(jnp.asarray(page_table), L, axis=0),
        jnp.asarray(write_pages, jnp.int32).reshape(-1),
    )
    return logits.reshape(B, L, -1), cache


def paged_draft_tokens(
    plan: ModelPlan, params, forced: jax.Array, n_forced, cache, pos0,
    page_table, write_pages,
):
    """Fused greedy draft proposal: S decode steps of the *draft* stack
    with the argmax feedback loop inside one ``lax.scan`` (DESIGN.md
    §Speculative-serving).

    Step ``j`` runs at position ``pos0[b] + j``: for ``j <
    n_forced[b]`` it is *teacher-forced* with ``forced[b, j]`` — already
    committed tokens replayed so the draft KV catches up to the target's
    committed frontier (after a fully-accepted round the bonus token
    never passed through the draft, so the catch-up is 2 tokens; 1 is
    the steady state) — and for later steps it feeds back its own
    previous argmax, producing draft proposals.  ``forced``: (B, S);
    ``n_forced``: (B,); ``pos0``: (B,) position of step 0;
    ``write_pages``: (B, S) — page of position ``pos0[b] + j``, null
    once the lane's step budget is exhausted.  Returns ``(tokens (B, S),
    cache)`` where ``tokens[b, j]`` is step j's argmax — the host slices
    proposals out of columns ``[n_forced-1, n_forced-1+d)``.  One
    dispatch per whole proposal is what lets speculation pay for itself
    when per-call overhead rivals the draft matmuls; ``jnp.argmax``
    breaks ties toward the lowest index, matching the engine's host-side
    ``np.argmax`` commit rule.
    """
    forced = jnp.asarray(forced, jnp.int32)
    B, S = forced.shape
    n_forced = jnp.asarray(n_forced, jnp.int32)
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (B,))

    def body(carry, xs):
        tok, c = carry
        frc, wp, j = xs
        inp = jnp.where(j < n_forced, frc, tok)
        logits, c = paged_decode_step(
            plan, params, inp[:, None], c, pos0 + j, page_table, wp,
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, c), nxt

    xs = (
        jnp.transpose(forced),
        jnp.transpose(jnp.asarray(write_pages, jnp.int32)),
        jnp.arange(S, dtype=jnp.int32),
    )
    (_, cache), drafts = jax.lax.scan(
        body, (jnp.zeros((B,), jnp.int32), cache), xs
    )
    return jnp.transpose(drafts), cache

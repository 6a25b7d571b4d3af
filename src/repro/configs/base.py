"""Model configuration schema + registry for the assigned architectures.

A model is a stack of ``n_periods`` repetitions of a *period pattern* — a
tuple of :class:`BlockDef` — so heterogeneous stacks (Gemma-2's
local/global alternation, Jamba's 1:7 attn:mamba interleave with MoE every
other layer) lower to a single `jax.lax.scan` over periods with stacked
params (HLO size independent of depth; see DESIGN.md §4).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Optional

import jax.numpy as jnp

__all__ = ["BlockDef", "ModelConfig", "register", "get_config", "list_configs", "ARCH_IDS"]


@dataclasses.dataclass(frozen=True)
class BlockDef:
    kind: str = "attn"  # "attn" | "mamba" | "conv" (gated short convolution)
    mlp: str = "dense"  # "dense" | "moe" | "none"
    window: Optional[int] = None  # sliding-window size (None = full)
    causal: bool = True  # False in encoder stacks
    cross: bool = False  # decoder cross-attention (enc-dec only)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str = "lm"  # "lm" | "encdec"
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 0  # 0 → d_model // n_heads
    d_ff: int = 2048
    vocab: int = 32000
    pattern: tuple = (BlockDef(),)
    n_periods: int = 2
    # attention / norms / mlp flavour
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    logit_softcap: Optional[float] = None
    attn_softcap: Optional[float] = None
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    act: str = "silu"  # "silu" | "gelu"
    gated_mlp: bool = True
    post_norms: bool = False  # gemma2-style post-sublayer norms
    tie_embeddings: bool = False
    pos: str = "rope"  # "rope" | "learned"
    max_seq: int = 1 << 19
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0  # 0 → d_ff
    router_norm_topk: bool = True
    router_score: str = "softmax"  # "softmax" | "sigmoid" (scores per expert)
    router_bias: bool = False  # learned per-expert bias added for selection only
    qk_norm: bool = False  # per-head RMSNorm on q and k before RoPE
    # Mamba2 (SSD)
    ssm_state: int = 128
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_ngroups: int = 1
    ssm_conv: int = 4  # depthwise causal conv width (mamba and short-conv blocks)
    # enc-dec (whisper)
    enc_pattern: tuple = ()
    n_enc_periods: int = 0
    n_frames: int = 1500
    # vlm stub (llava)
    n_prefix: int = 0
    dtype: Any = jnp.bfloat16

    # ---- derived ----
    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def n_layers(self) -> int:
        return self.n_periods * len(self.pattern)

    def reduced_stack(self) -> dict:
        """``pattern`` and ``n_periods`` of this stack cut for a small run:
        two periods of a repeating pattern; an unrolled one (one period,
        the published layer list) its shortest prefix that holds every
        distinct block."""
        if self.n_periods > 1:
            return dict(pattern=self.pattern, n_periods=2)
        n = 1 + max(self.pattern.index(b) for b in self.pattern)
        return dict(pattern=self.pattern[:n], n_periods=1)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_ngroups * self.ssm_state

    @property
    def moe_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def param_count(self) -> int:
        """Total parameter count N (for MODEL_FLOPS = 6·N·D roofline)."""
        return _count_params(self)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only top-k experts counted)."""
        return _count_params(self, active_only=True)


def _attn_params(cfg: ModelConfig, cross: bool = False) -> int:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n = d * h * hd + 2 * d * kv * hd + h * hd * d  # q, k, v, o
    if cfg.qkv_bias and not cross:
        n += (h + 2 * kv) * hd
    return n


def _mlp_params(cfg: ModelConfig, d_ff: int) -> int:
    d = cfg.d_model
    return (2 * d * d_ff if cfg.gated_mlp else d * d_ff) + d_ff * d


def _conv_params(cfg: ModelConfig) -> int:
    d = cfg.d_model
    return 3 * d * d + d * cfg.ssm_conv + d * d  # conv_in, depthwise conv, conv_out


def _mamba_params(cfg: ModelConfig) -> int:
    d = cfg.d_model
    d_in_proj = 2 * cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state + cfg.ssm_nheads
    n = d * d_in_proj + cfg.conv_dim * cfg.ssm_conv + cfg.conv_dim
    n += 3 * cfg.ssm_nheads + cfg.d_inner  # A_log, D, dt_bias, gate norm
    n += cfg.d_inner * d
    return n


def _count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    n = cfg.vocab * cfg.d_model  # embed
    if not cfg.tie_embeddings:
        n += cfg.d_model * cfg.vocab
    if cfg.pos == "learned":
        n += cfg.max_seq * cfg.d_model

    def block_count(b: BlockDef) -> int:
        c = 0
        if b.kind == "attn":
            c += _attn_params(cfg) + cfg.d_model  # + ln
            if cfg.qk_norm:
                c += 2 * cfg.hd
            if b.cross:
                c += _attn_params(cfg, cross=True) + cfg.d_model
        elif b.kind == "conv":
            c += _conv_params(cfg) + cfg.d_model
        else:
            c += _mamba_params(cfg) + cfg.d_model
        if b.mlp == "dense":
            c += _mlp_params(cfg, cfg.d_ff) + cfg.d_model
        elif b.mlp == "moe":
            e = cfg.top_k if active_only else cfg.n_experts
            c += cfg.d_model * cfg.n_experts  # router
            if cfg.router_bias:
                c += cfg.n_experts
            c += e * _mlp_params(cfg, cfg.moe_ff) + cfg.d_model
        return c

    n += cfg.n_periods * sum(block_count(b) for b in cfg.pattern)
    n += cfg.n_enc_periods * sum(block_count(b) for b in cfg.enc_pattern)
    return n


# --------------------------- registry ---------------------------------------

ARCH_IDS = (
    "stablelm_12b",
    "gemma2_27b",
    "qwen15_32b",
    "phi3_mini_3_8b",
    "whisper_large_v3",
    "jamba_1_5_large",
    "olmoe_1b_7b",
    "mixtral_8x22b",
    "mamba2_2_7b",
    "llava_next_34b",
    "lfm2_8b_a1b",
)

_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    name = name.replace("-", "_").replace(".", "_")
    if name not in _REGISTRY:
        try:
            importlib.import_module(f"repro.configs.{name}")
        except ModuleNotFoundError:
            # family modules registering several configs (paper's OPT family)
            importlib.import_module("repro.configs.opt_paper")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    for arch in ARCH_IDS:
        importlib.import_module(f"repro.configs.{arch}")
    return sorted(_REGISTRY)

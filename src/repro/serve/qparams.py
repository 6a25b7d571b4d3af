"""Quantized serving parameters: abstract shapes + logical axes.

The serve path (prefill/decode) runs on **QuantizedTensor** leaves for every
PTQ-target linear (core/solver.py QUANTIZABLE); norms, biases, embeddings,
router, mamba dynamics stay bf16.  This module builds:

  * ``qt_param_shapes(plan, bits)`` — ShapeDtypeStruct tree used by the
    dry-run (uint8 codes ⇒ the memory_analysis shows the real 4-bit serving
    footprint; the paper's deployment story),
  * ``qt_param_axes(plan)`` — logical axes per leaf, with fused-out-dim
    names (the QT codes matrix is (out_fused, in)): column-parallel linears
    shard codes dim0, row-parallel linears shard dim1 ⇒ identical
    communication pattern to the bf16 Megatron layout.

Axes names introduced here (resolved in dist/sharding.make_rules extras):
``kv_fused`` (= n_kv·hd), ``ssm_fused`` (= nh·hd), ``heads_fused``
(= kv_pad·g_pad·hd).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.solver import QUANTIZABLE, _MOE_NAMES
from repro.models import model as M
from repro.quant import QuantizedTensor

__all__ = [
    "qt_param_shapes",
    "qt_param_axes",
    "quantize_params_for_serving",
    "prepack_params_for_serving",
    "rtn_quantize_for_serving",
    "harmonize_qt_stack",
    "qt_rules_extra",
]


def _linear_meta(plan: M.ModelPlan, name: str):
    """(out_fused, d_in, axes_out, axes_in) for each quantizable leaf name."""
    cfg, hp = plan.cfg, plan.heads
    d, hd = cfg.d_model, cfg.hd
    table = {
        "wq": (hp.kv_pad * hp.g_pad * hd, d, "heads_fused", "embed"),
        "wk": (hp.n_kv * hd, d, "kv_fused", "embed"),
        "wv": (hp.n_kv * hd, d, "kv_fused", "embed"),
        "wo": (d, hp.kv_pad * hp.g_pad * hd, None, "heads_fused"),
        "wq_c": (hp.kv_pad * hp.g_pad * hd, d, "heads_fused", "embed"),
        "wk_c": (hp.n_kv * hd, d, "kv_fused", "embed"),
        "wv_c": (hp.n_kv * hd, d, "kv_fused", "embed"),
        "wo_c": (d, hp.kv_pad * hp.g_pad * hd, None, "heads_fused"),
        "wg": (cfg.d_ff, d, "ffn", "embed"),
        "wu": (cfg.d_ff, d, "ffn", "embed"),
        "wd": (d, cfg.d_ff, None, "ffn"),
        "wz": (cfg.ssm_nheads * cfg.ssm_headdim, d, "ssm_fused", "embed"),
        "wx": (cfg.ssm_nheads * cfg.ssm_headdim, d, "ssm_fused", "embed"),
        "wbc": (2 * cfg.ssm_ngroups * cfg.ssm_state, d, None, "embed"),
        "out_proj": (d, cfg.ssm_nheads * cfg.ssm_headdim, None, "ssm_fused"),
        "conv_in": (3 * d, d, None, "embed"),
        "conv_out": (d, d, None, None),
        "w_gate": (cfg.moe_ff, d, "expert_ffn", "embed"),
        "w_up": (cfg.moe_ff, d, "expert_ffn", "embed"),
        "w_down": (d, cfg.moe_ff, None, "expert_ffn"),
    }
    return table[name]


def qt_rules_extra(plan: M.ModelPlan, axis_n: int) -> dict:
    cfg, hp = plan.cfg, plan.heads

    def fits(n):
        return n > 0 and n % axis_n == 0

    return {
        "heads_fused": "model" if fits(hp.kv_pad * hp.g_pad * cfg.hd) else None,
        "kv_fused": "model" if fits(hp.n_kv * cfg.hd) else None,
        "ssm_fused": "model" if fits(cfg.ssm_nheads * cfg.ssm_headdim) else None,
    }


def _qt_leaf_shapes(plan, name, lead: tuple, bits: int):
    out_f, d_in, ax_o, ax_i = _linear_meta(plan, name)
    mk = lambda shape, dt: jax.ShapeDtypeStruct(lead + shape, dt)
    # int4 codes are stored packed two-per-byte (§Perf H1): weight HBM
    # traffic halves; the Pallas kernel unpacks in VMEM, the XLA ref path
    # unpacks inline (still reads only packed bytes from HBM).
    packed = bits == 4 and d_in % 2 == 0
    return QuantizedTensor(
        codes=mk((out_f, d_in // 2 if packed else d_in), jnp.uint8),
        scale=mk((out_f, 1), jnp.float32),
        zero=mk((out_f, 1), jnp.float32),
        bits=bits,
        group_size=None,
        packed=packed,
    )


def _qt_leaf_axes(plan, name, lead_axes: tuple):
    # Plain dict with the same *flatten order* as QuantizedTensor (codes,
    # scale, zero — Nones drop out), so shape/axes trees zip leaf-for-leaf.
    out_f, d_in, ax_o, ax_i = _linear_meta(plan, name)
    return {
        "codes": lead_axes + (ax_o, ax_i),
        "scale": lead_axes + (ax_o, None),
        "zero": lead_axes + (ax_o, None),
    }


def _map_stack(plan, stack_tree, pattern, fn_quant, fn_keep):
    """Rebuild a stacked block tree, replacing QUANTIZABLE leaves."""
    out = {}
    for key, blk in stack_tree.items():
        i = int(key[1:])
        b = pattern[i]
        new_blk = {}
        for name, leaf in blk.items():
            if name in QUANTIZABLE:
                new_blk[name] = fn_quant(name, leaf, b)
            else:
                new_blk[name] = fn_keep(name, leaf)
        out[key] = new_blk
    return out


def qt_param_shapes(plan: M.ModelPlan, bits: int = 4):
    dense = M.param_shapes(plan)
    cfg = plan.cfg

    def quant(name, leaf, b):
        lead = (cfg.n_periods,) if name not in _MOE_NAMES else (
            cfg.n_periods, cfg.n_experts,
        )
        return _qt_leaf_shapes(plan, name, lead, bits)

    out = dict(dense)
    out["dec"] = _map_stack(plan, dense["dec"], cfg.pattern, quant, lambda n, l: l)
    if "enc" in dense:
        def quant_enc(name, leaf, b):
            lead = (cfg.n_enc_periods,) if name not in _MOE_NAMES else (
                cfg.n_enc_periods, cfg.n_experts,
            )
            return _qt_leaf_shapes(plan, name, lead, bits)

        out["enc"] = _map_stack(plan, dense["enc"], cfg.enc_pattern, quant_enc, lambda n, l: l)
    return out


def qt_param_axes(plan: M.ModelPlan):
    dense = M.param_axes(plan)
    cfg = plan.cfg

    def quant(name, leaf, b):
        lead = ("layers",) if name not in _MOE_NAMES else ("layers", "experts")
        return _qt_leaf_axes(plan, name, lead)

    out = dict(dense)
    out["dec"] = _map_stack(plan, dense["dec"], cfg.pattern, quant, lambda n, l: l)
    if "enc" in dense:
        out["enc"] = _map_stack(plan, dense["enc"], cfg.enc_pattern, quant, lambda n, l: l)
    return out


def _qt_static_meta(qt: QuantizedTensor) -> tuple:
    """Everything that must agree for a plain leaf-for-leaf stack."""
    return (
        qt.bits,
        qt.group_size,
        qt.packed,
        qt.pack_layout,
        qt.pack_tile,
        None if qt.outlier_values is None else tuple(qt.outlier_values.shape),
        None if qt.outlier_col_idx is None else tuple(qt.outlier_col_idx.shape),
    )


def harmonize_qt_stack(leaves: list) -> list:
    """Normalize per-period QuantizedTensors to one common pytree structure.

    A mixed-precision artifact (per-layer bits from the auto-tuner) breaks
    the naive per-period stack: ``bits``/``packed`` are *static* pytree
    fields, so QuantizedTensors at different widths have different treedefs,
    and COO outlier planes come statically padded to per-layer ``s``.  The
    serving scan only needs shape/treedef uniformity — the dequant map
    ``(codes − zero)·scale`` is bits-independent once codes are unpacked —
    so heterogeneous stacks harmonize losslessly:

      * codes unpack to raw uint8 (``packed=False``; packing is a storage
        format, the scan slab is unpacked either way on the XLA ref path),
      * ``bits`` is set to the stack maximum (it only drives unpacking and
        the bits/weight accounting once ``packed`` is False; every period's
        codes are < 2^bits of *its own* grid, which the per-period
        scale/zero encode),
      * COO outlier planes pad to the stack-max ``s`` with (idx 0, value 0)
        entries — additive no-ops, the same padding contract the solver
        emits,
      * ``group_size`` must agree across the stack (per-period scale/zero
        column counts are shape-bearing); structured column outliers must
        be structurally identical (their ``.set`` semantics make padding
        destructive, so silent harmonization would corrupt column 0).

    Homogeneous stacks pass through untouched (packed 4-bit stays packed).
    """
    metas = {_qt_static_meta(l) for l in leaves}
    if len(metas) == 1:
        return leaves
    gsz = {l.group_size for l in leaves}
    if len(gsz) != 1:
        raise ValueError(
            f"heterogeneous group_size across stacked layers ({sorted(map(str, gsz))}) "
            "— per-period scale planes would not stack"
        )
    cols = {_qt_static_meta(l)[6] for l in leaves}
    if len(cols) != 1:
        raise ValueError(
            "structured column outliers must be structurally identical across "
            "a stack (padding a .set-semantics plane would clobber column 0)"
        )
    bits = max(l.bits for l in leaves)
    s_max = max(
        (0 if l.outlier_values is None else l.outlier_values.shape[-1])
        for l in leaves
    )
    out = []
    for l in leaves:
        codes = l.unpacked_codes()
        vals, idx = l.outlier_values, l.outlier_idx
        if s_max:
            if vals is None:
                lead = codes.shape[:-2]
                vals = jnp.zeros(lead + (s_max,), jnp.float16)
                idx = jnp.zeros(lead + (s_max,), jnp.int32)
            elif vals.shape[-1] < s_max:
                pad = [(0, 0)] * (vals.ndim - 1) + [(0, s_max - vals.shape[-1])]
                vals = jnp.pad(vals, pad)
                idx = jnp.pad(idx, pad)
        out.append(
            dataclasses.replace(
                l,
                codes=codes,
                bits=bits,
                packed=False,
                pack_layout="linear",
                pack_tile=None,
                outlier_values=vals,
                outlier_idx=idx,
            )
        )
    return out


def rtn_quantize_for_serving(plan: M.ModelPlan, params, *, bits: int,
                             outlier_frac: float = 0.0):
    """RTN-quantize every QUANTIZABLE dec leaf into the serving QT layout.

    The cheap artifact path: direct per-channel round-to-nearest over the
    dense stacked checkpoint — no calibration data, no solver.  It produces
    the same *byte layout* the solver pipeline emits — codes (packed
    two-per-byte at 4 bits), fp32 per-channel scale/zero, optional COO
    outlier planes (QuantEase Algorithm-3 structure: fp16 values + flat
    int32 indices) — so benchmarks (serving perf is weight-value
    independent) and on-the-fly draft construction (launch/serve.py
    ``--draft-bits``) can build a servable artifact from any dense
    checkpoint.  4-bit artifacts are then run through the roofline
    weight-layout decision (:func:`prepack_params_for_serving`).

    Returns ``(qt_params, layout_label)``.
    """
    import numpy as np

    from repro.quant import GridSpec, quantize_tensor
    from repro.quant.pack import pack_codes

    def qt_of(name, leaf):
        # Dense stacked leaves are (n_periods, in_dims..., out_dims...) with
        # fused head/ff axes; flatten through the same (out_f, d_in) meta the
        # serving QT layout uses (_linear_meta / core.solver._to_2d).
        n_p = leaf.shape[0]
        out_f, d_in = _linear_meta(plan, name)[:2]
        w = np.asarray(leaf, np.float32).reshape(n_p, d_in, out_f)
        w = w.transpose(0, 2, 1)  # (n_periods, out_f, d_in) — serving layout
        qts = []
        for i in range(n_p):
            qt = quantize_tensor(jnp.asarray(w[i]), GridSpec(bits=bits))
            if outlier_frac:
                resid = w[i] - np.asarray(qt.dequantize())
                s = max(1, int(outlier_frac * resid.size))
                idx = np.argsort(np.abs(resid).ravel())[-s:].astype(np.int32)
                qt = dataclasses.replace(
                    qt,
                    outlier_values=jnp.asarray(resid.ravel()[idx], jnp.float16),
                    outlier_idx=jnp.asarray(idx),
                )
            if bits == 4 and qt.codes.shape[-1] % 2 == 0:
                qt = dataclasses.replace(qt, codes=pack_codes(qt.codes, 4),
                                         packed=True)
            qts.append(qt)
        return jax.tree.map(lambda *ls: jnp.stack(ls), *qts)

    out = dict(params)
    out["dec"] = {
        key: {
            name: qt_of(name, leaf) if name in QUANTIZABLE else leaf
            for name, leaf in blk.items()
        }
        for key, blk in params["dec"].items()
    }
    out, decisions = prepack_params_for_serving(plan, out)
    labels = sorted(set(decisions.values())) or ["linear"]
    return out, "+".join(labels)


def quantize_params_for_serving(plan: M.ModelPlan, params, solver_qt_dec: list):
    """Restack solver emit='qt' per-period block lists into the scan layout.

    Heterogeneous-bits stacks (mixed-precision artifacts) are harmonized
    leaf-position-wise first — see :func:`harmonize_qt_stack`.
    """
    stacked = {}
    for key in solver_qt_dec[0]:
        blocks = [p[key] for p in solver_qt_dec]
        new_blk = {}
        for name in blocks[0]:
            leaves = [b[name] for b in blocks]
            if isinstance(leaves[0], QuantizedTensor):
                leaves = harmonize_qt_stack(leaves)
            new_blk[name] = jax.tree.map(lambda *ls: jnp.stack(ls), *leaves)
        stacked[key] = new_blk
    out = dict(params)
    out["dec"] = stacked
    return out


def prepack_params_for_serving(plan: M.ModelPlan, params, *, backend=None):
    """Roofline-selected weight-layout prepack (DESIGN.md §Packed-serving).

    Walks the serving param tree and, for every packed-4-bit
    QuantizedTensor still in the linear layout, asks
    :func:`repro.roofline.analysis.choose_weight_layout` whether the
    tile-native prepack (quant/pack.prepack_codes at the kernel's
    :func:`~repro.kernels.dequant_matmul.select_tile_k` k-tile) wins on the
    memory roofline for this backend.  Winning leaves are re-permuted
    **once, at pack time** — a pure column permutation, bit-exact under
    dequant — and tagged ``pack_layout="tile"`` / ``pack_tile=tk`` so the
    Pallas GEMM reads contiguous words per tile instead of interleaving.
    Off-TPU backends keep every leaf linear (the XLA ref path gains nothing
    from the reorder).

    Returns ``(params, decisions)`` where ``decisions`` maps
    ``"<block>.<name>"`` → the chosen
    :class:`~repro.roofline.analysis.WeightLayoutDecision` label (one entry
    per distinct leaf position; launch/serve.py prints them as the layout
    banner).
    """
    from repro.kernels.dequant_matmul import select_tile_k
    from repro.roofline.analysis import choose_weight_layout, hw_for

    if backend is None:
        backend = jax.default_backend()
    # On a chip, its own peaks (an unknown kind is an error); off the chip,
    # the roofline's planning target.
    hw_kw = (
        {"hw": hw_for(jax.devices()[0].device_kind)}
        if jax.default_backend() == "tpu" else {}
    )
    decisions: dict[str, str] = {}

    def prepack_leaf(path: str, leaf):
        if not isinstance(leaf, QuantizedTensor):
            return leaf
        if not (leaf.packed and leaf.bits == 4 and leaf.pack_layout == "linear"):
            return leaf
        q, p = leaf.shape[-2], leaf.shape[-1]
        tk = select_tile_k(p, leaf.group_size)
        dec = choose_weight_layout(
            q, p, bits=4, group_size=leaf.group_size, tile_k=tk,
            backend=backend, **hw_kw,
        )
        if dec.kind != "tile":
            # The prepack never unpacks checkpoint codes back into HBM, so a
            # "linear (unpacked)" roofline pick still serves linear-packed —
            # record the layout the leaf actually keeps.
            decisions[path] = "linear-packed"
            return leaf
        decisions[path] = dec.label
        from repro.quant.pack import prepack_codes, unpack_codes

        codes = prepack_codes(unpack_codes(leaf.codes, 4, p), 4, tk)
        return dataclasses.replace(
            leaf, codes=codes, pack_layout="tile", pack_tile=tk
        )

    out = dict(params)
    for stack_key in ("dec", "enc"):
        if stack_key not in params:
            continue
        stacked = {}
        for key, blk in params[stack_key].items():
            stacked[key] = {
                name: prepack_leaf(f"{key}.{name}", leaf)
                for name, leaf in blk.items()
            }
        out[stack_key] = stacked
    return out, decisions

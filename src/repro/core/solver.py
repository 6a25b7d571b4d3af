"""Whole-model PTQ: streaming, sharded, batched — the paper's pipeline at scale.

Mirrors the reference GPTQ/QuantEase flow (paper §5 setup), engineered per
DESIGN.md §Streaming-solver:

  * run calibration batches through the model **block by block**; the inputs
    feeding each block are the outputs of the *already-quantized* prefix
    (error propagation across blocks, as all layer-wise PTQ codebases do),
  * **streaming Σ capture**: per linear, a :class:`~repro.core.calib.CalibStats`
    accumulator folds each batch into Σ = XXᵀ the moment it is computed
    (fp32, the only statistic any method needs — ``p² + O(pq)`` memory,
    paper §3.2).  Raw per-layer activation lists are never materialized;
    peak capture memory per layer is O(p²), not O(n_calib·seq·p),
  * **batched solves**: same-shape captured linears of a block — and all E
    experts of an MoE matrix — are stacked and solved by a single vmapped
    ``quantease_quantize``/``gptq_quantize`` call instead of sequential
    Python loops (layer independence, as CDQuant exploits for parallel CD);
    an expert's ``w_gate`` and ``w_up`` read one input and one Σ, so they
    stack along their output rows (rows are independent in every batched
    method) and the gate/up group is E solves of (2·F, d), not 2·E,
  * **mesh sharding** (``ptq_quantize_model(..., mesh=...)``): calibration
    Gram accumulation is data-sharded with a psum (calib.sharded_gram), and
    the CD solve shard_maps over the independent q (output-row) dimension;
    with one device or no mesh everything degrades to the local path,
  * record per-layer relative errors — the data behind the paper's Fig. 2 —
    and report per-block progress through an optional callback.

Quantized leaf set: every matmul the model zoo routes through
``apply_linear`` except numerically-critical small tensors (mamba Δ
projection ``wdt``; the short conv's taps; norms; biases; MoE router and
its selection bias) — see DESIGN.md §Arch-applicability.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro import obs
from repro.core import awq, gptq, outlier, quantease, rtn, spqr
from repro.core.calib import CalibStats
from repro.core.quantease import relative_error
from repro.models import model as M
from repro.models.common import capture_gram_stats, capture_scope
from repro.quant import (
    GridSpec,
    QuantizedTensor,
    compute_grid,
    quantize_codes,
    quantize_dequantize,
)

__all__ = ["LayerSpec", "PTQConfig", "ptq_quantize_model", "QUANTIZABLE"]

QUANTIZABLE = {
    "wq", "wk", "wv", "wo", "wq_c", "wk_c", "wv_c", "wo_c",
    "wg", "wu", "wd",
    "wz", "wx", "wbc", "out_proj", "conv_in", "conv_out",
    "w_gate", "w_up", "w_down",
}
_MOE_NAMES = {"w_gate", "w_up", "w_down"}
# Leaves whose input is another leaf's: their Σ is captured once, under it.
_SHARED_SIGMA = {"w_up": "w_gate"}

# Methods batchable with a single vmapped call *and* row-shardable under a
# mesh.  qe_outlier/qe_outlier_struct also batch (one vmapped fused-engine
# call per same-shape group — see _solve_group) but never row-shard: the
# top-s projection is global across output rows.  The remainder (awq, spqr)
# fall back to a per-layer loop inside the same grouped interface.
_BATCHED_METHODS = {"rtn", "gptq", "quantease"}

# Sentinel distinguishing "inherit from the base config" from an explicit
# ``None`` (per-channel) group_size in a LayerSpec override.
_INHERIT = "__inherit__"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Per-layer override of the global PTQConfig (mixed-precision PTQ).

    Any field left at its default inherits the base config; ``group_size``
    uses the ``_INHERIT`` sentinel because ``None`` is itself a meaningful
    value (one group spanning the whole row).  Keys into
    ``PTQConfig.layer_specs`` are solver layer paths — ``"dec.p0.b1/wq"`` —
    or bare leaf names (``"wq"``) as a fallback matched when no exact path
    entry exists.
    """

    bits: Optional[int] = None
    group_size: object = _INHERIT
    outlier_frac: Optional[float] = None
    method: Optional[str] = None
    iterations: Optional[int] = None


@dataclasses.dataclass
class PTQConfig:
    method: str = "quantease"  # rtn|gptq|awq|quantease|awq_qe|spqr|qe_outlier|qe_outlier_struct
    spec: GridSpec = dataclasses.field(default_factory=lambda: GridSpec(bits=4))
    iterations: int = 25
    outlier_frac: float = 0.01  # for outlier-aware methods
    percdamp: float = 0.01
    block_size: int = 128
    emit: str = "fake"  # "fake" (dequantized bf16) | "qt" (QuantizedTensor)
    init_from_gptq: bool = False  # QuantEase warm start (paper §3.1)
    # Streaming capture: feed calibration batches through the capture pass in
    # chunks of this many sequences (0 = whole batch at once) so transient
    # activation memory is bounded independently of the calibration set size.
    # Σ is chunk-invariant, per-expert Σ too (the capture routes dropless).
    stream_chunk: int = 0
    # Shard the CD solve over output rows (and Gram accumulation over data)
    # when a mesh is passed to ptq_quantize_model.
    shard: bool = False
    # QuantEase engine knobs, threaded through QuantEaseConfig.solve_kwargs:
    # "auto" resolves to the compiled Pallas kernel on TPU, XLA elsewhere;
    # matmul_dtype="bfloat16" runs the Σ̃ correction matmuls with bf16
    # operands (fp32 accumulation — the β/quantize path stays fp32).
    use_kernel: str = "auto"
    matmul_dtype: str = "float32"
    # Mixed-precision: per-layer overrides keyed by solver layer path
    # ("dec.p0.b1/wq") or bare leaf name ("wq").  Same-shape batching splits
    # groups by the *effective* per-layer config, so layers assigned
    # different bits never share a vmapped solve.
    layer_specs: Optional[dict] = None
    # Auto-tuning sensitivity signal: when True, each progress_cb record
    # additionally carries per-layer λ_max(Σ) (power iteration — the IHT
    # step-size spectrum the tuner ranks on) under "lambda_max".
    collect_sensitivity: bool = False

    def qe_config(self) -> "quantease.QuantEaseConfig":
        """The CD-solver config this PTQ run resolves to (wired end-to-end)."""
        return quantease.QuantEaseConfig(
            iterations=self.iterations,
            percdamp=self.percdamp,
            use_kernel=self.use_kernel,
            matmul_dtype=self.matmul_dtype,
        )

    def for_layer(self, key: str) -> "PTQConfig":
        """Resolve the effective config for one layer path.

        Exact-path entries win over bare-name fallbacks; a layer with no
        entry uses the base config unchanged.  The returned config has
        ``layer_specs=None`` — it is fully resolved.
        """
        if not self.layer_specs:
            return self
        ov = self.layer_specs.get(key)
        if ov is None:
            ov = self.layer_specs.get(key.rsplit("/", 1)[-1])
        if ov is None:
            return dataclasses.replace(self, layer_specs=None)
        spec = dataclasses.replace(
            self.spec,
            bits=self.spec.bits if ov.bits is None else ov.bits,
            group_size=self.spec.group_size
            if ov.group_size is _INHERIT
            else ov.group_size,
        )
        return dataclasses.replace(
            self,
            layer_specs=None,
            spec=spec,
            method=self.method if ov.method is None else ov.method,
            outlier_frac=self.outlier_frac
            if ov.outlier_frac is None
            else ov.outlier_frac,
            iterations=self.iterations
            if ov.iterations is None
            else ov.iterations,
        )

    def _group_key(self) -> tuple:
        """Hashable identity of everything that changes a grouped solve."""
        return (
            self.method, self.spec, self.outlier_frac, self.iterations,
            self.init_from_gptq,
        )


# ---------------------------------------------------------------------------
# Single-layer and grouped solves
# ---------------------------------------------------------------------------


def _quantize_one(w2d: jax.Array, sigma: jax.Array, cfg: PTQConfig):
    """Single (q, p) solve.  Returns (w_hat fp32, h or None, grid or None).

    ``grid`` is the quantization grid the solve actually used, threaded to
    the emit path so stored codes round-trip the solve exactly; methods
    whose emitted tensor is not on a single known grid (AWQ's rescaled
    grids, SpQR's full-precision kept outliers) return None and the emit
    path falls back to re-deriving a grid from Ŵ.
    """
    spec = cfg.spec
    if cfg.method == "rtn":
        grid = compute_grid(w2d, spec)
        return quantize_dequantize(w2d, grid), None, grid
    if cfg.method == "gptq":
        grid = compute_grid(w2d, spec)
        return (
            gptq.gptq_quantize(
                w2d, sigma, spec,
                percdamp=cfg.percdamp, block_size=cfg.block_size, grid=grid,
            ),
            None,
            grid,
        )
    if cfg.method == "awq":
        return awq.awq_quantize(w2d, sigma, spec), None, None
    if cfg.method == "awq_qe":
        # AWQ auto-alpha rescale pre-pass + QuantEase CD on the scaled
        # problem (paper §6; the tuner's optional pre-pass).  The effective
        # weight is off any single uniform grid (column j is rescaled by
        # 1/s_j), so — like awq/spqr — no grid is returned and emit="qt"
        # falls back to a re-derived (lossy) grid.
        w_hat = awq.awq_then_quantease(
            w2d, sigma, spec,
            iterations=cfg.iterations, percdamp=cfg.percdamp,
        )
        return w_hat, None, None
    if cfg.method == "quantease":
        grid = compute_grid(w2d, spec)
        w_init = None
        if cfg.init_from_gptq:
            w_init = gptq.gptq_quantize(
                w2d, sigma, spec,
                percdamp=cfg.percdamp, block_size=cfg.block_size, grid=grid,
            )
        w_hat, _ = quantease.quantease_quantize(
            w2d, sigma, spec,
            w_init=w_init, grid=grid, **cfg.qe_config().solve_kwargs(),
        )
        return w_hat, None, grid
    if cfg.method == "spqr":
        s = max(int(cfg.outlier_frac * w2d.size), 1)
        w_hat, _ = spqr.spqr_quantize(
            w2d, sigma, spec, s=s, percdamp=cfg.percdamp, block_size=cfg.block_size
        )
        return w_hat, None, None
    if cfg.method in ("qe_outlier", "qe_outlier_struct"):
        s = max(int(cfg.outlier_frac * w2d.size), 1)
        res = outlier.outlier_quantease(
            w2d,
            sigma,
            spec,
            s=s,
            iterations=cfg.iterations,
            structured=cfg.method.endswith("struct"),
            percdamp=cfg.percdamp,
            use_kernel=cfg.use_kernel,
            matmul_dtype=cfg.matmul_dtype,
        )
        return res.w_hat, res.h, res.grid
    raise ValueError(cfg.method)


def _solve_batched(w3: jax.Array, sig3: jax.Array, cfg: PTQConfig, grid3):
    """Grouped solve: (G, q, p) × (G, p, p) → (G, q, p) in one vmapped call.

    ``grid3``: batched Grid (leaves (G, q, n_groups)) computed from the
    original weights — the same grid every method here quantizes onto, so
    the emit path can reuse it verbatim.
    """
    spec = cfg.spec
    if cfg.method == "rtn":
        return jax.vmap(quantize_dequantize)(w3, grid3)
    if cfg.method == "gptq":
        return gptq.gptq_quantize(
            w3, sig3, spec,
            percdamp=cfg.percdamp, block_size=cfg.block_size, grid=grid3,
        )
    w_init = None
    if cfg.init_from_gptq:
        w_init = gptq.gptq_quantize(
            w3, sig3, spec,
            percdamp=cfg.percdamp, block_size=cfg.block_size, grid=grid3,
        )
    w_hat, _ = quantease.quantease_quantize(
        w3, sig3, spec,
        w_init=w_init, grid=grid3, **cfg.qe_config().solve_kwargs(),
    )
    return w_hat


def _solve_group(w3: jax.Array, sig3: jax.Array, cfg: PTQConfig, mesh):
    """Solve G stacked same-shape layers; returns (w_hat (G,q,p), hs, grid3).

    Batchable methods go through one vmapped (optionally row-sharded) call;
    outlier-aware methods run per-layer inside the same interface so the
    grouped driver upstream stays method-agnostic.  ``grid3`` is the batched
    Grid (leaves (G, q, n_groups)) the solves quantized onto, or None where
    the method has none (AWQ, SpQR).
    """
    G = w3.shape[0]
    if cfg.method in _BATCHED_METHODS:
        grid3 = jax.vmap(lambda wi: compute_grid(wi, cfg.spec))(w3)
        solve = lambda w, s, g: _solve_batched(w, s, cfg, g)
        if mesh is not None and cfg.shard:
            w_hat = _shard_rows(solve, w3, sig3, grid3, mesh)
        else:
            w_hat = solve(w3, sig3, grid3)
        return w_hat, [None] * G, grid3
    if cfg.method in ("qe_outlier", "qe_outlier_struct"):
        # Fused outlier engine batches like everything else: one vmapped
        # solve per same-shape group.  (Never row-sharded: the unstructured
        # top-s projection is global across output rows, so splitting q
        # would change the solve.)
        s = max(int(cfg.outlier_frac * int(w3[0].size)), 1)
        res = outlier.outlier_quantease(
            w3,
            sig3,
            cfg.spec,
            s=s,
            iterations=cfg.iterations,
            structured=cfg.method.endswith("struct"),
            percdamp=cfg.percdamp,
            use_kernel=cfg.use_kernel,
            matmul_dtype=cfg.matmul_dtype,
        )
        return res.w_hat, [res.h[g] for g in range(G)], res.grid
    outs, hs = [], []
    for g in range(G):
        w_hat, h, _ = _quantize_one(w3[g], sig3[g], cfg)  # awq, spqr: no grid
        outs.append(w_hat)
        hs.append(h)
    return jnp.stack(outs), hs, None


def _shard_rows(solve: Callable, w3: jax.Array, sig3: jax.Array, grid3, mesh):
    """shard_map a grouped solve over the independent q (output-row) dim.

    Rows are independent in every column-sweep method (the CD update of row
    i never reads row j), so splitting q across devices is exact; the
    per-row grid shards along with the rows.  Rows pad up to the axis size;
    padded zero rows quantize in isolation (unit pad scale) and are
    stripped.  Single-device meshes skip the wrapper entirely.
    """
    from repro.core.calib import shard_axis

    axis = shard_axis(mesh)
    n = mesh.shape[axis]
    if n <= 1:
        return solve(w3, sig3, grid3)

    G, q, p = w3.shape
    pad = (-q) % n
    if pad:
        w3 = jnp.pad(w3, ((0, 0), (0, pad), (0, 0)))
        grid3 = dataclasses.replace(
            grid3,
            scale=jnp.pad(
                grid3.scale, ((0, 0), (0, pad), (0, 0)), constant_values=1.0
            ),
            zero=jnp.pad(grid3.zero, ((0, 0), (0, pad), (0, 0))),
        )

    sharded = jax.shard_map(
        solve,
        mesh=mesh,
        in_specs=(
            PartitionSpec(None, axis, None),
            PartitionSpec(None, None, None),
            PartitionSpec(None, axis, None),
        ),
        out_specs=PartitionSpec(None, axis, None),
        check_vma=False,
    )
    return sharded(w3, sig3, grid3)[:, :q]


# ---------------------------------------------------------------------------
# Leaf marshalling
# ---------------------------------------------------------------------------


def _to_2d(w: jax.Array, d_in: int) -> jax.Array:
    return w.reshape(d_in, -1).T.astype(jnp.float32)  # (out, in)


def _from_2d(w2d: jax.Array, like: jax.Array) -> jax.Array:
    d_in = like.shape[0] if like.ndim == 2 else int(np.prod(like.shape) // w2d.shape[0])
    return w2d.T.reshape(like.shape).astype(like.dtype)


def _emit_leaf(w_hat, h, like, cfg: PTQConfig, grid=None):
    if cfg.emit == "fake":
        w_eff = w_hat if h is None else w_hat + h
        return _from_2d(w_eff, like)
    if grid is None:
        # Fallback for methods that don't expose their grid (AWQ/SpQR):
        # re-derive from Ŵ — lossy if Ŵ doesn't attain its grid extremes.
        grid = compute_grid(w_hat, cfg.spec)
    codes = quantize_codes(w_hat, grid)
    packed = cfg.spec.bits == 4 and codes.shape[-1] % 2 == 0
    if packed:
        from repro.quant import pack_codes

        codes = pack_codes(codes, 4)
    qt = QuantizedTensor(
        codes=codes,
        scale=grid.scale,
        zero=grid.zero,
        bits=cfg.spec.bits,
        group_size=cfg.spec.group_size,
        packed=packed,
    )
    if h is not None:
        # Sparse-Ĥ artifact: COO with flat int32 indices + fp16 values
        # (48 bits/outlier — §5.4 accounting) instead of a dense (q, p)
        # fp32 array.  ‖Ĥ‖₀ ≤ s, so top-s by |value| captures the support
        # exactly; pad entries carry (idx 0, value 0) — additive no-ops.
        s = max(int(cfg.outlier_frac * w_hat.size), 1)
        flat = h.reshape(-1)
        _, idx = jax.lax.top_k(jnp.abs(flat), s)
        qt = dataclasses.replace(
            qt,
            outlier_values=flat[idx].astype(jnp.float16),
            outlier_idx=idx.astype(jnp.int32),
        )
    return qt


# ---------------------------------------------------------------------------
# Block quantization: group → batched solve → scatter back
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Item:
    """Captured linears that share one Σ, in solver layout: one leaf, or an
    expert layer's ``w_gate`` and ``w_up`` stacked along their output rows."""

    names: tuple  # leaf names in the block param dict
    qs: tuple  # output rows of each leaf (its share of w3's q)
    scope: str
    sigma_key: str  # the linear whose input Σ this is
    w3: jax.Array  # (G, Σqs, p) — G=1 for dense linears, G=E for MoE
    sig3: jax.Array  # (G, p, p)
    likes: tuple  # each original leaf (one expert's leaf for MoE), for reshaping
    moe: bool

    def key(self, name: str) -> str:
        return f"{self.scope}/{name}"


def _collect_items(p_blk: dict, stats: dict, scope: str, cfg: PTQConfig) -> list[_Item]:
    items: list[_Item] = []
    for name, w in p_blk.items():
        src = _SHARED_SIGMA.get(name, name)
        if name not in QUANTIZABLE or f"{scope}/{src}" not in stats:
            continue
        st: CalibStats = stats[f"{scope}/{src}"]
        if name in _MOE_NAMES:
            # w: (E, d_in, d_out); st.sigma: (E, p, p) — already stacked.
            w3 = jax.vmap(lambda we: we.reshape(w.shape[1], -1).T)(w).astype(
                jnp.float32
            )
            it = _Item((name,), (w3.shape[1],), scope, src, w3, st.sigma, (w[0],), True)
        else:
            w2 = _to_2d(w, st.p)
            it = _Item((name,), (w2.shape[0],), scope, src, w2[None], st.sigma[None],
                       (w,), False)
        # Rows are independent in the batched methods: leaves of one Σ that
        # solve alike stack along q instead of holding Σ twice.
        eff = cfg.for_layer(it.key(name))
        host = next((o for o in items if o.sigma_key == src and o.moe), None)
        if (host is not None and eff.method in _BATCHED_METHODS
                and cfg.for_layer(host.key(host.names[0]))._group_key() == eff._group_key()):
            host.w3 = jnp.concatenate([host.w3, it.w3], axis=1)
            host.names, host.qs, host.likes = (
                host.names + it.names, host.qs + it.qs, host.likes + it.likes)
        else:
            items.append(it)
    return items


def _quantize_block(
    p_blk: dict, stats: dict, scope: str, cfg: PTQConfig, report: dict, mesh,
    sens: Optional[dict] = None,
) -> dict:
    """Quantize every captured linear of one block (returns a new dict).

    Items are grouped by (solver shape, effective per-layer config): each
    group — e.g. wq/wk/wv sharing d_model inputs, or wg/wu, or the E
    experts of one MoE matrix — is solved by a single batched call.
    ``cfg.layer_specs`` splits otherwise-identical shapes into separate
    groups whenever their assigned bits/method/outlier budget differ, so
    mixed-precision never shares a vmapped solve across specs.

    ``sens``: optional dict filled with per-layer λ_max(Σ) (same keys as
    ``report``) when ``cfg.collect_sensitivity`` is set.
    """
    items = _collect_items(p_blk, stats, scope, cfg)
    groups: dict[tuple, tuple[PTQConfig, list[_Item]]] = {}
    for it in items:
        eff = cfg.for_layer(it.key(it.names[0]))
        gk = (it.w3.shape[1:], eff._group_key())
        groups.setdefault(gk, (eff, []))[1].append(it)

    new = dict(p_blk)
    for (shape, _), (eff, group) in groups.items():
        G_all = sum(it.w3.shape[0] for it in group)
        experts = {"experts": it.w3.shape[0] for it in group if it.moe}
        with obs.span("ptq.solve", block=scope, shape=str(tuple(shape)), G=G_all,
                      method=eff.method, **experts):
            if len(group) == 1:  # no copy: an expert group is GBs
                w3, sig3 = group[0].w3, group[0].sig3
            else:
                w3 = jnp.concatenate([it.w3 for it in group], axis=0)
                sig3 = jnp.concatenate([it.sig3 for it in group], axis=0)
            w_hat3, hs, grid3 = _solve_group(w3, sig3, eff, mesh)
            errs = _leaf_errors(group, w3, _effective(w_hat3, hs), sig3)
            if cfg.collect_sensitivity and sens is not None:
                for it in group:
                    lam = jax.vmap(outlier.power_lambda_max)(it.sig3)
                    for name in it.names:
                        if it.moe:
                            for e in range(it.sig3.shape[0]):
                                sens[f"{it.key(name)}.e{e}"] = float(lam[e])
                        else:
                            sens[it.key(name)] = float(lam[0])
        off = 0
        for it in group:
            G = it.w3.shape[0]
            sl = slice(off, off + G)
            grid_it = None if grid3 is None else jax.tree.map(lambda a: a[sl], grid3)
            _scatter_item(it, w_hat3[sl], hs[sl], errs[id(it)], new, eff, report, grid_it)
            off += G
    return new


def _leaf_errors(group, w3, eff3, sig3) -> dict:
    """Relative error of every leaf of the group: {id(item): [(G,) per
    leaf]}, from one read of the host per row layout (one for a group of
    single leaves); the read waits for the solve."""
    layouts = {}
    for it in group:
        bounds = np.cumsum((0,) + it.qs)
        layouts.update({(int(a), int(b)): None for a, b in zip(bounds[:-1], bounds[1:])})
    q = w3.shape[1]
    for a, b in layouts:
        rows = (slice(None),) if (a, b) == (0, q) else (slice(None), slice(a, b))
        layouts[(a, b)] = np.asarray(relative_error(w3[rows], eff3[rows], sig3))
    out, off = {}, 0
    for it in group:
        G = it.w3.shape[0]
        bounds = np.cumsum((0,) + it.qs)
        out[id(it)] = [layouts[(int(a), int(b))][off:off + G]
                       for a, b in zip(bounds[:-1], bounds[1:])]
        off += G
    return out


def _effective(w_hat3, hs):
    if all(h is None for h in hs):
        return w_hat3
    return jnp.stack(
        [w if h is None else w + h for w, h in zip(w_hat3, hs)]
    )


def _scatter_item(
    it: _Item, w_hat, hs, errs, new: dict, cfg: PTQConfig, report: dict, grid3
):
    """Emit each leaf of ``it`` into ``new`` and its errors into ``report``.
    Expert leaves emit all E experts in one vmapped call."""
    h3 = None if all(h is None for h in hs) else jnp.stack(hs)
    off = 0
    for name, q, like, err in zip(it.names, it.qs, it.likes, errs):
        multi = len(it.names) > 1
        rows = slice(off, off + q)
        w_p = w_hat[:, rows] if multi else w_hat
        h_p = None if h3 is None else (h3[:, rows] if multi else h3)
        g_p = grid3 if grid3 is None or not multi else jax.tree.map(
            lambda a: a[:, rows], grid3)
        off += q
        if not it.moe:
            report[it.key(name)] = float(err[0])
            g0 = None if g_p is None else jax.tree.map(lambda a: a[0], g_p)
            with obs.span("ptq.emit", block=it.scope, linear=name):
                new[name] = _emit_leaf(w_p[0], None if h_p is None else h_p[0], like,
                                       cfg, g0)
            continue
        for e in range(w_p.shape[0]):
            report[f"{it.key(name)}.e{e}"] = float(err[e])
        with obs.span("ptq.emit", block=it.scope, linear=name, experts=w_p.shape[0]):
            if cfg.emit == "fake":
                w_eff = w_p if h_p is None else w_p + h_p
                new[name] = jnp.swapaxes(w_eff, 1, 2).reshape(
                    (w_p.shape[0],) + like.shape).astype(new[name].dtype)
            else:
                new[name] = jax.vmap(
                    lambda w, h, g: _emit_leaf(w, h, like, cfg, g)
                )(w_p, h_p, g_p)


# ---------------------------------------------------------------------------
# Whole-model driver
# ---------------------------------------------------------------------------


def _slice_period(stack, i):
    return jax.tree.map(lambda a: a[i], stack)


def _set_period(stack, i, new_period):
    return jax.tree.map(
        lambda a, n: a.at[i].set(n.astype(a.dtype))
        if not hasattr(n, "codes")
        else n,
        stack,
        new_period,
    )


def _capture_chunks(x: jax.Array, chunk: int):
    """Split a (B, S, d) batch along B into ≤chunk-sequence slices."""
    if chunk <= 0 or x.shape[0] <= chunk:
        return [x]
    return [x[i : i + chunk] for i in range(0, x.shape[0], chunk)]


def _chunk_pairs(xs, enc_outs, chunk: int):
    """(x chunk, enc_out chunk or None) over every calibration batch."""
    for bi, x in enumerate(xs):
        x_chunks = _capture_chunks(x, chunk)
        eo = None if enc_outs is None else enc_outs[bi]
        eo_chunks = [None] * len(x_chunks) if eo is None else _capture_chunks(eo, chunk)
        yield from zip(x_chunks, eo_chunks)


# The block forwards of both passes run as compiled programs, traced once per
# (config, head plan, block kind, mesh) and argument shapes and reused for every
# batch, block and call: run op by op, each forward re-traced its attention
# scan while the device waited.  Module-level, so the cache outlives a call.


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=(7,))
def _capture_program(mcfg, hp, b, mesh, p_blk, x, enc_out, acc):
    """One chunk of the capture pass: the block forward with each linear's
    input folded into its Σ.  ``acc`` (donated) maps linear name →
    CalibStats so far (its ``n`` unused), or is None (for ``eval_shape``:
    this chunk's alone).  Returns name → CalibStats of Σ (and expert
    ``rows``) so far plus this chunk's, whose static ``n`` is this chunk's
    sample count.  Keys carry no block scope, so one trace serves every
    block.  MoE layers route dropless here (``_block_apply``'s default)."""
    obs.count("ptq.forward_traces")
    chunk: dict[str, CalibStats] = {}
    with capture_gram_stats(chunk, mesh=mesh), capture_scope(None):
        M._block_apply(
            mcfg, hp, b, p_blk, x,
            mode="train", pos_ids=jnp.arange(x.shape[1]), enc_out=enc_out,
        )
    if acc is None:
        return chunk
    return {k: CalibStats(acc[k].sigma + st.sigma, st.n,
                          None if st.rows is None else acc[k].rows + st.rows)
            for k, st in chunk.items()}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _block_program(mcfg, hp, b, p_blk, x, enc_out):
    """The block forward of the recompute pass (dense or QuantizedTensor
    weights): block output for one chunk."""
    obs.count("ptq.forward_traces")
    return M._block_apply(
        mcfg, hp, b, p_blk, x,
        mode="train", pos_ids=jnp.arange(x.shape[1]), enc_out=enc_out,
    )[0]


def _capture(mcfg, hp, b, p_blk, xs, enc_outs, chunk: int, mesh, scope: str):
    """Σ of every linear of one block over all calibration chunks, as
    {scope/name: CalibStats}."""
    acc, n = None, {}
    for xc, ec in _chunk_pairs(xs, enc_outs, chunk):
        if acc is None:
            shapes = _capture_program.eval_shape(mcfg, hp, b, mesh, p_blk, xc, ec, None)
            acc = {k: jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), st)
                   for k, st in shapes.items()}
        out = _capture_program(mcfg, hp, b, mesh, p_blk, xc, ec,
                               {k: dataclasses.replace(st, n=0) for k, st in acc.items()})
        acc = out
        for k, st in out.items():
            n[k] = n.get(k, 0) + st.n
    return {f"{scope}/{k}": dataclasses.replace(st, n=n[k]) for k, st in (acc or {}).items()}


def _moe_counts(stats: dict) -> dict:
    """The block's routed copies, from the Σ of its expert layers' input
    (``w_gate``): copies the router sent, those no expert's Σ holds (over
    capacity), and the fewest and most any expert received.  Reads the
    host once; the capture has finished."""
    layers = [st for k, st in stats.items() if st.rows is not None and k.endswith("/w_gate")]
    if not layers:
        return {}
    kept = np.concatenate([np.asarray(st.rows) for st in layers])
    routed = sum(st.n for st in layers)
    out = {"moe.routed_copies": routed, "moe.dropped_copies": routed - int(kept.sum()),
           "moe.expert_tokens_min": int(kept.min()), "moe.expert_tokens_max": int(kept.max())}
    obs.count("moe.routed_copies", out["moe.routed_copies"])
    obs.count("moe.dropped_copies", out["moe.dropped_copies"])
    return out


def _apply_chunked(mcfg, plan, b, blk, x, enc_out, chunk: int) -> jax.Array:
    """Forward one block over ≤chunk-sequence slices (batch dim independent)."""
    eos = None if enc_out is None else [enc_out]
    outs = [
        _block_program(mcfg, plan.heads, b, blk, xc, ec)
        for xc, ec in _chunk_pairs([x], eos, chunk)
    ]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


def ptq_quantize_model(
    plan: M.ModelPlan,
    params,
    calib_batches: list[dict],
    cfg: PTQConfig,
    mesh=None,
    progress_cb: Optional[Callable[[dict], None]] = None,
):
    """Quantize a model's decoder (+ encoder) stacks.

    Returns (new_params, report) where report maps layer path → relative
    reconstruction error (paper Fig. 2 metric).

    ``emit="fake"`` keeps the stacked-scan param layout (dequantized values)
    — usable by train_loss/prefill/decode directly.  ``emit="qt"`` returns
    per-period *lists* of blocks with QuantizedTensor leaves (the serving
    engine consumes this unrolled layout).

    ``mesh`` (+ ``cfg.shard``): data-shard Gram accumulation and row-shard
    the CD solves; identical results on one device.  ``progress_cb``
    receives one dict per quantized block — the launcher renders these as
    progress lines and a block-level progress file (an audit trail for
    post-hoc/restart inspection; quantization itself restarts from scratch).
    Its ``seconds`` is host time from the block's start up to the dispatch of
    its recompute: it includes the waits on the solves, not the recompute's
    device time, so it is not the device's time for the block.  Inside
    ``obs.record()`` the dict also holds ``phase_s`` (host seconds in each
    ``ptq.*`` span of the block), the block's ``compiles``/``compile_s``
    (``cache_loads`` of them read from the persistent compilation cache) and
    ``ptq.forward_traces``, the block forwards it traced (0 once each block
    kind and chunk shape has been seen in the process).  A block with
    experts adds ``moe.routed_copies``, ``moe.dropped_copies`` (also
    counters) and ``moe.expert_tokens_min``/``_max`` (:func:`_moe_counts`).

    Phase spans (``repro.obs``), each with ``block=<scope>``: ``ptq.capture``
    once a block, ``ptq.solve`` once per same-shape group (with ``shape``,
    ``G``, ``method``; it ends after the host reads the group's errors),
    ``ptq.emit`` once per linear, ``ptq.recompute`` once a block.  On expert
    layers ``ptq.solve`` and ``ptq.emit`` also carry ``experts=<E>``.
    """
    mcfg = plan.cfg
    report: dict[str, float] = {}
    calib_mesh = mesh if (mesh is not None and cfg.shard) else None

    # --- embed calibration batches once ---
    xs, enc_outs = [], []
    for batch in calib_batches:
        tokens = batch["tokens"]
        x = M._embed_tokens(plan, params, tokens)
        if mcfg.n_prefix:
            pre = M.apply_norm(params["prefix_ln"], batch["patches"].astype(plan.dtype), mcfg.norm)
            x = jnp.concatenate([pre, x], axis=1)
        if mcfg.pos == "learned":
            S = x.shape[1]
            x = x + jax.lax.dynamic_slice(
                params["pos_emb"], (0, 0), (S, mcfg.d_model)
            )[None].astype(plan.dtype)
        xs.append(x)
        enc_outs.append(None)

    new_params = dict(params)

    # --- encoder first (whisper): quantize, then freeze its outputs ---
    if mcfg.family == "encdec":
        enc_inputs = [
            batch["frames"].astype(plan.dtype)
            + params["enc_pos_emb"][None].astype(plan.dtype)
            for batch in calib_batches
        ]
        new_params["enc"], enc_inputs = _quantize_stack(
            plan, params["enc"], mcfg.enc_pattern, mcfg.n_enc_periods,
            enc_inputs, "enc", cfg, report, enc_outs=None,
            mesh=calib_mesh, progress_cb=progress_cb,
        )
        enc_outs = [
            M.apply_norm(params["enc_final_norm"], e, mcfg.norm) for e in enc_inputs
        ]

    new_params["dec"], _ = _quantize_stack(
        plan, params["dec"], mcfg.pattern, mcfg.n_periods, xs, "dec", cfg, report,
        enc_outs=enc_outs, mesh=calib_mesh, progress_cb=progress_cb,
    )
    return new_params, report


def _quantize_stack(
    plan, stack, pattern, n_periods, xs, stack_name, cfg, report, enc_outs,
    mesh=None, progress_cb=None,
):
    mcfg = plan.cfg
    quantized_periods = []  # for emit="qt": list of {bi: block params}
    stack_out = stack
    n_blocks_total = n_periods * len(pattern)
    for period in range(n_periods):
        p_period = _slice_period(stack, period)
        new_period = {}
        for i, b in enumerate(pattern):
            t0 = time.monotonic()
            recorder = obs.active()
            snap = recorder.snapshot() if recorder is not None else None
            scope = f"{stack_name}.p{period}.b{i}"
            obs.count("ptq.forward_traces", 0)  # reported when nothing traces
            # Capture pass: current block, current (quantized-prefix) inputs.
            # Each chunk's activations fold into Σ inside its compiled
            # forward — nothing but the p×p accumulators survives this loop.
            with obs.span("ptq.capture", block=scope):
                stats = _capture(
                    mcfg, plan.heads, b, p_period[f"b{i}"], xs, enc_outs,
                    cfg.stream_chunk, mesh, scope,
                )
            n_before = len(report)
            sens: dict[str, float] = {}
            new_blk = _quantize_block(
                p_period[f"b{i}"], stats, scope, cfg, report, mesh, sens=sens
            )
            moe = _moe_counts(stats)
            new_period[f"b{i}"] = new_blk
            # Recompute this block's outputs with quantized weights — chunked
            # like the capture pass, so stream_chunk bounds transient
            # activation memory in *both* passes (the stored block inputs xs
            # themselves are the pipeline's irreducible working set).
            with obs.span("ptq.recompute", block=scope):
                xs = [
                    _apply_chunked(
                        mcfg, plan, b, new_blk, x,
                        None if enc_outs is None else enc_outs[bi],
                        cfg.stream_chunk,
                    )
                    for bi, x in enumerate(xs)
                ]
            if progress_cb is not None:
                new_keys = list(report)[n_before:]
                errs = [report[k] for k in new_keys]
                rec = {
                    "stack": stack_name,
                    "period": period,
                    "block": i,
                    "done_blocks": period * len(pattern) + i + 1,
                    "total_blocks": n_blocks_total,
                    "n_linears": len(new_keys),
                    "mean_rel_error": float(np.mean(errs)) if errs else 0.0,
                    # Full-resolution per-layer errors, keyed by layer path.
                    # The auto-tuner ranks layers on these — never on any
                    # downstream-rounded aggregate (eval/harness.py rounds
                    # its reported mean to 6 digits; that rounding must not
                    # reach the sensitivity signal).
                    "layer_errors": {k: float(report[k]) for k in new_keys},
                    # Host time up to the recompute's dispatch: it waits on
                    # the solves, not on the recompute's device time.
                    "seconds": round(time.monotonic() - t0, 3),
                }
                if sens:
                    rec["lambda_max"] = sens
                rec.update(moe)
                if recorder is not None:
                    rec.update(recorder.since(snap))
                progress_cb(rec)
        quantized_periods.append(new_period)
        if cfg.emit == "fake":
            stack_out = _set_period(stack_out, period, new_period)
    if cfg.emit == "qt":
        return quantized_periods, xs
    return stack_out, xs

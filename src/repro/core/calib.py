"""Calibration statistics for layer-wise PTQ.

Every algorithm in this package consumes only second-order statistics of the
calibration activations — ``Σ = X Xᵀ`` (p×p) and optionally ``W Σ`` — never
the raw ``X`` (n ≫ p, so this is the memory win the paper highlights:
``p² + O(pq)`` footprint).  ``CalibStats`` supports *streaming* accumulation
over calibration batches (fp32 accumulators) — the whole-model solver
(core/solver.py) feeds it batch-by-batch during the capture pass — and
sharded accumulation under a mesh: each data shard accumulates its local
Gram matrix inside a ``shard_map`` and a ``psum`` makes it global
(:func:`sharded_gram`); with one device or no mesh the same call degrades
to the plain local matmul.

MoE layers carry one Σ per expert: a ``CalibStats`` whose ``sigma`` has a
leading expert axis ``(E, p, p)`` and whose ``rows`` counts the routed
copies each expert's Σ holds, updated from rows grouped by expert in one
grouped product (see DESIGN.md §Streaming-solver).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

__all__ = ["CalibStats", "gram", "sharded_gram", "shard_axis", "damp_sigma"]


def gram(x: jax.Array) -> jax.Array:
    """Σ = X Xᵀ for X: (p, n) — fp32 accumulation regardless of input dtype."""
    x = x.astype(jnp.float32)
    return x @ x.T


@functools.lru_cache(maxsize=None)
def _sharded_gram_fn(mesh, axis: str):
    """One cached shard_mapped executable per (mesh, axis) — the capture
    pass calls this per linear per chunk, so a fresh wrapper per call would
    retrace every time."""

    def local(xl):
        return jax.lax.psum(xl.T @ xl, axis)

    return jax.jit(
        jax.shard_map(
            local,
            mesh=mesh,
            in_specs=PartitionSpec(axis, None),
            out_specs=PartitionSpec(None, None),
        )
    )


def shard_axis(mesh) -> Optional[str]:
    """The mesh axis PTQ shards over: "data" if present, else the first
    axis.  Single source of truth for Gram accumulation and the row-sharded
    CD solve, so they always engage (or fall back) together."""
    if mesh is None:
        return None
    return "data" if "data" in mesh.shape else next(iter(mesh.shape))


def sharded_gram(x2d: jax.Array, mesh=None, axis: Optional[str] = None) -> jax.Array:
    """Σ = XᵀX for X: (n, p) token-major, data-sharded over ``axis``
    (default: :func:`shard_axis`).

    Each shard contracts its local rows; a ``psum`` over the data axis
    produces the global Gram matrix without ever gathering activations.
    Rows pad internally with zeros up to the axis size (zero rows
    contribute nothing to Σ).  Falls back to the single-device matmul when
    ``mesh`` is None or the axis has size 1 (the result is bit-identical
    up to fp32 reduction order).
    """
    x2d = x2d.astype(jnp.float32)
    axis = axis or shard_axis(mesh)
    n_shards = 1 if mesh is None else mesh.shape.get(axis, 1)
    if n_shards <= 1:
        return x2d.T @ x2d
    pad = (-x2d.shape[0]) % n_shards
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return _sharded_gram_fn(mesh, axis)(x2d)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CalibStats:
    """Streaming Σ accumulator for one linear layer.

    ``sigma`` is the *unnormalized* Gram matrix — ``(p, p)``, or ``(E, p, p)``
    for expert-stacked MoE linears; ``n`` counts samples.  The algorithms are
    scale-invariant in Σ (β̃ in Lemma 1 uses only ratios Σ_{j,k}/Σ_{j,j}),
    so no normalization by n is required.  ``n`` is static pytree metadata:
    a compiled capture returns it from the shapes it traced.  For expert
    stats ``n`` counts the copies the router sent and ``rows`` (E,) those
    each expert's Σ holds; ``n − Σ rows`` were dropped over capacity.
    """

    sigma: jax.Array  # (p, p) or (E, p, p) fp32
    n: int = dataclasses.field(metadata=dict(static=True), default=0)
    rows: Optional[jax.Array] = None  # (E,) int32 for expert stats

    @classmethod
    def zeros(cls, p: int, experts: int = 0) -> "CalibStats":
        if experts:
            return cls(jnp.zeros((experts, p, p), jnp.float32), 0,
                       jnp.zeros((experts,), jnp.int32))
        return cls(sigma=jnp.zeros((p, p), jnp.float32), n=0)

    @property
    def p(self) -> int:
        return self.sigma.shape[-1]

    def update(self, x: jax.Array) -> "CalibStats":
        """x: (p, n_batch) activations feeding the layer (paper layout)."""
        return CalibStats(sigma=self.sigma + gram(x), n=self.n + x.shape[1])

    def update_tokens(self, x_tokens: jax.Array, mesh=None) -> "CalibStats":
        """x_tokens: (..., p) activation tensor in model layout.

        With a mesh, the flattened token rows accumulate via
        :func:`sharded_gram` (local matmul + psum); otherwise locally.
        """
        x2 = x_tokens.reshape(-1, x_tokens.shape[-1])
        return CalibStats(
            sigma=self.sigma + sharded_gram(x2, mesh), n=self.n + x2.shape[0]
        )

    def update_expert_rows(self, x_rows: jax.Array, group_sizes: jax.Array,
                           kept: jax.Array, routed: int) -> "CalibStats":
        """x_rows: (R, p) rows grouped by expert, group e being
        ``group_sizes[e]`` consecutive rows (MoE path).  One grouped product
        (``ragged_dot_general``, ragged over the contracted rows) gives every
        expert's Gram matrix; ``kept[e]`` of its rows are routed copies,
        out of ``routed`` the router sent."""
        dn = jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
        )
        g = jax.lax.ragged_dot_general(x_rows, x_rows, group_sizes, dn,
                                       preferred_element_type=jnp.float32)
        return CalibStats(self.sigma + g, self.n + routed, self.rows + kept)


def damp_sigma(sigma: jax.Array, percdamp: float = 0.01) -> jax.Array:
    """λ-damping: Σ + λI with λ = percdamp · mean(diag Σ).

    Identical to GPTQ's stabilization.  For QuantEase it additionally
    guarantees Σ_{j,j} > 0 (Lemma 1 footnote: dead input features would
    otherwise make the CD update ill-defined).  Columns with Σ_{j,j}=0 before
    damping are untouched by the objective, so damping them towards
    round-to-nearest is exactly the right behavior.  Batched Σ (leading
    dims) damp per-matrix.
    """
    p = sigma.shape[-1]
    diag = jnp.diagonal(sigma, axis1=-2, axis2=-1)
    mean_diag = jnp.clip(jnp.mean(diag, axis=-1), 1e-8, None)
    eye = jnp.eye(p, dtype=sigma.dtype)
    return sigma + (percdamp * mean_diag)[..., None, None] * eye

"""Mixture-of-Experts layer: top-k token-choice routing, two dispatches.

1. The router (fp32) scores every expert: softmax, or sigmoid with a
   per-expert bias that moves the selection but not the weights.  Each
   token picks its top-k experts; the k weights are optionally
   renormalized.
2. **Dropless** (``capacity_factor=None``; every non-training path: PTQ
   capture and recompute, prefill, decode): within each dispatch group the
   routed copies are sorted by expert and each projection is one grouped
   product (``jax.lax.ragged_dot_general``) over the stacked expert weights
   (E, D, F), so no copy is dropped and a token's output does not depend on
   its batch.  The sorted copies take N·k rows — no padding of each expert
   to N.
3. **Capacity** (training): copies are assigned slots in an (E, C) table
   (C = ceil(N·k/E · capacity_factor)); overflow drops, the standard
   Switch/GShard behavior, and static shapes suit GSPMD: gather → (E, C, D),
   one einsum per projection, weighted scatter-add back to (N, D).

Sharding: expert-stacked weights shard on the expert axis over "model"
when E is divisible (EP: OLMoE 64, Jamba 16), else on the per-expert ffn
axis (TP: Mixtral 8) — resolved by the rules engine.  Both dispatches split
the tokens into ``dispatch_groups`` aligned with the "data" shards (§Perf
H2), so slots and sorted copies shard over "data" with their tokens.

Under ``capture_gram_stats`` each expert projection's input folds into one
Σ per expert, with the count of routed copies each expert received.
``w_gate`` and ``w_up`` read the same input, so it is recorded once, under
``w_gate``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.dist.sharding import current_rules, logical_constraint
from repro.models.common import _record_linear, activation

__all__ = ["moe_apply", "router_aux_loss", "MOE_TRAIN_CAPACITY"]

MOE_TRAIN_CAPACITY = 1.25  # the training path's capacity factor


def _route(p, xf, *, n_experts, top_k, norm_topk, score):
    """xf: (N, D) → (weights (N, k) fp32, expert ids (N, k), router probs
    (N, E) for the auxiliary loss).  The router runs in fp32 at full
    precision, so a selection is not decided by a bf16 rounding."""
    logits = jnp.einsum(
        "nd,de->ne", xf.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        sel = probs + p["expert_bias"].astype(jnp.float32) if "expert_bias" in p else probs
        _, top_e = jax.lax.top_k(sel, top_k)
        top_w = jnp.take_along_axis(probs, top_e, axis=-1)
        if norm_topk:
            top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-6)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, top_k)  # (n, k)
        if norm_topk:
            top_w = top_w / jnp.clip(top_w.sum(-1, keepdims=True), 1e-9, None)
    return top_w, top_e, probs


def _dense_experts(w) -> jax.Array:
    """Stacked expert weights as (E, d_in, d_out): dense as stored, a
    QuantizedTensor (codes (E, d_out, d_in)) or its hoisted view dequantized
    to fp32."""
    if hasattr(w, "codes"):
        return jnp.swapaxes(jax.vmap(lambda qt: qt.dequantize())(w), 1, 2)
    if hasattr(w, "w"):  # HoistedDequant: per-expert (E, d_out, d_in)
        return jnp.swapaxes(w.w, 1, 2)
    return w


def _ragged_matmul(w, xs: jax.Array, sizes: jax.Array) -> jax.Array:
    """xs: (R, d_in) rows grouped by expert (group e: ``sizes[e]``
    consecutive rows) → (R, d_out); quantized
    weights run in fp32, as :func:`_expert_matmul`'s."""
    wd = _dense_experts(w)
    xin = xs if wd.dtype == xs.dtype else xs.astype(wd.dtype)
    y = jax.lax.ragged_dot(xin, wd, sizes, preferred_element_type=jnp.float32)
    return y.astype(xs.dtype)


def _experts_mlp(p, xs, sizes, *, act, gated):
    """One group's sorted copies through their experts: (R, D) → (R, D)."""
    h = activation(_ragged_matmul(p["w_gate"], xs, sizes), act)
    if gated:
        h = h * _ragged_matmul(p["w_up"], xs, sizes)
    return h, _ragged_matmul(p["w_down"], h, sizes)


def _combine(ys, order, top_w):
    """One dispatch group's sorted expert outputs ys (ng·k, D) back to
    token order, weighted and summed over each token's k copies → (ng, D)
    fp32."""
    n, k = top_w.shape
    ys = jnp.take(ys, jnp.argsort(order), axis=0).reshape(n, k, -1)
    return (ys.astype(jnp.float32) * top_w[..., None]).sum(1)


def _experts_on_mesh(rules, p, xs, sizes, order, top_w, *, act, gated):
    """Mesh path of the dropless experts: xs (g, ng·k, D) sorted copies,
    sizes (g, E), order (g, ng·k), top_w (g, ng, k) → (g, ng, D) fp32.
    ``ragged_dot`` has no sharding rule that keeps expert- or ffn-sharded
    weights in place (GSPMD all-gathers them), so each device runs its data
    shard's groups through the weights it holds: with EP (experts over
    "model") the rows of its own experts, a contiguous run of the sorted
    copies, rolled to the front; with TP (per-expert ffn over "model")
    every row on its ffn slice.  Each device combines its part per token,
    and a ``psum`` over the weights' axis adds the parts."""
    e_ax, f_ax = rules.table.get("experts"), rules.table.get("expert_ffn")
    b_ax = rules.table.get("batch")
    n_b = 1
    for a in (b_ax,) if isinstance(b_ax, str) else (b_ax or ()):
        n_b *= rules.mesh.shape[a]
    if xs.shape[0] % n_b:
        b_ax = None  # fewer groups than data shards: every shard runs them all
    w_in, w_out = P(e_ax, None, f_ax), P(e_ax, f_ax, None)
    names = ("w_gate", "w_up", "w_down") if gated else ("w_gate", "w_down")
    ws = {n: _dense_experts(p[n]) for n in names}

    def local(ws, xs, sizes, order, top_w):
        n_e = ws["w_gate"].shape[0]
        outs = []
        for x, s, o, w in zip(xs, sizes, order, top_w):  # this shard's dispatch groups
            if e_ax is None:
                y = _experts_mlp(ws, x, s, act=act, gated=gated)[1]
            else:
                first = jax.lax.axis_index(e_ax) * n_e
                mine = jax.lax.dynamic_slice(s, (first,), (n_e,))
                start = jnp.where(jnp.arange(s.shape[0]) < first, s, 0).sum()
                y = _experts_mlp(ws, jnp.roll(x, -start, axis=0), mine, act=act, gated=gated)[1]
                y = jnp.where((jnp.arange(x.shape[0]) < mine.sum())[:, None], y, 0)
                y = jnp.roll(y, start, axis=0)
            outs.append(_combine(y, o, w))
        y = jnp.stack(outs)
        axes = tuple(a for a in (e_ax, f_ax) if a is not None)
        return jax.lax.psum(y, axes) if axes else y

    return jax.shard_map(
        local, mesh=rules.mesh,
        in_specs=({n: (w_out if n == "w_down" else w_in) for n in names},
                  P(b_ax, None, None), P(b_ax, None), P(b_ax, None), P(b_ax, None, None)),
        out_specs=P(b_ax, None, None),
    )(ws, xs, sizes, order, top_w)


def _dropless(p, xf, top_w, top_e, *, n_experts, act, gated, groups):
    """Every routed copy through its expert: (N, D) → (N, D) fp32.

    On a mesh, tokens split into ``groups`` data-local dispatch groups, as
    the capacity path's; each sorts its own copies by expert, so the gather
    and the combine never cross a data shard.  On one device they form one
    group."""
    n, k = top_e.shape
    d = xf.shape[-1]
    rules = current_rules()
    on_mesh = rules is not None and rules.mesh.size > 1
    if not on_mesh:
        groups = 1
    ng = n // groups
    xg = logical_constraint(xf.reshape(groups, ng, d), ("batch", None, None))
    eg = top_e.reshape(groups, ng * k)
    wg = top_w.reshape(groups, ng, k)
    order = jnp.argsort(eg, axis=1, stable=True)  # each group's copies by expert
    sizes = jax.vmap(lambda e: jnp.bincount(e, length=n_experts))(eg).astype(jnp.int32)
    xs = jnp.take_along_axis(xg, (order // k)[..., None], axis=1)  # (g, ng·k, D)
    xs = logical_constraint(xs, ("batch", None, None))
    if on_mesh:
        y = _experts_on_mesh(rules, p, xs, sizes, order, wg, act=act, gated=gated)
    else:
        rows = (sizes[0], sizes[0], n * k)
        _record_linear("w_gate", xs[0], expert_rows=rows)  # also w_up's input
        h, y = _experts_mlp(p, xs[0], sizes[0], act=act, gated=gated)
        _record_linear("w_down", h, expert_rows=rows)
        y = _combine(y, order[0], wg[0])[None]
    return logical_constraint(y, ("batch", None, None)).reshape(n, d)


def _expert_matmul(w, xs: jax.Array) -> jax.Array:
    """xs: (E, C, d_in) × stacked expert weights → (E, C, d_out).

    ``w`` is dense (E, d_in, d_out) or a QuantizedTensor with codes
    (E, d_out, d_in) (per-expert grids stacked on the leading axis).
    """
    if hasattr(w, "codes"):
        from repro.kernels.ref import dequant_matmul_ref

        return jax.vmap(
            lambda x_e, c_e, s_e, z_e: dequant_matmul_ref(
                x_e, c_e, s_e, z_e, out_dtype=xs.dtype
            )
        )(xs, w.unpacked_codes(), w.scale, w.zero)
    if hasattr(w, "w"):  # HoistedDequant: per-expert pre-dequantized (E, d_out, d_in)
        return jax.vmap(
            lambda x_e, w_e: (x_e.astype(jnp.float32) @ w_e.T).astype(xs.dtype)
        )(xs, w.w)
    return jnp.einsum("ecd,edf->ecf", xs, w)


def _dispatch_table(expert_ids: jax.Array, n_experts: int, capacity: int):
    """expert_ids: (R,) routed-copy expert assignment → (token-slot table
    (E*C,) int32 with -1 empty, per-copy slot position or -1 if dropped)."""
    r = expert_ids.shape[0]
    order = jnp.argsort(expert_ids)  # stable: groups copies by expert
    sorted_e = expert_ids[order]
    # Position of each routed copy within its expert group.
    starts = jnp.searchsorted(sorted_e, jnp.arange(n_experts), side="left")
    pos_in_e = jnp.arange(r) - starts[sorted_e]
    keep = pos_in_e < capacity
    slot_sorted = jnp.where(keep, sorted_e * capacity + pos_in_e, n_experts * capacity)
    # Invert the sort to get each copy's slot.
    slot = jnp.zeros((r,), jnp.int32).at[order].set(slot_sorted.astype(jnp.int32))
    # slot → copy index (overflow bucket at the end, trimmed after scatter).
    copy_for_slot = (
        jnp.full((n_experts * capacity + 1,), -1, jnp.int32)
        .at[slot]
        .set(jnp.arange(r, dtype=jnp.int32))[:-1]
    )
    return copy_for_slot, slot


def moe_apply(
    p: dict,
    x: jax.Array,  # (B, S, D)
    *,
    n_experts: int,
    top_k: int,
    act: str,
    gated: bool,
    norm_topk: bool,
    score: str = "softmax",
    capacity_factor: Optional[float] = None,
    return_aux: bool = False,
    dispatch_groups: int = 1,
):
    """Returns (y, router_probs or None).

    ``capacity_factor=None`` routes dropless; a factor selects the capacity
    dispatch, which ``dispatch_groups`` (§Perf H2) splits: dispatch/combine
    are computed within ``dispatch_groups`` independent token groups aligned
    with the data-parallel sharding.  With groups == data-axis size the gather and
    scatter-add never cross data shards — GSPMD otherwise all-gathers the
    whole (N, D) token array per MoE layer (measured: 131 GB/device/layer
    on mixtral prefill_32k).  Capacity is per (group, expert); the drop
    criterion becomes group-local, which is exactly what per-host routing
    does on real fleets.
    """
    B, S, D = x.shape
    n = B * S
    xf = x.reshape(n, D)
    top_w, top_e, probs = _route(
        p, xf, n_experts=n_experts, top_k=top_k, norm_topk=norm_topk, score=score,
    )
    g = dispatch_groups if n % dispatch_groups == 0 else 1
    if capacity_factor is None:
        y = _dropless(p, xf, top_w, top_e, n_experts=n_experts, act=act, gated=gated, groups=g)
        return y.reshape(B, S, D).astype(x.dtype), (probs if return_aux else None)

    ng = n // g  # tokens per group
    capacity = max(int(ng * top_k / n_experts * capacity_factor), 8)
    copy_for_slot, _ = jax.vmap(
        lambda e: _dispatch_table(e, n_experts, capacity)
    )(top_e.reshape(g, ng * top_k))  # (g, E·C)

    token_for_slot = jnp.where(copy_for_slot >= 0, copy_for_slot // top_k, 0)
    w_for_slot = jnp.where(
        copy_for_slot >= 0,
        jnp.take_along_axis(
            top_w.reshape(g, -1), jnp.clip(copy_for_slot, 0), axis=1
        ),
        0.0,
    )  # (g, E·C)

    # Constraints pin the dispatch group axis to the data shards at every
    # hop; without them GSPMD replicates the gather/scatter (and their
    # transposes in backward) and all-reduces full (N, D) fp32 tensors over
    # the entire mesh — measured at ~7 TB/device/step on jamba train_4k.
    xg = xf.reshape(g, ng, D)
    xg = logical_constraint(xg, ("batch", None, None))
    xs = jnp.take_along_axis(xg, token_for_slot[..., None], axis=1)
    xs = logical_constraint(xs, ("batch", None, None))
    xs = xs.reshape(g, n_experts, capacity, D).transpose(1, 0, 2, 3)
    xs = xs.reshape(n_experts, g * capacity, D)
    xs = logical_constraint(xs, ("experts", "batch", None))
    # Σ capture: each expert's g·C slots as its rows, the filled ones counted.
    slots = jnp.full((n_experts,), g * capacity, jnp.int32)
    filled = (copy_for_slot >= 0).reshape(g, n_experts, capacity).sum((0, 2)).astype(jnp.int32)
    rows = (slots, filled, n * top_k)
    _record_linear("w_gate", xs.reshape(-1, D), expert_rows=rows)  # also w_up's input
    h = _expert_matmul(p["w_gate"], xs)
    h = activation(h, act)
    if gated:
        h = h * _expert_matmul(p["w_up"], xs)
    h = logical_constraint(h, ("experts", "batch", "expert_ffn"))
    _record_linear("w_down", h.reshape(-1, h.shape[-1]), expert_rows=rows)
    ys = _expert_matmul(p["w_down"], h)  # (E, g·C, D)
    ys = ys.reshape(n_experts, g, capacity, D).transpose(1, 0, 2, 3)
    ys = ys.reshape(g, n_experts * capacity, D)
    ys = logical_constraint(ys, ("batch", None, None))
    ys = ys * w_for_slot[..., None].astype(ys.dtype)

    yg = jnp.zeros((g, ng, D), jnp.float32)
    yg = yg.at[jnp.arange(g)[:, None], token_for_slot].add(
        jnp.where((copy_for_slot >= 0)[..., None], ys.astype(jnp.float32), 0.0)
    )
    yg = logical_constraint(yg, ("batch", None, None))
    y = yg.reshape(B, S, D).astype(x.dtype)
    return (y, probs if return_aux else None)


def router_aux_loss(probs: jax.Array, top_e: Optional[jax.Array] = None) -> jax.Array:
    """Switch-style load-balancing loss: E · Σ_e f_e · P_e."""
    n, e = probs.shape
    pe = probs.mean(0)
    fe = (probs == probs.max(-1, keepdims=True)).astype(jnp.float32).mean(0)
    return e * jnp.sum(fe * pe)

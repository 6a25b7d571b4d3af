"""AOT compiles of the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each case lowers a kernel at phi3_mini_3_8b's published widths
and compiles it with the TPU compiler for a v5e chip that is described, not
attached, then checks the Mosaic kernel is in the program.  This is what the
interpret-mode kernel tests cannot see: tiling rules, casts Mosaic lacks, and
the scoped-VMEM limit the fit gates in kernels/ops.py budget against.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.calib import CalibStats
from repro.kernels import ops
from repro.kernels import quantease_cd as qcd
from repro.kernels.dequant_matmul import dequant_matmul_pallas, select_tile_k
from repro.kernels.paged_attention import paged_attention_pallas

CFG = get_config("phi3_mini_3_8b")
D, FF, HD = CFG.d_model, CFG.d_ff, CFG.hd
BSZ = 256  # QuantEaseConfig.block_size


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize(
    "q,p,matmul_dtype",
    [(FF, D, "float32"), (FF, D, "bfloat16"), (D, FF, "float32"), (D, FF, "bfloat16")],
)
def test_fused_iteration_compiles(one_chip, q, p, matmul_dtype):
    tq = ops.fused_iteration_tq(p, BSZ, matmul_dtype)
    assert tq is not None
    fn = functools.partial(
        qcd.quantease_fused_iteration_pallas, n_levels=16, quantize=True,
        bsz=BSZ, tq=tq, matmul_dtype=matmul_dtype, interpret=False,
    )
    s = functools.partial(_s, one_chip)
    _compile(fn, s((q, p)), s((p, p)), s((q, p)), s((q, p)), s((q, p)), s((q, p)))


def test_outlier_iteration_compiles(one_chip):
    tq = ops.outlier_iteration_tq(D, BSZ, "float32")
    fn = functools.partial(
        qcd.quantease_outlier_iteration_pallas, n_levels=16, quantize=True,
        bsz=BSZ, tq=tq, interpret=False,
    )
    s = functools.partial(_s, one_chip)
    _compile(fn, s((D, D)), s((D, D)), *[s((D, D))] * 5)


def test_block_sweep_compiles(one_chip):
    fn = functools.partial(
        qcd.quantease_block_sweep_pallas, n_levels=16, quantize=True,
        tq=ops.block_sweep_tq(FF, BSZ), interpret=False,
    )
    s = functools.partial(_s, one_chip)
    _compile(fn, s((FF, BSZ)), s((BSZ, BSZ)), s((FF, BSZ)), s((FF, BSZ)), s((FF, BSZ)))


@pytest.mark.parametrize("layout", ["per-channel", "int4-linear", "int4-tile", "grouped"])
@pytest.mark.parametrize("m", [4, 64])
def test_dequant_matmul_compiles(one_chip, layout, m):
    q, p = FF, D
    kw = dict(interpret=False, out_dtype=jnp.bfloat16)
    n_groups = 1
    if layout != "per-channel":
        kw["packed4"] = True
    if layout == "int4-tile":
        kw.update(pack_layout="tile", tk=select_tile_k(p))
    if layout == "grouped":
        n_groups = p // 128
    s = functools.partial(_s, one_chip)
    codes = s((q, p // 2 if kw.get("packed4") else p), jnp.uint8)
    _compile(
        functools.partial(dequant_matmul_pallas, **kw),
        s((m, p), jnp.bfloat16), codes, s((q, n_groups)), s((q, n_groups)),
    )


@pytest.mark.parametrize("kv", ["bf16", "int8", "int4"])
def test_paged_attention_compiles(one_chip, kv):
    B, kvp, g, psz, n_pgs = 4, CFG.n_kv_heads, CFG.n_heads // CFG.n_kv_heads, 16, 34
    n_pages = 1 + B * n_pgs
    s = functools.partial(_s, one_chip)
    dtype, page_hd = {"bf16": (jnp.bfloat16, HD), "int8": (jnp.int8, HD),
                      "int4": (jnp.uint8, HD // 2)}[kv]
    pages = s((n_pages, psz, kvp, page_hd), dtype)
    args = [s((B, kvp, g, HD), jnp.bfloat16), pages, pages,
            s((B, n_pgs), jnp.int32), s((B,), jnp.int32)]
    if kv == "bf16":
        _compile(functools.partial(paged_attention_pallas, interpret=False), *args)
        return
    scales = s((n_pages, psz, kvp, 1))

    def fn(q, k, v, pt, ln, ks, vs):
        return paged_attention_pallas(
            q, k, v, pt, ln, k_scale_pages=ks, v_scale_pages=vs, interpret=False
        )

    _compile(fn, *args, scales, scales)


def _block_structs(sharding):
    """One phi3 block's dense parameters, an 8 × 2048 chunk of its input,
    and the plan, config and block kind the driver's programs take."""
    from repro.models import make_plan
    from repro.models.model import param_shapes

    plan = make_plan(CFG)
    dec = param_shapes(plan)["dec"]
    p_blk = jax.tree.map(lambda a: _s(sharding, a.shape[1:], a.dtype), dec)["b0"]
    x = _s(sharding, (8, 2048, D), jnp.bfloat16)
    return plan, CFG.pattern[0], p_blk, x


def test_ptq_block_programs_compile(one_chip, monkeypatch):
    """The PTQ driver's capture and recompute programs at phi3 widths: the
    capture aliases its donated Σ accumulators (not held twice), and the
    recompute over packed 4-bit weights keeps the Mosaic GEMM."""
    from repro.core import solver
    from repro.quant import GridSpec, compute_grid

    plan, b, p_blk, x = _block_structs(one_chip)
    hp = plan.heads
    sig = solver._capture_program.eval_shape(CFG, hp, b, None, p_blk, x, None, None)
    sigmas = {k: _s(one_chip, st.sigma.shape) for k, st in sig.items()}
    assert len(sigmas) == 7
    sigma_bytes = sum(4 * s.size for s in sigmas.values())
    assert sigma_bytes == 494_927_872
    acc = {k: CalibStats(s) for k, s in sigmas.items()}
    cap = solver._capture_program.lower(CFG, hp, b, None, p_blk, x, None, acc).compile()
    cap_mem = cap.memory_analysis()
    assert cap_mem.alias_size_in_bytes >= sigma_bytes

    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # trace as on the chip
    cfg = solver.PTQConfig(spec=GridSpec(bits=4), emit="qt")

    def emit(w, p_in):
        w2d = solver._to_2d(w, p_in)
        return solver._emit_leaf(w2d, None, w, cfg, compute_grid(w2d, cfg.spec))

    qblk = dict(p_blk)
    for name, s in sigmas.items():
        qt = jax.eval_shape(functools.partial(emit, p_in=s.shape[-1]), p_blk[name])
        assert qt.packed
        qblk[name] = jax.tree.map(lambda a: _s(one_chip, a.shape, a.dtype), qt)
    rec = solver._block_program.lower(CFG, hp, b, qblk, x, None).compile()
    assert "tpu_custom_call" in rec.as_text()
    print(f"capture temp {cap_mem.temp_size_in_bytes} B, alias "
          f"{cap_mem.alias_size_in_bytes} B; recompute (packed 4-bit) temp "
          f"{rec.memory_analysis().temp_size_in_bytes} B")


LFM2 = get_config("lfm2_8b_a1b")


@pytest.mark.parametrize("layer", [2, 3], ids=["attn_moe", "conv_moe"])
def test_ptq_lfm2_block_programs_compile(one_chip, monkeypatch, layer):
    """The capture and recompute programs of an LFM2 routed-expert block at
    its published widths, 8 × 2048 tokens a chunk: the capture's per-expert
    Σ are aliased (one (E, d, d) Σ for the gate and up input, one (E, F, F)
    for the down input), and the recompute over packed 4-bit experts runs
    its grouped products as the TPU's own ragged dot."""
    import dataclasses

    from repro.core import solver
    from repro.models import make_plan
    from repro.models.model import param_shapes
    from repro.quant import GridSpec, compute_grid

    cfg_l = dataclasses.replace(LFM2, pattern=(LFM2.pattern[layer],))
    plan = make_plan(cfg_l)
    b, hp = cfg_l.pattern[0], plan.heads
    p_blk = jax.tree.map(lambda a: _s(one_chip, a.shape[1:], a.dtype),
                         param_shapes(plan)["dec"])["b0"]
    x = _s(one_chip, (8, 2048, LFM2.d_model), jnp.bfloat16)
    sig = solver._capture_program.eval_shape(cfg_l, hp, b, None, p_blk, x, None, None)
    E, d, F = LFM2.n_experts, LFM2.d_model, LFM2.moe_ff
    assert sig["w_gate"].sigma.shape == (E, d, d) and "w_up" not in sig
    assert sig["w_down"].sigma.shape == (E, F, F) and sig["w_down"].n == 8 * 2048 * LFM2.top_k
    acc = jax.tree.map(lambda a: _s(one_chip, a.shape, a.dtype), sig)
    cap = solver._capture_program.lower(cfg_l, hp, b, None, p_blk, x, None, acc).compile()
    cap_mem = cap.memory_analysis()
    sigma_bytes = sum(4 * st.sigma.size for st in sig.values())
    assert cap_mem.alias_size_in_bytes >= sigma_bytes
    assert "ragged" in cap.as_text()

    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # trace as on the chip
    cfg = solver.PTQConfig(spec=GridSpec(bits=4), emit="qt")

    def emit(w2d):
        return solver._emit_leaf(w2d, None, None, cfg, compute_grid(w2d, cfg.spec))

    qblk = dict(p_blk)
    for name in solver.QUANTIZABLE & set(p_blk):
        w = p_blk[name]
        if name in solver._MOE_NAMES:
            w2 = jax.ShapeDtypeStruct((w.shape[0], w.shape[2], w.shape[1]), jnp.float32)
            qt = jax.eval_shape(jax.vmap(emit), w2)
        else:
            p_in = sig[solver._SHARED_SIGMA.get(name, name)].sigma.shape[-1]
            qt = jax.eval_shape(emit, jax.ShapeDtypeStruct((w.size // p_in, p_in), jnp.float32))
        assert qt.packed
        qblk[name] = jax.tree.map(lambda a: _s(one_chip, a.shape, a.dtype), qt)
    rec = solver._block_program.lower(cfg_l, hp, b, qblk, x, None).compile()
    assert "ragged" in rec.as_text()
    print(f"LFM2 layer {layer}: capture temp {cap_mem.temp_size_in_bytes} B, alias "
          f"{cap_mem.alias_size_in_bytes} B; recompute (packed 4-bit) temp "
          f"{rec.memory_analysis().temp_size_in_bytes} B")


@pytest.mark.parametrize("G,q,p", [(32, 2 * LFM2.moe_ff, LFM2.d_model),
                                   (32, LFM2.d_model, LFM2.moe_ff)],
                         ids=["gate_up", "down"])
def test_expert_group_solve_compiles(one_chip, monkeypatch, G, q, p):
    """The grouped CD solves of an LFM2 expert layer as the solver runs
    them: all 32 experts in one vmapped call, gate and up stacked along
    their rows (one Σ each), on the fused kernel.  Together with the
    model, the calibration set and the block's Σ they fit one v5e chip:
    arguments, temporaries and output of the larger group take 9.5 GB
    (PERF.md §4), so the solver runs each group whole."""
    from repro.core import quantease
    from repro.quant import GridSpec, compute_grid

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    spec = GridSpec(bits=4)
    w = _s(one_chip, (G, q, p))
    grid = jax.eval_shape(jax.vmap(lambda a: compute_grid(a, spec)), w)
    grid = jax.tree.map(lambda a: _s(one_chip, a.shape, a.dtype), grid)
    c = quantease.quantease_quantize.lower(
        w, _s(one_chip, (G, p, p)), spec, grid=grid, iterations=25, percdamp=0.01,
        use_kernel="auto").compile()
    assert "tpu_custom_call" in c.as_text()
    m = c.memory_analysis()
    total = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
    print(f"expert group ({G}, {q}, {p}): arguments {m.argument_size_in_bytes} B, "
          f"temp {m.temp_size_in_bytes} B, output {m.output_size_in_bytes} B")
    assert total < 10e9


@pytest.fixture(scope="module")
def v5e_mesh(one_chip):
    """The described 2×2 v5e as a ("data", "model") mesh."""
    import numpy as np
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return jax.sharding.Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.mark.parametrize("parallel", ["ep", "tp"])
@pytest.mark.parametrize("tokens", [(16, 1), (2, 512)], ids=["decode", "prefill"])
def test_dropless_moe_keeps_expert_weights_in_place(v5e_mesh, parallel, tokens):
    """Dropless MoE at OLMoE's widths (64 experts of 2048 → 1024, top-8) on a
    2×2 mesh, dispatch groups on "data": with the experts (EP) or their ffn
    (TP) on "model", no expert weight is all-gathered — the TPU's ragged
    dot has no sharding rule of its own, and GSPMD would gather them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.sharding import axis_rules, make_rules
    from repro.models.moe import moe_apply

    E, d, f = 64, 2048, 1024
    rules = make_rules(v5e_mesh, n_experts=E if parallel == "ep" else 0, moe_ff=f)
    w_in = rules.sharding(("experts", "embed", "expert_ffn"))
    w_out = rules.sharding(("experts", "expert_ffn", "embed"))
    p = {"router": _s(NamedSharding(v5e_mesh, P()), (d, E)),
         "w_gate": _s(w_in, (E, d, f), jnp.bfloat16), "w_up": _s(w_in, (E, d, f), jnp.bfloat16),
         "w_down": _s(w_out, (E, f, d), jnp.bfloat16)}
    x = _s(rules.sharding(("batch", None, None)), tokens + (d,), jnp.bfloat16)

    def fn(p, x):
        with axis_rules(rules):
            return moe_apply(p, x, n_experts=E, top_k=8, act="silu", gated=True,
                             norm_topk=False, dispatch_groups=2)[0]

    text = jax.jit(fn).lower(p, x).compile().as_text()
    gathered = [functools.reduce(lambda a, b: a * b, map(int, dims.split(",")), 1)
                for dims in re.findall(r"= \w+\[([0-9,]+)\][^=\n]* all-gather(?:-start)?\(", text)]
    assert max(gathered, default=0) < d * f // 2, gathered

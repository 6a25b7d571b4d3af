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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
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
    cap = solver._capture_program.lower(CFG, hp, b, None, p_blk, x, None, sigmas).compile()
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

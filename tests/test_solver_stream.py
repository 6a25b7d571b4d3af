"""Streaming/batched/sharded PTQ engine: parity against the record-based oracle.

The seed engine captured raw activation lists per linear and quantized
layers one-by-one in Python loops.  The streaming engine accumulates
CalibStats (Σ only) during capture and solves same-shape groups in batched
vmapped calls.  These tests pin the refactor to the old semantics:

* streaming Σ == Σ rebuilt from raw records (fp32 tolerance),
* grouped/vmapped solves == sequential per-layer solves,
* whole-model relative-error reports match a record-based reference engine
  within 1e-4 (ISSUE 1 acceptance bar),
* sharded paths == local paths (psum gram fallback on 1 device; the
  2-device shard_map run is skip-guarded on jax.device_count()).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core import solver
from repro.core.calib import CalibStats, sharded_gram
from repro.core.solver import (
    PTQConfig,
    QUANTIZABLE,
    _MOE_NAMES,
    _quantize_one,
    ptq_quantize_model,
)
from repro.core.quantease import quantease_quantize, relative_error
from repro.launch.mesh import make_data_mesh
from repro.core.gptq import gptq_quantize
from repro.models import init_params, make_plan, train_loss
from repro.models import model as M
from repro.models.common import (
    capture_gram_stats,
    capture_linear_inputs,
    capture_scope,
)
from repro.quant import GridSpec
from tests.conftest import reduce_cfg


def _small(arch="stablelm_12b", **over):
    cfg = reduce_cfg(get_config(arch), **over)
    plan = make_plan(cfg, 1)
    params = init_params(plan, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    calib = [
        {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 48)).astype(np.int32))}
        for _ in range(2)
    ]
    return plan, params, calib


def _capture_both(plan, params, calib):
    """One block's capture pass under both mechanisms at once."""
    mcfg = plan.cfg
    xs = [M._embed_tokens(plan, params, b["tokens"]) for b in calib]
    p_blk = jax.tree.map(lambda a: a[0], params["dec"])["b0"]
    records, stats = {}, {}
    with capture_linear_inputs(records), capture_gram_stats(stats), capture_scope("s"):
        for x in xs:
            M._block_apply(
                mcfg, plan.heads, mcfg.pattern[0], p_blk, x,
                mode="train", pos_ids=jnp.arange(x.shape[1]),
            )
    return p_blk, records, stats


def _sigma_from_records(xs_list):
    p = xs_list[0].shape[-1]
    sigma = jnp.zeros((p, p), jnp.float32)
    for x in xs_list:
        x32 = x.astype(jnp.float32)
        sigma = sigma + x32.T @ x32
    return sigma


def test_streaming_sigma_matches_records():
    plan, params, calib = _small()
    _, records, stats = _capture_both(plan, params, calib)
    assert set(records) == set(stats)
    assert records, "no linears captured"
    for key, xs_list in records.items():
        ref = _sigma_from_records(xs_list)
        got = stats[key].sigma
        scale = float(jnp.max(jnp.abs(ref))) + 1e-9
        assert float(jnp.max(jnp.abs(got - ref))) / scale < 1e-5, key
        assert stats[key].n == sum(x.shape[0] for x in xs_list)


def test_streaming_sigma_matches_records_moe():
    plan, params, calib = _small("olmoe_1b_7b")
    _, records, stats = _capture_both(plan, params, calib)
    moe_keys = [k for k in stats if k.split("/")[-1] in _MOE_NAMES]
    assert moe_keys, "no MoE linears captured"
    for key in moe_keys:
        sig = stats[key].sigma
        E = sig.shape[0]
        assert sig.ndim == 3
        for e in range(E):
            ref = _sigma_from_records([x[e] for x in records[key]])
            scale = float(jnp.max(jnp.abs(ref))) + 1e-9
            assert float(jnp.max(jnp.abs(sig[e] - ref))) / scale < 1e-5, (key, e)


@pytest.mark.parametrize("method", ["gptq", "quantease"])
def test_batched_solve_matches_sequential(layer_problem, method):
    w, sigma = layer_problem
    r = np.random.default_rng(1)
    # Three distinct layers of one shape: perturb w and sigma per group slot.
    w3 = jnp.stack([w, w * 0.5, w + 0.1])
    x2 = jnp.asarray(r.standard_normal((w.shape[1], 300)).astype(np.float32))
    sig3 = jnp.stack([sigma, sigma * 2.0, x2 @ x2.T])
    spec = GridSpec(bits=4)
    if method == "gptq":
        batched = gptq_quantize(w3, sig3, spec)
        seq = [gptq_quantize(w3[g], sig3[g], spec) for g in range(3)]
    else:
        batched, objs = quantease_quantize(
            w3, sig3, spec, iterations=4, track_objective=True
        )
        assert objs.shape == (3, 4)
        seq = [quantease_quantize(w3[g], sig3[g], spec, iterations=4)[0] for g in range(3)]
    for g in range(3):
        np.testing.assert_allclose(
            np.asarray(batched[g]), np.asarray(seq[g]), atol=2e-5
        )


def test_moe_vmapped_experts_match_per_expert_loop():
    plan, params, calib = _small("olmoe_1b_7b")
    cfg = PTQConfig(method="quantease", spec=GridSpec(bits=4), iterations=4)
    _, report = ptq_quantize_model(plan, params, calib, cfg)
    # Reference: per-expert sequential solves from the same streaming stats.
    _, _, stats = _capture_both(plan, params, calib)
    p_blk = jax.tree.map(lambda a: a[0], params["dec"])["b0"]
    checked = 0
    for name in sorted(_MOE_NAMES & set(p_blk)):
        st = stats[f"s/{solver._SHARED_SIGMA.get(name, name)}"]
        w = p_blk[name]
        for e in range(w.shape[0]):
            w2d = w[e].reshape(w.shape[1], -1).T.astype(jnp.float32)
            w_hat, _, _ = _quantize_one(w2d, st.sigma[e], cfg)
            ref = float(relative_error(w2d, w_hat, st.sigma[e]))
            got = report[f"dec.p0.b0/{name}.e{e}"]
            assert abs(got - ref) < 1e-4, (name, e)
            checked += 1
    assert checked >= plan.cfg.n_experts


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _ref_block_forward(mcfg, hp, b, p_blk, x):
    """The reference engine's own compiled block forward: Σ rebuilt from the
    raw linear inputs it records (keyed by linear name), and the block
    output."""
    records = {}
    with capture_linear_inputs(records):
        y = M._block_apply(
            mcfg, hp, b, p_blk, x, mode="train", pos_ids=jnp.arange(x.shape[1]),
        )[0]
    return {k: _sigma_from_records(v) for k, v in records.items()}, y


def test_engine_report_matches_record_based_reference():
    """ISSUE 1 acceptance: streaming+batched engine reports == a record-based
    sequential engine within 1e-4 on a reduced config.  Both engines compile
    their block forwards, so what is compared is streaming Σ and grouped
    solves against records and sequential solves, not compiled against
    op-by-op rounding."""
    plan, params, calib = _small(d_model=96, head_dim=24, d_ff=192, n_periods=2)
    cfg = PTQConfig(method="quantease", spec=GridSpec(bits=3), iterations=6)
    _, report = ptq_quantize_model(plan, params, calib, cfg)

    # Reference engine: raw records → per-layer Σ → sequential solves, with
    # the same quantized-prefix propagation structure.
    mcfg = plan.cfg
    xs = [M._embed_tokens(plan, params, b["tokens"]) for b in calib]
    ref_report = {}
    stack = params["dec"]
    for period in range(mcfg.n_periods):
        p_period = jax.tree.map(lambda a: a[period], stack)
        for i, b in enumerate(mcfg.pattern):
            scope = f"dec.p{period}.b{i}"
            sigmas = {}
            for x in xs:
                sig, _ = _ref_block_forward(mcfg, plan.heads, b, p_period[f"b{i}"], x)
                for name, s in sig.items():
                    key = f"{scope}/{name}"
                    sigmas[key] = s if key not in sigmas else sigmas[key] + s
            new_blk = dict(p_period[f"b{i}"])
            for name, w in p_period[f"b{i}"].items():
                key = f"{scope}/{name}"
                if name not in QUANTIZABLE or key not in sigmas:
                    continue
                sigma = sigmas[key]
                w2d = w.reshape(sigma.shape[0], -1).T.astype(jnp.float32)
                w_hat, _, _ = _quantize_one(w2d, sigma, cfg)
                ref_report[key] = float(relative_error(w2d, w_hat, sigma))
                new_blk[name] = w_hat.T.reshape(w.shape).astype(w.dtype)
            xs = [_ref_block_forward(mcfg, plan.heads, b, new_blk, x)[1] for x in xs]
    assert set(ref_report) == set(report)
    for key in ref_report:
        assert abs(report[key] - ref_report[key]) < 1e-4, key


def test_stream_chunking_changes_nothing():
    plan, params, calib = _small()
    cfg_whole = PTQConfig(method="gptq", spec=GridSpec(bits=4))
    cfg_chunk = PTQConfig(method="gptq", spec=GridSpec(bits=4), stream_chunk=1)
    _, rep_whole = ptq_quantize_model(plan, params, calib, cfg_whole)
    _, rep_chunk = ptq_quantize_model(plan, params, calib, cfg_chunk)
    assert set(rep_whole) == set(rep_chunk)
    for k in rep_whole:
        assert abs(rep_whole[k] - rep_chunk[k]) < 1e-5, k


def test_progress_callback_reports_every_block():
    plan, params, calib = _small()
    seen = []
    cfg = PTQConfig(method="rtn", spec=GridSpec(bits=4))
    _, report = ptq_quantize_model(
        plan, params, calib, cfg, progress_cb=seen.append
    )
    total = plan.cfg.n_periods * len(plan.cfg.pattern)
    assert len(seen) == total
    assert seen[-1]["done_blocks"] == seen[-1]["total_blocks"] == total
    assert sum(r["n_linears"] for r in seen) == len(report)


def test_sharded_gram_fallback_matches_local(rng):
    x = jnp.asarray(rng.standard_normal((64, 24)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(sharded_gram(x, None)), np.asarray(x.T @ x), rtol=1e-6
    )


@pytest.mark.skipif(jax.device_count() < 2, reason="needs ≥2 devices")
def test_sharded_engine_matches_single_device():
    mesh = make_data_mesh()
    plan, params, calib = _small()
    cfg = PTQConfig(method="quantease", spec=GridSpec(bits=4), iterations=4)
    _, rep_local = ptq_quantize_model(plan, params, calib, cfg)
    cfg_sh = PTQConfig(
        method="quantease", spec=GridSpec(bits=4), iterations=4, shard=True
    )
    _, rep_shard = ptq_quantize_model(plan, params, calib, cfg_sh, mesh=mesh)
    assert set(rep_local) == set(rep_shard)
    for k in rep_local:
        assert abs(rep_local[k] - rep_shard[k]) < 1e-4, k


def test_sharded_engine_parity_subprocess():
    """Run the 2-device parity check on forged host devices.

    Subprocess because xla_force_host_platform_device_count must be set
    before jax initializes (same pattern as test_dryrun_small)."""
    import os
    import subprocess
    import sys

    script = (
        "import os; os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=2';"
        "import sys; sys.path.insert(0,'src'); sys.path.insert(0,'.');"
        "from tests.test_solver_stream import test_sharded_engine_matches_single_device as t;"
        "t(); print('OK')"
    )
    root = os.path.dirname(os.path.dirname(__file__))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH="src"), timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_quantized_model_still_runs():
    plan, params, calib = _small()
    qp, _ = ptq_quantize_model(
        plan, params, calib,
        PTQConfig(method="quantease", spec=GridSpec(bits=4), iterations=4,
                  stream_chunk=1),
    )
    assert bool(jnp.isfinite(train_loss(plan, qp, calib[0])))


def test_quantize_launcher_loads_params_only_checkpoint(tmp_path, monkeypatch, capsys):
    """launch/quantize.py takes a params-only checkpoint (no AdamW moments)
    as well as a train checkpoint; its template is shapes, not arrays."""
    import sys

    from repro.dist import checkpoint as ckpt
    from repro.launch import compile_cache, quantize
    from repro.launch.train import reduced

    plan = make_plan(reduced(get_config("stablelm_12b")), 1)
    ckpt.save_checkpoint(
        str(tmp_path / "src"), 3, {"params": init_params(plan, jax.random.PRNGKey(0))}
    )
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [
        "quantize", "--arch", "stablelm_12b", "--reduce",
        "--ckpt-dir", str(tmp_path / "src"), "--out-dir", str(tmp_path / "out"),
        "--method", "rtn", "--calib-batches", "1", "--seq", "16",
    ])
    quantize.main()
    out = capsys.readouterr().out
    assert "loaded checkpoint step 3" in out
    assert ckpt.latest_step(str(tmp_path / "out")) == 3
    # Each block's line says what its seconds are and splits them by phase.
    assert out.count("host to recompute dispatch") == plan.cfg.n_periods
    assert "capture " in out and " compiles " in out
    recs = quantize.load_progress(str(tmp_path / "out" / "progress.jsonl"))
    assert len(recs) == plan.cfg.n_periods
    for r in recs:
        assert set(r["phase_s"]) == {"ptq.capture", "ptq.solve", "ptq.emit", "ptq.recompute"}
        assert {"seconds", "compiles", "compile_s", "cache_loads"} <= set(r)


_DRIVER_ARCHS = ["phi3_mini_3_8b", "olmoe_1b_7b", "mamba2_2_7b", "jamba_1_5_large"]


@pytest.mark.parametrize("arch", _DRIVER_ARCHS)
def test_warm_driver_compiles_and_traces_no_block_forward(arch):
    """The block forwards of both passes are compiled once per block kind and
    chunk shape: a second call compiles nothing and traces no forward, in
    every block (dense, MoE, mamba, hybrid; packed weights in the recompute)."""
    plan, params, calib = _small(arch)
    cfg = PTQConfig(method="rtn", spec=GridSpec(bits=4), emit="qt")
    ptq_quantize_model(plan, params, calib, cfg)  # warm
    seen = []
    with obs.record():
        ptq_quantize_model(plan, params, calib, cfg, progress_cb=seen.append)
    assert len(seen) == plan.cfg.n_periods * len(plan.cfg.pattern)
    assert [(r["compiles"], r["ptq.forward_traces"]) for r in seen] == [(0, 0)] * len(seen)


def _compiled_block_parity(arch):
    """The driver's compiled block forward equals the op-by-op one bit for
    bit, and its compiled Σ matches eager capture_gram_stats; run where XLA
    may not keep intermediates wider than their dtype."""
    plan, params, calib = _small(arch)
    mcfg, hp = plan.cfg, plan.heads
    xs = [M._embed_tokens(plan, params, c["tokens"]) for c in calib]
    p_period = jax.tree.map(lambda a: a[0], params["dec"])
    for i, b in enumerate(mcfg.pattern):
        p_blk = p_period[f"b{i}"]
        got = solver._capture(mcfg, hp, b, p_blk, xs, None, 0, None, "s")
        ref = {}
        with capture_gram_stats(ref), capture_scope("s"):
            outs = [
                M._block_apply(mcfg, hp, b, p_blk, x, mode="train",
                               pos_ids=jnp.arange(x.shape[1]))[0]
                for x in xs
            ]
        assert ref and set(got) == set(ref)
        for key, st in ref.items():
            scale = float(jnp.max(jnp.abs(st.sigma))) + 1e-9
            assert float(jnp.max(jnp.abs(got[key].sigma - st.sigma))) / scale < 1e-5, key
            assert got[key].n == st.n, key
        for x, y in zip(xs, outs):
            y_prog = solver._block_program(mcfg, hp, b, p_blk, x, None)
            assert y_prog.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(y_prog), np.asarray(y))
        xs = outs


@pytest.mark.parametrize("arch", ["phi3_mini_3_8b", "olmoe_1b_7b", "mamba2_2_7b"])
def test_compiled_block_forward_parity_subprocess(arch):
    """Subprocess: XLA_FLAGS must be set before jax initializes."""
    import os
    import subprocess
    import sys

    script = (
        "import sys; sys.path.insert(0,'src'); sys.path.insert(0,'.');"
        "from tests.test_solver_stream import _compiled_block_parity as t;"
        f"t({arch!r}); print('OK')"
    )
    flags = (os.environ.get("XLA_FLAGS", "") + " --xla_allow_excess_precision=false").strip()
    root = os.path.dirname(os.path.dirname(__file__))
    r = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, cwd=root,
        env=dict(os.environ, PYTHONPATH="src", XLA_FLAGS=flags), timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout

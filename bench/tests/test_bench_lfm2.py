"""LFM2 at a size a CPU test holds (d 64, 4 query / 2 kv heads, 8 experts
top-2, the published order of the first 6 layer types): the program against
the plain reference ``lib/reference_lfm2.py``, and a tiny copy of the cell
``lfm2moe.quantize``, where a sound run reads ``correct: true`` and each
fault of ``lib/faults_moe.py`` reads ``false``."""

import dataclasses
import time

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the path)
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.solver as solver
from lib import faults_moe, flops_moe, harness, quantize_moe, reference_lfm2, weights
from lib.peaks import PEAKS

TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2, num_experts=8,
            num_experts_per_tok=2, vocab_size=256)
SEED = 2**33 + 16


def tiny_cell() -> harness.Cell:
    """The cell at the tiny size.  Its 2048 calibration tokens give each
    expert some hundreds of routed copies, so its Σ (p = 64) has full rank;
    with a few tens, a near-tie rounding moves an expert's objective by
    percents and a sound run reads above the cell's limit."""
    cell = harness.resolve(harness.load_manifest(), "lfm2moe.quantize")
    cell.config = dict(cell.config, **TINY)
    cell.mix = dict(cell.mix, calib_sequences=16, calib_seq_len=128, calib_batch=8,
                    iterations=4)
    return cell


def run_tiny(seed: int = SEED, **kw):
    cell = tiny_cell()
    return quantize_moe.run(cell, seed=seed, seconds=0.5, trace=False,
                            peaks=PEAKS["TPU v5 lite"], t_start=time.perf_counter(), **kw)


@pytest.fixture(scope="module")
def tiny_model():
    """The program's tiny LFM2, its seeded weights, and the same weights as
    the reference reads them."""
    from repro.models import make_plan
    from repro.models.model import param_shapes

    cfgj = tiny_cell().config
    mcfg = quantize_moe.model_config(cfgj)
    plan = make_plan(mcfg)
    shapes = param_shapes(plan)
    params = weights.make_dense(shapes, SEED)
    ref = {"embed": params["embed"], "final_norm/scale": params["final_norm"]["scale"],
           "blocks": [weights.dense_block(shapes, SEED, 0, block=f"b{i}")
                      for i in range(mcfg.n_layers)]}
    kinds = [flops_moe.block_kind(cfgj, i) for i in range(mcfg.n_layers)]
    dims = dict(quantize_moe._ref_dims(cfgj))
    return plan, params, ref, kinds, dims


def _tokens(n, s, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, (n, s)).astype(np.int32))


def _ref_logits(tiny_model, toks, dt=jnp.float32):
    _, _, ref, kinds, dims = tiny_model
    with jax.default_matmul_precision("highest"):
        return reference_lfm2.forward(ref, toks, kinds=[k for k, _ in kinds],
                                      mlps=[m for _, m in kinds], dims=dims, dt=dt)


def test_config_is_the_published_one():
    from repro.configs import get_config

    cfg = get_config("lfm2_8b_a1b")
    attn = [i for i, b in enumerate(cfg.pattern) if b.kind == "attn"]
    assert attn == [2, 6, 10, 14, 18, 21] and cfg.n_layers == 24
    assert [b.mlp for b in cfg.pattern[:3]] == ["dense", "dense", "moe"]
    assert (cfg.hd, cfg.n_kv_heads, cfg.n_experts, cfg.top_k, cfg.moe_ff) == (64, 8, 32, 4, 1792)
    assert abs(cfg.param_count() / 1e9 - 8.34) < 0.01


def test_prefill_logits_match_reference(tiny_model):
    """The program's bf16 prefill against the reference's float32 forward.
    Limit 0.01 of the largest logit: the reading is 0.0028, the reference's
    own bf16 forward against its float32 one 0.0027 (bf16 rounding, 2^-9 a
    step through 6 blocks); routing by softmax reads 0.031, leaving out the
    qk-norm 0.14."""
    from repro.models import init_cache, prefill

    plan, params, *_ = tiny_model
    toks = _tokens(2, 24)
    ref = _ref_logits(tiny_model, toks)[:, -1]

    def rel(p):
        got, _ = prefill(p, params, {"tokens": toks}, init_cache(p, 2, 32))
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)) / jnp.max(jnp.abs(ref)))

    assert rel(plan) < 0.01
    for wrong in (dict(router_score="softmax"), dict(qk_norm=False)):
        assert rel(dataclasses.replace(plan, cfg=dataclasses.replace(plan.cfg, **wrong))) > 0.01


def test_prefill_then_decode_matches_full_forward(tiny_model):
    """Prefill of 20 tokens, then 4 contiguous decode steps through the conv
    state and the KV cache, against one full forward of the 24 tokens in bf16
    activations (reads 0.0017–0.0027 of the largest logit; limit 0.01)."""
    from repro.models import decode_step, init_cache, prefill

    plan, params, *_ = tiny_model
    toks = _tokens(2, 24, seed=1)
    full = _ref_logits(tiny_model, toks, dt=jnp.bfloat16)
    _, cache = prefill(plan, params, {"tokens": toks[:, :20]}, init_cache(plan, 2, 32))
    scale = float(jnp.max(jnp.abs(full)))
    for t in range(20, 24):
        lg, cache = decode_step(plan, params, toks[:, t:t + 1], cache, jnp.int32(t))
        rel = float(jnp.max(jnp.abs(lg.astype(jnp.float32) - full[:, t]))) / scale
        assert rel < 0.01, (t, rel)


def test_moe_output_independent_of_batch(tiny_model):
    """Dropless routing: a token's output is the same alone, in a batch, and
    in a batch where every other token chose its experts; the capacity path
    of training is not."""
    from repro.models.moe import moe_apply

    plan, params, *_ = tiny_model
    cfg = plan.cfg
    p = jax.tree.map(lambda a: a[0], params["dec"]["b3"])
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32, cfg.d_model), jnp.bfloat16)
    kw = dict(n_experts=cfg.n_experts, top_k=cfg.top_k, act="silu", gated=True,
              norm_topk=True, score="sigmoid")
    whole, _ = moe_apply(p, x, **kw)
    alone, _ = moe_apply(p, x[1:2, 5:6], **kw)
    other, _ = moe_apply(p, jnp.concatenate([x[1:2, 5:6], x[3:4] * 3], axis=1), **kw)
    np.testing.assert_array_equal(np.asarray(whole[1, 5]), np.asarray(alone[0, 0]))
    np.testing.assert_array_equal(np.asarray(whole[1, 5]), np.asarray(other[0, 0]))
    same = jnp.concatenate([x[:1, :1]] * 64, axis=1)  # 64 copies of one token: over capacity
    capped, _ = moe_apply(p, same, capacity_factor=1.25, **kw)
    dropless, _ = moe_apply(p, same, **kw)
    assert float(jnp.max(jnp.abs(capped[0, -1] - dropless[0, -1]))) > 0


def test_expert_sigma_matches_reference(tiny_model):
    """Per-expert Σ and routed counts of the program's capture pass against
    the reference's, for the attention+MoE block and a conv+MoE block.  The
    largest difference reads 0.0094 of the largest entry (the hidden Σ of
    the attention block, whose bf16 activations round differently after the
    program's attention); limit 0.02."""
    plan, params, ref, kinds, dims = tiny_model
    toks = _tokens(2, 32, seed=2)
    x = jnp.take(params["embed"], toks, axis=0).astype(jnp.bfloat16)
    for i in (2, 3):
        b = plan.cfg.pattern[i]
        p_blk = jax.tree.map(lambda a: a[0], params["dec"][f"b{i}"])
        stats = solver._capture(plan.cfg, plan.heads, b, p_blk, [x], [None], 0, None, "s")
        want, _ = reference_lfm2.capture_chunk(x, ref["blocks"][i], *kinds[i],
                                               quantize_moe._ref_dims(tiny_cell().config))
        for name, ref_key in (("w_gate", "expert_in"), ("w_down", "expert_hidden")):
            st = stats[f"s/{name}"]
            np.testing.assert_array_equal(np.asarray(st.rows), np.asarray(want["expert_rows"]))
            assert st.n == toks.size * plan.cfg.top_k
            got, exp = np.asarray(st.sigma), np.asarray(want[ref_key])
            assert np.max(np.abs(got - exp)) / np.max(np.abs(exp)) < 0.02, (i, name)
        assert "s/w_up" not in stats  # one Σ for the gate and up input


def test_ptq_quantize_pack_serve(tiny_model):
    """ptq_quantize_model → QuantizedTensor leaves → restacked → the
    contiguous engine (the paged one rejects conv state): greedy tokens
    equal the argmax of the quantized model's own full forward."""
    from repro.models import init_cache, prefill
    from repro.quant import GridSpec
    from repro.serve.engine import Request, ServingEngine
    from repro.serve.qparams import quantize_params_for_serving

    plan, params, *_ = tiny_model
    calib = [{"tokens": _tokens(2, 32, seed=3)}]
    recs = []
    qt, report = solver.ptq_quantize_model(
        plan, params, calib,
        solver.PTQConfig(method="quantease", spec=GridSpec(bits=4), iterations=3, emit="qt"),
        progress_cb=recs.append)
    assert sum(k.endswith(".e0") for k in report) == 3 * 4  # gate, up, down × 4 MoE blocks
    moe = [r for r in recs if "moe.routed_copies" in r]
    assert len(moe) == 4 and all(r["moe.dropped_copies"] == 0 for r in moe)
    served = quantize_params_for_serving(plan, params, qt["dec"])
    prompt = np.asarray(_tokens(1, 9, seed=4))[0]
    eng = ServingEngine(plan, served, max_batch=2, max_seq=32)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
    out = eng.run()[0].output
    seq = list(prompt)
    for _ in range(4):
        lg, _ = prefill(plan, served, {"tokens": jnp.asarray([seq])}, init_cache(plan, 1, 32))
        seq.append(int(jnp.argmax(lg[0])))
    assert out == seq[len(prompt):]


@pytest.mark.parametrize("fault", [None, faults_moe.capacity, faults_moe.softmax],
                         ids=["sound", "capacity", "softmax"])
def test_cell_faults(fault):
    """The bf16 CD control is read on the chip only: at this size its
    excess (0.0007–0.010 over four seeds, the program's own bf16 path
    emulated in float32) lies among the sound readings' (0.0001–0.006)."""
    if fault is None:
        run = run_tiny()
        assert harness.is_correct(run.compared), run.compared
        return
    with fault():
        run = run_tiny()
    assert not harness.is_correct(run.compared), run.compared


def test_faults_named():
    assert set(faults_moe.FAULTS) == {"capacity", "softmax"}


def test_expert_span_metrics():
    """``moe.expert_solve_s`` and ``moe.expert_emit_s`` read the host
    seconds of the ``ptq.*`` spans that carry ``experts``, per block, and
    read nothing where no span carries it (a program without the attribute)."""
    from types import SimpleNamespace

    from repro import obs

    with obs.record() as rec:
        with obs.span("ptq.solve", experts=8):
            time.sleep(0.02)
        with obs.span("ptq.solve"):
            time.sleep(0.05)
        with obs.span("ptq.emit", linear="wq"):
            pass
    info = quantize_moe.expert_span_s(rec, 2)
    run = SimpleNamespace(info=info)
    solve = harness.metric_reader("moe.expert_solve_s")(run)
    assert 0.01 <= solve < 0.025
    assert harness.metric_reader("moe.expert_emit_s")(run) is None

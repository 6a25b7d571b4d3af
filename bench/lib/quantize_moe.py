"""Driver of ``kind: quantize_moe`` mixes: QuantEase PTQ of an LFM2
configuration, block after block, on its routed-expert blocks.

Set-up makes the whole dense model on the device from the seed, draws the
calibration tokens, and quantizes the mix's ``warmup_blocks`` (one of each
block kind the window runs, so every program compiles here).  The window
then calls ``ptq_quantize_model`` once per block of ``window_blocks``,
cycling, each call a one-block model fed the calibration set through its
embedding: capture Σ (per expert from its routed copies, dropless), solve
every linear (the experts in grouped solves), emit the codes, recompute the
block's outputs.  It stops at the first block boundary after ``seconds``.
As in ``quantize.py``, the inputs are the embedded calibration tokens, so
the reference can rebuild any block's inputs from the seed alone.

Check: for one window block drawn from the seed, ``reference_lfm2``
captures every linear's Σ from the same tokens and weights (per expert,
routed dropless with sigmoid + bias) and ``reference.cd_solve`` solves
every linear, the experts' 3·E matrices included.  Compared: the largest
excess of the program's layer objective ‖(W − Ŵ)X‖² over the reference's —
for a routed matrix the sum over its E experts, Σ_e ‖(W_e − Ŵ_e)X_e‖², the
error the layer adds over all its routed copies — and
``routed_copies_missing``: how far the routed copies the program's
per-expert Σ holds fall from the N·k the router sent.  Under ``--trace 1``
``obs.record()`` is open over the window, and the host seconds of the
expert groups' ``ptq.solve`` and ``ptq.emit`` spans go to ``run.info``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from lib import flops_moe, harness, reference, reference_lfm2, traffic, weights


def model_config(config: dict):
    """The program's LFM2 configuration with every size the file states;
    the layer types are the published list cut to ``num_hidden_layers``."""
    from repro.configs import BlockDef, get_config

    if config["routed_scaling_factor"] != 1:
        raise ValueError("the program routes with a scale of 1 only")
    n = config["num_hidden_layers"]
    pattern = tuple(
        BlockDef(kind=flops_moe.block_kind(config, i)[0], mlp=flops_moe.block_kind(config, i)[1])
        for i in range(n))
    return dataclasses.replace(
        get_config(config["program_config"]),
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        moe_d_ff=config["moe_intermediate_size"],
        n_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        router_norm_topk=config["norm_topk_prob"],
        router_bias=config["use_expert_bias"],
        ssm_conv=config["conv_L_cache"],
        vocab=config["vocab_size"],
        rope_theta=float(config["rope_theta"]),
        pattern=pattern,
        n_periods=1,
    )


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool, peaks: dict,
        t_start: float, matmul_dtype: str = "float32", warmup: bool = True
        ) -> harness.RunResult:
    """One run of the cell.  ``matmul_dtype="bfloat16"`` is the program's
    own lower-precision CD path (the control); ``warmup=False`` skips the
    warm-up blocks where the process has compiled them already."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core.solver import PTQConfig, ptq_quantize_model
    from repro.models import make_plan
    from repro.models.model import param_shapes
    from repro.quant import GridSpec

    cfgj, mix, spans = cell.config, cell.mix, harness.Spans()
    mcfg = model_config(cfgj)
    shapes = param_shapes(make_plan(mcfg))

    t = time.perf_counter()
    harness.log(f"setup: process and device {t - t_start:.3f}s")
    dense = jax.block_until_ready(weights.make_dense(shapes, seed))
    harness.log(f"setup: weights {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    toks = traffic.calib_tokens(mix["calib_sequences"], mix["calib_seq_len"],
                                cfgj["vocab_size"], seed)
    harness.log(f"setup: calibration tokens {time.perf_counter() - t:.3f}s")
    bsz = mix["calib_batch"]
    calib = [{"tokens": jnp.asarray(toks[i:i + bsz])} for i in range(0, len(toks), bsz)]
    pcfg = PTQConfig(method="quantease", spec=GridSpec(bits=mix["bits"]),
                     iterations=mix["iterations"], percdamp=mix["percdamp"], emit="qt",
                     matmul_dtype=matmul_dtype)
    records = []

    def on_block(rec):
        with spans("bench.block_done"):
            records.append(rec)

    def call(i):
        """Quantize layer i as a one-block model; returns its emitted block
        and its progress record."""
        plan_i = make_plan(dataclasses.replace(mcfg, pattern=(mcfg.pattern[i],)))
        params_i = {k: v for k, v in dense.items() if k != "dec"}
        params_i["dec"] = {"b0": dense["dec"][f"b{i}"]}
        with spans("bench.ptq"):
            out, _ = ptq_quantize_model(plan_i, params_i, calib, pcfg, progress_cb=on_block)
            qt = jax.block_until_ready(out["dec"][0]["b0"])
        return qt, records[-1]

    if warmup:
        for i in mix["warmup_blocks"]:
            t = time.perf_counter()
            call(i)  # compiles the capture, the grouped solves and the recompute
            harness.log(f"setup: warm-up block {i} {time.perf_counter() - t:.3f}s")
    setup_s = time.perf_counter() - t_start
    harness.log(f"setup_s {setup_s:.3f}")

    order = mix["window_blocks"]
    # The check reads the first window block of a layer drawn from the seed
    # (the last block, were the window over before it); only that block's
    # codes stay on the device, beside the grouped solves.
    check_layer = order[int(traffic.rng_for(seed, "check").integers(len(order)))]
    done, kept = [], None  # (layer, progress record); (layer, emitted block, record)
    with harness.profiled(trace) as get_trace:
        with (obs.record() if trace else contextlib.nullcontext()) as recorder, \
                spans("bench.window"):
            t0 = time.perf_counter()
            i = 0
            while True:
                layer, qt = order[i % len(order)], None  # the last codes may go
                qt, rec = call(layer)
                done.append((layer, rec))
                if kept is None and layer == check_layer:
                    kept = (layer, qt, rec)
                t_last = time.perf_counter()
                if t_last - t0 >= seconds:
                    break
                i += 1
    n_blocks = len(done)
    block_s = (t_last - t0) / n_blocks
    harness.log(f"window: {n_blocks} blocks in {t_last - t0:.3f}s, {block_s:.4f} s/block")
    for layer, rec in done:
        harness.log(f"  block {layer}: {rec['seconds']:.3f}s host, routed "
                    f"{rec.get('moe.routed_copies')}, dropped {rec.get('moe.dropped_copies')}, "
                    f"expert tokens {rec.get('moe.expert_tokens_min')}..."
                    f"{rec.get('moe.expert_tokens_max')}, compiles {rec.get('compiles')}")
    mem_peak = harness.memory_peak_bytes(cell.chips)

    if kept is None:
        kept = (layer, qt, rec)
    layer, qt, rec = kept
    del dense, calib, kept
    t_ref = time.perf_counter()
    compared = check_block(cell, shapes, seed, layer, toks, qt, rec)
    harness.log(f"check of block {layer}: {time.perf_counter() - t_ref:.1f}s")

    layers = [order[j % len(order)] for j in range(n_blocks)]
    its = mix["iterations"]
    info = {"n_blocks": n_blocks, "block_s": block_s, "window_s": t_last - t0,
            "flops_per_block": float(np.mean([flops_moe.quantize_block_flops(
                cfgj, lay, mix["calib_sequences"], mix["calib_seq_len"], its)
                for lay in layers])),
            "cd_least_s_per_block": float(np.mean([
                flops_moe.cd_block_least_s(cfgj, lay, its, peaks) for lay in layers])),
            "cd_kernel_calls_per_block": _same([flops_moe.cd_kernel_calls(cfgj, lay, its)
                                                for lay in layers])}
    if recorder is not None:
        info.update(expert_span_s(recorder, n_blocks))
    return harness.RunResult(
        cell=cell, peaks=peaks, end_to_end={"setup_s": setup_s, "quant_block_s": block_s},
        compared=compared, attempted=n_blocks, failed=0, memory_peak_bytes=mem_peak,
        spans=spans, info=info, trace=get_trace())


def _same(values: list) -> int:
    if len(set(values)) != 1:
        raise ValueError(f"window blocks launch different CD kernel counts: {values}")
    return values[0]


def expert_span_s(recorder, n_blocks: int) -> dict:
    """Host seconds a block in the expert groups' ``ptq.solve`` and
    ``ptq.emit`` spans (those with ``experts``), or nothing where no span
    carries it."""
    out = {}
    for span, key in (("ptq.solve", "moe.expert_solve_s"), ("ptq.emit", "moe.expert_emit_s")):
        ts = [s.t1 - s.t0 for s in recorder.spans if s.name == span and "experts" in s.attrs]
        if ts:
            out[key] = sum(ts) / n_blocks
    return out


def _ref_dims(cfgj: dict) -> tuple:
    w = flops_moe.widths(cfgj)
    return tuple(sorted({"h": w["h"], "kv": w["kv"], "hd": w["hd"],
                         "theta": float(cfgj["rope_theta"]), "top_k": w["top_k"]}.items()))


def reference_sigmas(cfgj: dict, shapes: dict, seed: int, layer: int, toks, chunk: int):
    """The reference's Σ of one block over the calibration tokens, and the
    block's weights as the reference reads them."""
    import jax.numpy as jnp

    blk = weights.dense_block(shapes, seed, 0, block=f"b{layer}")
    embed = weights.top_leaf(shapes, seed, "embed")
    kind, mlp = flops_moe.block_kind(cfgj, layer)
    sig = None
    for c0 in range(0, len(toks), chunk):
        x = embed[jnp.asarray(toks[c0:c0 + chunk])]
        inc, _ = reference_lfm2.capture_chunk(x, blk, kind, mlp, _ref_dims(cfgj))
        sig = inc if sig is None else {k: sig[k] + inc[k] for k in sig}
    return sig, blk


def check_block(cell, shapes, seed, layer, toks, qt, rec) -> list:
    """Reference solve of one block; returns [(name, value, limit)]."""
    import jax
    import jax.numpy as jnp

    cfgj, mix = cell.config, cell.mix
    limits = cell.params["limits"]
    t = time.perf_counter()
    sig, blk = reference_sigmas(cfgj, shapes, seed, layer, toks, mix["calib_batch"])
    harness.log(f"  reference capture: {time.perf_counter() - t:.1f}s")
    solve = jax.vmap(lambda w, s: reference.cd_solve(
        w, s, mix["bits"], mix["iterations"], mix["percdamp"]))
    objective = jax.vmap(reference.objective)
    excess = -np.inf
    for name, (q, p, n) in flops_moe.block_linears(cfgj, layer).items():
        t = time.perf_counter()
        s = sig[reference_lfm2.SIGMA_OF[name]]
        if n == 1:  # (p, ...) → (1, q, p)
            w = blk[name].reshape(p, q).T[None].astype(jnp.float32)
            wh_prog = qt[name].dequantize()[None]
            s = s[None]
        else:  # (E, p, q) → (E, q, p)
            w = jnp.swapaxes(blk[name], 1, 2).astype(jnp.float32)
            wh_prog = jax.vmap(lambda a: a.dequantize())(qt[name])
        wh_ref = solve(w, s)[0]
        prog, ref = np.asarray(objective(w, wh_prog, s)), np.asarray(objective(w, wh_ref, s))
        ratio = float(prog.sum() / ref.sum())
        harness.log(f"  {name}: objective ratio {ratio:.8f}, per matrix {(prog / ref).min():.6f}"
                    f"..{(prog / ref).max():.6f} over {len(prog)} ({time.perf_counter() - t:.1f}s)")
        excess = max(excess, ratio - 1.0)
        del wh_ref, wh_prog
    routed = int(toks.size) * cfgj["num_experts_per_tok"]
    held = rec.get("moe.routed_copies", 0) - rec.get("moe.dropped_copies", 0)
    ref_rows = np.asarray(sig["expert_rows"])
    harness.log(f"  routed copies: program holds {held}, reference {int(ref_rows.sum())} "
                f"(expert tokens {ref_rows.min()}..{ref_rows.max()})")
    return [("objective_excess", excess, limits["objective_excess"]),
            ("routed_copies_missing", abs(held - routed), limits["routed_copies_missing"])]

"""Model zoo: per-arch smoke (reduced configs) + decode≡prefill consistency."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import (
    decode_step,
    init_cache,
    init_params,
    make_plan,
    prefill,
    train_loss,
)
from tests.conftest import reduce_cfg


def _batch(cfg, rng, B, S):
    out = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)).astype(np.int32))}
    if cfg.family == "encdec":
        out["frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_frames, cfg.d_model)), jnp.bfloat16
        )
    if cfg.n_prefix:
        out["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_prefix, cfg.d_model)), jnp.bfloat16
        )
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke(arch, rng):
    """One forward/train step on CPU: output shapes + no NaNs (+grad)."""
    cfg = reduce_cfg(get_config(arch))
    plan = make_plan(cfg, axis_n=1)
    params = init_params(plan, jax.random.PRNGKey(0))
    batch = _batch(cfg, rng, 2, 48)
    loss, grads = jax.value_and_grad(lambda p: train_loss(plan, p, batch))(params)
    assert jnp.isfinite(loss)
    gnorm = sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    assert bool(jnp.isfinite(gnorm))


@pytest.mark.parametrize(
    "arch,window",
    [
        ("stablelm_12b", None),  # GQA + rope
        ("gemma2_27b", 24),  # local/global + softcaps + post-norms + tied
        ("qwen15_32b", None),  # MHA + qkv bias
        ("mamba2_2_7b", None),  # pure SSD recurrence
        ("jamba_1_5_large", None),  # hybrid + MoE
        ("whisper_large_v3", None),  # enc-dec + cross cache + learned pos
        ("mixtral_8x22b", 24),  # MoE + SWA ring cache
        ("llava_next_34b", None),  # prefix stub
    ],
)
def test_decode_matches_prefill(arch, window, rng):
    cfg = reduce_cfg(get_config(arch))
    if window is not None:
        cfg = dataclasses.replace(
            cfg,
            pattern=tuple(
                dataclasses.replace(b, window=window if b.window else None)
                for b in cfg.pattern
            ),
        )
    plan = make_plan(cfg, axis_n=1)
    params = init_params(plan, jax.random.PRNGKey(1))
    B, S = 2, 40
    toks = rng.integers(0, cfg.vocab, (B, S + 1)).astype(np.int32)
    batch_s = _batch(cfg, np.random.default_rng(5), B, S)
    batch_s["tokens"] = jnp.asarray(toks[:, :S])
    batch_s1 = dict(batch_s, tokens=jnp.asarray(toks))

    npre = cfg.n_prefix or 0
    cache = init_cache(plan, B, 128)
    _, cache = prefill(plan, params, batch_s, cache)
    lg_dec, _ = decode_step(
        plan, params, jnp.asarray(toks[:, S : S + 1]), cache, jnp.int32(S + npre)
    )
    lg_ref, _ = prefill(plan, params, batch_s1, init_cache(plan, B, 128))
    diff = float(jnp.max(jnp.abs(lg_dec.astype(jnp.float32) - lg_ref.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(lg_ref.astype(jnp.float32)))) + 1e-9
    assert diff / scale < 0.05, f"{arch}: decode diverges from prefill ({diff})"


def test_flash_attention_matches_naive(rng):
    from repro.models.common import flash_attention

    B, S, KV, G, hd = 2, 50, 2, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, KV, G, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.float32)

    def naive(q, k, v, window=None):
        s = jnp.einsum("bskgd,btkd->bkgst", q, k) / np.sqrt(hd)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        if window is not None:
            mask &= jnp.arange(S)[:, None] - jnp.arange(S)[None, :] < window
        s = jnp.where(mask[None, None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("bkgst,btkd->bskgd", p, v)

    for window, qc, kc in [(None, 16, 16), (13, 8, 16), (None, 64, 64)]:
        out = flash_attention(q, k, v, causal=True, window=window, q_chunk=qc, kv_chunk=kc)
        expect = naive(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=3e-3)


def test_head_plan_cases():
    from repro.models.common import make_head_plan

    hp = make_head_plan(32, 8, 160, 16)  # stablelm GQA
    assert (hp.dup, hp.kv_pad, hp.g_pad, hp.h_pad) == (2, 16, 2, 32)
    hp = make_head_plan(40, 40, 128, 16)  # qwen MHA → zero-pad 48
    assert (hp.dup, hp.kv_pad, hp.g_pad) == (1, 48, 1)
    hp = make_head_plan(56, 8, 128, 16)  # llava ragged GQA
    assert (hp.dup, hp.kv_pad) == (2, 16) and hp.h_pad >= 56
    hp = make_head_plan(20, 20, 64, 16)  # whisper MHA → 32
    assert hp.kv_pad == 32 and hp.dup == 1
    hp = make_head_plan(32, 8, 128, 1)  # no mesh: untouched
    assert (hp.dup, hp.kv_pad, hp.g_pad) == (1, 8, 4)


def test_param_counts_match_targets():
    targets = {
        "stablelm_12b": 12.1, "gemma2_27b": 27.2, "qwen15_32b": 35.2,
        "phi3_mini_3_8b": 3.8, "jamba_1_5_large": 398, "olmoe_1b_7b": 6.9,
        "mixtral_8x22b": 141, "mamba2_2_7b": 2.7, "llava_next_34b": 34.4,
    }
    for arch, tgt in targets.items():
        n = get_config(arch).param_count() / 1e9
        assert abs(n - tgt) / tgt < 0.06, f"{arch}: {n:.2f}B vs {tgt}B"


def test_int8_kv_cache_decode_matches(rng):
    """§Perf H1: int8 KV cache decode tracks the bf16 path closely."""
    import dataclasses as dc

    import jax

    cfg = reduce_cfg(get_config("stablelm_12b"))
    params = init_params(make_plan(cfg, 1), jax.random.PRNGKey(1))
    B, S = 2, 40
    toks = rng.integers(0, cfg.vocab, (B, S + 1)).astype(np.int32)
    outs = {}
    for kvd in ("bf16", "int8"):
        plan = make_plan(cfg, 1, kv_cache_dtype=kvd)
        cache = init_cache(plan, B, 128)
        _, cache = prefill(plan, params, {"tokens": jnp.asarray(toks[:, :S])}, cache)
        lg, _ = decode_step(plan, params, jnp.asarray(toks[:, S:S+1]), cache, jnp.int32(S))
        outs[kvd] = lg.astype(jnp.float32)
    diff = float(jnp.max(jnp.abs(outs["bf16"] - outs["int8"])))
    scale = float(jnp.max(jnp.abs(outs["bf16"]))) + 1e-9
    assert diff / scale < 0.05


def test_moe_dispatch_groups_equivalent(rng):
    """§Perf H2: grouped dispatch changes only the (rare) drop pattern."""
    import jax

    cfg = reduce_cfg(get_config("olmoe_1b_7b"))
    params = init_params(make_plan(cfg, 1), jax.random.PRNGKey(2))
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)).astype(np.int32))}
    losses = []
    for g in (1, 4):
        plan = make_plan(cfg, 1, dispatch_groups=g)
        losses.append(float(train_loss(plan, params, batch)))
    assert abs(losses[0] - losses[1]) < 0.02  # capacity-local drops only


@pytest.mark.parametrize("arch", ["mamba2_2_7b", "jamba_1_5_large", "lfm2_8b_a1b"])
def test_padded_prefill_state_stops_at_length(arch):
    """A right-padded prefill told its length leaves the recurrent state
    (conv buffers, SSM state) the exact prefill of the real tokens leaves,
    so the contiguous engine's padded buckets serve these stacks; and the
    engine's greedy tokens equal the argmax of full forwards."""
    from repro.serve.engine import Request, ServingEngine

    cfg = reduce_cfg(get_config(arch))
    plan = make_plan(cfg, 1)
    params = init_params(plan, jax.random.PRNGKey(5))
    toks = jnp.asarray(np.random.default_rng(5).integers(0, cfg.vocab, (1, 16)).astype(np.int32))
    n = 11
    _, exact = prefill(plan, params, {"tokens": toks[:, :n]}, init_cache(plan, 1, 32))
    _, padded = prefill(plan, params, {"tokens": toks, "length": jnp.int32(n)},
                        init_cache(plan, 1, 32))
    for i, b in enumerate(cfg.pattern):
        if b.kind == "attn":
            continue
        for got, want in zip(jax.tree.leaves(padded[f"b{i}"]), jax.tree.leaves(exact[f"b{i}"])):
            np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                       rtol=1e-5, atol=1e-5)
    eng = ServingEngine(plan, params, max_batch=2, max_seq=32, prefill_pad=8)
    prompt = np.asarray(toks[0, :n])
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=3))
    seq = list(prompt)
    for _ in range(3):
        lg, _ = prefill(plan, params, {"tokens": jnp.asarray([seq])}, init_cache(plan, 1, 32))
        seq.append(int(jnp.argmax(lg[0])))
    assert eng.run()[0].output == seq[n:]

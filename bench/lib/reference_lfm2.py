"""Plain reference of LFM2-8B-A1B's blocks: the forward and one block's
calibration capture — straightforward ``jax.numpy`` with no kernels, caches
or batching, matmuls at ``Precision.HIGHEST``.  It imports nothing of the
program; the coordinate descent is ``reference.cd_solve``.

The model is the configuration as the program runs it (``configs/
lfm2_8b_a1b.json`` lists where that departs from the published model):
RMSNorm (eps 1e-6, weight ``1 + scale``), sequential residuals
``h = x + mixer(norm(x))``, ``out = h + ffn(norm(h))``.  Mixers: the gated
short convolution ``B, C, x = split3(x W_in)``, ``(C ⊙ dwconv_k(B ⊙ x))
W_out`` (causal, depthwise, no bias); or grouped-query attention with a
per-head RMSNorm on q and k before rotate-half RoPE, query head h reading
key/value head h // (heads / kv_heads).  FFN: SwiGLU, or 32 routed SwiGLU
experts: scores ``s = sigmoid(x W_r)``, the top k of ``s + b`` chosen,
weights ``s_sel / (Σ s_sel + 1e-6)`` (the published routed scale is 1),
every routed copy computed (dropless).

Weights are a flat dict per block, named as the program's leaves with their
shapes: ``ln/scale``, ``conv_in`` (d, 3d), ``conv_w`` (d, k), ``conv_out``,
``wq`` (d, KV, G, hd), ``wk``/``wv`` (d, KV, hd), ``wo`` (KV, G, hd, d),
``q_norm/scale``, ``k_norm/scale``, ``ln2/scale``, ``wg``/``wu``/``wd``,
``router`` (d, E), ``expert_bias`` (E,), ``w_gate``/``w_up`` (E, d, F),
``w_down`` (E, F, d).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def rmsnorm(x, scale, out_dtype):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(out_dtype)


def rope(x, pos, theta):
    """x: (S, heads, hd) float32; pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, q_chunk=512):
    """Causal softmax attention. q: (S, H, hd), k/v: (S, KV, hd), float32."""
    S, H, hd = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for c0 in range(0, S, q_chunk):
        qc = q[c0:c0 + q_chunk]
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI) / math.sqrt(hd)
        mask = (c0 + jnp.arange(qc.shape[0]))[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    return jnp.concatenate(outs, 0)


def route(h, router, bias, top_k):
    """h: (N, d) → (expert ids (N, k), weights (N, k)), in float32."""
    s = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32), router.astype(jnp.float32),
                                  precision=HI))
    _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / (w.sum(-1, keepdims=True) + 1e-6)


def _gram(a):
    a = a.reshape(-1, a.shape[-1]).astype(jnp.float32)
    return jnp.matmul(a.T, a, precision=HI)


def _lin(dt):
    """x @ w with the activations rounded to ``dt`` after each product."""
    def lin(a, w):
        return jnp.einsum("...i,io->...o", a.astype(dt), w.astype(dt),
                          preferred_element_type=jnp.float32, precision=HI).astype(dt)
    return lin


def block(x, blk, *, kind, mlp, dims, dt, capture=False):
    """One block over a batch of sequences x: (B, S, d).  ``dt`` is the
    activations' dtype between ops (bf16 as the model states them, or
    float32).  Returns (output, Σ) with Σ the Gram matrix of each distinct
    linear input when ``capture`` (per expert for the routed experts, with
    ``expert_rows`` the copies each received), else None."""
    B, S, d = x.shape
    lin = _lin(dt)
    sig = {}
    h = rmsnorm(x, blk["ln/scale"], dt)
    if capture:
        sig["mixer_in"] = _gram(h)
    if kind == "conv":
        bb, cc, xv = jnp.split(lin(h, blk["conv_in"]), 3, axis=-1)
        bx = (bb * xv).astype(jnp.float32)
        k = blk["conv_w"].shape[-1]
        pad = jnp.pad(bx, ((0, 0), (k - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * blk["conv_w"][:, i].astype(jnp.float32)
                   for i in range(k))
        y = (cc * conv.astype(dt)).astype(dt)
        if capture:
            sig["mixer_out"] = _gram(y)
        x = x + lin(y, blk["conv_out"])
    else:
        H, KV, hd = dims["h"], dims["kv"], dims["hd"]
        pos = jnp.arange(S)
        q = lin(h, blk["wq"].reshape(d, -1)).reshape(B, S, H, hd)
        kk = lin(h, blk["wk"].reshape(d, -1)).reshape(B, S, KV, hd)
        v = lin(h, blk["wv"].reshape(d, -1)).reshape(B, S, KV, hd)
        q = rmsnorm(q, blk["q_norm/scale"], dt)
        kk = rmsnorm(kk, blk["k_norm/scale"], dt)
        q = jax.vmap(lambda a: rope(a.astype(jnp.float32), pos, dims["theta"]))(q).astype(dt)
        kk = jax.vmap(lambda a: rope(a.astype(jnp.float32), pos, dims["theta"]))(kk).astype(dt)
        o = jax.vmap(lambda a, b, c: attention(a.astype(jnp.float32), b.astype(jnp.float32),
                                               c.astype(jnp.float32)))(q, kk, v)
        o = o.reshape(B, S, -1).astype(dt)
        if capture:
            sig["mixer_out"] = _gram(o)
        x = x + lin(o, blk["wo"].reshape(-1, d))
    h2 = rmsnorm(x, blk["ln2/scale"], dt)
    if mlp == "dense":
        hid = (jax.nn.silu(lin(h2, blk["wg"])) * lin(h2, blk["wu"])).astype(dt)
        if capture:
            sig["mlp_in"], sig["mlp_hidden"] = _gram(h2), _gram(hid)
        return x + lin(hid, blk["wd"]), (sig if capture else None)
    y, moe_sig = experts(h2.reshape(B * S, d), blk, top_k=dims["top_k"], dt=dt, capture=capture)
    sig.update(moe_sig)
    return x + y.reshape(B, S, d).astype(dt), (sig if capture else None)


def experts(h, blk, *, top_k, dt, capture):
    """The routed SwiGLU experts over tokens h: (N, d), one expert at a
    time over every token, each token's output the weighted sum of its
    copies' (none dropped).  Σ per expert from the tokens routed to it."""
    lin = _lin(dt)
    E = blk["router"].shape[-1]
    ids, w = route(h, blk["router"], blk["expert_bias"], top_k)
    weight = jnp.zeros((h.shape[0], E), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], ids].set(w)  # (N, E), 0 where not routed
    routed = jnp.zeros((h.shape[0], E), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], ids].set(1.0)

    def one(out, args):
        wg, wu, wd, we, m = args
        hid = (jax.nn.silu(lin(h, wg)) * lin(h, wu)).astype(dt)
        out = out + lin(hid, wd).astype(jnp.float32) * we[:, None]
        if not capture:
            return out, None
        hm = h.astype(jnp.float32) * m[:, None]
        return out, (jnp.matmul(hm.T, h.astype(jnp.float32), precision=HI),
                     jnp.matmul((hid.astype(jnp.float32) * m[:, None]).T,
                                hid.astype(jnp.float32), precision=HI))

    out, sigs = jax.lax.scan(
        one, jnp.zeros(h.shape, jnp.float32),
        (blk["w_gate"], blk["w_up"], blk["w_down"], weight.T, routed.T))
    if not capture:
        return out, {}
    return out, {"expert_in": sigs[0], "expert_hidden": sigs[1],
                 "expert_rows": routed.sum(0).astype(jnp.int32)}


@functools.partial(jax.jit, static_argnames=("kind", "mlp", "dims"))
def capture_chunk(x, blk, kind, mlp, dims):
    """Σ increments of one chunk of calibration sequences through one block
    in bf16 activations (the model's), and the block's output."""
    y, sig = block(x, blk, kind=kind, mlp=mlp, dims=dict(dims), dt=jnp.bfloat16,
                   capture=True)
    return sig, y


# Which captured Σ each linear of a block solves against.
SIGMA_OF = {"conv_in": "mixer_in", "wq": "mixer_in", "wk": "mixer_in", "wv": "mixer_in",
            "conv_out": "mixer_out", "wo": "mixer_out", "wg": "mlp_in", "wu": "mlp_in",
            "wd": "mlp_hidden", "w_gate": "expert_in", "w_up": "expert_in",
            "w_down": "expert_hidden"}


def forward(params, tokens, *, kinds, mlps, dims, dt=jnp.float32):
    """Logits of every position: embedding, the blocks, final RMSNorm, the
    tied head.  ``params``: {"embed", "final_norm/scale", "blocks": [flat
    block dicts]}."""
    x = params["embed"][tokens].astype(dt)
    for blk, kind, mlp in zip(params["blocks"], kinds, mlps):
        x, _ = block(x, blk, kind=kind, mlp=mlp, dims=dims, dt=dt)
    x = rmsnorm(x, params["final_norm/scale"], jnp.float32)
    return jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(jnp.float32), precision=HI)

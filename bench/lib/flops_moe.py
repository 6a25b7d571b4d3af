"""Operations and bytes of one quantize block of an LFM2 configuration, from
its shapes: a short-conv or attention mixer, then a dense SwiGLU or routed
experts.  The algorithm's counts, as in ``flops.py``: the experts at
routed-token counts (N·k copies, none dropped), the CD sweeps of every
linear at ``flops.cd_iteration``."""

from __future__ import annotations

from lib import flops


def widths(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "hd": d // h,
            "ff": cfg["intermediate_size"], "eff": cfg["moe_intermediate_size"],
            "experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
            "conv": cfg["conv_L_cache"]}


def block_kind(cfg: dict, layer: int) -> tuple[str, str]:
    """(mixer, ffn) of a layer: ("conv" | "attn", "dense" | "moe")."""
    mixer = "attn" if cfg["layer_types"][layer] == "full_attention" else "conv"
    return mixer, ("dense" if layer < cfg["num_dense_layers"] else "moe")


def block_linears(cfg: dict, layer: int) -> dict:
    """(q, p) = (out, in) of each linear of one block, and how many of it
    (the experts for a routed matrix)."""
    w = widths(cfg)
    mixer, ffn = block_kind(cfg, layer)
    d = w["d"]
    if mixer == "conv":
        out = {"conv_in": (3 * d, d, 1), "conv_out": (d, d, 1)}
    else:
        out = {"wq": (w["h"] * w["hd"], d, 1), "wk": (w["kv"] * w["hd"], d, 1),
               "wv": (w["kv"] * w["hd"], d, 1), "wo": (d, w["h"] * w["hd"], 1)}
    if ffn == "dense":
        out.update(wg=(w["ff"], d, 1), wu=(w["ff"], d, 1), wd=(d, w["ff"], 1))
    else:
        e = w["experts"]
        out.update(w_gate=(w["eff"], d, e), w_up=(w["eff"], d, e), w_down=(d, w["eff"], e))
    return out


def cd_solves(cfg: dict, layer: int) -> list:
    """(q, p, count) of the solves the program runs for one block: an
    expert's gate and up share their input, so they solve as one (2F, d)."""
    lin = block_linears(cfg, layer)
    if "w_gate" in lin:
        q, p, e = lin.pop("w_gate")
        lin["w_gate"] = (q + lin.pop("w_up")[0], p, e)
    return list(lin.values())


def cd_kernel_calls(cfg: dict, layer: int, iterations: int) -> int:
    """Fused CD kernel launches of one block: one per same-shape group and
    iteration."""
    return iterations * len({(q, p) for q, p, _ in cd_solves(cfg, layer)})


def cd_block_least_s(cfg: dict, layer: int, iterations: int, peaks: dict) -> float:
    """Least time of all CD sweeps of one block, each sweep at its own
    roofline."""
    return sum(n * iterations * flops.roofline_s(*flops.cd_iteration(q, p), peaks)[0]
               for q, p, n in cd_solves(cfg, layer))


def cd_block_flops(cfg: dict, layer: int, iterations: int) -> float:
    return sum(n * iterations * flops.cd_iteration(q, p)[0] for q, p, n in cd_solves(cfg, layer))


def quantize_block_flops(cfg: dict, layer: int, n_seqs: int, seq_len: int,
                         iterations: int) -> float:
    """FLOP one block of PTQ needs: two block forwards over the calibration
    tokens (capture, then the quantized recompute), one Σ per distinct
    linear input (per expert over its routed copies), and the CD sweeps."""
    w = widths(cfg)
    mixer, ffn = block_kind(cfg, layer)
    n = n_seqs * seq_len
    d = w["d"]
    lin = block_linears(cfg, layer)
    dense = sum(q * p for name, (q, p, e) in lin.items() if e == 1)
    fwd = 2.0 * dense * n
    sigma = 2.0 * n * d * d * 2  # mixer input, mixer output (conv gate or attention)
    if mixer == "attn":
        fwd += n_seqs * 2.0 * seq_len * seq_len * w["h"] * w["hd"]
    else:
        fwd += n * d * (2 * w["conv"] + 2)  # taps, the two gates
    if ffn == "dense":
        sigma += 2.0 * n * (d * d + w["ff"] ** 2)
    else:
        copies = n * w["top_k"]
        fwd += 2.0 * n * d * w["experts"] + 2.0 * copies * 3 * d * w["eff"]
        sigma += 2.0 * copies * (d * d + w["eff"] ** 2)
    return 2 * fwd + sigma + cd_block_flops(cfg, layer, iterations)

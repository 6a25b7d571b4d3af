"""Operations and bytes each measured unit of work needs, from its shapes.

These are the algorithm's counts, not the compiler's: a roofline share is
the least time these counts allow over the time the trace measured, so a
count set too high would read above 100%.  Sizes come from a configuration
file (``hidden_size`` and so on) and the mix.
"""

from __future__ import annotations


def widths(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = d // h
    return {"d": d, "h": h, "kv": kv, "hd": hd, "ff": cfg["intermediate_size"],
            "vocab": cfg["vocab_size"], "layers": cfg["num_hidden_layers"]}


def block_linears(cfg: dict) -> dict:
    """(q, p) = (out, in) of each linear of one block."""
    w = widths(cfg)
    return {
        "wq": (w["h"] * w["hd"], w["d"]), "wk": (w["kv"] * w["hd"], w["d"]),
        "wv": (w["kv"] * w["hd"], w["d"]), "wo": (w["d"], w["h"] * w["hd"]),
        "wg": (w["ff"], w["d"]), "wu": (w["ff"], w["d"]), "wd": (w["d"], w["ff"]),
    }


def block_params(cfg: dict) -> int:
    return sum(q * p for q, p in block_linears(cfg).values())


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time on the chip and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# --- QuantEase CD ------------------------------------------------------------

def cd_iteration(q: int, p: int) -> tuple[float, float]:
    """One coordinate-descent sweep over all p columns of a (q, p) layer.

    Column j needs Σ_k Ŵ_ik Σ̃_kj for every row: q·p multiply-adds, so a
    sweep is q·p² of them.  Bytes: the fp32 state read and written once —
    in: base, Ŵ, scale, zero, Δ; out: Ŵ, base, Δ (8·q·p·4) — plus Σ̃ (p²·4).
    """
    return 2.0 * q * p * p, 4.0 * (8 * q * p + p * p)


def cd_block_least_s(cfg: dict, iterations: int, peaks: dict) -> float:
    """Least time of all CD sweeps of one block (every linear, every
    iteration), each sweep at its own roofline."""
    t = 0.0
    for q, p in block_linears(cfg).values():
        f, b = cd_iteration(q, p)
        t += iterations * roofline_s(f, b, peaks)[0]
    return t


def quantize_block_flops(cfg: dict, n_seqs: int, seq_len: int, iterations: int) -> float:
    """FLOP one block of PTQ needs: two block forwards over the calibration
    tokens (capture, then the quantized recompute), one Σ = XᵀX per distinct
    linear input (q/k/v share one, g/u share one), and the CD sweeps."""
    w = widths(cfg)
    n = n_seqs * seq_len
    fwd = 2.0 * block_params(cfg) * n + n_seqs * 2.0 * seq_len * seq_len * w["h"] * w["hd"]
    sigma = 2.0 * n * (3 * w["d"] ** 2 + w["ff"] ** 2)
    cd = iterations * sum(cd_iteration(q, p)[0] for q, p in block_linears(cfg).values())
    return 2 * fwd + sigma + cd

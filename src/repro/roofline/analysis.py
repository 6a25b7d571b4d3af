"""Roofline analysis from compiled artifacts (no hardware required).

Three terms per (arch × shape × mesh), all in seconds **per step**:

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = Σ link-bytes per device / ICI_bw

`compiled.cost_analysis()` on the SPMD-partitioned module reports
**per-device** flops / bytes (verified empirically — see DESIGN.md §3), so
no ÷chips is applied.  Collective bytes are not in cost_analysis: we parse
the post-partitioning HLO text and sum operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, converting
to per-device *link* bytes with ring-algorithm factors over the size of the
participating group.

Hardware model: per-chip peaks keyed by ``device_kind`` (:data:`HW_BY_KIND`);
a kind missing from the table is an error, never a silent default.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

__all__ = [
    "HW", "HW_BY_KIND", "TARGET_KIND", "hw_for", "RooflineReport", "analyze_compiled", "collective_bytes",
    "WeightLayoutDecision", "choose_weight_layout", "weight_bytes",
    "paged_kv_bytes_per_token",
]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float  # bf16
    hbm_bw: float
    ici_bw: float  # per link (one direction)


# Published per-chip peaks, keyed by jax's ``device.device_kind``.
# TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s of ICI over four links).
HW_BY_KIND = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

# The chip this repo plans for where no device is attached (dry runs of the
# production mesh, serving-layout decisions made off the chip).
TARGET_KIND = "TPU v5 lite"


def hw_for(device_kind: str) -> HW:
    """Peaks of one chip of ``device_kind``; unknown kinds are an error."""
    try:
        return HW_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add them "
            "to roofline.analysis.HW_BY_KIND with their source"
        ) from None


# s4/u4 are *packed* two-per-byte in HBM (quant/pack.py, the paged int4 KV
# pages): 0.5 bytes/element, not 1 — at 1 the memory term of every packed
# layout came out 2× too high and the roofline could never prefer it.
_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        # Integer half-byte units so packed sub-byte dtypes round *up*: a
        # ragged s4 row still occupies its last half-filled byte.
        total += -(-n * int(2 * _DTYPE_BYTES[dtype]) // 2)
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].strip("{ ")
        ids = [x for x in first.split(",") if x.strip() != ""]
        return max(len(ids), 1)
    return default


def collective_bytes(hlo_text: str, n_devices: int) -> dict:
    """Per-device *link* bytes by collective kind (ring-algorithm factors).

    For a group of size g over per-device output/input bytes b:
      all-gather:        each device receives (g−1)/g · (total bytes) ≈ b_out·(g−1)/g
      reduce-scatter:    same as all-gather on input bytes
      all-reduce:        2·(g−1)/g · b (ring RS+AG)
      all-to-all:        (g−1)/g · b
      collective-permute: b
    Output-shape bytes are HLO *result* shapes, which are already global for
    AG (gathered) and per-device for RS — we account accordingly.
    """
    out = {
        "all-gather": 0.0, "all-reduce": 0.0, "reduce-scatter": 0.0,
        "all-to-all": 0.0, "collective-permute": 0.0,
    }
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        if "-done(" in line:
            continue  # avoid double-count of async pairs (count the -start)
        b = _shape_bytes(shape_str)
        g = _group_size(line, n_devices)
        if g <= 1 or b == 0:
            continue
        f = (g - 1) / g
        if kind == "all-gather":
            out[kind] += b * f  # result = gathered global shape
        elif kind == "reduce-scatter":
            out[kind] += b * (g - 1)  # result = per-device shard
        elif kind == "all-reduce":
            out[kind] += 2 * b * f  # ring RS + AG
        elif kind == "all-to-all":
            out[kind] += b * f
        else:  # collective-permute
            out[kind] += b
        counts[kind] += 1
    out["counts"] = counts
    return out


# ---------------------------------------------------------------------------
# Pack-time layout decisions (serving GEMM / paged KV)
# ---------------------------------------------------------------------------
#
# The serving GEMM (kernels/dequant_matmul.py) can consume weight codes in
# three storage layouts; decode is memory-bound (m ≈ batch tokens, tiny), so
# the pack decision is a pure roofline call: minimize the memory term,
# modelling the *effective* bandwidth of each unpack pattern.
#
#   linear-unpacked : 1 B/elem, contiguous reads            (any bits)
#   linear-packed   : 0.5 B/elem, in-kernel nibble interleave — Mosaic
#                     cannot shuffle lanes, so the kernel restores column
#                     order with a (tk × tk) 0/1 permutation on the MXU;
#                     modelled as `_INTERLEAVE_DERATE` of peak HBM bw
#                     (bits == 4)
#   tile-native     : 0.5 B/elem, codes pre-reordered so each k-tile's low
#                     nibbles are its first tk/2 columns and the high
#                     nibbles the rest — unpack is two shifts + a concat,
#                     contiguous words per tile, full bandwidth (bits == 4,
#                     p divisible by the kernel tile)

_INTERLEAVE_DERATE = 0.5  # effective-bw factor for the in-kernel interleave


def weight_bytes(q: int, p: int, *, bits: int, n_groups: int = 1,
                 packed: bool = False) -> float:
    """HBM bytes one decode step reads for a (q, p) quantized weight:
    codes (via _DTYPE_BYTES — 0.5 B/elem when packed int4) + the fp32
    scale/zero planes."""
    per_elem = _DTYPE_BYTES["u4"] if (packed and bits == 4) else _DTYPE_BYTES["u8"]
    return q * p * per_elem + q * n_groups * 8.0


def paged_kv_bytes_per_token(page_size: int, kvp: int, hd: int, n_periods: int,
                             *, kv_dtype: str, context_pages: float = 1.0) -> float:
    """Roofline-predicted KV-read bytes per decoded token: ``context_pages``
    pages × (k+v) × per-slot bytes × layers.  int8 stores 1 B/elem + an 8 B
    fp32 (k, v) scale pair per (token, head); int4 packs 2 elems/byte with
    the same scale planes."""
    elem = {"bf16": 2.0, "int8": 1.0, "int4": 0.5}[kv_dtype]
    per_slot = kvp * hd * elem + (kvp * 4.0 if kv_dtype != "bf16" else 0.0)
    return 2.0 * per_slot * page_size * n_periods * context_pages


@dataclasses.dataclass(frozen=True)
class WeightLayoutDecision:
    kind: str  # "linear" | "tile"
    packed: bool  # codes stored two-per-byte
    tile_k: Optional[int]  # prepack k-tile (kind == "tile")
    tiling: str  # "whole-groups" | "tile-in-group" | "per-channel"
    bytes_per_step: float  # weight HBM bytes per decode step (memory term)
    memory_s: float  # bytes / effective bw — the decided-on quantity
    compute_s: float  # 2·m·q·p / peak — context only, decode never trips it

    @property
    def label(self) -> str:
        if self.kind == "tile":
            return f"tile{self.tile_k}/{self.tiling}"
        return "linear-packed" if self.packed else "linear"


def choose_weight_layout(
    q: int, p: int, *, bits: int, group_size: Optional[int] = None,
    tile_k: Optional[int] = None, backend: str = "tpu", m: int = 1,
    hw: HW = HW_BY_KIND[TARGET_KIND],
) -> WeightLayoutDecision:
    """Pick the serving storage layout for one (q, p) quantized linear.

    ``tile_k`` is the Pallas kernel's snapped k-tile for this shape
    (kernels.dequant_matmul.select_tile_k) — pass None when the kernel
    cannot consume a tile-native plane for it (ragged groups, odd p, p not
    a tile multiple).  Non-TPU backends serve through the XLA reference,
    which un-prepacks; tile-native buys nothing there, so the decision
    degrades to the best linear layout.
    """
    gsz = group_size if group_size else p
    n_groups = -(-p // gsz)
    compute_s = 2.0 * m * q * p / hw.peak_flops

    def mem_s(packed, derate=1.0):
        return weight_bytes(q, p, bits=bits, n_groups=n_groups, packed=packed) / (
            hw.hbm_bw * derate
        )

    # Packed candidates lead so exact ties (the derate can cancel the byte
    # halving) resolve to the layout the artifact actually stores — serving
    # never unpacks checkpoint codes back into HBM.
    cands = []
    if bits == 4 and p % 2 == 0:
        cands.append(("linear", True, None, mem_s(True, _INTERLEAVE_DERATE)))
        if backend == "tpu" and tile_k is not None and p % tile_k == 0:
            cands.append(("tile", True, tile_k, mem_s(True)))
    cands.append(("linear", False, None, mem_s(False)))
    kind, packed, tk, memory_s = min(cands, key=lambda c: c[3])
    if kind == "tile":
        tiling = "whole-groups" if group_size and tk % gsz == 0 else (
            "tile-in-group" if group_size else "per-channel"
        )
    else:
        tiling = "per-channel" if not group_size else "whole-groups"
        tk = None
    return WeightLayoutDecision(
        kind=kind, packed=packed, tile_k=tk, tiling=tiling,
        bytes_per_step=weight_bytes(q, p, bits=bits, n_groups=n_groups, packed=packed),
        memory_s=memory_s, compute_s=compute_s,
    )


@dataclasses.dataclass
class RooflineReport:
    flops_per_device: float
    bytes_per_device: float
    collective_link_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    model_flops_ratio: float  # MODEL_FLOPS / (HLO_FLOPs × chips)
    coll_detail: dict
    memory_stats: dict

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def analyze_compiled(
    compiled,
    n_devices: int,
    model_flops: float,
    hw: HW = HW_BY_KIND[TARGET_KIND],
) -> RooflineReport:
    from repro.roofline.hlo_cost import analyze_hlo

    hlo = compiled.as_text()
    # Trip-count-aware re-derivation: XLA's cost_analysis() counts while
    # bodies once (scan-over-layers would be undercounted ~100×) — see
    # hlo_cost.py.  The raw cost_analysis numbers are kept for reference.
    cs = analyze_hlo(hlo, n_devices)
    flops = cs.flops
    byts = cs.hbm_bytes
    coll = dict(cs.collective_by_kind)
    coll["counts"] = cs.collective_counts
    coll["trip_counts"] = cs.while_trip_counts[:50]
    link_bytes = cs.collective_link_bytes
    compute_s = flops / hw.peak_flops
    memory_s = byts / hw.hbm_bw
    collective_s = link_bytes / hw.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    mem = compiled.memory_analysis()
    memory_stats = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "peak_hbm_est": mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes,
    }
    return RooflineReport(
        flops_per_device=flops,
        bytes_per_device=byts,
        collective_link_bytes=link_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        bottleneck=max(terms, key=terms.get),
        model_flops=model_flops,
        model_flops_ratio=model_flops / max(flops * n_devices, 1.0),
        coll_detail=coll,
        memory_stats=memory_stats,
    )

"""Operations and bytes from shapes, against counts made by hand."""

import bench_tiny  # noqa: F401
import pytest

from lib import flops
from lib.harness import load_json
from lib.peaks import PEAKS, peaks_for

PHI3 = load_json("configs", "phi3_mini_3_8b.json")
V5E = PEAKS["TPU v5 lite"]


def test_block_linears():
    assert flops.block_linears(PHI3)["wq"] == (3072, 3072)
    assert flops.block_linears(PHI3)["wd"] == (3072, 8192)
    # Grouped-query attention: k/v project to kv_heads · head_dim.
    gqa = dict(PHI3, num_key_value_heads=8)
    assert flops.block_linears(gqa)["wk"] == (8 * 96, 3072)
    # phi3: 4·3072² + 3·3072·8192 parameters a block.
    assert flops.block_params(PHI3) == 4 * 3072**2 + 3 * 3072 * 8192


def test_cd_iteration_at_phi3_gate():
    f, b = flops.cd_iteration(8192, 3072)
    assert f == pytest.approx(1.546e11, rel=1e-3)  # 2·8192·3072²
    assert b == pytest.approx(8 * 8192 * 3072 * 4 + 3072**2 * 4)  # ≈ 0.843 GB
    t, bound = flops.roofline_s(f, b, V5E)
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_cd_block_least_s_phi3():
    # Each sweep at its own roofline over the seven linears, 25 iterations:
    # the fp32 state (8·q·p·4 bytes) plus Σ̃ (p²·4) at 819 GB/s, or 2·q·p²
    # FLOP at 197 TFLOP/s where that is longer — only d (p = 8192) is.
    lin = [(3072, 3072)] * 4 + [(8192, 3072)] * 2 + [(3072, 8192)]
    per = [max(4.0 * (8 * q * p + p * p) / 819e9, 2.0 * q * p * p / 197e12) for q, p in lin]
    assert per[-1] == 2.0 * 3072 * 8192**2 / 197e12
    assert flops.cd_block_least_s(PHI3, 25, V5E) == pytest.approx(25 * sum(per))
    assert flops.cd_block_least_s(PHI3, 25, V5E) == pytest.approx(0.145, rel=0.01)


def test_quantize_block_flops_phi3():
    n = 128 * 2048
    params = 4 * 3072**2 + 3 * 3072 * 8192
    fwd = 2 * params * n + 128 * 2 * 2048**2 * 3072  # linears, then causal attention
    sigma = 2 * n * (3 * 3072**2 + 8192**2)  # XᵀX of the four distinct inputs
    cd = 25 * 2 * (4 * 3072 * 3072**2 + 2 * 8192 * 3072**2 + 3072 * 8192**2)
    assert flops.quantize_block_flops(PHI3, 128, 2048, 25) == pytest.approx(2 * fwd + sigma + cd)
    assert flops.quantize_block_flops(PHI3, 128, 2048, 25) == pytest.approx(2.0e14, rel=0.05)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v99")

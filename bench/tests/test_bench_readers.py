"""The per-layer readers of the quantize cell on a hand-made trace: each
reads what its definition says, and none reads a number where the run holds
nothing to read it from."""

import bench_tiny  # noqa: F401
import pytest

from lib import harness, readers
from lib.peaks import PEAKS
from lib.trace import Event, Trace

CD = "vmap_jit_quantease_fused_iteration_pallas__"


def _run(trace, n_blocks=2, calls=3):
    # Two blocks in a 10 s window; each block's CD sweeps are `calls` kernel
    # events of 1 s; other ops take 2 s in all.
    info = {"n_blocks": n_blocks, "block_s": 5.0, "window_s": 10.0,
            "flops_per_block": 1e14, "cd_least_s_per_block": 0.05,
            "cd_kernel_calls_per_block": calls}
    return harness.RunResult(cell=None, peaks=PEAKS["TPU v5 lite"], end_to_end={},
                             compared=[], attempted=n_blocks, failed=0, memory_peak_bytes=0,
                             spans=harness.Spans(), info=info, trace=trace)


def _trace():
    ops = [[Event(f"{CD}.{i}", i, i + 1) for i in range(6)]
           + [Event("fusion.9", 7, 9)]]
    return Trace(ops=ops, spans=[Event("bench.window", 0, 10)])


def test_readers_on_a_trace():
    run = _run(_trace())
    assert readers.idle_share(run) == pytest.approx(20.0)  # busy 8 of 10 s
    assert readers.cd_share(run) == pytest.approx(75.0)  # 6 of 8 busy s
    assert readers.cd_roofline(run) == pytest.approx(100.0 * 2 * 0.05 / 6.0)
    assert readers.capture_device_s(run) == pytest.approx(1.0)  # (8 − 6) / 2
    assert readers.quant_mfu(run) == pytest.approx(100.0 * 1e14 / 5.0 / 197e12)


def test_cd_roofline_silent_when_a_sweep_left_the_kernel():
    # Five kernel events for two blocks of three sweeps: one sweep ran
    # elsewhere, so the kernel's time is not the whole solve.
    tr = _trace()
    tr.ops[0].pop(0)
    assert readers.cd_roofline(_run(tr)) is None


@pytest.mark.parametrize("reader", ["idle_share", "cd_share", "cd_roofline",
                                    "capture_device_s"])
def test_trace_readers_silent_without_a_trace(reader):
    assert getattr(readers, reader)(_run(None)) is None


@pytest.mark.parametrize("name", ["quant.capture_device_s", "quant.cd_share",
                                  "quant.cd_roofline", "quant.idle_share", "quant.mfu"])
def test_metric_files_read_through_the_shared_readers(name):
    run = _run(_trace())
    assert harness.metric_reader(name)(run) == pytest.approx(
        {"quant.capture_device_s": 1.0, "quant.cd_share": 75.0,
         "quant.cd_roofline": 100.0 * 0.1 / 6.0, "quant.idle_share": 20.0,
         "quant.mfu": 100.0 * 1e14 / 5.0 / 197e12}[name])

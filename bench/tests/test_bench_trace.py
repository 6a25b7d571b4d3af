"""The trace reduction on a small hand-made trace with known busy and idle
intervals."""

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import pytest

from lib.trace import Event, Trace

CD = "vmap_jit_quantease_fused_iteration_pallas__"


def _trace():
    # One device. Window 0..10 s.  Ops: [1,3] and [2,4] overlap (busy 1..4),
    # a CD kernel at [6,7], and an op at [9,12] that runs past the window.
    ops = [[Event("fusion.1", 1, 3), Event("fusion.2", 2, 4),
            Event(f"{CD}.3", 6, 7), Event("copy.4", 9, 12)]]
    spans = [Event("bench.window", 0, 10), Event("bench.ptq", 0, 5),
             Event("bench.block_done", 4.2, 5.8), Event("bench.ptq", 5.8, 10)]
    return Trace(ops=ops, spans=spans)


def test_busy_and_window():
    tr = _trace()
    assert tr.window_s() == pytest.approx(10.0)
    assert tr.busy_s() == pytest.approx(3 + 1 + 1)  # 1..4, 6..7, 9..10


def test_kernel_time():
    tr = _trace()
    assert tr.kernel_s(("quantease",)) == pytest.approx(1.0)
    assert [e.name for e in tr.kernel_events(("quantease",))] == [f"{CD}.3"]
    assert tr.kernel_s(("dequant_matmul",)) == 0.0


def test_top_ops_and_gaps():
    tr = _trace()
    top = dict(tr.top_ops())
    assert top["fusion"] == pytest.approx(4.0)
    gaps = tr.idle_gaps()
    # Gaps: 0..1 (ptq), 4..6 (mid 5: block_done is innermost), 7..9 (ptq).
    assert [g[0] for g in gaps] == ["bench.block_done", "bench.ptq", "bench.ptq"]
    assert [g[1] for g in gaps] == pytest.approx([2.0, 2.0, 1.0])


def test_busy_is_averaged_over_devices():
    ops = [[Event("fusion.1", 0, 4)], [Event("fusion.1", 0, 2)]]
    tr = Trace(ops=ops, spans=[Event("bench.window", 0, 10)])
    assert tr.busy_s() == pytest.approx(3.0)
    assert tr.kernel_s(("fusion",)) == pytest.approx(3.0)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        Trace(ops=[[Event("fusion.1", 0, 1)]], spans=[]).window_s()

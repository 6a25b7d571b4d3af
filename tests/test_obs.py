"""Phase spans and counters (repro.obs), and the spans the PTQ driver opens."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.core.solver import PTQConfig, ptq_quantize_model
from repro.models import init_params, make_plan
from repro.quant import GridSpec
from tests.conftest import reduce_cfg


def test_spans_nest_with_parent_links():
    with obs.record() as rec:
        with obs.span("a", block="x"):
            with obs.span("b"):
                with obs.span("c", k=1):
                    pass
            with obs.span("d"):
                pass
        with obs.span("e"):
            pass
    assert [s.name for s in rec.spans] == ["a", "b", "c", "d", "e"]
    assert [s.parent for s in rec.spans] == [None, 0, 1, 0, None]
    assert rec.spans[0].attrs == {"block": "x"} and rec.spans[2].attrs == {"k": 1}
    for s in rec.spans:
        assert s.t0 <= s.t1
    a, b, c, d, _ = rec.spans
    assert a.t0 <= b.t0 <= c.t0 <= c.t1 <= b.t1 <= d.t0 <= d.t1 <= a.t1


def test_span_closes_when_its_body_raises():
    with obs.record() as rec:
        with pytest.raises(ValueError):
            with obs.span("a"):
                raise ValueError
        with obs.span("b"):
            pass
    assert not math.isnan(rec.spans[0].t1)
    assert rec.spans[1].parent is None


def test_counters_and_since():
    with obs.record() as rec:
        obs.count("x")
        obs.count("x", 2)
        snap = rec.snapshot()
        obs.count("x", 4)
        with obs.span("p"):
            pass
        with obs.span("p"):
            pass
        with obs.span("q"):
            pass
    assert rec.counters["x"] == 7
    got = rec.since(snap)
    assert set(got["phase_s"]) == {"p", "q"}
    assert got["phase_s"]["p"] == pytest.approx(
        sum(s.t1 - s.t0 for s in rec.spans if s.name == "p"))
    assert got["compiles"] == got["cache_loads"] == 0 and got["compile_s"] == 0.0


def test_compile_listener_counts_a_fresh_jit_once():
    def fresh(x):
        return x * 3 + 1

    f = jax.jit(fresh)
    x = jnp.ones(5)
    with obs.record() as rec:
        jax.block_until_ready(f(x))
        first = rec.counters.get("compiles", 0)
        jax.block_until_ready(f(x))
    assert first == 1
    assert rec.counters["compiles"] == 1
    assert rec.counters["compile_s"] > 0


def test_nothing_is_kept_without_a_recorder():
    assert obs.active() is None
    with obs.span("a"):
        obs.count("x")
    with obs.record() as rec:
        assert obs.active() is rec
        with obs.record() as inner:
            obs.count("y")
        assert obs.active() is rec
    assert obs.active() is None
    assert rec.spans == [] and rec.counters == {}
    assert inner.counters == {"y": 1}


@pytest.fixture
def launched(monkeypatch):
    """Labels of the mark programs launched, in order."""
    seen, real = [], obs.Recorder._mark

    def spy(self, label):
        seen.append(label)
        real(self, label)

    monkeypatch.setattr(obs.Recorder, "_mark", spy)
    return seen


def test_device_marks_only_when_asked(launched):
    with obs.span("t.none"):
        pass
    with obs.record() as plain:
        with obs.span("t.plain"):
            pass
    assert launched == [] and plain._mark_x is None

    with obs.record(device_marks=True) as rec:
        with obs.span("t.marked"):
            with obs.span("t.inner"):
                pass
    assert launched == ["t.marked_begin", "t.inner_begin", "t.inner_end", "t.marked_end"]
    assert int(rec._mark_x) != 0  # the programs ran
    bodies = set()
    for label in launched:
        text = obs._mark_fns[label].lower(np.int32(0)).as_text()
        module = text.splitlines()[0]
        assert f"@jit_obs_mark_{label.replace('.', '_')} " in module
        # The trace's kernel readers find the CD and decode kernels by these
        # substrings; a mark must never match them.
        assert "quantease" not in module and "dequant_matmul" not in module
        bodies.add("\n".join(text.splitlines()[1:]))
    # Programs alike but for their name would share one compiled executable,
    # and the trace would give every mark one name.
    assert len(bodies) == 4


def _tiny_ptq(method="rtn"):
    cfg = reduce_cfg(get_config("stablelm_12b"))
    plan = make_plan(cfg, 1)
    params = init_params(plan, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    calib = [{"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (2, 32)).astype(np.int32))}
             for _ in range(2)]
    return plan, params, calib, PTQConfig(method=method, spec=GridSpec(bits=4), iterations=2)


@pytest.mark.parametrize("device_marks", [False, True])
def test_ptq_driver_spans_in_phase_order(device_marks, launched):
    plan, params, calib, cfg = _tiny_ptq()
    seen = []
    with obs.record(device_marks=device_marks) as rec:
        _, report = ptq_quantize_model(plan, params, calib, cfg, progress_cb=seen.append)
    n_blocks = plan.cfg.n_periods * len(plan.cfg.pattern)
    assert len(seen) == n_blocks
    assert all(s.parent is None for s in rec.spans)
    by_block: dict = {}
    for s in rec.spans:
        by_block.setdefault(s.attrs["block"], []).append(s)
    assert list(by_block) == [f"dec.p{p}.b0" for p in range(n_blocks)]
    for scope, spans in by_block.items():
        names = [s.name for s in spans]
        assert names[0] == "ptq.capture" and names[-1] == "ptq.recompute"
        assert names.count("ptq.capture") == names.count("ptq.recompute") == 1
        # Each grouped solve is followed by the emits of its G linears.
        middle, i, solved = spans[1:-1], 0, []
        while i < len(middle):
            solve = middle[i]
            assert solve.name == "ptq.solve" and solve.attrs["method"] == "rtn"
            emits = middle[i + 1:i + 1 + solve.attrs["G"]]
            assert [e.name for e in emits] == ["ptq.emit"] * solve.attrs["G"]
            solved += [e.attrs["linear"] for e in emits]
            i += 1 + solve.attrs["G"]
        assert sorted(f"{scope}/{n}" for n in solved) == sorted(
            k for k in report if k.startswith(scope + "/"))
        assert names.count("ptq.emit") == 7  # wq wk wv wo wg wu wd
        assert {s.attrs["shape"] for s in spans if s.name == "ptq.solve"} == {
            "(64, 64)", "(32, 64)", "(128, 64)", "(64, 128)"}
    for r in seen:
        assert set(r["phase_s"]) == {"ptq.capture", "ptq.solve", "ptq.emit", "ptq.recompute"}
        assert all(v >= 0 for v in r["phase_s"].values())
        assert r["compile_s"] >= 0 and 0 <= r["cache_loads"] <= r["compiles"]
        assert sum(r["phase_s"].values()) <= r["seconds"] + 2e-3
    expected = [f"{s.name}_{edge}" for s in rec.spans for edge in ("begin", "end")]
    assert launched == (expected if device_marks else [])


def test_ptq_progress_record_without_a_recorder_is_unchanged():
    plan, params, calib, cfg = _tiny_ptq()
    seen = []
    ptq_quantize_model(plan, params, calib, cfg, progress_cb=seen.append)
    assert seen and not any({"phase_s", "compiles", "compile_s", "cache_loads"} & set(r)
                            for r in seen)


def test_ptq_progress_counts_each_compile_in_its_block():
    plan, params, calib, cfg = _tiny_ptq()
    ptq_quantize_model(plan, params, calib, cfg)  # warm
    seen = []
    with obs.record() as rec:
        ptq_quantize_model(plan, params, calib, cfg, progress_cb=seen.append)
    assert sum(r["compiles"] for r in seen) == rec.counters.get("compiles", 0)
    assert sum(r["compile_s"] for r in seen) == pytest.approx(
        rec.counters.get("compile_s", 0.0))

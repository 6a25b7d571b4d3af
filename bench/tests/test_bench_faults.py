"""``correct`` comes out false when the timed path is broken underneath, and
for the control one precision below — at a size a CPU test run holds.

Each test skips only the harness's look for a chip and drives the rest of
a run (set-up, window, check) of a tiny copy of the cell, with a fault of
``bench/lib/faults.py`` planted: a solve that returns its state unchanged,
Σ captured from half of each batch, emitted codes altered where they are
produced.  There is no exchange between chips in a one-chip cell.
"""

from unittest import mock

import bench_tiny
import jax.numpy as jnp
import pytest

import repro.core.solver as solver
from lib import faults, harness


def _control():
    """The control on the CPU: Σ rounded to bfloat16 before the solve — the
    operand the program's ``matmul_dtype="bfloat16"`` path rounds (XLA:CPU
    runs no bf16×bf16→f32 dot, so that path itself runs on the chip only)."""
    orig = solver.quantease.quantease_quantize

    def low(w, sigma, *a, **k):
        return orig(w, sigma.astype(jnp.bfloat16).astype(jnp.float32), *a, **k)

    return mock.patch.object(solver.quantease, "quantease_quantize", low)


@pytest.mark.parametrize("fault", [None, faults.unchanged, faults.half_batch, faults.altered,
                                   _control],
                         ids=["sound", "unchanged", "half_batch", "altered", "control"])
def test_quantize_faults(fault):
    if fault is None:
        assert harness.is_correct(bench_tiny.run_tiny("phi3.quantize").compared)
        return
    with fault():
        run = bench_tiny.run_tiny("phi3.quantize")
    assert not harness.is_correct(run.compared), run.compared


def test_planted_names_every_fault():
    assert set(faults.FAULTS) == {"unchanged", "half_batch", "altered"}
    with faults.planted(None):
        pass
    with pytest.raises(KeyError):
        faults.planted("no_such_fault")

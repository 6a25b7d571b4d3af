"""Faults planted in the timed path underneath a run, to see that ``correct``
comes out false: the CPU tests drive them at a tiny size, and
``bench/calibrate.py --fault`` reads them at a cell's own size.

* ``unchanged``: the CD solve returns its state (W) unchanged;
* ``half_batch``: Σ is captured from the first half of each calibration
  batch only;
* ``altered``: every seventh emitted 4-bit code c becomes 15 − c, where it
  is produced.

A one-chip cell has no exchange between chips to leave out.
"""

from __future__ import annotations

import contextlib
from unittest import mock


def unchanged():
    import repro.core.solver as solver

    return mock.patch.object(solver.quantease, "quantease_quantize",
                             lambda w, *a, **k: (w, None))


def half_batch():
    import repro.core.solver as solver

    return mock.patch.object(solver, "_capture_chunks",
                             lambda x, chunk: [x[: max(1, x.shape[0] // 2)]])


def altered():
    import jax.numpy as jnp

    import repro.core.solver as solver

    orig = solver.quantize_codes

    def alter(w, grid):
        c = orig(w, grid)
        flip = (jnp.arange(c.size).reshape(c.shape) % 7) == 0
        return jnp.where(flip, 15 - c, c).astype(c.dtype)

    return mock.patch.object(solver, "quantize_codes", alter)


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "altered": altered}


def planted(name: str | None):
    """The named fault as a context manager; none for ``None``."""
    return FAULTS[name]() if name else contextlib.nullcontext()

"""Faults planted in the routed-expert path underneath a run of a
``quantize_moe`` cell, to see that ``correct`` comes out false: the CPU tests
drive them at a tiny size, and ``bench/calibrate_moe.py --fault`` reads
them at the cell's own size.

* ``capacity``: the experts run at the capacity factor 1.25, so copies over
  an expert's capacity are dropped from its Σ;
* ``softmax``: the router scores by softmax and ignores the selection bias.

The third reading, the bf16 CD control, is ``calibrate.py``'s own control
(``--control-seeds``).  Each fault patches the MoE layer inside the
compiled block forwards, so it clears JAX's caches on the way in and out.
"""

from __future__ import annotations

import contextlib
from unittest import mock


@contextlib.contextmanager
def _in_moe_layer(**forced):
    import jax

    import repro.models.model as M

    orig = M.moe_apply

    def patched(p, x, **kw):
        if forced.get("score") == "softmax":
            p = {k: v for k, v in p.items() if k != "expert_bias"}
        return orig(p, x, **dict(kw, **forced))

    jax.clear_caches()
    try:
        with mock.patch.object(M, "moe_apply", patched):
            yield
    finally:
        jax.clear_caches()


def capacity():
    from repro.models.moe import MOE_TRAIN_CAPACITY

    return _in_moe_layer(capacity_factor=MOE_TRAIN_CAPACITY)


def softmax():
    return _in_moe_layer(score="softmax")


FAULTS = {"capacity": capacity, "softmax": softmax}

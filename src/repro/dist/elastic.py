"""Elastic execution: retry-from-checkpoint loop + degraded-capacity meshes.

``RetryingRunner`` rolls any *recoverable* exception inside a step back to
the last checkpoint via ``restore_fn`` and keeps going, up to a total
retry budget, sleeping a jittered exponential backoff between recoveries
(thundering-herd hygiene for multi-host restarts; the jitter stream is
seeded so tests replay the exact delays).  Exceptions classified as
**permanent** — :class:`repro.faults.PermanentFault` always, plus any
caller-supplied types — are re-raised immediately: retrying an
unrecoverable error only burns the budget that a later transient will
need.  Determinism comes from the caller's exact-step data replay
(``data_step`` in the checkpoint meta), not from anything here — see
trainer tests for the contract.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.faults import PermanentFault

__all__ = ["RetryingRunner", "elastic_mesh"]


class RetryingRunner:
    """Run ``step_fn(state, step)`` for a span of steps with crash recovery.

    ``restore_fn() -> (state, step)`` must rebuild state from the latest
    checkpoint and report the step to resume at.  ``fault_hook(step)`` is a
    test seam: it runs before each step and may raise to simulate a failure.

    Retry policy: up to ``max_retries`` total recoveries across the run
    (a *budget*, not per-step), with delay
    ``min(backoff_max_s, backoff_base_s · backoff_mult^k)`` before the
    k-th recovery, multiplied by a seeded uniform jitter in
    ``[1−jitter, 1+jitter]``.  ``sleep_fn`` is injectable (tests pass a
    recorder); ``self.delays`` keeps the slept values for audit.
    ``permanent`` lists extra exception types that must never be retried.
    """

    def __init__(
        self,
        step_fn: Callable,
        restore_fn: Callable,
        fault_hook: Optional[Callable] = None,
        max_retries: int = 3,
        *,
        backoff_base_s: float = 0.01,
        backoff_mult: float = 2.0,
        backoff_max_s: float = 2.0,
        jitter: float = 0.5,
        permanent: tuple = (),
        sleep_fn: Callable[[float], None] = time.sleep,
        seed: int = 0,
    ):
        self.step_fn = step_fn
        self.restore_fn = restore_fn
        self.fault_hook = fault_hook
        self.max_retries = max_retries
        self.backoff_base_s = backoff_base_s
        self.backoff_mult = backoff_mult
        self.backoff_max_s = backoff_max_s
        self.jitter = jitter
        self.permanent = tuple(permanent) + (PermanentFault,)
        self.sleep_fn = sleep_fn
        self.recoveries = 0
        self.delays: list[float] = []
        self._rng = np.random.default_rng(seed)

    def _backoff(self) -> float:
        delay = min(
            self.backoff_max_s,
            self.backoff_base_s * self.backoff_mult ** self.recoveries,
        )
        if self.jitter:
            delay *= 1.0 + self.jitter * (2.0 * float(self._rng.random()) - 1.0)
        return delay

    def run(self, state, start: int, n_steps: int):
        step, end = start, start + n_steps
        while step < end:
            try:
                if self.fault_hook is not None:
                    self.fault_hook(step)
                state = self.step_fn(state, step)
                step += 1
            except self.permanent:
                raise
            except Exception:
                if self.recoveries >= self.max_retries:
                    raise
                delay = self._backoff()
                self.delays.append(delay)
                self.sleep_fn(delay)
                self.recoveries += 1
                state, step = self.restore_fn()
        return state, step


def elastic_mesh(model_axis: int = 1, devices=None):
    """Largest ("data", "model") mesh the *currently alive* devices support.

    On a restart after losing hosts, the surviving device count may no
    longer fill the original mesh; this trims the data axis to the largest
    multiple of ``model_axis`` that fits (dropping remainder devices) so
    training resumes at degraded capacity instead of wedging.
    """
    devs = list(devices if devices is not None else jax.devices())
    if model_axis <= 0 or len(devs) < model_axis:
        raise ValueError(
            f"{len(devs)} device(s) cannot host model_axis={model_axis}"
        )
    data = len(devs) // model_axis
    keep = devs[: data * model_axis]
    return jax.make_mesh(
        (data, model_axis), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2, devices=keep,
    )

"""A tiny copy of a cell for CPU tests: the real manifest entry with its
sizes cut to a few hundred units, so a run takes seconds."""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import harness  # noqa: E402
from lib.peaks import PEAKS  # noqa: E402

TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=4, num_hidden_layers=2, vocab_size=256)


def tiny_cell(workload: str) -> harness.Cell:
    cell = harness.resolve(harness.load_manifest(), workload)
    cell.config = dict(cell.config, **TINY)
    cell.mix = dict(cell.mix, calib_sequences=4, calib_seq_len=64, calib_batch=2,
                    iterations=4)
    return cell


def run_tiny(workload: str, seed: int = 2**33 + 7, seconds: float = 0.5, **kw):
    import time

    cell = tiny_cell(workload)
    drv = harness.driver(cell.mix["kind"])
    return drv.run(cell, seed=seed, seconds=seconds, trace=False,
                   peaks=PEAKS["TPU v5 lite"], t_start=time.perf_counter(), **kw)

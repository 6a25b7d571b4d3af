"""What every cell shares: the manifest and the files it names, host spans,
the device check, the trace around the window, and the result line."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: {[e['name'] for e in entries]}")


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    name = name.replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload with everything the manifest and its files say."""

    name: str
    chips: int
    config: dict  # bench/configs/<config>.json
    mix: dict  # bench/mixes/<traffic>.json
    params: dict  # bench/cells/<workload>.json
    end_to_end: list  # metric entries this cell reports
    per_layer: list


def resolve(manifest: dict, workload: str) -> Cell:
    wl = find(manifest["workloads"], workload, "workload")
    cfg_entry = find(manifest["configs"], wl["config"], "config")
    config = load_json(os.path.relpath(os.path.join(ROOT, cfg_entry["file"]), BENCH_DIR))
    mix = load_json("mixes", f"{wl['traffic']}.json")
    cell_file = os.path.join(BENCH_DIR, "cells", f"{workload}.json")
    params = load_json("cells", f"{workload}.json") if os.path.exists(cell_file) else {}

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(
        name=workload, chips=int(wl["chips"]), config=config, mix=mix, params=params,
        end_to_end=[m for m in manifest["end_to_end"] if mine(m)],
        per_layer=[m for m in manifest["per_layer"] if mine(m)],
    )


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"), f"metric_{name}").read


def driver(kind: str):
    """The module ``bench/lib/<kind>.py`` that runs mixes of that kind."""
    return importlib.import_module(f"lib.{kind}")


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform!r} devices only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Spans:
    """Host spans around the harness's own calls into the program: kept in
    memory (for per-layer readers) and written into the profiler's trace as
    TraceAnnotations (to name the device's idle gaps)."""

    def __init__(self):
        self.records: list = []  # (name, t0, t1) perf_counter seconds

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1 in self.records if n == name]


@contextlib.contextmanager
def profiled(enabled: bool):
    """The profiler around the measured window when ``enabled``; yields a
    callable that returns the reduced trace once the block has closed."""
    if not enabled:
        yield lambda: None
        return
    import jax

    from lib import trace as tr

    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    holder = {}
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield lambda: holder.get("trace")
        finally:
            jax.profiler.stop_trace()
            holder["trace"] = tr.load(tr.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def is_correct(compared: list) -> bool:
    """A run is correct when it compared something and every number
    compared is within its limit (a NaN is not)."""
    return bool(compared) and all(value <= limit for _, value, limit in compared)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def result_line(correct: bool, attempted: int, failed: int, metrics: dict, device: dict,
                compared: list, breakdown=None) -> str:
    """The last line of standard output.  ``compared``: [(name, value,
    limit)], also printed as the last lines of standard error."""
    for name, value, limit in compared:
        log(f"compare {name} = {value!r} (limit {limit!r})")
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in compared}
    return json.dumps(out)


@dataclasses.dataclass
class RunResult:
    """What a driver hands back: the end-to-end numbers, the compared
    numbers with their limits, and what per-layer readers read."""

    cell: Cell
    peaks: dict
    end_to_end: dict
    compared: list  # [(name, value, limit)]
    attempted: int
    failed: int
    memory_peak_bytes: int
    spans: Spans
    info: dict  # driver-specific counts and sizes for the readers
    trace: object = None  # lib.trace.Trace of the window, with --trace 1


def model_config(config: dict):
    """The program's registered configuration with every size the file
    states; the file is the configuration as it is run."""
    import dataclasses as dc

    from repro.configs import get_config

    base = get_config(config["program_config"])
    return dc.replace(
        base,
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        d_ff=config["intermediate_size"],
        vocab=config["vocab_size"],
        n_periods=config["num_hidden_layers"],
        rope_theta=float(config["rope_theta"]),
    )

"""The harness finds a configuration, a mix and a metric by name; a new
one is files plus manifest entries; the result line's schema; no chip, no
result."""

import json
import os
import shutil
import subprocess
import sys

import bench_tiny
import pytest

from lib import harness


def test_every_manifest_name_resolves():
    m = harness.load_manifest()
    for wl in m["workloads"]:
        cell = harness.resolve(m, wl["name"])
        assert cell.config["name"] == wl["config"]
        assert cell.end_to_end and cell.per_layer
        assert any(e["name"] == "setup_s" for e in cell.end_to_end)
        for metric in cell.per_layer:
            assert callable(harness.metric_reader(metric["name"]))
        assert harness.driver(cell.mix["kind"]).run


def test_new_cell_is_files_and_entries(tmp_path):
    """A configuration, a mix, a cell and a metric added as new files and
    manifest entries, with no existing file edited, are found by name."""
    root = tmp_path / "repo"
    shutil.copytree(bench_tiny.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = harness.load_manifest()
    (root / "bench" / "configs" / "toy_cfg.json").write_text(json.dumps(
        dict(harness.load_json("configs", "phi3_mini_3_8b.json"), name="toy_cfg")))
    (root / "bench" / "mixes" / "toy_mix.json").write_text(json.dumps(
        dict(harness.load_json("mixes", "quantize.json"), calib_batch=4)))
    (root / "bench" / "cells" / "toy_cfg.toy_mix.json").write_text(json.dumps(
        {"limits": {"objective_excess": 0.5}}))
    (root / "bench" / "metrics" / "toy.batch.py").write_text(
        "def read(run):\n    return run.info['calib_batch']\n")
    m["configs"].append({"name": "toy_cfg", "source": "x", "file": "bench/configs/toy_cfg.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "toy_cfg.toy_mix", "config": "toy_cfg",
                           "traffic": "toy_mix", "chips": 1, "why": "x"})
    m["per_layer"].append({"name": "toy.batch", "unit": "seqs", "better": "higher",
                           "source": "program_counter", "layer": "x", "moves": "quant_block_s",
                           "workloads": ["toy_cfg.toy_mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    probe = (
        "import sys; sys.path.insert(0, sys.argv[1]); from lib import harness\n"
        "m = harness.load_manifest()\n"
        "c = harness.resolve(m, 'toy_cfg.toy_mix')\n"
        "r = harness.metric_reader('toy.batch')(type('R', (), {'info': {'calib_batch': c.mix['calib_batch']}}))\n"
        "print(c.config['name'], c.mix['calib_batch'], c.params['limits']['objective_excess'], "
        "[x['name'] for x in c.per_layer], r)\n")
    out = subprocess.run([sys.executable, "-c", probe, str(root / "bench")],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["toy_cfg", "4", "0.5", "['toy.batch']", "4"]


def test_result_line_schema():
    line = harness.result_line(
        True, 10, 0, {"setup_s": {"value": 1.5, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 7},
        [("objective_excess", 0.001, 0.004)], breakdown={"device_ops": [], "idle_gaps": []})
    d = json.loads(line)
    assert list(d)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(d)[-1] == "compared"  # the numbers compared come last
    assert d["compared"] == {"objective_excess": {"value": 0.001, "limit": 0.004}}
    assert d["metrics"]["setup_s"] == {"value": 1.5, "unit": "s"}


@pytest.mark.parametrize("compared, correct", [
    ([], False),
    ([("a", 0.001, 0.004)], True),
    ([("a", 0.004, 0.004)], True),
    ([("a", 0.001, 0.004), ("b", 0.02, 0.01)], False),
    ([("a", float("nan"), 0.004)], False),
], ids=["nothing", "under", "at_limit", "one_over", "nan"])
def test_is_correct(compared, correct):
    assert harness.is_correct(compared) is correct


def test_unknown_workload_names_the_known_ones():
    with pytest.raises(KeyError, match="phi3.quantize"):
        harness.resolve(harness.load_manifest(), "no_such_cell")


@pytest.mark.parametrize("workload", ["phi3.quantize"])
def test_no_tpu_no_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.BENCH, "run.py"), "--workload", workload,
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=bench_tiny.ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr

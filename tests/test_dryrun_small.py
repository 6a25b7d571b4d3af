"""Reduced-mesh dry-run smoke: lower+compile on forged host devices.

Runs in a SUBPROCESS because xla_force_host_platform_device_count must be
set before jax initializes (the main pytest process keeps 1 device).
"""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import dataclasses, json, sys
sys.path.insert(0, "src"); sys.path.insert(0, "tests")
import jax, jax.numpy as jnp
from conftest import reduce_cfg
from repro.configs import get_config
import repro.configs.base as base
from repro.launch.specs import build_cell, CELLS
import repro.launch.specs as specs

mesh = jax.make_mesh((4, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
CELLS["tiny_train"] = dict(seq=64, batch=16, kind="train")
CELLS["tiny_decode"] = dict(seq=64, batch=16, kind="decode")
out = {}
for arch in json.loads(sys.argv[1]):
    cfg = reduce_cfg(get_config(arch), n_kv_heads=min(get_config(arch).n_kv_heads, 2), vocab=256)
    base._REGISTRY[cfg.name] = cfg  # reduced config under the same name
    for shape in ("tiny_train", "tiny_decode"):
        if arch == "whisper_large_v3" and shape == "tiny_decode":
            pass
        spec = build_cell(arch, shape, mesh)
        lowered = jax.jit(spec.fn, donate_argnums=spec.donate).lower(*spec.args)
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        out[f"{arch}/{shape}"] = m.temp_size_in_bytes
print(json.dumps(out))
"""


@pytest.mark.parametrize(
    "archs",
    [["stablelm_12b", "mamba2_2_7b"], ["olmoe_1b_7b", "whisper_large_v3"]],
)
def test_reduced_mesh_dryrun(archs):
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", _SCRIPT, json.dumps(archs)],
        capture_output=True, text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=env, timeout=560,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out) == 2 * len(archs)


_MOE_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re, sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.dist.sharding import axis_rules, make_rules
from repro.models.moe import moe_apply

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
D, F = 64, 128
out = {}
for E in (4, 3):  # 4 experts shard over "model" (EP), 3 shard their ffn (TP)
    ks = jax.random.split(jax.random.PRNGKey(E), 5)
    p = {"router": jax.random.normal(ks[0], (D, E)) * 0.3,
         "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.1,
         "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.1,
         "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.1}
    x = jax.random.normal(ks[4], (4, 16, D))
    kw = dict(n_experts=E, top_k=2, act="silu", gated=True, norm_topk=True)
    ref = jax.jit(lambda p, x: moe_apply(p, x, **kw)[0])(p, x)
    rules = make_rules(mesh, n_experts=E, moe_ff=F)

    def on_mesh(p, x):
        with axis_rules(rules):
            return moe_apply(p, x, dispatch_groups=2, **kw)[0]

    c = jax.jit(on_mesh).lower(p, x).compile()
    gathered = [int(n) for m in re.finditer(r"= f32\[([0-9,]*)\][^=]*all-gather", c.as_text())
                for n in [eval("*".join(m.group(1).split(",")) or "1")]]
    out[str(E)] = dict(diff=float(jnp.max(jnp.abs(c(p, x) - ref))),
                       sharded=[rules.table["experts"], rules.table["expert_ffn"]],
                       largest_gather=max(gathered, default=0))
print(json.dumps(out))
"""


def test_dropless_moe_on_mesh_matches_one_device():
    """Dropless MoE under a data×model mesh (dispatch groups on "data",
    experts or their ffn on "model") gives the one-device result, and no
    expert weight is all-gathered."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run(
        [sys.executable, "-c", _MOE_MESH_SCRIPT],
        capture_output=True, text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
        env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["4"]["sharded"] == ["model", None] and out["3"]["sharded"] == [None, "model"]
    for v in out.values():
        assert v["diff"] < 1e-5, out
        assert v["largest_gather"] < 64 * 128, out  # smaller than one expert's matrix

"""Plan autotuner: candidate search over a tiny dry-run (subprocess)."""
import json
import subprocess
import sys

from repro.core.autotune import default_candidates
from repro.configs import get_config


def test_candidate_sets():
    dense = default_candidates(get_config("qwen3-1.7b"))
    moe = default_candidates(get_config("mixtral-8x7b"))
    assert {c.name for c in dense} == {
        "planner-default", "force-spatial", "force-temporal", "split-qkv"
    }
    assert "moe-sort-dispatch" in {c.name for c in moe}


_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax
import repro.launch.mesh as mesh_mod
mesh_mod.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
    (2, 4), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
from repro.configs import TRAIN_4K, get_config
from repro.core.autotune import autotune
import repro.core.autotune as at
shape = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=8)
best, scored = autotune("qwen3-1.7b-reduced", shape)
print(json.dumps({
    "best": best.name if best else None,
    "n_ok": sum(1 for c in scored if c.step_s is not None),
    "steps": {c.name: c.step_s for c in scored if c.step_s is not None},
}))
"""


def test_autotune_small_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _SNIPPET],
        capture_output=True, text=True, timeout=560,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}, cwd=".",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["best"] is not None
    assert out["n_ok"] >= 3  # all dense candidates should compile
    assert out["steps"][out["best"]] == min(out["steps"].values())

"""End-to-end behaviour: training descends, serving generates, the
multi-device dry-run machinery works (subprocess: tests keep 1 device)."""
import json
import subprocess
import sys

import numpy as np


def test_training_loss_descends(tmp_path):
    from repro.launch.train import run

    losses, _ = run(
        "smollm-135m-reduced", steps=40, batch=4, seq=64, lr=1e-3, log_every=0
    )
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.05, f"loss did not descend: {first:.3f} -> {last:.3f}"


def test_gradient_compression_still_descends():
    from repro.launch.train import run

    losses, _ = run(
        "smollm-135m-reduced", steps=30, batch=4, seq=64, lr=1e-3,
        compression="int8", log_every=0,
    )
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_greedy_generation_runs(key):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.plan import derive_plan
    from repro.models import init_params
    from repro.serve.engine import greedy_generate

    cfg = get_config("qwen3-1.7b").reduced()
    plan = derive_plan(cfg, {"data": 1, "model": 1}, batch=2, seq_len=8, training=False)
    params = init_params(key, cfg, plan, dtype=jnp.float32)
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab_size)}
    out = greedy_generate(params, cfg, plan, batch, n_steps=4, cache_len=16)
    assert out.shape == (2, 4)
    assert int(out.max()) < cfg.vocab_size


_DRYRUN_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro.configs import get_config, TRAIN_4K
import repro.configs.shapes as shapes
import dataclasses
from repro.launch.dryrun import build_cell
from repro.core.hlo_cost import analyze_hlo

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("qwen3-1.7b").reduced()
shape = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=8)
fn, args, plan = build_cell(cfg, shape, mesh)
compiled = fn.lower(*args).compile()
hc = analyze_hlo(compiled.as_text())
print(json.dumps({
    "flops": hc.flops,
    "n_coll": len(hc.collectives),
    "coll_bytes": hc.collective_operand_bytes,
}))
"""


def test_sharded_dryrun_subprocess():
    """8 fake devices in a child process: lower+compile+cost must succeed and
    produce collectives (the distribution config is coherent)."""
    r = subprocess.run(
        [sys.executable, "-c", _DRYRUN_SNIPPET],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=".",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["flops"] > 0
    assert out["n_coll"] > 0  # sharded training must communicate


_DRYRUN_CELL_SNIPPET = """
import json, sys
from repro.configs import ALL_SHAPES
from repro.launch.dryrun import run_cell

shape = next(s for s in ALL_SHAPES if s.name == "train_4k")
r = run_cell("smollm-135m", shape, multi_pod=False, force=True, out_dir=sys.argv[1])
print(json.dumps({k: r.get(k) for k in ("status", "mesh", "error")}))
"""


def test_dryrun_cell_on_production_mesh_subprocess(tmp_path):
    """The dry-run CLI's cell path on its 256-chip single-pod mesh: the
    smollm-135m train_4k cell compiles and its record is written."""
    r = subprocess.run(
        [sys.executable, "-c", _DRYRUN_CELL_SNIPPET, str(tmp_path)],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=".",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out == {"status": "ok", "mesh": "single", "error": None}
    rec = json.loads((tmp_path / "single" / "smollm-135m__train_4k.json").read_text())
    assert rec["status"] == "ok"

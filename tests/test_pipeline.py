"""Pipeline parallelism over the pod axis: correctness vs sequential
execution on a multi-device mesh (subprocess: tests keep 1 device)."""
import json
import subprocess
import sys

from repro.dist.pipeline import bubble_fraction


def test_bubble_fraction():
    assert bubble_fraction(1, 2) == 0.5
    assert abs(bubble_fraction(16, 2) - 1 / 17) < 1e-9
    assert bubble_fraction(8, 1) == 0.0


_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from repro.dist.pipeline import pipeline_forward

mesh = jax.make_mesh((4,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
D = 16
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (4, D, D), jnp.float32) * 0.3  # one layer per stage
micro = jax.random.normal(jax.random.fold_in(key, 1), (6, 2, D), jnp.float32)

def stage_fn(wi, x):
    return jnp.tanh(x @ wi[0])  # wi: this stage's (1, D, D) leading-dim slice

pp = jax.jit(pipeline_forward(stage_fn, mesh, axis="pod"))
got = pp(w, micro)

# sequential oracle
x = micro
for i in range(4):
    x = jnp.tanh(x @ w[i])
err = float(jnp.max(jnp.abs(got - x)))
n_perm = jax.jit(pipeline_forward(stage_fn, mesh)).lower(w, micro).compile().as_text().count("collective-permute")
print(json.dumps({"err": err, "n_perm": n_perm}))
"""


def test_pipeline_matches_sequential_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _SNIPPET],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}, cwd=".",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["err"] < 1e-5, f"pipeline output diverges: {out}"
    assert out["n_perm"] >= 1  # the stage handoff is a collective-permute

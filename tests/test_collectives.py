"""Manual-collective primitives: ring-overlap matmul and compressed psum
(subprocess with 4 fake devices; main test process keeps 1 device)."""
import json
import subprocess
import sys


_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.dist.collectives import overlap_all_gather_matmul, compressed_psum
from jax import shard_map

mesh = jax.make_mesh((4,), ("model",), axis_types=(jax.sharding.AxisType.Auto,))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (8, 64), jnp.float32)
w = jax.random.normal(jax.random.fold_in(key, 1), (64, 32), jnp.float32) * 0.1

got = jax.jit(lambda x, w: overlap_all_gather_matmul(mesh, x, w))(x, w)
want = x @ w
err = float(jnp.max(jnp.abs(got - want)))

# the overlap schedule uses collective-permute, not all-gather
hlo = jax.jit(lambda x, w: overlap_all_gather_matmul(mesh, x, w)).lower(x, w).compile().as_text()
n_perm = hlo.count("collective-permute")
n_ag = sum(1 for l in hlo.splitlines() if " all-gather(" in l)

# compressed psum: sums per-device grads within int8 tolerance
g = jax.random.normal(jax.random.fold_in(key, 2), (4, 16), jnp.float32)
f = shard_map(lambda gi: compressed_psum(gi[0], "model", "int8"),
              mesh=mesh, in_specs=P("model", None), out_specs=P())
got_sum = f(g)
want_sum = g.sum(0)
rel = float(jnp.max(jnp.abs(got_sum - want_sum)) / jnp.max(jnp.abs(want_sum)))
print(json.dumps({"err": err, "n_perm": n_perm, "n_ag": n_ag, "psum_rel": rel}))
"""


def test_ring_matmul_and_compressed_psum_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _SNIPPET],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}, cwd=".",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["err"] < 1e-4, out
    assert out["n_perm"] >= 1 and out["n_ag"] == 0, (
        "overlap schedule should replace all-gather with collective-permute",
        out,
    )
    assert out["psum_rel"] < 0.06, out

"""pod_role="pipeline" end-to-end: a plan trains through launch/train.py on
a 4-device fake mesh (2 stages x dp 2) with loss matching the data-parallel
baseline (subprocess: the main test process keeps 1 device)."""
import json
import subprocess
import sys

import jax.tree_util as jtu
import pytest

from repro.configs import get_config
from repro.core.plan import derive_plan
from repro.dist.sharding import Shardings
from repro.models.transformer import check_pipeline_supported


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


class Leaf:
    def __init__(self, shape):
        self.shape = shape


PIPE_MESH = {"pod": 2, "data": 2, "model": 1}


def _pipe_plan(arch="smollm-135m-reduced", batch=8, **kw):
    cfg = get_config(arch)
    return cfg, derive_plan(
        cfg, PIPE_MESH, batch=batch, seq_len=32, training=True,
        pod_role="pipeline", **kw,
    )


def test_pipeline_plan_fills_the_pipe():
    cfg, plan = _pipe_plan()
    assert plan.pod_role == "pipeline"
    # enough microbatches to amortize the bubble, still dividing the batch
    assert plan.microbatches >= plan.pod_axis
    assert 8 % plan.microbatches == 0
    # and the microbatch still folds over the data axis
    assert (8 // plan.microbatches) % PIPE_MESH["data"] == 0


def test_param_spec_slices_stack_over_pod():
    cfg, plan = _pipe_plan()
    sh = Shardings(FakeMesh(PIPE_MESH), plan, cfg)
    path = [jtu.DictKey(k) for k in ("blocks", "stack", "attn", "wqkv")]
    spec = sh.param_spec(path, Leaf((2, 64, 128)))
    assert spec[0] == "pod"  # per-stage slice on the stacked leading dim
    # non-stack leaves stay unsliced
    spec2 = sh.param_spec([jtu.DictKey("embed")], Leaf((512, 64)))
    assert spec2[0] != "pod"


def test_pipeline_rejects_moe():
    cfg, plan = _pipe_plan("mixtral-8x7b-reduced")
    with pytest.raises(ValueError, match="MoE"):
        check_pipeline_supported(cfg, plan, 8)


_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
from repro.launch.train import run

lp, _ = run("smollm-135m-reduced", steps=3, batch=8, seq=32,
            pipeline=2, dp=2, log_every=0)
lb, _ = run("smollm-135m-reduced", steps=3, batch=8, seq=32, dp=4, log_every=0)
print(json.dumps({"pipeline": lp, "baseline": lb}))
"""


def test_pipeline_train_matches_data_parallel_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", _SNIPPET],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"}, cwd=".",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    diffs = [abs(a - b) for a, b in zip(out["pipeline"], out["baseline"])]
    assert max(diffs) < 1e-4, f"pipeline diverges from DP baseline: {out}"
    # the run actually went somewhere (optimizer applied every step)
    assert out["pipeline"][0] != out["pipeline"][-1]

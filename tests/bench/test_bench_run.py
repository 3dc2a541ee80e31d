"""``bench/run.py`` refuses to measure without a TPU: it exits non-zero and
prints no result line."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import os
import subprocess
import sys

from bench import registry


def test_run_exits_nonzero_without_a_tpu():
    env = {"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu", "HOME": os.environ.get("HOME", "/tmp")}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.code", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=registry.ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr

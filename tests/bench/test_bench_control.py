"""The control: the reference computed in int8 in place of the program (the
precision below the configurations' stated bfloat16) reads a wider logit
gap than the program, and fails the limit that the program meets.  On the
CPU the program's matmuls are float32, so its gap is 0.  On the chip each
cell of ``BENCHMARK.json`` brings the readings of its control runs as a
file of its own, ``data/control/<cell>.json``; its committed limits are
held against them here, and the limits and the readings they were set from
are in PERF.md."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, registry
from bench import weights as W

import specrules
import tinybench

dense = registry.Registry().arch({})

# Wide and deep enough that, as at the published widths, the layers and not
# the tied input embedding set the logits, so near ties occur.
C = {"num_hidden_layers": 4, "hidden_size": 512, "num_attention_heads": 8,
     "num_key_value_heads": 4, "head_dim": 64, "intermediate_size": 1024,
     "vocab_size": 8192, "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "qk_norm": False}
L = 128
CELLS = [w["name"] for w in registry.Registry().spec["workloads"]]


def test_control_reads_wider_gaps_than_the_exact_program():
    w = jax.jit(lambda: W.draw(dense, C, 0, 77))()
    greedy = jax.jit(lambda toks, p: jnp.argmax(
        jnp.matmul(dense.hidden(C, w, toks)[p], w["embed"].T, precision="highest")))
    rng = np.random.default_rng(5)
    sample = []
    for _ in range(4):  # greedy decode with the float32 pass: the exact program
        prompt = rng.integers(0, C["vocab_size"], 40).tolist()
        toks = np.zeros((L,), np.int32)
        toks[:40] = prompt
        served = []
        for p in range(39, 39 + 40):
            nxt = int(greedy(jnp.asarray(toks), p))
            served.append(nxt)
            toks[p + 1] = nxt
        sample.append((prompt, served))
    got = reference.gaps(dense, C, w, sample, L, control=True)
    assert got["tokens"] == 160
    assert got["max_logit_gap"] <= 1e-5
    assert got["control_max_logit_gap"] > 1e-3, json.dumps(got)


def test_control_run_is_judged_by_the_control(tmp_path):
    """A control run goes through the harness's own decision: the int8
    reference's picks stand in for the served tokens and are held to the
    cell's limits; the program's own gaps are reported beside them."""
    reg = tinybench.make(tmp_path)
    out = tinybench.run(reg, "tiny.code", control=True)
    assert set(out["program_gaps"]) == {"max_logit_gap", "mean_logit_gap"}
    assert out["check"]["max_logit_gap"]["value"] > out["program_gaps"]["max_logit_gap"]
    assert out["correct"] is all(v["value"] <= v["limit"] for v in out["check"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_committed_limits_fail_the_chip_control_and_pass_the_program(cell):
    """The readings of this cell's control runs on the chip, at its own size
    and load (``data/control/<cell>.json``): under the committed limits each
    reads ``correct`` false, and the program's own gaps on the same samples
    read true.  A cell without the file fails here."""
    specrules.limits_fail_the_control(registry.Registry(), cell)

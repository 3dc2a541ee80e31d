"""Architecture modules (``bench/archs/``): the dense module reproduces, bit
for bit, what the benchmark read before its code moved there
(``data/arch_dense_pinned.json``, recorded at the commit before the move on
the tiny configuration of ``tinybench.py``); a configuration that names a
module added as a file is drawn, counted and compared with no edit to the
shared code; the dense guard still refuses what the program cannot serve;
and the reference part imports nothing of the program."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import hashlib
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, model, reference, registry
from bench import weights as W

import specrules

PINNED = json.loads((pathlib.Path(__file__).parent / "data" / "arch_dense_pinned.json").read_text())
C = PINNED["config"]
dense = registry.Registry().arch(C)


def _draw(arch, c, seed):
    hi, lo = W.seed_words(seed)
    return jax.jit(lambda a, b: W.draw(arch, c, a, b))(np.uint32(hi), np.uint32(lo))


def test_a_file_without_arch_module_is_served_as_dense():
    reg = registry.Registry()
    for c in reg.spec["configs"]:
        specrules.served_by_its_module(reg, c["name"])
    assert "arch_module" not in reg.config("qwen3-1.7b")
    assert pathlib.Path(dense.__file__) == registry.BENCH / "archs" / "dense.py"


@pytest.mark.parametrize("seed", sorted(PINNED["seeds"]))
def test_dense_weights_are_as_pinned(seed):
    w = _draw(dense, C, int(seed))
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest() for k, v in sorted(w.items())}
    assert got == PINNED["seeds"][seed]["weights_sha256"]


@pytest.mark.parametrize("control", [False, True])
@pytest.mark.parametrize("seed", sorted(PINNED["seeds"]))
def test_dense_reference_gaps_are_as_pinned(seed, control):
    pin = PINNED["seeds"][seed]
    w = _draw(dense, C, int(seed))
    got = reference.gaps(dense, C, w, pin["sample"], C["max_seq_len"], control=control)
    assert got == pin["gaps_control" if control else "gaps"]


@pytest.mark.parametrize("mix", sorted(PINNED["step_work"]))
@pytest.mark.parametrize("kv_dtype", ["int8", "bf16"])
def test_dense_step_work_is_as_pinned(mix, kv_dtype):
    pin = PINNED["step_work"][mix]
    assert flops.step_work(dense, C, [tuple(s) for s in pin["slots"]], kv_dtype) == pin[kv_dtype]


# A copy of dense that rotates only the file's fraction of each head, each
# frequency divided by the file's LongRoPE short factor, and keeps every
# configuration its reference pass was traced with.
EDITS = [
    ('    frac = float(c.get("partial_rotary_factor", 1.0))\n',
     '    SEEN.append(c)\n'
     '    frac = (float(c["partial_rotary_factor"]), c["rope_scaling"]["short_factor"])\n'),
    ('    rot = int(Dh * fraction)\n'
     '    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)\n',
     '    fraction, factors = fraction\n'
     '    rot = int(Dh * fraction)\n'
     '    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)\n'
     '    freqs = freqs / jnp.asarray(factors, jnp.float32)\n'),
]
PARTIAL = {
    "arch_module": "partial_longrope", "max_seq_len": 64,
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "qk_norm": False,
    "partial_rotary_factor": 0.75,
    "rope_scaling": {"type": "longrope", "short_factor": [1.0, 1.5, 2.0, 2.5, 3.0, 3.5]},
}


def _added(tmp_path, c):
    base = tmp_path / "bench"
    (base / "archs").mkdir(parents=True)
    (base / "configs").mkdir()
    src = (registry.BENCH / "archs" / "dense.py").read_text()
    for old, new in EDITS:
        assert src.count(old) == 1, old
        src = src.replace(old, new)
    (base / "archs" / "partial_longrope.py").write_text(src + "\nSEEN = []\n")
    (base / "configs" / "partial.json").write_text(json.dumps(c))
    spec = {"configs": [{"name": "partial", "file": "bench/configs/partial.json"}],
            "workloads": [], "end_to_end": [], "per_layer": []}
    return registry.Registry(base, spec)


def test_added_module_is_found_and_reads_the_whole_file(tmp_path):
    reg = _added(tmp_path, PARTIAL)
    c = reg.config("partial")
    arch = reg.arch(c)
    assert pathlib.Path(arch.__file__) == tmp_path / "bench" / "archs" / "partial_longrope.py"
    assert not (registry.BENCH / "archs" / "partial_longrope.py").exists()

    w = _draw(arch, c, 2**31 + 99)
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, c["vocab_size"], 64), jnp.int32)
    new = reference._readings(w, tokens, tokens, arch=arch, c_key=reference._hashable(c), block=64)
    assert arch.SEEN == [c]  # every key, the nested rope_scaling too, reached hidden()
    old = reference._readings(w, tokens, tokens, arch=dense, c_key=reference._hashable(c), block=64)
    assert not np.array_equal(np.asarray(new[0]), np.asarray(old[0]))

    sample = [(tokens[:20].tolist(), tokens[20:30].tolist())]
    got = reference.gaps(arch, c, w, sample, c["max_seq_len"], control=True)
    assert got["tokens"] == 10 and np.isfinite(list(got.values())).all()
    work = flops.step_work(arch, c, [(0, 20, 1), (29, 1, 1)], "int8")
    assert work == flops.step_work(dense, c, [(0, 20, 1), (29, 1, 1)], "int8")


def test_a_missing_module_names_its_path(tmp_path):
    reg = _added(tmp_path, PARTIAL)
    path = tmp_path / "bench" / "archs" / "no_such_arch.py"
    with pytest.raises(FileNotFoundError, match=str(path)):
        reg.arch({"arch_module": "no_such_arch"})


# Phi-4-mini-instruct's published sizes (hf:microsoft/Phi-4-mini-instruct),
# cut to 16 layers; its LongRoPE factors are left out, since the guard
# refuses any rope_scaling.
PHI4 = {
    "arch": "phi4-mini-3.8b", "hidden_size": 3072, "intermediate_size": 8192,
    "num_hidden_layers": 16, "num_attention_heads": 24, "num_key_value_heads": 8,
    "head_dim": 128, "vocab_size": 200064, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": True, "qk_norm": False, "partial_rotary_factor": 0.75,
    "rope_scaling": {"type": "longrope"},
}


@pytest.mark.parametrize("change, message", [
    ({}, "phi4-mini-3.8b: rms_norm_eps=1e-05 in the file, 1e-06 in the program"),
    ({"rms_norm_eps": 1e-6}, "phi4-mini-3.8b: the program rotates the whole head with plain RoPE"),
    ({"rms_norm_eps": 1e-6, "partial_rotary_factor": 1.0},
     "phi4-mini-3.8b: the program rotates the whole head with plain RoPE"),
])
def test_dense_guard_refuses_phi4_mini(change, message):
    with pytest.raises(ValueError) as e:
        model.program_config(dense, dict(PHI4, **change))
    assert str(e.value) == message


def test_dense_reference_imports_nothing_of_the_program():
    root = registry.ROOT
    code = (
        "import sys, jax.numpy as jnp\n"
        "from bench import reference, registry, weights as W\n"
        f"c = {PARTIAL!r}\n"
        "dense = registry.Registry().arch({})\n"
        "w = W.draw(dense, c, 0, 1)\n"
        "dense.hidden(c, w, jnp.arange(16))\n"
        "reference.gaps(dense, c, w, [([1, 2, 3], [4, 5])], 64, control=True)\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'repro'))\n"
    )
    env = {"PATH": os.environ.get("PATH", ""), "JAX_PLATFORMS": "cpu",
           "HOME": os.environ.get("HOME", "/tmp"),
           "PYTHONPATH": os.pathsep.join([str(root), str(root / "src")])}
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == "[]"

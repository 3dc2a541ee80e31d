"""The benchmark grown by a second configuration as a later change brings
one: new files (a configuration naming an architecture module of its own, a
copy of ``dense.py``, the cell's check file and its control readings) and
names appended to ``workloads`` lists, with no edit to a file that is there.
The tiny cells of the first configuration stay as they were, the new one
gets tiny cells of its own that run and read ``correct``, and the rules of
``specrules.py`` hold on the grown checkout as on the repo."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import dataclasses
import json
import pathlib
import shutil
import types

import pytest

from bench import model, registry

import specrules
import tinybench

ROOT = registry.ROOT
GROWN = dict(tinybench.CONFIG, arch_module="dense_copy")


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A checkout holding the repo's spec and parts, grown by configuration
    ``grown`` and its cell ``grown.batch``; the path of its spec."""
    root = tmp_path_factory.mktemp("grown")
    for d in ("archs", "generators", "metrics", "configs", "traffic", "checks"):
        shutil.copytree(ROOT / "bench" / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    control = root / "tests" / "bench" / "data" / "control"
    shutil.copytree(ROOT / "tests" / "bench" / "data" / "control", control)

    shutil.copy(root / "bench" / "archs" / "dense.py", root / "bench" / "archs" / "dense_copy.py")
    (root / "bench" / "configs" / "grown.json").write_text(json.dumps(GROWN))
    shutil.copy(root / "bench" / "checks" / "qwen3-1.7b.batch.json",
                root / "bench" / "checks" / "grown.batch.json")
    shutil.copy(control / "qwen3-1.7b.batch.json", control / "grown.batch.json")

    spec = registry.load_json(ROOT / "BENCHMARK.json")
    spec["configs"].append({"name": "grown", "source": "https://example.org/grown",
                            "file": "bench/configs/grown.json", "reduced": [],
                            "why": "a second configuration served by a module of its own"})
    spec["workloads"].append({"name": "grown.batch", "config": "grown", "traffic": "batch",
                              "chips": 1, "why": "the grown configuration's offline job"})
    next(m for m in spec["end_to_end"] if m["name"] == "output_tok_s")["workloads"].append(
        "grown.batch")
    spec["per_layer"].append({"name": "iter_ms.grown", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "engine",
                              "moves": "output_tok_s", "workloads": ["grown.batch"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root / "BENCHMARK.json"


def _cells(reg):
    return {w["name"]: ([m["name"] for m in reg.metrics_for(w["name"], False)],
                        [m["name"] for m in reg.metrics_for(w["name"], True)])
            for w in reg.spec["workloads"]}


def test_the_first_configurations_tiny_cells_are_as_on_the_repos_spec(grown, tmp_path):
    before = tinybench.make(tmp_path / "repo")
    after = tinybench.make(tmp_path / "grown", spec=grown, stands_for="qwen3-1.7b")
    assert after.spec["workloads"] == before.spec["workloads"]
    assert _cells(after) == _cells(before)
    assert set(_cells(before)) == {"tiny.batch", "tiny.code"}


def test_the_grown_configurations_tiny_cell_reports_its_own_metrics(grown, tmp_path):
    reg = tinybench.make(tmp_path, spec=grown, stands_for="grown", config=GROWN)
    assert _cells(reg) == {"tiny.batch": (["output_tok_s", "setup_s"], ["iter_ms.grown"])}
    with pytest.raises(ValueError, match="arch_module None is not grown's 'dense_copy'"):
        tinybench.make(tmp_path / "plain", spec=grown, stands_for="grown")


def test_a_closed_loop_run_of_the_grown_cell_is_correct(grown, tmp_path, monkeypatch):
    reg = tinybench.make(tmp_path, spec=grown, stands_for="grown", config=GROWN)
    served = []
    arch = reg.arch
    monkeypatch.setattr(reg, "arch", lambda c: served.append(arch(c)) or served[-1])
    out = tinybench.run(reg, "tiny.batch")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert served and {pathlib.Path(m.__file__) for m in served} == {
        tmp_path / "bench" / "archs" / "dense_copy.py"}


@pytest.mark.parametrize("which", ["repo", "grown"])
def test_the_spec_rules_hold(grown, which):
    root = ROOT if which == "repo" else grown.parent
    reg = registry.Registry(root / "bench")
    for c in reg.spec["configs"]:
        specrules.served_by_its_module(reg, c["name"])
    for w in reg.spec["workloads"]:
        specrules.limits_fail_the_control(reg, w["name"])
    if which == "grown":
        assert pathlib.Path(reg.arch(reg.config("grown")).__file__).name == "dense_copy.py"


def test_the_tiny_program_config_is_as_before(tmp_path):
    """The program configuration taken through ``PROGRAM_KEYS`` equals, field
    by field, the one built from qwen3-1.7b's with six sizes named by hand."""
    from repro.configs import get_config

    reg = tinybench.make(tmp_path)
    c = reg.config("tiny")
    arch = reg.arch(c)
    C = tinybench.CONFIG
    by_hand = dataclasses.replace(
        get_config("qwen3-1.7b"), d_model=C["hidden_size"], d_ff=C["intermediate_size"],
        n_heads=C["num_attention_heads"], n_kv_heads=C["num_key_value_heads"],
        d_head=C["head_dim"], vocab_size=C["vocab_size"])
    before = model.program_config(arch, c, by_hand)
    after = model.program_config(arch, c, tinybench.program_cfg(arch, c))
    for f in dataclasses.fields(before):
        assert getattr(after, f.name) == getattr(before, f.name), f.name


def test_a_module_without_program_keys_is_named():
    bare = types.ModuleType("bench_archs_bare")
    with pytest.raises(AttributeError, match="bench_archs_bare has no PROGRAM_KEYS"):
        tinybench.program_cfg(bare, tinybench.CONFIG)

"""A configuration, traffic mix, generator and metric added as files are
found by name, with no edit to the harness."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import json
import shutil

from bench import registry


def test_new_parts_are_found_by_name(tmp_path):
    base = tmp_path / "bench"
    shutil.copytree(registry.BENCH / "metrics", base / "metrics")
    (base / "configs").mkdir()
    (base / "configs" / "newmodel.json").write_text(json.dumps({"hidden_size": 7}))
    (base / "traffic").mkdir()
    (base / "traffic" / "bursts.json").write_text(json.dumps({"generator": "bursty"}))
    (base / "generators").mkdir()
    (base / "generators" / "bursty.py").write_text(
        "class Generator:\n    closed = False\n    kind = 'bursty'\n")
    (base / "metrics" / "new_metric.x.py").write_text("def read(record):\n    return 41 + 1\n")
    spec = {
        "configs": [{"name": "newmodel", "file": "bench/configs/newmodel.json"}],
        "workloads": [{"name": "newmodel.bursts", "config": "newmodel", "traffic": "bursts",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "new_metric.x", "unit": "%", "workloads": ["newmodel.bursts"]},
                      {"name": "other.y", "unit": "%", "workloads": ["elsewhere"]}],
    }
    reg = registry.Registry(base, spec)
    cell = reg.cell("newmodel.bursts")
    assert reg.config(cell["config"]) == {"hidden_size": 7}
    t = reg.traffic(cell["traffic"])
    assert reg.generator(t["generator"]).Generator.kind == "bursty"
    names = [m["name"] for m in reg.metrics_for("newmodel.bursts", traced=True)]
    assert names == ["new_metric.x"]
    assert reg.metric("new_metric.x").read({}) == 42
    assert [m["name"] for m in reg.metrics_for("newmodel.bursts", traced=False)] == ["setup_s"]


def test_every_metric_of_the_benchmark_has_a_reader():
    reg = registry.Registry()
    for m in reg.spec["end_to_end"] + reg.spec["per_layer"]:
        assert callable(reg.metric(m["name"]).read), m["name"]
    for w in reg.spec["workloads"]:
        reg.config(w["config"])
        reg.generator(reg.traffic(w["traffic"])["generator"])
        assert (registry.BENCH / "checks" / f"{w['name']}.json").is_file()

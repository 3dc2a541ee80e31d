"""Finds the benchmark's parts by name: a cell in ``BENCHMARK.json``, its
configuration and traffic files, a configuration's architecture module, a
generator kind and a metric reader.

A later cell, traffic mix, architecture, generator or metric is added as a
file; nothing here lists them."""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


class Registry:
    """The parts under ``base`` (the ``bench/`` directory by default), and
    the cells and metrics of ``spec`` (``BENCHMARK.json``)."""

    def __init__(self, base=BENCH, spec=None):
        self.base = pathlib.Path(base)
        if spec is None:
            spec = load_json(self.base.parent / "BENCHMARK.json")
        self.spec = spec

    def _file(self, kind: str, name: str, suffix: str) -> pathlib.Path:
        path = self.base / kind / f"{name}{suffix}"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind[:-1]} named {name!r} at {path}")
        return path

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(self.base.parent / c["file"])
        raise KeyError(f"no configuration named {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self._file("traffic", name, ".json"))

    def module(self, kind: str, name: str) -> ModuleType:
        path = self._file(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def arch(self, c: dict) -> ModuleType:
        """The architecture module configuration file ``c`` names under
        ``"arch_module"``: ``archs/<name>.py``, ``archs/dense.py`` where it
        names none."""
        return self.module("archs", c.get("arch_module", "dense"))

    def generator(self, kind: str) -> ModuleType:
        return self.module("generators", kind)

    def metric(self, name: str) -> ModuleType:
        """The reader of metric ``name``: ``metrics/<name>.py``, or for a
        name with a cell suffix (``iter_ms.code``) that has no file of its
        own, the reader it shares (``metrics/iter_ms.py``)."""
        base = name.rsplit(".", 1)[0]
        if base != name and not (self.base / "metrics" / f"{name}.py").is_file():
            return self.module("metrics", base)
        return self.module("metrics", name)

    def metrics_for(self, cell: str, traced: bool) -> list[dict]:
        """The metrics a run of ``cell`` reports: the end-to-end ones
        untraced, the per-layer ones traced; a metric without a
        ``workloads`` list belongs to every cell."""
        group = self.spec["per_layer"] if traced else self.spec["end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

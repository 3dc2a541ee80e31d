"""Small cells for the benchmark's CPU tests.  They stand for one
configuration of a benchmark spec, named by the caller (qwen3-1.7b of the
repo's ``BENCHMARK.json`` by default): ``tiny.<traffic>`` for each of its
cells whose traffic has a small mix here, served as a small configuration
file (``CONFIG``: qwen3-1.7b's program at small widths, wide and deep enough
that attention over the cache, and not the tied input embedding, sets the
next token) through the architecture module that configuration names, with
a small plan without rolled decode spans (as the chip cells derive).  The
spec's generators, metric readers and architecture modules are copied into
a temporary directory laid out like ``bench/``."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]

CONFIG = {
    "arch": "qwen3-1.7b", "mesh": {"data": 1, "model": 1}, "max_seq_len": 128,
    "hidden_size": 1024, "intermediate_size": 2048, "num_hidden_layers": 4,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 128,
    "vocab_size": 8192, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "qk_norm": True,
}
LENGTHS = {"prompt": {"median": 20, "sigma": 0.8, "min": 4, "max": 60}}
TRAFFIC = {
    "code": {"generator": "open_poisson", "rate_per_s": 6.0, "lead_s": 0.5, **LENGTHS,
             "output": {"median": 6, "sigma": 1.0, "min": 1, "max": 30}, "drain_s": 30.0},
    "batch": {"generator": "closed_backlog", "backlog": 8, "segment": 8, **LENGTHS,
              "output": {"median": 12, "sigma": 0.8, "min": 2, "max": 40}},
}
SERVE = dict(decode_batch=4, mixed_slab_width=16, prefill_chunk=16, n_blocks=1 + 4 * 8,
             rolled_steps=1)


def make(tmp, limit: float = 0.05, checks: dict = None, *, spec=None,
         stands_for: str = "qwen3-1.7b", config: dict = CONFIG) -> "object":
    """The tiny cells' registry, for configuration ``stands_for`` of the spec
    at path ``spec`` (the repo's ``BENCHMARK.json`` by default; its parts are
    read from the ``bench/`` beside it).  The tiny file ``config`` keeps that
    configuration's ``arch`` and ``arch_module``.  Each metric's
    ``workloads`` keeps the cells that have a tiny one, renamed; a list left
    empty reports in no tiny cell.  Their limit: the small plan keeps bf16
    pages, so a served token flips at a near tie by a few thousandths of a
    logit on the CPU, where a broken step reads gaps of whole logits.
    ``checks`` ({tiny cell: benchmark cell}) takes a benchmark cell's own
    check file instead."""
    from bench import registry

    spec_path = pathlib.Path(spec or ROOT / "BENCHMARK.json")
    src = spec_path.parent / "bench"
    spec = registry.load_json(spec_path)
    real = registry.Registry(src, spec).config(stands_for)
    for key in ("arch", "arch_module"):
        if config.get(key) != real.get(key):
            raise ValueError(f"the tiny file's {key} {config.get(key)!r} is not "
                             f"{stands_for}'s {real.get(key)!r}")
    cells = {w["name"]: w["traffic"] for w in spec["workloads"]
             if w["config"] == stands_for and w["traffic"] in TRAFFIC}

    base = pathlib.Path(tmp) / "bench"
    for d in ("generators", "metrics", "archs"):
        shutil.copytree(src / d, base / d, dirs_exist_ok=True)
    for d in ("configs", "traffic", "checks"):
        (base / d).mkdir(parents=True, exist_ok=True)
    (base / "configs" / "tiny.json").write_text(json.dumps(config))
    spec["configs"] = [{"name": "tiny", "file": "bench/configs/tiny.json"}]
    spec["workloads"] = [{"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1}
                         for t in cells.values()]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted(f"tiny.{cells[w]}" for w in m["workloads"] if w in cells)
    for w in spec["workloads"]:
        t = w["traffic"]
        (base / "traffic" / f"{t}.json").write_text(json.dumps(TRAFFIC[t]))
        check = {"limits": {"max_logit_gap": limit, "mean_logit_gap": limit / 10},
                 "tokens": 40, "max_requests": 8}
        if checks:
            check["limits"] = registry.load_json(src / "checks" / f"{checks[w['name']]}.json")["limits"]
        (base / "checks" / f"{w['name']}.json").write_text(json.dumps(check))
    return registry.Registry(base, spec)


def program_cfg(arch, c: dict):
    """The program's ArchConfig for tiny file ``c``: the registry arch it
    names, with every field that architecture module ``arch`` maps from the
    file (``PROGRAM_KEYS``: file key -> ArchConfig field) set as the file
    states."""
    from repro.configs import get_config

    keys = getattr(arch, "PROGRAM_KEYS", None)
    if keys is None:
        raise AttributeError(f"architecture module {arch.__name__} has no PROGRAM_KEYS")
    return dataclasses.replace(get_config(c["arch"]), **{f: c[k] for k, f in keys.items()})


def run(reg, workload: str, seconds: float = 2.0, seed: int = 2**31 + 12345, **kw) -> dict:
    import jax
    from bench import harness

    c = reg.config(reg.cell(workload)["config"])
    return harness.run_cell(workload, seed, seconds, False, reg=reg, devices=jax.devices(),
                            program_cfg=program_cfg(reg.arch(c), c),
                            serve_overrides=SERVE, **kw)

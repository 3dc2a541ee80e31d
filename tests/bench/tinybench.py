"""A small cell for the benchmark's CPU tests: qwen3-1.7b's program at small
widths (wide and deep enough that attention over the cache, and not the tied
input embedding, sets the next token), a small plan without rolled decode
spans (as the chip cells derive), and copies of the benchmark's generators,
metric readers and architecture modules in a temporary directory laid out
like ``bench/``."""
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


def make(tmp, limit: float = 0.05, checks: dict = None) -> "object":
    """The tiny cells' registry.  Their limit: the small plan keeps bf16 pages,
    so a served token flips at a near tie by a few thousandths of a logit on
    the CPU, where a broken step reads gaps of whole logits.  ``checks``
    ({tiny cell: benchmark cell}) takes a benchmark cell's own check file
    instead."""
    from bench import registry

    base = pathlib.Path(tmp) / "bench"
    for d in ("generators", "metrics", "archs"):
        shutil.copytree(ROOT / "bench" / d, base / d, dirs_exist_ok=True)
    for d in ("configs", "traffic", "checks"):
        (base / d).mkdir(parents=True, exist_ok=True)
    (base / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    for name, t in TRAFFIC.items():
        (base / "traffic" / f"{name}.json").write_text(json.dumps(t))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "file": "bench/configs/tiny.json"}]
    spec["workloads"] = [{"name": f"tiny.{t}", "config": "tiny", "traffic": t, "chips": 1}
                         for t in TRAFFIC]
    rename = {"qwen3-1.7b.batch": "tiny.batch", "qwen3-1.7b.code": "tiny.code"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({rename[w] for w in m["workloads"]})
    for w in spec["workloads"]:
        check = {"limits": {"max_logit_gap": limit, "mean_logit_gap": limit / 10},
                 "tokens": 40, "max_requests": 8}
        if checks:
            committed = json.loads((ROOT / "bench" / "checks" / f"{checks[w['name']]}.json").read_text())
            check["limits"] = committed["limits"]
        (base / "checks" / f"{w['name']}.json").write_text(json.dumps(check))
    return registry.Registry(base, spec)


def run(reg, workload: str, seconds: float = 2.0, seed: int = 2**31 + 12345, **kw) -> dict:
    import jax
    from bench import harness
    from repro.configs import get_config

    cfg = dataclasses.replace(
        get_config("qwen3-1.7b"), d_model=CONFIG["hidden_size"], d_ff=CONFIG["intermediate_size"],
        n_heads=CONFIG["num_attention_heads"], n_kv_heads=CONFIG["num_key_value_heads"],
        d_head=CONFIG["head_dim"], vocab_size=CONFIG["vocab_size"])
    return harness.run_cell(workload, seed, seconds, False, reg=reg, devices=jax.devices(),
                            program_cfg=cfg,
                            serve_overrides=SERVE, **kw)

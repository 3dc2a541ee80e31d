"""The system under test as the benchmark builds it: the program's model
configuration, its derived plans, the seed's weights placed in the program's
parameter tree, and the serving engine.

This is the only file of the benchmark that reaches into the program's
construction; the plans are what the program derives, never fixed here."""
from __future__ import annotations

import dataclasses
import inspect

import jax
import numpy as np

from bench import weights as W

# configuration-file key -> the program's ArchConfig field
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
    "tie_word_embeddings": "tie_embeddings",
}


def program_config(c: dict, cfg=None):
    """The program's ArchConfig for configuration file ``c``: the registry
    arch with its depth set as the file states.  Any other size that
    differs from the file is an error, not an override."""
    from repro.configs import get_config

    cfg = cfg or get_config(c["arch"])
    cfg = dataclasses.replace(cfg, n_layers=int(c["num_hidden_layers"]))
    for key, field in PROGRAM_KEYS.items():
        want, got = c[key], getattr(cfg, field)
        if (float(want) if isinstance(want, float) else want) != got:
            raise ValueError(f"{c['arch']}: {key}={want} in the file, {field}={got} in the program")
    if cfg.activation != "swiglu" or cfg.norm != "rmsnorm" or cfg.layer_pattern != ("attn",):
        raise ValueError(f"{cfg.name}: the reference covers rmsnorm/swiglu/full-attention models only")
    # the program's RMSNorm epsilon and RoPE are fixed in its layers: the
    # file may state only what they do, since the reference follows the file
    from repro.models import layers
    eps = inspect.signature(layers.rmsnorm).parameters["eps"].default
    if float(c["rms_norm_eps"]) != eps:
        raise ValueError(f"{c['arch']}: rms_norm_eps={c['rms_norm_eps']} in the file, {eps} in the program")
    if float(c.get("partial_rotary_factor", 1.0)) != 1.0 or c.get("rope_scaling") is not None:
        raise ValueError(f"{c['arch']}: the program rotates the whole head with plain RoPE")
    return cfg


def plans(c: dict, cfg, mesh, hw):
    """The execution plan and serve plan the program derives for this
    configuration on this mesh and chip (``launch/serve.run_batched``'s
    derivation, with the file's context bound)."""
    from repro.core.plan import derive_plan, derive_serve_plan

    plan = derive_plan(cfg, dict(mesh.shape), hw, batch=4, seq_len=32, training=False)
    serve = derive_serve_plan(cfg, dict(mesh.shape), hw, max_seq_len=int(c["max_seq_len"]))
    return plan, serve


def to_program_tree(w: dict, cfg, plan) -> dict:
    """Named weights -> the program's parameter tree (one scanned group of
    one attention layer per model layer; norm scales are offsets from 1)."""
    attn = {"ln": {"scale": w["attn_norm"]}, "wo": w["wo"]}
    if plan.fuse_qkv:
        attn["wqkv"] = jax.numpy.concatenate([w["wq"], w["wk"], w["wv"]], axis=-1)
    else:
        attn.update(wq=w["wq"], wk=w["wk"], wv=w["wv"])
    if cfg.qk_norm:
        attn.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    ffn = {"ln": {"scale": w["ffn_norm"]}, "w1": w["w_gate"], "w3": w["w_up"], "w2": w["w_down"]}
    return {
        "blocks": {"stack": ({"attn": attn, "ffn": ffn},), "tail": ()},
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
    }


def check_tree(c: dict, cfg, plan) -> None:
    """The tree above has exactly the program's structure, shapes and
    dtypes (``models/params.init_params`` at the served dtype)."""
    from repro.core.plan import SERVE_WEIGHT_DTYPE
    from repro.models.params import init_params

    want = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, plan, dtype=SERVE_WEIGHT_DTYPE)
    )
    got = jax.eval_shape(lambda: to_program_tree(W.draw(c, 0, 0), cfg, plan))
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"parameter tree differs from the program's: {jax.tree.structure(got)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"parameter leaf {b.shape} {b.dtype} != program's {a.shape} {a.dtype}")


def weights_program(c: dict, cfg, plan, out_shardings=None):
    """One jitted program that draws the seed's weights straight into the
    program's tree and placement; call it with ``weights.seed_words(seed)``."""
    return jax.jit(
        lambda hi, lo: to_program_tree(W.draw(c, hi, lo), cfg, plan),
        out_shardings=out_shardings,
    )


def build(c: dict, seed: int, *, cfg=None, serve_overrides=None):
    """(engine, cfg, plan, serve) for configuration ``c`` with the seed's
    weights.  ``cfg``/``serve_overrides`` exist for the CPU tests, which run
    a small model with a small plan."""
    from repro.core.hardware import resolve_hardware
    from repro.dist.sharding import Shardings
    from repro.serve import ServingEngine

    n = c["mesh"]["data"] * c["mesh"]["model"]
    mesh = jax.make_mesh(
        (c["mesh"]["data"], c["mesh"]["model"]), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=jax.devices()[:n],
    )
    hw = resolve_hardware("tpu_v5e")  # the chip's own spec on a TPU
    cfg = program_config(c, cfg)
    plan, serve = plans(c, cfg, mesh, hw)
    if serve_overrides:
        serve = dataclasses.replace(serve, **serve_overrides)
    check_tree(c, cfg, plan)
    sh = Shardings(mesh, plan, cfg)
    shape_tree = jax.eval_shape(lambda: to_program_tree(W.draw(c, 0, 0), cfg, plan))
    draw = weights_program(c, cfg, plan, out_shardings=sh.param_shardings(shape_tree))
    hi, lo = W.seed_words(seed)
    params = draw(np.uint32(hi), np.uint32(lo))
    engine = ServingEngine(params, cfg, plan, serve, shardings=sh, hw=hw)
    return engine, cfg, plan, serve


def release(engine) -> None:
    """Free the engine's device state (weights and KV pools) now."""
    for x in jax.tree.leaves(engine.pools) + jax.tree.leaves(engine.params):
        x.delete()
    engine.pools = engine.params = None

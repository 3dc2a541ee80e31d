"""The system under test as the benchmark builds it: the program's model
configuration, its derived plans, the seed's weights placed in the program's
parameter tree, and the serving engine.

This file and the program hooks of the architecture modules
(``bench/archs/``) are the only parts of the benchmark that reach into the
program's construction; the plans are what the program derives, never
fixed here."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import weights as W


def program_config(arch, c: dict, cfg=None):
    """The program's ArchConfig for configuration file ``c``: the registry
    arch with its depth set as the file states, checked by the guard of
    architecture module ``arch`` (any other size that differs from the file
    is an error, not an override)."""
    from repro.configs import get_config

    cfg = cfg or get_config(c["arch"])
    cfg = dataclasses.replace(cfg, n_layers=int(c["num_hidden_layers"]))
    arch.check_program(c, cfg)
    return cfg


def plans(c: dict, cfg, mesh, hw):
    """The execution plan and serve plan the program derives for this
    configuration on this mesh and chip (``launch/serve.run_batched``'s
    derivation, with the file's context bound)."""
    from repro.core.plan import derive_plan, derive_serve_plan

    plan = derive_plan(cfg, dict(mesh.shape), hw, batch=4, seq_len=32, training=False)
    serve = derive_serve_plan(cfg, dict(mesh.shape), hw, max_seq_len=int(c["max_seq_len"]))
    return plan, serve


def check_tree(arch, c: dict, cfg, plan) -> None:
    """The tree ``arch.to_program_tree`` makes has exactly the program's
    structure, shapes and dtypes (``models/params.init_params`` at the
    served dtype)."""
    from repro.core.plan import SERVE_WEIGHT_DTYPE
    from repro.models.params import init_params

    want = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, plan, dtype=SERVE_WEIGHT_DTYPE)
    )
    got = jax.eval_shape(lambda: arch.to_program_tree(W.draw(arch, c, 0, 0), cfg, plan))
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"parameter tree differs from the program's: {jax.tree.structure(got)}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"parameter leaf {b.shape} {b.dtype} != program's {a.shape} {a.dtype}")


def weights_program(arch, c: dict, cfg, plan, out_shardings=None):
    """One jitted program that draws the seed's weights straight into the
    program's tree and placement; call it with ``weights.seed_words(seed)``."""
    return jax.jit(
        lambda hi, lo: arch.to_program_tree(W.draw(arch, c, hi, lo), cfg, plan),
        out_shardings=out_shardings,
    )


def build(arch, c: dict, seed: int, *, cfg=None, serve_overrides=None):
    """(engine, cfg, plan, serve) for configuration ``c`` (architecture
    module ``arch``) with the seed's weights.  ``cfg``/``serve_overrides``
    exist for the CPU tests, which run a small model with a small plan."""
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
    cfg = program_config(arch, c, cfg)
    plan, serve = plans(c, cfg, mesh, hw)
    if serve_overrides:
        serve = dataclasses.replace(serve, **serve_overrides)
    check_tree(arch, c, cfg, plan)
    sh = Shardings(mesh, plan, cfg)
    shape_tree = jax.eval_shape(lambda: arch.to_program_tree(W.draw(arch, c, 0, 0), cfg, plan))
    draw = weights_program(arch, c, cfg, plan, out_shardings=sh.param_shardings(shape_tree))
    hi, lo = W.seed_words(seed)
    params = draw(np.uint32(hi), np.uint32(lo))
    engine = ServingEngine(params, cfg, plan, serve, shardings=sh, hw=hw)
    return engine, cfg, plan, serve


def release(engine) -> None:
    """Free the engine's device state (weights and KV pools) now."""
    for x in jax.tree.leaves(engine.pools) + jax.tree.leaves(engine.params):
        x.delete()
    engine.pools = engine.params = None

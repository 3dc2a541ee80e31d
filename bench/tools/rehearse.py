"""Compile a configuration's served programs for a described TPU v5e,
without the chip: the seed's weight program, the engine's unified step at
the derived plan, and the reference over one padded sequence.

    JAX_PLATFORMS=cpu python3 bench/tools/rehearse.py qwen3-1.7b [--layers 8]

Prints the derived serve plan and each program's ``memory_analysis()``; the
compiler raises where a program does not fit the chip."""
import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import model, reference, registry  # noqa: E402
from bench import weights as W  # noqa: E402


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = lambda x: round(x / 1e9, 3)  # noqa: E731
    return {"args_gb": gb(m.argument_size_in_bytes), "temp_gb": gb(m.temp_size_in_bytes),
            "out_gb": gb(m.output_size_in_bytes), "alias_gb": gb(m.alias_size_in_bytes)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--skip-reference", action="store_true")
    a = ap.parse_args()
    from jax.experimental import topologies
    from repro import kernels
    from repro.core.hardware import TPU_V5E
    from repro.models.cache import init_paged_cache
    from repro.serve.engine import make_mixed_step

    kernels.interpret_mode = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    reg = registry.Registry()
    c = reg.config(a.config)
    if a.layers:
        c = dict(c, num_hidden_layers=a.layers)
    arch = reg.arch(c)
    cfg = model.program_config(arch, c)
    mesh = jax.sharding.Mesh([[topo.devices[0]]], ("data", "model"))
    plan, serve = model.plans(c, cfg, mesh, TPU_V5E)
    out = {"config": a.config, "layers": cfg.n_layers, "params_b": cfg.param_count() / 1e9,
           "serve": serve.to_record()}
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    u32 = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one)
    shape_tree = jax.eval_shape(
        lambda: arch.to_program_tree(W.draw(arch, c, 0, 0), cfg, plan))
    wp = model.weights_program(arch, c, cfg, plan,
                               out_shardings=jax.tree.map(lambda _: one, shape_tree))
    out["weights_program"] = mem(wp.lower(u32, u32).compile())
    params = place(shape_tree)
    pools = place(jax.eval_shape(lambda: init_paged_cache(cfg, plan, serve)))
    B, Wd, MB = serve.decode_batch, serve.mixed_slab_width, serve.max_blocks_per_seq
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    step = make_mixed_step(cfg, plan, serve, fused=True)
    comp = step.lower(params, pools, S((B, Wd), jnp.int32), S((B, MB), jnp.int32),
                      S((B,), jnp.int32), S((B,), jnp.int32), S((B,), jnp.float32)).compile()
    out["step"] = mem(comp)
    out["step"]["tpu_custom_call"] = "tpu_custom_call" in comp.as_text()
    out["step"]["fits_gb"] = (TPU_V5E.hbm_bytes - 2**28) / 1e9
    if not a.skip_reference:
        L = int(c["max_seq_len"])
        wts = place(jax.eval_shape(lambda: W.draw(arch, c, 0, 0)))
        for ctl in (False, True):
            fn = reference._readings.lower(
                wts, S((L,), jnp.int32), S((L,), jnp.int32),
                arch=arch, c_key=reference._hashable(c), quantized=ctl, block=256)
            out["reference_control" if ctl else "reference"] = mem(fn.compile())
    print(json.dumps(out, indent=1), flush=True)


if __name__ == "__main__":
    main()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh and record memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]

    # accelerator-family mode: sweep the serving design space on one device
    # and print/write the Pareto frontier (tokens/s vs $/token vs J/token)
    PYTHONPATH=src python -m repro.launch.dryrun --family --hardware tpu_v5e

Results go to benchmarks/results/dryrun/<mesh>/<arch>__<shape>.json
(incremental: existing cells are skipped unless --force); --family reports
go to benchmarks/results/family/<hardware>__<arch>.json.
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; this must
# run before ANY other import that touches jax.  --calibrate actually *runs*
# a serving stream, so it keeps the real device topology instead.
import os
import sys

if "--calibrate" not in sys.argv:
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ALL_SHAPES, ASSIGNED_ARCHS, applicable, get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import derive_plan, derive_serve_plan, serve_feasible
from repro.core.roofline import analyze, analytic_memory_floor, model_flops_for
from repro.dist.pipeline import bubble_fraction
from repro.dist.sharding import Shardings
from repro.launch.mesh import make_production_mesh, mesh_axes_dict
from repro.models.cache import init_cache
from repro.models.params import init_params
from repro.serve.engine import make_decode_step, make_prefill_step
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import make_train_step

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"


def input_specs(cfg, shape, plan):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    specs = {}
    if shape.kind == "decode":
        specs["tokens"] = jax.ShapeDtypeStruct((B, 1), i32)
        return specs
    text = S
    if cfg.frontend != "none" and cfg.n_prefix_embeds:
        text = S - cfg.n_prefix_embeds
        specs["prefix_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_prefix_embeds, cfg.d_model), bf16
        )
    if cfg.enc_dec:
        specs["enc_embeds"] = jax.ShapeDtypeStruct((B, cfg.enc_seq, cfg.d_model), bf16)
    if cfg.vocab_size > 1:
        specs["tokens"] = jax.ShapeDtypeStruct((B, text), i32)
    if shape.kind == "train":
        specs["targets"] = jax.ShapeDtypeStruct((B, text), i32)
    return specs


def build_cell(cfg, shape, mesh, *, plan_overrides=None):
    """Returns (jitted_fn, example_args_as_SDS) for one cell."""
    axes = mesh_axes_dict(mesh)
    overrides = plan_overrides or {}
    plan = derive_plan(
        cfg,
        axes,
        TPU_V5E,
        batch=shape.global_batch,
        seq_len=shape.seq_len,
        training=shape.kind == "train",
        **overrides,
    )
    sh = Shardings(mesh, plan, cfg)
    params_sds = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, plan, dtype=jnp.bfloat16)
    )
    param_sh = sh.param_shardings(params_sds)
    batch_sds = input_specs(cfg, shape, plan)
    batch_sh = sh.batch_shardings(batch_sds)

    if shape.kind == "train":
        from repro.train.optimizer import TrainState

        f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
        state_sds = TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            params=params_sds,
            m=jax.tree.map(f32, params_sds),
            v=jax.tree.map(f32, params_sds),
            residual=None,
        )
        state_sh = TrainState(
            step=sh._ns(jax.sharding.PartitionSpec()),
            params=param_sh,
            m=param_sh,
            v=param_sh,
            residual=None,
        )
        step = make_train_step(
            cfg, plan, OptimizerConfig(), shard=sh.constrain,
            grad_shardings=param_sh, mesh=mesh,
        )
        fn = jax.jit(
            step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        args = (state_sds, batch_sds)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, plan, shard=sh.constrain)
        fn = jax.jit(step, in_shardings=(param_sh, batch_sh))
        args = (params_sds, batch_sds)
    else:  # decode
        cache_sds = jax.eval_shape(
            lambda: init_cache(cfg, plan, shape.global_batch, shape.seq_len)
        )
        cache_sh = sh.cache_shardings(cache_sds)
        step = make_decode_step(cfg, plan, shard=sh.constrain)
        fn = jax.jit(
            step,
            in_shardings=(param_sh, batch_sh["tokens"], cache_sh),
            donate_argnums=(2,),
        )
        args = (params_sds, batch_sds["tokens"], cache_sds)
    return fn, args, plan


def run_cell(arch, shape, *, multi_pod, force=False, out_dir=RESULTS,
             plan_overrides=None, tag=""):
    mesh_name = "multi" if multi_pod else "single"
    out = pathlib.Path(out_dir) / mesh_name
    out.mkdir(parents=True, exist_ok=True)
    fname = out / f"{arch}__{shape.name}{tag}.json"
    if fname.exists() and not force:
        return json.loads(fname.read_text())

    cfg = get_config(arch)
    ok, reason = applicable(cfg, shape)
    record = {
        "arch": arch,
        "shape": shape.name,
        "mesh": mesh_name,
        "status": "skipped",
        "reason": reason,
    }
    if ok:
        t0 = time.time()
        try:
            mesh = make_production_mesh(multi_pod=multi_pod)
            n_chips = 512 if multi_pod else 256
            fn, args, plan = build_cell(cfg, shape, mesh, plan_overrides=plan_overrides)
            lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            ma = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
            rep = analyze(
                arch=arch,
                shape=shape.name,
                mesh_name=mesh_name,
                n_chips=n_chips,
                cost=cost,
                hlo_text=hlo,
                hw=TPU_V5E,
                model_flops=model_flops_for(cfg, shape, shape.kind == "train"),
                arg_bytes=float(ma.argument_size_in_bytes),
                temp_bytes=float(ma.temp_size_in_bytes),
                memory_floor_bytes=analytic_memory_floor(cfg, shape, plan, n_chips),
            )
            record = {
                "status": "ok",
                "lower_s": round(t1 - t0, 2),
                "compile_s": round(t2 - t1, 2),
                "plan": {
                    "mha_mode": plan.mha.mode,
                    "ffn_mode": plan.ffn.mode,
                    "mha_factor1": plan.mha.factor1,
                    "ffn_factor1": plan.ffn.factor1,
                    "fuse_qkv": plan.fuse_qkv,
                    "p_atb": plan.p_atb,
                    "head_shards": plan.head_shards,
                    "remat": plan.remat,
                    "microbatches": plan.microbatches,
                    "embed_shard": plan.embed_shard,
                    "moe_mode": plan.moe_mode,
                    "moe_dispatch": plan.moe_dispatch,
                    "seq_shard": plan.seq_shard,
                    "seq_parallel_acts": plan.seq_parallel_acts,
                    "grad_compression": plan.grad_compression,
                    # pod-axis accounting: role + GPipe bubble the schedule
                    # pays at this (stages, microbatches) point
                    "pod_role": plan.pod_role,
                    "pipeline_stages": (
                        plan.pod_axis if plan.pod_role == "pipeline" else 1
                    ),
                    "pipeline_bubble": (
                        bubble_fraction(plan.microbatches, plan.pod_axis)
                        if plan.pod_role == "pipeline"
                        else 0.0
                    ),
                    # serving cells also record the derived serve knobs
                    # (decode batch / block size / KV dtype / speculative
                    # draft depth) so the plan->serve mapping is
                    # inspectable per mesh; draft="ngram" (the model-free
                    # source every arch can run) makes the record show the
                    # roofline-slack gamma this cell would get
                    "serve": (
                        derive_serve_plan(
                            cfg,
                            mesh_axes_dict(mesh),
                            TPU_V5E,
                            max_seq_len=shape.seq_len,
                            draft="ngram",
                        ).to_record()
                        if shape.kind in ("decode", "prefill")
                        and serve_feasible(cfg)[0]
                        else None
                    ),
                },
                **rep.to_dict(),
            }
        except Exception as e:  # a failure here is a bug in the system
            record = {
                "arch": arch,
                "shape": shape.name,
                "mesh": mesh_name,
                "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-3000:],
            }
    fname.write_text(json.dumps(record, indent=1, default=str))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument(
        "--autotune", action="store_true",
        help="search plan candidates for --arch/--shape and report the winner",
    )
    ap.add_argument(
        "--family", action="store_true",
        help="design-space search: emit the Pareto frontier of serving "
             "accelerator variants for --arch on --hardware "
             "(tokens/s vs $/token vs J/token; docs/PLANNER.md)",
    )
    ap.add_argument(
        "--hardware", default="tpu_v5e",
        help="registered device name for --family (see "
             "repro.core.hardware.registered_hardware)",
    )
    ap.add_argument(
        "--calibrate", action="store_true",
        help="run a short serving stream on the real backend and print the "
             "planner drift report: predicted (roofline) vs measured wall "
             "time per phase (docs/OBSERVABILITY.md §Drift)",
    )
    ap.add_argument(
        "--max-seq", type=int, default=2048,
        help="serving context bound for the --family sweep",
    )
    ap.add_argument(
        "--bench-out", default=None,
        help="write an aggregate JSON of all cells run (CI benchmark artifact)",
    )
    a = ap.parse_args()

    if a.calibrate:
        from repro.launch.mesh import make_host_mesh
        from repro.launch.serve import ServeArgs, run_batched

        arch = a.arch or "smollm-135m"
        cfg = get_config(arch)
        ok, reason = serve_feasible(cfg)
        if not ok:
            raise SystemExit(f"{arch}: {reason} (pick a serve-feasible --arch)")
        sargs = ServeArgs(
            arch=arch, requests=6, prompt_len=16, gen=12, stagger=2,
            max_seq=96, batch=3, fix_batch=True, prefill_chunk=16,
            hardware=a.hardware,
        )
        cal = run_batched(sargs, cfg, make_host_mesh()).summary()["calibration"]
        print()
        print(f"planner drift calibration ({arch}, roofline priced as {a.hardware}):")
        for phase, rep in (cal.get("phases") or {}).items():
            if rep is None:
                continue
            print(
                f"  {phase:8s} n={rep['n']:4d}"
                f" predicted={rep['predicted_ms_mean']:8.3f}ms"
                f" measured={rep['measured_ms_mean']:8.3f}ms"
                f" ratio={rep['ratio']:8.2f}"
                f" p50={rep['ratio_p50']:8.2f} p90={rep['ratio_p90']:8.2f}"
            )
        print(f"  overall ratio: {cal.get('overall_ratio')}")
        print(f"  {cal.get('note')}")
        if a.bench_out:
            pathlib.Path(a.bench_out).write_text(
                json.dumps({"arch": arch, "hardware": a.hardware,
                            "calibration": cal}, indent=1, default=str)
            )
            print(f"wrote {a.bench_out}")
        return

    if a.family:
        from repro.core.search import family_report

        arch = a.arch or "qwen3-1.7b"
        out_dir = RESULTS.parent / "family"
        result, record = family_report(
            arch, a.hardware, max_seq_len=a.max_seq, out_dir=out_dir,
        )
        print(record["markdown"])
        print(f"wrote {out_dir / (result.hardware + '__' + arch + '.json')}")
        if a.bench_out:
            pathlib.Path(a.bench_out).write_text(
                json.dumps(record, indent=1, default=str)
            )
            print(f"wrote {a.bench_out}")
        if not result.frontier:
            raise SystemExit("empty frontier: no feasible design point")
        return

    if a.autotune:
        from repro.configs import ALL_SHAPES as _AS
        from repro.core.autotune import autotune

        shape = next(s for s in _AS if s.name == (a.shape or "train_4k"))
        best, scored = autotune(a.arch, shape, multi_pod=a.mesh == "multi")
        for c in scored:
            print(
                f"{c.name:18s} step={c.step_s if c.step_s is None else round(c.step_s, 3)}"
                f" fits={c.fits} err={c.error and c.error[:80]}"
            )
        print(f"winner: {best.name if best else 'none'}")
        return

    archs = [a.arch] if a.arch else list(ASSIGNED_ARCHS)
    shapes = [s for s in ALL_SHAPES if a.shape in (None, s.name)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[a.mesh]
    n_ok = n_err = n_skip = 0
    records = []
    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                r = run_cell(arch, shape, multi_pod=multi, force=a.force)
                records.append(r)
                status = r.get("status")
                n_ok += status == "ok"
                n_err += status == "error"
                n_skip += status == "skipped"
                extra = (
                    f" bottleneck={r.get('bottleneck')} "
                    f"compile={r.get('compile_s')}s"
                    if status == "ok"
                    else " " + str(r.get("reason") or r.get("error", ""))[:120]
                )
                print(
                    f"[{'multi' if multi else 'single'}] {arch:22s} "
                    f"{shape.name:12s} {status:8s}{extra}",
                    flush=True,
                )
    print(f"done: ok={n_ok} err={n_err} skipped={n_skip}")
    if a.bench_out:
        pathlib.Path(a.bench_out).write_text(
            json.dumps(
                {"ok": n_ok, "err": n_err, "skipped": n_skip, "cells": records},
                indent=1,
                default=str,
            )
        )
        print(f"wrote {a.bench_out} ({len(records)} cells)")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()

"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m-reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --resume

    # pipeline the layer stack over 2 pod stages (+ DP inside each stage)
    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m-reduced \
        --steps 20 --batch 8 --seq 64 --pipeline 2 --dp 2

    # Megatron-SP: seq-sharded residual, ring-overlap collectives
    PYTHONPATH=src python -m repro.launch.train --arch bert-base-reduced \
        --steps 20 --batch 8 --seq 64 --dp 2 --seq-parallel

The plan decides, this file executes (docs/ARCHITECTURE.md): pod_role=
"pipeline" routes the step through dist.pipeline (bubble accounting is
printed at startup), --compression rides the compressed_psum wire path
when the mesh is pure-DP and falls back to accumulation-dtype otherwise.

Fault tolerance: periodic async checkpoints (atomic manifests), --resume
picks the latest complete step and the deterministic data pipeline replays
from there; a per-step watchdog flags stragglers (wall-clock budget).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.core.hardware import TPU_V5E, resolve_hardware
from repro.core.plan import derive_plan
from repro.data.pipeline import DataConfig, DataIterator
from repro.dist.sharding import Shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.params import init_params
from repro.dist.pipeline import bubble_fraction
from repro.train.compression import CompressionConfig
from repro.train.optimizer import OptimizerConfig, init_state
from repro.train.train_step import make_train_step, wire_compression_axes


class StepWatchdog:
    """Flags steps that exceed a wall-clock budget (straggler detection)."""

    def __init__(self, budget_factor: float = 3.0, warmup: int = 3):
        self.budget_factor = budget_factor
        self.warmup = warmup
        self.times: list[float] = []
        self.flagged: list[int] = []

    def observe(self, step: int, dt: float) -> bool:
        self.times.append(dt)
        if len(self.times) <= self.warmup:
            return False
        median = sorted(self.times[self.warmup :])[len(self.times[self.warmup :]) // 2]
        if dt > self.budget_factor * max(median, 1e-6):
            self.flagged.append(step)
            return True
        return False


def run(
    arch: str,
    *,
    steps: int = 100,
    batch: int = 8,
    seq: int = 128,
    lr: float = 3e-4,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    resume: bool = False,
    compression: str = "none",
    pipeline: int = 0,
    dp: int = 1,
    seq_parallel: bool = False,
    force_mode: str | None = None,
    seed: int = 0,
    dtype=jnp.float32,
    log_every: int = 10,
):
    cfg = get_config(arch)
    if pipeline > 1 and dp == 1:
        # pipeline composes with DP, not TP: fold the spare devices into
        # the data axis instead of leaving a >1 model axis
        dp = max(1, len(jax.devices()) // pipeline)
    mesh = make_host_mesh(pod=pipeline if pipeline > 1 else 1, data=dp)
    plan = derive_plan(
        cfg, dict(mesh.shape), resolve_hardware(TPU_V5E.name),
        batch=batch, seq_len=seq, training=True,
        pod_role="pipeline" if pipeline > 1 else "data",
        seq_parallel=seq_parallel, grad_compression=compression,
        force_mode=force_mode,
    )
    if plan.pod_role == "pipeline" and plan.pod_axis > 1:
        print(
            f"pipeline: {plan.pod_axis} stages x {plan.microbatches} microbatches"
            f" (bubble {bubble_fraction(plan.microbatches, plan.pod_axis):.1%})"
        )
    if seq_parallel and not plan.seq_parallel_acts:
        print("seq-parallel requested but infeasible for this (arch, mesh); off")
    sh = Shardings(mesh, plan, cfg)
    params = init_params(jax.random.PRNGKey(seed), cfg, plan, dtype=dtype)
    param_sh = sh.param_shardings(params)
    params = jax.device_put(params, param_sh)
    # Error-feedback residual only serves the accumulation-dtype fallback;
    # the wire path (compressed_psum) quantizes on a shared grid instead.
    wire = wire_compression_axes(plan, mesh, batch) is not None
    state = init_state(params, with_residual=compression != "none" and not wire)

    opt = OptimizerConfig(peak_lr=lr, warmup_steps=max(steps // 20, 5), total_steps=steps)
    cc = CompressionConfig(mode=compression)
    step_fn = jax.jit(
        make_train_step(
            cfg, plan, opt, shard=sh.constrain, compression=cc,
            grad_shardings=param_sh, mesh=mesh,
        ),
        donate_argnums=(0,),
    )

    start = 0
    if resume and ckpt_dir:
        k = latest_step(ckpt_dir)
        if k is not None:
            # Elastic restore: re-place the big trees on the current mesh so
            # a resumed run keeps the sharded layout of a fresh one.
            state_sh = state._replace(
                step=sh._ns(jax.sharding.PartitionSpec()),
                params=param_sh, m=param_sh, v=param_sh,
                residual=None if state.residual is None else param_sh,
            )
            state = restore_checkpoint(ckpt_dir, k, state, shardings=state_sh)
            start = k
            print(f"resumed from step {k}")

    data = DataIterator(
        DataConfig(cfg.vocab_size, seq, batch, seed=seed), start_step=start
    )
    dog = StepWatchdog()
    losses = []
    pending = None
    batch_sh = None  # built from the first batch; shapes are loop-invariant
    for step in range(start, steps):
        b = next(data)
        if batch_sh is None:
            batch_sh = sh.batch_shardings(b)
        b = jax.device_put(b, batch_sh)
        t0 = time.time()
        state, metrics = step_fn(state, b)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        losses.append(loss)
        if dog.observe(step, dt):
            print(f"[watchdog] step {step} took {dt:.2f}s (straggler)")
        if log_every and step % log_every == 0:
            print(
                f"step {step:5d} loss {loss:.4f} gnorm "
                f"{float(metrics['grad_norm']):.3f} ({dt*1e3:.0f} ms)"
            )
        if ckpt_dir and ckpt_every and (step + 1) % ckpt_every == 0:
            if pending is not None:
                pending.join()
            pending = save_checkpoint(
                ckpt_dir, step + 1, state, meta={"arch": arch}, async_save=True
            )
    if pending is not None:
        pending.join()
    return losses, state


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compression", default="none", choices=["none", "bf16", "int8"])
    ap.add_argument(
        "--pipeline", type=int, default=0,
        help="pipeline the layer stack over this many pod stages (0/1: off)",
    )
    ap.add_argument(
        "--dp", type=int, default=1,
        help="data-parallel axis extent of the host mesh",
    )
    ap.add_argument(
        "--seq-parallel", action="store_true",
        help="Megatron-SP: seq-shard the residual over the model axis",
    )
    ap.add_argument("--force-mode", default=None, choices=["spatial", "temporal"])
    a = ap.parse_args()
    enable_compile_cache()
    losses, _ = run(
        a.arch, steps=a.steps, batch=a.batch, seq=a.seq, lr=a.lr,
        ckpt_dir=a.ckpt_dir, ckpt_every=a.ckpt_every, resume=a.resume,
        compression=a.compression, pipeline=a.pipeline, dp=a.dp,
        seq_parallel=a.seq_parallel, force_mode=a.force_mode,
    )
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    main()

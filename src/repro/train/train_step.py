"""The jitted training step.

Microbatch gradient accumulation (plan.microbatches, the Factor2' outcome)
runs as a lax.scan so activation memory scales with the microbatch, not the
global batch; remat of the layer scan is plan.remat.  Optimizer update and
optional gradient compression happen once per step.

The ExecutionPlan is the control plane here (docs/ARCHITECTURE.md):

* ``plan.pod_role == "pipeline"`` routes the loss through
  ``models.transformer.pipeline_lm_loss`` — the stacked layer-groups run
  as pipeline stages over the ``pod`` axis via
  ``dist.pipeline.pipeline_forward`` and the pipeline does its own
  microbatching (the outer accumulation scan is disabled).
* ``plan.grad_compression`` picks the gradient wire format.  On a pure
  data-parallel mesh the step runs the exchange itself — per-replica
  gradients inside shard_map, summed by
  ``dist.collectives.compressed_psum`` — so compression happens once, on
  the wire.  On meshes the manual region cannot host (tensor/sequence
  parallel weights, ZeRO shards) the same mode falls back to
  ``train/compression.py``'s accumulation-dtype quantization with error
  feedback.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.plan import ExecutionPlan
from repro.dist.collectives import compressed_psum
from repro.models.transformer import lm_loss, pipeline_lm_loss
from repro.train.compression import CompressionConfig, compress_grads
from repro.train.optimizer import OptimizerConfig, TrainState, adamw_update

PyTree = Any
Identity = lambda x, name=None: x

logger = logging.getLogger(__name__)


def _split_micro(batch: dict, n: int) -> dict:
    def r(x):
        return x.reshape(n, x.shape[0] // n, *x.shape[1:])

    return {k: r(v) for k, v in batch.items()}


def make_loss_fn(
    cfg: ArchConfig, plan: ExecutionPlan, shard: Callable = Identity, mesh=None
):
    if plan.pod_role == "pipeline" and plan.pod_axis > 1:
        if mesh is None:
            raise ValueError(
                "plan.pod_role == 'pipeline' needs a real mesh to execute; "
                "pass mesh= to make_train_step"
            )

        def loss_fn(params, batch):
            return pipeline_lm_loss(
                params, batch, cfg=cfg, plan=plan, mesh=mesh, shard=shard
            )

        return loss_fn

    def loss_fn(params, batch):
        return lm_loss(params, batch, cfg=cfg, plan=plan, shard=shard, mesh=mesh)

    return loss_fn


def wire_compression_axes(
    plan: ExecutionPlan, mesh, batch: Optional[int] = None
) -> Optional[tuple[str, ...]]:
    """Mesh axes the compressed gradient exchange runs over, or None when
    the wire path cannot host this plan.

    The manual region computes loss/grads on *replicated* params with only
    the batch sharded, so every weight-sharding feature (tensor parallel,
    ZeRO, FSDP-folded model axis, sequence parallel) and the pipeline
    scheduler disqualify it — those plans keep the dtype-level fallback.
    Pass ``batch`` (the global batch size) to also apply the runtime
    divisibility requirement — launchers should, so they allocate the
    error-feedback residual whenever the fallback will actually run.
    """
    if mesh is None or plan.grad_compression == "none":
        return None
    if (
        plan.pod_role == "pipeline"
        or plan.zero_weights
        or plan.dp_over_model
        or plan.seq_shard
        or plan.seq_parallel_acts
    ):
        return None
    sizes = dict(mesh.shape)
    axes = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    if not axes:
        return None
    if any(v > 1 for k, v in sizes.items() if k not in axes):
        return None  # a >1 model axis means params are not replicated
    if batch is not None:
        n_dp = math.prod(sizes[a] for a in axes)
        if batch % (n_dp * max(1, plan.microbatches)):
            return None  # local batch would not split into microbatches
    return axes


def make_train_step(
    cfg: ArchConfig,
    plan: ExecutionPlan,
    opt: OptimizerConfig,
    shard: Callable = Identity,
    compression: Optional[CompressionConfig] = None,
    grad_shardings=None,
    mesh=None,
):
    pipelined = plan.pod_role == "pipeline" and plan.pod_axis > 1
    loss_fn = make_loss_fn(cfg, plan, shard, mesh=mesh)
    _vg = jax.value_and_grad(loss_fn)
    # The pipeline schedules its own microbatches; no outer accumulation.
    n_micro = 1 if pipelined else max(1, plan.microbatches)
    # plan.grad_compression is the control-plane knob; an explicit
    # CompressionConfig only overrides its error-feedback detail, so
    # plan-only callers still get the dtype fallback on wire-less meshes.
    cc = compression or CompressionConfig(mode=plan.grad_compression)
    wire_axes = wire_compression_axes(plan, mesh)
    if wire_axes:
        # The wire path recomputes grads per replica: constraints and the
        # GSPMD shard callable are not legal inside the manual region.
        _vg_local = jax.value_and_grad(make_loss_fn(cfg, plan, Identity))
        n_dp = math.prod(dict(mesh.shape)[a] for a in wire_axes)
        wire_entry = wire_axes if len(wire_axes) > 1 else wire_axes[0]

    def vg(params, batch):
        loss, grads = _vg(params, batch)
        if grad_shardings is not None:
            # Pin gradient layout at the autodiff boundary: the backward scan
            # then reduce-scatters per layer instead of all-reducing a full
            # fp32 partial-gradient buffer (§Perf iteration 7).
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        return loss, grads

    def accumulate(vg_fn, params, batch):
        """loss/grads with the microbatch accumulation scan when n_micro>1."""
        if n_micro == 1:
            return vg_fn(params, batch)
        micro = _split_micro(batch, n_micro)

        def acc(carry, mb):
            gsum, lsum = carry
            l, g = vg_fn(params, mb)
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (gsum, lsum + l), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), _ = lax.scan(acc, (g0, jnp.zeros(())), micro)
        grads = jax.tree.map(lambda g: g / n_micro, gsum)
        return lsum / n_micro, grads

    def train_step(state: TrainState, batch: dict):
        use_wire = bool(wire_axes)
        if use_wire:
            b0 = jax.tree.leaves(batch)[0].shape[0]
            # local batch must still split into microbatches on each replica
            use_wire = b0 % (n_dp * n_micro) == 0
        if use_wire:
            batch_specs = jax.tree.map(lambda _: P(wire_entry), batch)

            def local(params, b):
                loss, g = accumulate(_vg_local, params, b)
                g = jax.tree.map(
                    lambda x: compressed_psum(x, wire_axes, plan.grad_compression)
                    / n_dp,
                    g,
                )
                return lax.pmean(loss, wire_axes), g

            loss, grads = shard_map(
                local,
                mesh=mesh,
                in_specs=(P(), batch_specs),
                out_specs=(P(), P()),
                check_vma=False,
            )(state.params, batch)
            if grad_shardings is not None:
                grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
            residual = state.residual  # wire mode: compression already done
        else:
            loss, grads = accumulate(vg, state.params, batch)
            residual = state.residual
            if residual is not None:
                grads, residual = compress_grads(grads, residual, cc)
            elif cc.mode != "none":
                # Compression requested but no error-feedback residual in
                # the train state (wire path disqualified at trace time, or
                # a plan-only caller on a weight-sharded mesh): still honor
                # the requested mode statelessly rather than silently
                # training uncompressed.
                logger.warning(
                    "gradient compression (mode=%s) running statelessly: "
                    "no error-feedback residual in the train state and the "
                    "wire path is unavailable on this mesh/batch", cc.mode,
                )
                zeros = jax.tree.map(
                    lambda g: jnp.zeros(g.shape, jnp.float32), grads
                )
                grads, _ = compress_grads(
                    grads, zeros,
                    dataclasses.replace(cc, error_feedback=False),
                )
        new_state, metrics = adamw_update(state, grads, opt)
        new_state = new_state._replace(residual=residual)
        metrics["loss"] = loss
        return new_state, metrics

    return train_step

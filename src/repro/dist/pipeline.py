"""Pipeline parallelism over the ``pod`` axis (paper mode (2), multi-EDPU).

Paper-to-code map: docs/ARCHITECTURE.md §"Pod axis".  The paper's TEMPORAL
mode runs PRGs serially, each using all compute resources; across pods the
analogous schedule is a microbatch pipeline: stage s (one pod) runs
layer-group s, handing activations to stage s+1 via ``collective-permute``
each tick.

Microbatch schedule (GPipe, all-forward):

    tick t = 0 .. M + S - 2       (M microbatches, S stages)
      stage 0    consumes microbatch ``min(t, M-1)`` (ramp-down ticks feed
                 it stale data whose results are never written),
      stage s>0  consumes whatever stage s-1 permuted to it on tick t-1,
      stage S-1  writes microbatch ``t - (S-1)`` once ``t >= S-1``.

    Every device is busy every tick, so the only idle time is the ramp:
    ``bubble_fraction(M, S) = (S-1)/(M+S-1)`` of step time — the planner
    (core/plan.py) trades this against per-microbatch activation memory by
    raising M when ``pod_role == "pipeline"``.

Wire format of the handoff: one activation tensor (mb, ...) per tick per
stage boundary, moved by ``collective-permute`` (point-to-point, no
all-to-all, no host round-trip).  The final ``psum`` over the pod axis is
zero-cost information-wise (all stages but the last hold zeros) and
replicates the result for ``out_specs``.

The tick loop is a ``lax.scan`` (not ``fori_loop``) so the whole schedule
is reverse-mode differentiable: ``launch/train.py`` routes
``pod_role == "pipeline"`` plans straight through ``jax.value_and_grad``
of a loss built on :func:`pipeline_forward`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P


def bubble_fraction(n_micro: int, n_stage: int) -> float:
    """GPipe bubble: (S-1)/(M+S-1) of step time is idle ramp-up/down."""
    if n_stage <= 1:
        return 0.0
    if n_micro < 1:
        return 1.0
    return (n_stage - 1) / (n_micro + n_stage - 1)


def pipeline_forward(stage_fn, mesh, axis: str = "pod", batch_axes: tuple = ()):
    """Build a pipelined forward over ``axis``.

    ``stage_fn(w_stage, x) -> x`` is one stage's compute.  ``w_stage`` is
    the stage's *local* slice of the weights: a pytree whose leaves keep
    their leading dim — ``n_groups / n_stage`` layer-groups per stage (the
    per-stage param slicing that ``Shardings.param_spec`` mirrors by
    putting ``pod`` on the stacked leading dim).  The returned callable
    takes ``w`` (leaves ``(n_groups, ...)``, ``n_groups % n_stage == 0``)
    and ``micro`` ``(n_micro, mb, ...)`` microbatches, and returns the
    microbatches after all stages, bit-identical to running the stages
    sequentially.  ``batch_axes`` names mesh axes carrying data
    parallelism on the microbatch dim (dim 1), so pipeline and DP compose
    in one shard_map.
    """
    n_stage = dict(mesh.shape)[axis]

    def pipelined(w, micro):
        def body(wi, mb):
            stage = lax.axis_index(axis)
            n_micro = mb.shape[0]
            ticks = n_micro + n_stage - 1
            perm = [(j, j + 1) for j in range(n_stage - 1)]

            def tick(carry, t):
                out, recv = carry
                # Stage 0 injects microbatch t (clipped: ramp-down ticks feed
                # it stale data whose results are never written); later stages
                # consume what the previous stage permuted to them.
                x_in = jnp.where(stage == 0, mb[jnp.clip(t, 0, n_micro - 1)], recv)
                y = stage_fn(wi, x_in)
                # Only the last stage writes: microbatch t - (n_stage-1).
                out_idx = t - (n_stage - 1)
                wr = jnp.clip(out_idx, 0, n_micro - 1)
                keep = (stage == n_stage - 1) & (out_idx >= 0)
                out = out.at[wr].set(jnp.where(keep, y, out[wr]))
                recv = y if n_stage == 1 else lax.ppermute(y, axis, perm)
                return (out, recv), None

            (out, _), _ = lax.scan(
                tick, (jnp.zeros_like(mb), jnp.zeros_like(mb[0])), jnp.arange(ticks)
            )
            # Results live on the last stage only; the psum (zeros elsewhere)
            # both completes the sum and replicates for the out_specs.
            return lax.psum(out, axis)

        batch_entry = (
            batch_axes
            if len(batch_axes) > 1
            else (batch_axes[0] if batch_axes else None)
        )
        micro_spec = P(None, batch_entry, *([None] * (micro.ndim - 2)))
        w_specs = jax.tree.map(
            lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), w
        )
        return shard_map(
            body,
            mesh=mesh,
            in_specs=(w_specs, micro_spec),
            out_specs=micro_spec,
            check_vma=False,
        )(w, micro)

    return pipelined

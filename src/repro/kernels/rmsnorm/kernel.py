"""Fused RMSNorm(+residual) Pallas kernel.

The paper's C6 ("memory-bound nonlinear operators ride the MM dataflow")
applied to the norm that brackets every EDPU stage: one HBM round-trip
instead of three (residual add, mean-square reduce, scale) — on TPU the row
block stays in VMEM across all three.

Grid (rows / block_rows,); each step normalizes a (block_rows, d) tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _rmsnorm_kernel(x_ref, s_ref, *rest, eps: float, has_residual: bool):
    if has_residual:
        r_ref, o_ref = rest
    else:
        (o_ref,) = rest
    x = x_ref[...].astype(jnp.float32)
    if has_residual:
        x = x + r_ref[...].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    y = y * (1.0 + s_ref[...].astype(jnp.float32))
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_call(
    x: jax.Array,
    scale: jax.Array,
    residual=None,
    *,
    block_rows: int = 256,
    eps: float = 1e-6,
):
    """x: (N, d); scale: (d,); residual: (N, d) or None -> (N, d)."""
    N, d = x.shape
    br = min(block_rows, N)
    while N % br:
        br //= 2
    in_specs = [
        pl.BlockSpec((br, d), lambda i: (i, 0)),
        pl.BlockSpec((d,), lambda i: (0,)),
    ]
    args = [x, scale]
    if residual is not None:
        in_specs.append(pl.BlockSpec((br, d), lambda i: (i, 0)))
        args.append(residual)
    kernel = functools.partial(
        _rmsnorm_kernel, eps=eps, has_residual=residual is not None
    )
    return pl.pallas_call(
        kernel,
        grid=(N // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        interpret=kernels.interpret_mode(),
    )(*args)

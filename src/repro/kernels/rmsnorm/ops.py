"""Jitted wrapper: (..., d) model layout -> kernel rows."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm_call


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, residual=None, *, eps: float = 1e-6, block_rows: int = 256):
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    rf = residual.reshape(-1, shape[-1]) if residual is not None else None
    out = rmsnorm_call(
        xf, scale, rf, block_rows=block_rows, eps=eps
    )
    return out.reshape(shape)

"""Jitted public wrapper for the MM PU kernel.

Picks the tile spec via the CAT solver (paper: "select the appropriate AIE MM
PU specification according to the Transformer model specification"), pads to
tile multiples (the ViT L=197 padding effect, reported via ``pad_overhead``),
and dispatches to the Pallas kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.hardware import DEFAULT_HARDWARE
from repro.core.pu import MMTileSpec, pick_pu
from repro.kernels.mm_pu.kernel import mm_pu_call


def _pad_to(x, m0, m1):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def pad_overhead(m: int, n: int, k: int, spec: MMTileSpec) -> float:
    """Fraction of MXU work spent on padding for this (mm, spec) pairing."""
    pm = -(-m // spec.block_m) * spec.block_m
    pn = -(-n // spec.block_n) * spec.block_n
    pk = -(-k // spec.block_k) * spec.block_k
    return pm * pn * pk / (m * n * k) - 1.0


@functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "activation", "out_dtype",
    ),
)
def mm_pu(
    x: jax.Array,
    w: jax.Array,
    *,
    spec: Optional[MMTileSpec] = None,
    bias: Optional[jax.Array] = None,
    residual: Optional[jax.Array] = None,
    w_scale: Optional[jax.Array] = None,
    activation: str = "none",
    out_dtype=None,
):
    """x: (M, K) @ w: (K, N) with fused epilogue. Returns (M, N)."""
    M, K = x.shape
    N = w.shape[1]
    if spec is None:
        spec = pick_pu(M, N, K, DEFAULT_HARDWARE, x.dtype.itemsize)
    bm = min(spec.block_m, max(128, 1 << (M - 1).bit_length()))
    bn = min(spec.block_n, max(128, 1 << (N - 1).bit_length()))
    bk = min(spec.block_k, max(128, 1 << (K - 1).bit_length()))
    xp = _pad_to(x, bm, bk)
    wp = _pad_to(w, bk, bn)
    biasp = _pad_to(bias, 1, bn) if bias is not None else None
    resp = _pad_to(residual, bm, bn) if residual is not None else None
    scalep = _pad_to(w_scale, 1, bn) if w_scale is not None else None
    out = mm_pu_call(
        xp, wp,
        block_m=bm, block_n=bn, block_k=bk,
        bias=biasp, residual=resp, w_scale=scalep,
        activation=activation, out_dtype=out_dtype,
    )
    return out[:M, :N]

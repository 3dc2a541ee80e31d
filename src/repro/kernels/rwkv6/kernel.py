"""Chunked RWKV-6 WKV Pallas kernel.

TPU adaptation of the Finch recurrence (DESIGN.md: the ATB of the attention-
free arch).  A GPU implementation leans on warp-level scans; on TPU we use
the chunked linear-attention form so the MXU does the work: per chunk, a
(c x c) decay-weighted intra-chunk matmul plus a (c x D) state contraction,
with the (D_k x D_v) state carried in VMEM scratch across the sequential
chunk grid.

Grid (B*H, S/c), chunk dim innermost.  Decay ratios are computed as
exp(L_{t-1} - L_j) with the exponent masked <= 0 (never overflows; the
factored exp(L)*exp(-L) form would).

Layouts: r/k/v/logw (B*H, S, D); u (H, D); out (B*H, S, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *, c: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)  # (c, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)  # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)  # (D,)

    L = jnp.cumsum(lw, axis=0)  # (c, D) inclusive
    Lq = L - lw  # L_{t-1}
    # intra-chunk: att[t,s] = sum_d r[t,d] k[s,d] exp(Lq[t,d] - L[s,d]), s < t
    delta = Lq[:, None, :] - L[None, :, :]  # (c, c, D)
    tri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) > jax.lax.broadcasted_iota(
        jnp.int32, (c, c), 1
    )
    delta = jnp.where(tri[..., None], delta, -jnp.inf)
    att = jnp.einsum("td,sd,tsd->ts", r, k, jnp.exp(delta))
    o = jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    # bonus (current token): (sum_d r u k) * v_t
    o += jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True) * v
    # cross-chunk state contribution
    rdec = r * jnp.exp(Lq)
    o += jax.lax.dot_general(
        rdec, s_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # state update: S = exp(Lc) * S + (k * exp(Lc - L))^T @ v
    Lc = L[-1]  # (D,)
    kfut = k * jnp.exp(Lc[None, :] - L)
    s_ref[...] = jnp.exp(Lc)[:, None] * s_ref[...] + jax.lax.dot_general(
        kfut, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0] = o.astype(o_ref.dtype)


def wkv_call(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,
    u: jax.Array,
    *,
    n_heads: int,
    chunk: int = 64,
):
    """r/k/v/logw: (BH, S, D) with BH = B * n_heads; u: (H, D)."""
    BH, S, D = r.shape
    assert S % chunk == 0, (S, chunk)
    H = n_heads
    kernel = functools.partial(_wkv_kernel, c=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, D), lambda b, j: (b % H, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, D), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), r.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=kernels.interpret_mode(),
    )(r, k, v, logw, u)

"""Flash-attention Pallas kernel — the ATB (paper Fig. 3) on TPU.
(Eq. 7/8 head-parallelism map: docs/ARCHITECTURE.md §"Eq. 7/8".)

The paper inserts softmax into the MM dataflow between the two attention
matmuls as a PL pipeline branch (C6); on TPU that is exactly the online-
softmax block schedule: scores never leave VMEM, the (m, l, acc) carry rides
across kv blocks.  Supports causal, sliding-window and prefix-LM masking and
GQA (kv head = q head // group).

Layouts: q (B*H, Sq, D); k/v (B*KH, Sk, D).  Grid (B*H, Sq/bq, Sk/bk),
kv innermost; scratch m/l/acc persists across the kv sweep.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, bq: int, bk: int, nk: int, causal: bool, window: int, prefix: int,
    scale: float,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0].astype(jnp.float32)  # (bk, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    iq = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    ik = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        c = iq >= ik
        if prefix > 0:
            c |= ik < prefix
        mask &= c
    if window > 0:
        mask &= (iq - ik) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


def flash_attention_call(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    n_q_per_kv: int,
    block_q: int,
    block_k: int,
    causal: bool,
    window: int = 0,
    prefix: int = 0,
    softmax_scale=None,
):
    """q: (BH, Sq, D); k/v: (BKH, Sk, D), BH = BKH * n_q_per_kv (per batch).

    NOTE caller lays heads out so q row h maps to kv row h // n_q_per_kv.
    """
    BH, Sq, D = q.shape
    BKH, Sk, _ = k.shape
    assert BH == BKH * n_q_per_kv
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    G = n_q_per_kv

    kernel = functools.partial(
        _flash_kernel,
        bq=block_q, bk=block_k, nk=nk,
        causal=causal, window=window, prefix=prefix, scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=kernels.interpret_mode(),
    )(q, k, v)

"""Jitted public wrapper: (B, S, H, D) model layout -> kernel layout.

Block sizes default to the CAT plan's MHA-stage PU tile (clamped to the
sequence), mirroring how the paper assigns ATB work to PU specifications.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_call


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix", "block_q", "block_k"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    prefix: int = 0,
    block_q: int = 128,
    block_k: int = 128,
):
    """q: (B, Sq, H, D); k/v: (B, Sk, KH, D). Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    bq = min(block_q, Sq)
    while Sq % bq:
        bq //= 2
    bk = min(block_k, Sk)
    while Sk % bk:
        bk //= 2
    # (B, S, H, D) -> (B*H, S, D) with q head h consuming kv head h // G
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)
    out = flash_attention_call(
        qr, kr, vr,
        n_q_per_kv=G, block_q=bq, block_k=bk,
        causal=causal, window=window, prefix=prefix,
    )
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)

"""Jitted wrapper: model layout (B, S, H, D) -> kernel layout (B*H, S, D)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6.kernel import wkv_call


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    chunk: int = 64,
):
    """r/k/v/w: (B, S, H, D) with w the decay in (0,1); u: (H, D)."""
    B, S, H, D = r.shape
    c = min(chunk, S)
    while S % c:
        c //= 2
    to_k = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    logw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-12))
    out = wkv_call(
        to_k(r), to_k(k), to_k(v), to_k(logw), u,
        n_heads=H, chunk=c,
    )
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)

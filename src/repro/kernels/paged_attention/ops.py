"""Jitted public wrapper: model layout -> kernel layout for paged attention.

``pages_per_tile`` is the plan knob (``ServePlan.pages_per_tile``, derived
from the MXU width and the VMEM budget in ``core/plan.derive_serve_plan``);
it is clamped here to a divisor of the table width so the tile sweep covers
the row exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.plan import largest_divisor_of
from repro.kernels.paged_attention.kernel import paged_attention_call


@functools.partial(
    jax.jit,
    static_argnames=("block_size", "window", "pages_per_tile"),
)
def paged_attention(
    q: jax.Array,
    entry: dict,
    table: jax.Array,
    lens: jax.Array,
    q_lens: jax.Array,
    *,
    block_size: int,
    window: int = 0,
    pages_per_tile: int = 0,
) -> jax.Array:
    """q: (B, W, H, D) model layout; entry: paged pool entry
    ({"k","v"[,"k_scale","v_scale"]}, models/cache layout); table (B, MB);
    lens/q_lens (B,).  Returns (B, W, H, D); rows >= q_lens[b] are zeros.
    """
    B, W, H, D = q.shape
    KH = entry["k"].shape[2]
    G = H // KH
    MB = table.shape[1]
    ppt = largest_divisor_of(MB, pages_per_tile or MB)
    # (B, W, H, D) -> (B, KH, W*G, D): q head h = kh*G + g consumes kv head
    # kh (same GQA map as models/layers + the flash kernel); row r = i*G + g,
    # so query i's G heads sit side by side and a slot's live rows are its
    # first G * q_lens[b] (the kernel computes only the rows that hold them).
    qr = q.reshape(B, W, KH, G, D).transpose(0, 2, 1, 3, 4).reshape(B, KH, W * G, D)
    out = paged_attention_call(
        qr,
        entry["k"],
        entry["v"],
        entry.get("k_scale"),
        entry.get("v_scale"),
        table.astype(jnp.int32),
        lens.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        slab=W,
        block_size=block_size,
        pages_per_tile=ppt,
        window=window,
    )
    return out.reshape(B, KH, W, G, D).transpose(0, 2, 1, 3, 4).reshape(B, W, H, D)

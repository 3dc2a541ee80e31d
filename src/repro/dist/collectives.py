"""Manual shard_map collectives (EA4RCA-style communication avoiding).

GSPMD's automatic collectives are the baseline; these primitives are the
hand-scheduled alternatives for the two hot exchanges (paper-to-code map:
docs/ARCHITECTURE.md §"Communication schedule").

``ring_gather_matmul`` / ``overlap_all_gather_matmul``
    The Megatron all-gather-then-matmul replaced by a ring schedule: each
    device matmuls the chunk it currently holds while passing it to its
    neighbour via ``collective-permute``, so communication hides behind
    compute and no ``all-gather`` op appears in the HLO.
    ``ring_gather_matmul`` is the manual-mode core (call it *inside* an
    enclosing ``shard_map`` — the Megatron-SP layer stack does exactly
    that); ``overlap_all_gather_matmul`` wraps it in its own ``shard_map``
    for standalone use.  Both are written with ``lax.scan`` (not
    ``fori_loop``) so the schedule is reverse-mode differentiable and the
    SP layer stack can train through it.

``seq_scatter``
    The inverse half of the Megatron-SP pair: a row-parallel partial
    product is summed *and* re-sharded onto the sequence dim in one
    ``reduce-scatter`` — the residual stream never materializes replicated.

``compressed_psum``
    Gradient cross-replica sum in a quantized domain, reusing
    ``train/compression.py``'s grid (``quantize`` with a shared pmax
    scale).  Wire formats:

    * ``bf16`` — payload crosses the wire as bf16 (16-bit mantissa+exp),
      summed directly; one cast back to fp32 on arrival.
    * ``int8`` — one scalar ``pmax`` establishes a shared grid, each
      replica's payload is an int8 lattice point on that grid, the
      exchange sums small integers (carried as int32 words on this
      backend — a true narrow-wire transport is future work, see
      ROADMAP), and a single multiply reconstructs fp32.
    * anything else — plain fp32 ``psum`` (the uncompressed baseline).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.train.compression import quantize


def ring_gather_matmul(xi, wi, axis: str, n: int, gather_dim: int = 0):
    """Manual-mode ring matmul: gather ``xi`` over ``axis`` while multiplying.

    Call *inside* shard_map.  ``xi`` is this device's chunk, sharded over
    ``axis`` on ``gather_dim`` (0: (m, K) rows; 1: (B, s, K) sequence);
    ``wi`` is this device's (K, N) weight shard (replicated or
    column-parallel — the ring does not care).  At step i each device
    multiplies the chunk that originated ``i`` hops behind it and forwards
    it around the ring; after ``n`` steps every device holds the full
    ``x @ wi`` with no all-gather of ``x`` ever materialized.
    """
    idx = lax.axis_index(axis)
    chunk_len = xi.shape[gather_dim]
    out_shape = list(xi.shape)
    out_shape[gather_dim] = chunk_len * n
    out_shape[-1] = wi.shape[1]
    out0 = jnp.zeros(tuple(out_shape), xi.dtype)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        out, chunk = carry
        src = (idx - i) % n  # origin of the chunk currently held
        start = [0] * len(out_shape)
        start[gather_dim] = src * chunk_len
        out = lax.dynamic_update_slice(out, chunk @ wi, tuple(start))
        chunk = lax.ppermute(chunk, axis, perm)
        return (out, chunk), None

    (out, _), _ = lax.scan(step, (out0, xi), jnp.arange(n))
    return out


def overlap_all_gather_matmul(mesh, x, w, axis: str = "model"):
    """Compute ``x @ w`` with x row-sharded over ``axis``, w replicated.

    Standalone shard_map wrapper around :func:`ring_gather_matmul`: after
    ``n`` ring steps every device holds the replicated (M, N) product
    without ever materializing an all-gather of x.
    """
    n = dict(mesh.shape)[axis]

    def ring(xi, wi):
        return ring_gather_matmul(xi, wi, axis, n, gather_dim=0)

    return shard_map(
        ring,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )(x, w)


def seq_scatter(partial, axis: str, scatter_dim: int = 1):
    """Manual-mode reduce-scatter: sum row-parallel partials over ``axis``
    and hand each device its ``scatter_dim`` chunk (the Megatron-SP
    "g-bar" collective that returns the residual to sequence sharding)."""
    return lax.psum_scatter(partial, axis, scatter_dimension=scatter_dim, tiled=True)


def compressed_psum(g, axis, mode: str = "int8"):
    """Cross-replica gradient sum with a compressed wire format.

    Call inside shard_map; ``axis`` may be one name or a tuple.  int8: a
    shared scale (one scalar pmax) puts every replica's payload on the same
    int8 grid (``train/compression.quantize`` with an explicit scale), the
    exchange sums small integers, and one multiply reconstructs fp32 — the
    mantissa crossing the wire is 8-bit.  bf16: the exchange itself runs in
    bf16.  Both reductions are plain psums so shard_map's replication
    checker accepts ``out_specs=P()``.
    """
    if mode == "bf16":
        q, _ = quantize(g, mode)
        return lax.psum(q, axis).astype(jnp.float32)
    if mode == "int8":
        g32 = g.astype(jnp.float32)
        amax = lax.pmax(jnp.max(jnp.abs(g32)), axis)  # shared grid scale
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q, _ = quantize(g32, mode, scale=scale)
        return lax.psum(q.astype(jnp.int32), axis).astype(jnp.float32) * scale
    return lax.psum(g, axis)

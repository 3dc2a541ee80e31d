"""Fused paged-attention Pallas kernel: decode/mixed-slab attention straight
off the block table.

The serving engine's old path gathered every slot's KV pages into a dense
``(B, cache_len, KH, D)`` HBM buffer before attending — one full write + read
of the whole cache per layer per step.  This kernel consumes the block-table
row directly: for each slot it walks the table in tiles of
``pages_per_tile`` pages, streams whole int8/bf16 pages (all KV heads at
once — one contiguous DMA per pool per page) into a VMEM tile, dequantizes
int8 pages in-kernel on ``train/compression.quantize``'s per-(token,
kv-head) grid, and runs the online-softmax flash loop with per-slot length
masking and sliding-window wraparound.  No dense gathered cache ever exists
in HBM.

Layouts (ops.py does the model-layout shuffle):
  q       (B, KH, G*W, D)   row r of slot b = query i = r // G of group
                            g = r % G, at absolute position lens[b] + i;
                            a slot's live rows are its first G * q_lens[b]
  pools   (N, bs, KH, D)    k/v pages; 16-bit float pools arrive bitcast
                            to int16 (bits are bits for a DMA, and the
                            interpreter's bf16 copy path is pathological)
  scales  (N, 1, P) f32    int8 pools only: one scale per (token, head)
                            at lane i * KH + h, P = bs * KH rounded up to
                            128 lanes, so a page's scales are one
                            lane-aligned DMA (core/plan.int8_scale_lanes)
  table   (B, MB) int32     scalar-prefetched; block 0 is the trash block
  lens    (B)    int32      positions already cached per slot
  q_lens  (B)    int32      live query rows (0 idle / 1 decode / <=W prefill)

Grid (B, NT), table tiles innermost; the (m, l, acc) scratch carries the
online softmax across the tile sweep, all KV heads batched in one program.
Tiles entirely past a slot's high-water mark (or entirely below its
attention window) skip both the DMA and the compute — per-slot work is
proportional to the slot's live context, not to ``cache_len``.

Within a live tile the score, softmax and accumulate work follows the
slot's live rows, not the whole G*W (:func:`row_sizes`): one update of the
slot's first Rs rows (G rounded up to the 8-row sublane tile) when the
live rows fit them — every decode slot and every W = 1 step — else of the
first multiple of 128 rows that holds them, up to all G*W for a full
prefill chunk.  Rows past the update keep their zero carry and come back
as exact zeros.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30

# VMEM one grid step holds per element of its page tile (ppt * bs * KH * D
# elements): the DMA'd tile plus the widened, dequantized and re-laid-out
# copies Mosaic keeps live around the two dots.  An upper bound fitted to
# compile probes on a described v5e (KH 4-8, D 128, bs 16, slab 1-256,
# f32 queries), where it measured 38-44 B (16-bit pages), 56-64 B (int8)
# and 68 B (fp32).
_TILE_BYTES_PER_ELEM = {"bf16": 48, "int8": 72, "fp32": 72}


# The kernel's name: the scope of its custom call in the compiled HLO's op
# metadata (``.../paged_attention/pallas_call``), where a trace reader finds it.
NAME = "paged_attention"


def _padded(n: int, m: int) -> int:
    return -(-n // m) * m


def row_sizes(G: int, W: int) -> tuple[int, ...]:
    """Query-row counts a live tile's update can cover, each from a slot's
    first row: G rounded up to the 8-row sublane tile (a decode slot), then
    multiples of 128 rows (of a larger step where G * W > 1024, so there are
    at most 8) up to all G * W.  A slot's update covers the smallest that
    holds its G * q_len live rows."""
    GW = G * W
    step = 128 * -(-GW // 1024)
    return tuple(sorted({min(_padded(G, 8), GW), *range(step, GW, step), GW}))


def rows_computed(G: int, W: int, q_lens) -> np.ndarray:
    """Query rows per KV head the kernel computes on each live tile of slots
    with ``q_lens`` live queries (0 for an idle slot), by :func:`row_sizes`."""
    sizes = np.asarray(row_sizes(G, W))
    n = G * np.asarray(q_lens, np.int64)
    return np.where(n == 0, 0, sizes[np.searchsorted(sizes, n)])


def vmem_bytes(
    *, kv_dtype: str, q_itemsize: int, kv_heads: int, rows: int,
    head_dim: int, block_size: int, pages_per_tile: int,
) -> int:
    """VMEM the kernel needs for one (slot, tile) grid step.

    ``rows`` = G * W query rows per KV head.  Counts the double-buffered q
    and output blocks, the (m, l, acc) carry, the two (KH, rows, T) f32
    score/probability arrays and the page tile with its in-kernel copies,
    each padded to the (8 x 128) vreg tiling the way Mosaic lays it out."""
    KH, D, T = kv_heads, _padded(head_dim, 128), pages_per_tile * block_size
    sub = 8 * (4 // max(q_itemsize, 1))  # sublanes of one q/out vreg tile
    blocks = 4 * KH * _padded(rows, sub) * D * q_itemsize
    carry = KH * _padded(rows, 8) * D * 4 + 2 * _padded(KH, 8) * _padded(rows, 128) * 4
    scores = 2 * KH * _padded(rows, 8) * _padded(T, 128) * 4
    tile = T * _padded(KH, 8) * head_dim * _TILE_BYTES_PER_ELEM[kv_dtype]
    return blocks + carry + scores + tile


def _paged_kernel(
    # scalar prefetch
    tbl_ref, lens_ref, qlens_ref,
    # inputs: q block in VMEM, pools pinned in HBM/ANY
    q_ref, k_ref, v_ref, ks_ref, vs_ref,
    # output
    o_ref,
    # scratch
    kt, vt, kst, vst, m_ref, l_ref, acc_ref, sems,
    *, G: int, W: int, bs: int, ppt: int, nt: int, window: int, scale: float,
    quantized: bool,
):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = lens_ref[b]  # first live query position of this slot
    q_len = qlens_ref[b]
    tile_lo = t * (ppt * bs)  # absolute position of the tile's first key
    tile_hi = tile_lo + ppt * bs - 1
    # Tile liveness: anything to attend here?  Keys above the slot's last
    # query position are future/trash; with a sliding window, keys below
    # base - (window - 1) are out of every row's window (SWA "wraparound":
    # contexts longer than the window skip their own oldest tiles).
    live = (q_len > 0) & (tile_lo <= base + q_len - 1)
    if window > 0:
        live &= tile_hi >= base - (window - 1)

    @pl.when(live)
    def _tile():
        def copies(p):
            blk = tbl_ref[b, t * ppt + p]
            ops = [
                pltpu.make_async_copy(k_ref.at[blk], kt.at[p], sems.at[0]),
                pltpu.make_async_copy(v_ref.at[blk], vt.at[p], sems.at[1]),
            ]
            if quantized:
                ops += [
                    pltpu.make_async_copy(ks_ref.at[blk], kst.at[p], sems.at[2]),
                    pltpu.make_async_copy(vs_ref.at[blk], vst.at[p], sems.at[3]),
                ]
            return ops

        # Stream the tile's pages into VMEM, one page-fetch ahead of the
        # wait (double-buffered pipeline; a fori_loop so the trace stays
        # O(1) in pages_per_tile instead of unrolling every DMA).
        for cp in copies(0):
            cp.start()

        def fetch(p, _):
            @pl.when(p + 1 < ppt)
            def _next():
                for cp in copies(p + 1):
                    cp.start()

            for cp in copies(p):
                cp.wait()
            return 0

        jax.lax.fori_loop(0, ppt, fetch, 0)

        KH, D = q_ref.shape[1], q_ref.shape[3]
        T = ppt * bs

        def pages(tile, st=None):  # (ppt, bs, KH, D) -> (T, KH, D)
            if tile.dtype == jnp.int16:
                # bf16 bits in an int16 carrier — re-tag and keep the MXU
                # operand in bf16 (f32 accumulate): no widening pass over
                # the tile, the matmul upconverts in-register.
                return jax.lax.bitcast_convert_type(
                    tile.reshape(T, KH, D), jnp.bfloat16
                )
            x = tile.astype(jnp.float32)
            if st is not None:
                # int8 dequant on the per-(token, head) grid: scales
                # (ppt, 1, P) hold token i, head h of page p at lane
                # i * KH + h; as a (ppt, bs * KH, 1) column they broadcast
                # over D
                sc = st.reshape(ppt, st.shape[-1])[:, : bs * KH]
                x = x.reshape(ppt, bs * KH, D) * sc[:, :, None]
            return x.reshape(T, KH, D)

        k = pages(kt[...], kst[...] if quantized else None)
        v = pages(vt[...], vst[...] if quantized else None)

        # Pages stay in their DMA'd (token, head, d) layout; the head dim
        # rides as a dot_general batch dim so no in-VMEM transpose is paid.
        def update(R):
            """Online-softmax update of the slot's first R query rows."""
            q = q_ref[0, :, :R, :].astype(k.dtype)  # (KH, R, D)
            s = (
                jax.lax.dot_general(
                    q, k, (((2,), (2,)), ((0,), (1,))),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # (KH, R, T)

            # row r holds query r // G at position base + r // G; with
            # d = G * (key position - base), the key is causal for row r
            # iff d <= r and inside the window iff r < d + G * window, so
            # the mask needs no integer division
            r = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
            d = G * (tile_lo - base + jax.lax.broadcasted_iota(jnp.int32, (R, T), 1))
            valid = (d <= r) & (r < G * q_len)
            if window > 0:
                valid &= r < d + G * window
            valid = valid[None]  # broadcast over KH
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[:, :R]
            m_new = jnp.maximum(m_prev, s.max(axis=-1))
            # `where` (not bare exp) so fully-masked rows contribute exactly
            # 0 while m is still NEG_INF — exp(NEG_INF - NEG_INF) would be 1.
            p_ = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, :R] = l_ref[:, :R] * corr + p_.sum(axis=-1)
            acc_ref[:, :R, :] = acc_ref[:, :R, :] * corr[..., None] + (
                jax.lax.dot_general(
                    p_.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
                    preferred_element_type=jnp.float32,
                )
            )
            m_ref[:, :R] = m_new

        # one update of the smallest row count that holds the live rows: a
        # static slice, so every size is its own branch
        n_live, lo = G * q_len, 0
        for R in row_sizes(G, W):
            pl.when((n_live > lo) & (n_live <= R))(functools.partial(update, R))
            lo = R

    @pl.when(t == nt - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        ).astype(o_ref.dtype)


def paged_attention_call(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_scale,
    v_scale,
    table: jax.Array,
    lens: jax.Array,
    q_lens: jax.Array,
    *,
    slab: int,
    block_size: int,
    pages_per_tile: int,
    window: int = 0,
    softmax_scale=None,
):
    """q: (B, KH, G*W, D) kernel layout; pools (N, bs, KH, D), int8 scales
    (N, 1, P); returns the same (B, KH, G*W, D).  ``pages_per_tile`` must
    divide the table width."""
    B, KH, GW, D = q.shape
    MB = table.shape[1]
    bs = block_size
    ppt = pages_per_tile
    assert MB % ppt == 0, (MB, ppt)
    nt = MB // ppt
    quantized = k_scale is not None
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    if k_pages.dtype == jnp.bfloat16:
        # DMA bits, not floats: the interpreter copies bf16 element-wise
        # (~70x slower than int16); on hardware the bitcast is a no-op and
        # the kernel re-widens with a 16-bit shift.
        k_pages = jax.lax.bitcast_convert_type(k_pages, jnp.int16)
        v_pages = jax.lax.bitcast_convert_type(v_pages, jnp.int16)

    kernel = functools.partial(
        _paged_kernel,
        G=GW // slab, W=slab, bs=bs, ppt=ppt, nt=nt, window=window, scale=scale,
        quantized=quantized,
    )
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    scale_tile = (ppt,) + k_scale.shape[1:] if quantized else (1, 1, 128)
    scratch = [
        pltpu.VMEM((ppt, bs, KH, D), k_pages.dtype),  # k tile
        pltpu.VMEM((ppt, bs, KH, D), k_pages.dtype),  # v tile
        pltpu.VMEM(scale_tile, jnp.float32),  # k scales (int8 only)
        pltpu.VMEM(scale_tile, jnp.float32),  # v scales
        pltpu.VMEM((KH, GW), jnp.float32),  # m
        pltpu.VMEM((KH, GW), jnp.float32),  # l
        pltpu.VMEM((KH, GW, D), jnp.float32),  # acc
        pltpu.SemaphoreType.DMA((4,)),
    ]
    if not quantized:  # keep operand count static: pass the pools twice
        k_scale, v_scale = k_pages, v_pages
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nt),
            in_specs=[
                pl.BlockSpec((1, KH, GW, D), lambda b, t, *_: (b, 0, 0, 0)),
                pool_spec, pool_spec, pool_spec, pool_spec,
            ],
            out_specs=pl.BlockSpec((1, KH, GW, D), lambda b, t, *_: (b, 0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, GW, D), q.dtype),
        interpret=kernels.interpret_mode(),
        name=NAME,
    )(table, lens, q_lens, q, k_pages, v_pages, k_scale, v_scale)

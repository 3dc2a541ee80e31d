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

Grid (B,): one program per slot, which sweeps only the slot's live table
tiles (:func:`sweep_schedule`: none past its high-water mark, none wholly
below its attention window) in a loop, so per-slot work is proportional to
the slot's live context, not to ``cache_len``; the (m, l, acc) scratch
carries the online softmax across the sweep, all KV heads batched.  The
page tiles are double-buffered across the whole kernel: each tile's DMAs
start before the tile ahead of it computes — the slot's next tile, or at
a slot's last tile the next live slot's first — and are waited on where
the tile begins.

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

# VMEM the kernel holds per element of its page tile (ppt * bs * KH * D
# elements): the two DMA buffers plus the widened, dequantized and
# re-laid-out copies Mosaic keeps live around the two dots.  Fitted to
# compile probes on a described v5e (KH 8, D 128, bs 16, slab 1 and 256,
# f32 queries, 1-32 pages a tile): between 4 and 32 pages the footprint
# grew by 50-56 B an element (16-bit pages), 56-84 B (int8) and FP32_TBD B
# (fp32).
_TILE_BYTES_PER_ELEM = {"bf16": 52, "int8": 76, "fp32": 80}
# What the probes needed beyond the model's parts at one page a tile.
_FIXED_BYTES = 2**18


# Mosaic's default scoped-VMEM limit (an eighth of a v5e's 128 MiB), what a
# kernel that asks for nothing may use.
_MOSAIC_SCOPED_VMEM = 16 * 2**20

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
    """VMEM the kernel needs: an upper bound on what Mosaic allocates.

    ``rows`` = G * W query rows per KV head.  Counts the double-buffered q
    and output blocks and the (m, l, acc) carry, each padded to the
    (8 x 128) vreg tiling the way Mosaic lays it out, then the larger of
    the two (KH, rows, T) f32 score/probability arrays and the page tiles
    with their in-kernel copies: the compile probes grew by the larger
    alone, never by both (at 32 pages and 512 rows the scores alone would
    be 16 MiB, and the probe held 37 MiB beyond the blocks and carry)."""
    KH, D, T = kv_heads, _padded(head_dim, 128), pages_per_tile * block_size
    sub = 8 * (4 // max(q_itemsize, 1))  # sublanes of one q/out vreg tile
    blocks = 4 * KH * _padded(rows, sub) * D * q_itemsize
    carry = KH * _padded(rows, 8) * D * 4 + 2 * _padded(KH, 8) * _padded(rows, 128) * 4
    scores = 2 * KH * _padded(rows, 8) * _padded(T, 128) * 4
    tile = T * _padded(KH, 8) * head_dim * _TILE_BYTES_PER_ELEM[kv_dtype]
    return blocks + carry + max(scores, tile) + _FIXED_BYTES


def live_tiles(lens, q_lens, *, tile: int, n_tiles: int, window: int = 0, xp=jnp):
    """(first live table tile, live tiles) of each slot, 0 tiles for an
    idle one: the tiles of ``tile`` keys that hold a key at or below the
    slot's last query position and, with a sliding window, one inside the
    first query's window (SWA "wraparound": contexts longer than the window
    skip their own oldest tiles).  ``xp`` is ``jnp`` in the kernel's
    wrapper and ``np`` for the engine's counters on the host."""
    end = xp.where(q_lens > 0, xp.minimum((lens + q_lens - 1) // tile + 1, n_tiles), 0)
    lo = xp.maximum(lens - (window - 1), 0) // tile if window > 0 else xp.zeros_like(lens)
    lo = xp.minimum(lo, end)
    return lo, end - lo


def sweep_schedule(lens, q_lens, *, tile: int, n_tiles: int, window: int = 0):
    """The kernel's sweep: a (4, B) int32 array of, per slot, its first live
    tile, its number of live tiles (:func:`live_tiles`), the next slot with
    live tiles (B if none) and the ordinal of its first tile in the whole
    kernel's sweep, which fixes the buffer the tile lands in."""
    B = lens.shape[0]
    lo, n = live_tiles(lens, q_lens, tile=tile, n_tiles=n_tiles, window=window)
    first = jnp.cumsum(n) - n
    live_idx = jnp.where(n > 0, jnp.arange(B, dtype=n.dtype), B)
    nxt = jnp.append(jax.lax.cummin(live_idx, reverse=True)[1:], B)
    return jnp.stack([lo, n, nxt, first]).astype(jnp.int32)


def vmem_limit(**shape) -> int:
    """The scoped VMEM the kernel asks Mosaic for, from its own shapes
    (:func:`vmem_bytes`' arguments): the modelled footprint and a quarter
    more, never below Mosaic's default scoped limit."""
    need = vmem_bytes(**shape)
    return max(need + need // 4, _MOSAIC_SCOPED_VMEM)


def _paged_kernel(
    # scalar prefetch
    tbl_ref, lens_ref, qlens_ref, sweep_ref,
    # inputs: q block in VMEM, pools pinned in HBM/ANY
    q_ref, k_ref, v_ref, ks_ref, vs_ref,
    # output
    o_ref,
    # scratch
    kt, vt, kst, vst, m_ref, l_ref, acc_ref, sems,
    *, G: int, W: int, bs: int, ppt: int, window: int, scale: float,
    quantized: bool,
):
    b = pl.program_id(0)
    B = pl.num_programs(0)
    base = lens_ref[b]  # first live query position of this slot
    q_len = qlens_ref[b]
    t_lo, n_tiles, nxt, first = (sweep_ref[i, b] for i in range(4))
    T = ppt * bs

    def copies(slot, t, buf, p):
        """Page p of table tile t of ``slot`` into buffer ``buf``."""
        blk = tbl_ref[slot, t * ppt + p]
        pairs = [(k_ref, kt), (v_ref, vt)] + ([(ks_ref, kst), (vs_ref, vst)] if quantized else [])
        return [
            pltpu.make_async_copy(src.at[blk], dst.at[buf, p], sems.at[buf, i])
            for i, (src, dst) in enumerate(pairs)
        ]

    def dma(slot, t, buf, op):
        # a fori_loop so the trace stays O(1) in pages_per_tile
        def page(p, c):
            for cp in copies(slot, t, buf, p):
                getattr(cp, op)()
            return c

        jax.lax.fori_loop(0, ppt, page, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    # the kernel's first live tile: no earlier tile prefetched it
    pl.when((first == 0) & (n_tiles > 0))(lambda: dma(b, t_lo, 0, "start"))

    KH, D = q_ref.shape[1], q_ref.shape[3]

    def pages(tile, st=None):  # (ppt, bs, KH, D) -> (T, KH, D)
        if tile.dtype == jnp.int16:
            # bf16 bits in an int16 carrier — re-tag and keep the MXU
            # operand in bf16 (f32 accumulate): no widening pass over
            # the tile, the matmul upconverts in-register.
            return jax.lax.bitcast_convert_type(tile.reshape(T, KH, D), jnp.bfloat16)
        x = tile.astype(jnp.float32)
        if st is not None:
            # int8 dequant on the per-(token, head) grid: scales
            # (ppt, 1, P) hold token i, head h of page p at lane
            # i * KH + h; as a (ppt, bs * KH, 1) column they broadcast
            # over D
            sc = st.reshape(ppt, st.shape[-1])[:, : bs * KH]
            x = x.reshape(ppt, bs * KH, D) * sc[:, :, None]
        return x.reshape(T, KH, D)

    # Pages stay in their DMA'd (token, head, d) layout; the head dim
    # rides as a dot_general batch dim so no in-VMEM transpose is paid.
    def update(R, k, v, tile_lo):
        """Online-softmax update of the slot's first R query rows by the
        tile whose first key sits at position ``tile_lo``."""
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

    def sweep(R):
        """The slot's live tiles, each updating its first R rows."""

        def tile(i, c):
            t = t_lo + i
            buf = jax.lax.rem(first + i, 2)
            # prefetch the tile after this one into the other buffer (its
            # last reader, the tile before, has finished), then wait here
            pl.when(i + 1 < n_tiles)(lambda: dma(b, t + 1, 1 - buf, "start"))
            nb = jnp.minimum(nxt, B - 1)
            pl.when((i + 1 == n_tiles) & (nxt < B))(
                lambda: dma(nb, sweep_ref[0, nb], 1 - buf, "start")
            )
            dma(b, t, buf, "wait")
            k = pages(kt[buf], kst[buf] if quantized else None)
            v = pages(vt[buf], vst[buf] if quantized else None)
            update(R, k, v, t * T)
            return c

        jax.lax.fori_loop(0, n_tiles, tile, 0)

    # one sweep of the smallest row count that holds the live rows: a
    # static slice, so every size is its own branch
    n_live, lo = G * q_len, 0
    for R in row_sizes(G, W):
        pl.when((n_live > lo) & (n_live <= R))(functools.partial(sweep, R))
        lo = R

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
    quantized = k_scale is not None
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    kv_dtype = "int8" if quantized else {2: "bf16", 4: "fp32"}[k_pages.dtype.itemsize]
    if k_pages.dtype == jnp.bfloat16:
        # DMA bits, not floats: the interpreter copies bf16 element-wise
        # (~70x slower than int16); on hardware the bitcast is a no-op and
        # the kernel re-widens with a 16-bit shift.
        k_pages = jax.lax.bitcast_convert_type(k_pages, jnp.int16)
        v_pages = jax.lax.bitcast_convert_type(v_pages, jnp.int16)
    sweep = sweep_schedule(lens, q_lens, tile=ppt * bs, n_tiles=MB // ppt, window=window)

    kernel = functools.partial(
        _paged_kernel,
        G=GW // slab, W=slab, bs=bs, ppt=ppt, window=window, scale=scale,
        quantized=quantized,
    )
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    scale_tile = (2, ppt) + k_scale.shape[1:] if quantized else (2, 1, 1, 128)
    scratch = [
        pltpu.VMEM((2, ppt, bs, KH, D), k_pages.dtype),  # k tiles
        pltpu.VMEM((2, ppt, bs, KH, D), k_pages.dtype),  # v tiles
        pltpu.VMEM(scale_tile, jnp.float32),  # k scales (int8 only)
        pltpu.VMEM(scale_tile, jnp.float32),  # v scales
        pltpu.VMEM((KH, GW), jnp.float32),  # m
        pltpu.VMEM((KH, GW), jnp.float32),  # l
        pltpu.VMEM((KH, GW, D), jnp.float32),  # acc
        pltpu.SemaphoreType.DMA((2, 4)),  # (buffer, pool)
    ]
    if not quantized:  # keep operand count static: pass the pools twice
        k_scale, v_scale = k_pages, v_pages
    limit = vmem_limit(
        kv_dtype=kv_dtype, q_itemsize=q.dtype.itemsize, kv_heads=KH, rows=GW,
        head_dim=D, block_size=bs, pages_per_tile=ppt,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, KH, GW, D), lambda b, *_: (b, 0, 0, 0)),
                pool_spec, pool_spec, pool_spec, pool_spec,
            ],
            out_specs=pl.BlockSpec((1, KH, GW, D), lambda b, *_: (b, 0, 0, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, KH, GW, D), q.dtype),
        # the page buffers and their semaphores carry from one slot's
        # program to the next, so the slots run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=limit,
        ),
        interpret=kernels.interpret_mode(),
        name=NAME,
    )(table, lens, q_lens, sweep, q, k_pages, v_pages, k_scale, v_scale)

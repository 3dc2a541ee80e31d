"""Fused paged-attention kernel vs the jnp oracle: bf16/int8 pages, SWA
wraparound, ragged per-slot lengths, empty slots on trash block 0, mixed
prefill/decode slabs, GQA, and tile-sweep invariance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import int8_scale_lanes
from repro.kernels.paged_attention.kernel import row_sizes, rows_computed, sweep_schedule
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref

KEY = jax.random.PRNGKey(0)


def _quantize(x):
    """train/compression.quantize's per-(token, kv-head) int8 grid; pages
    (N, bs, KH, D) int8, scales in the pool's (N, 1, P) row layout."""
    s = jnp.maximum(jnp.abs(x).max(-1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
    return q, _scale_rows(s)


def _scale_rows(s):
    """(N, bs, KH, 1) scales -> the pool's token-major (N, 1, P) rows."""
    N, bs, KH, _ = s.shape
    rows = s.reshape(N, 1, bs * KH)
    pad = int8_scale_lanes(KH, bs) - bs * KH
    return jnp.pad(rows, ((0, 0), (0, 0), (0, pad)))


def _mk_case(B, W, H, KH, D, bs, MB, kv_dtype, seed=0, q_lens=None, lens=None):
    """Random pools + prefix-dense tables with ragged lens/q_lens (or the
    ones given); slot 0 is empty (kinds 0, whole table on trash block 0)."""
    key = jax.random.fold_in(KEY, seed)
    N = 1 + B * MB
    q = jax.random.normal(key, (B, W, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (N, bs, KH, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (N, bs, KH, D), jnp.float32)
    if kv_dtype == "int8":
        qk, sk = _quantize(k)
        qv, sv = _quantize(v)
        entry = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    else:
        entry = {"k": k.astype(kv_dtype), "v": v.astype(kv_dtype)}
    rng = np.random.default_rng(seed)
    drawn = rng.integers(0, MB * bs - W, B)
    lens = np.asarray(drawn if lens is None else lens, np.int32)
    drawn = rng.integers(1, W + 1, B)
    q_lens = np.asarray(drawn if q_lens is None else q_lens, np.int32)
    table = np.zeros((B, MB), np.int32)
    for b in range(B):
        nb = -(-(int(lens[b]) + W) // bs)
        table[b, :nb] = 1 + b * MB + np.arange(nb)
    table[0], lens[0], q_lens[0] = 0, 0, 0  # empty slot on trash block 0
    return q, entry, jnp.asarray(table), jnp.asarray(lens), jnp.asarray(q_lens)


CASES = [
    # (B, W, H, KH, D, bs, MB, kv_dtype, window, pages_per_tile)
    (3, 1, 4, 2, 32, 4, 8, "float32", 0, 2),  # pure decode, GQA
    (3, 4, 4, 2, 32, 4, 8, "float32", 0, 8),  # mixed slab, one-tile sweep
    (2, 8, 2, 1, 64, 8, 16, "bfloat16", 0, 4),  # bf16 pages, prefill rows
    (2, 4, 4, 2, 32, 4, 16, "int8", 0, 16),  # int8 in-kernel dequant
    (3, 4, 2, 2, 32, 4, 16, "float32", 12, 1),  # SWA, page-at-a-time
    (2, 1, 4, 1, 64, 8, 8, "int8", 20, 2),  # SWA decode past the window
    # live-row updates, with q_lens per slot (slot 0 stays empty):
    # G = 2, G*W = 384 (row sizes 8, 128, 256, 384): decode and full-chunk
    # slots mixed
    (4, 192, 4, 2, 32, 8, 40, "float32", 0, 4, (0, 1, 192, 1)),
    # G = 8 on one KV head (MQA): decode rows fill the 8-row update
    (3, 48, 8, 1, 32, 8, 16, "int8", 0, 2, (0, 48, 1)),
    # G = 1: G*q_len at the small size 8, at 8 + 1 and at 128 + 1
    (4, 256, 2, 2, 32, 8, 48, "float32", 0, 8, (0, 8, 9, 129)),
    # G = 2 with SWA: G*q_len at 8, at 128 + 2, and the full 256 rows
    (4, 128, 4, 2, 32, 8, 32, "bfloat16", 24, 2, (0, 4, 65, 128)),
    # G = 16 on one KV head: sizes G and G*W = 128
    (3, 8, 16, 1, 32, 8, 8, "int8", 0, 2, (0, 1, 8)),
    # multi-page tiles, with the prefetch crossing slot boundaries; each
    # entry also gives lens, so contexts end where the case says:
    # 8-page tiles (32 keys), an idle slot between two live ones, contexts
    # ending mid-tile (slot 1 at key 37, slot 3 at key 72)
    (4, 4, 4, 2, 32, 4, 32, "int8", 0, 8, (0, 1, 0, 3), (0, 37, 50, 70)),
    (4, 4, 4, 2, 32, 4, 32, "bfloat16", 0, 8, (0, 1, 0, 3), (0, 37, 50, 70)),
    # 16-page tiles (64 keys): a prefill slot after a decode slot, one
    # context ending on a tile's first key (slot 3 at key 64)
    (4, 4, 4, 2, 32, 4, 32, "int8", 0, 16, (0, 1, 4, 1), (0, 100, 20, 64)),
    (4, 4, 4, 2, 32, 4, 32, "bfloat16", 0, 16, (0, 1, 4, 1), (0, 100, 20, 64)),
    # windowed slots whose first live tile is not tile 0 (slot 1 from tile
    # 3, slot 3 from tile 6), around an idle slot and a short prefill one
    (5, 2, 4, 2, 32, 4, 16, "int8", 12, 2, (0, 2, 0, 1, 2), (0, 40, 5, 61, 3)),
    (5, 2, 4, 2, 32, 4, 16, "bfloat16", 12, 2, (0, 2, 0, 1, 2), (0, 40, 5, 61, 3)),
    # a window over 8-page tiles: slot 2 sweeps tiles 2-3 of 4, slot 1
    # only its last
    (3, 4, 4, 2, 32, 4, 32, "int8", 20, 8, (0, 4, 1), (0, 120, 100)),
]


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_ref(case):
    B, W, H, KH, D, bs, MB, kv_dtype, window, ppt, *fixed = case
    q, entry, table, lens, q_lens = _mk_case(B, W, H, KH, D, bs, MB, kv_dtype, 0, *fixed)
    got = np.asarray(
        paged_attention(
            q, entry, table, lens, q_lens,
            block_size=bs, window=window, pages_per_tile=ppt,
        ),
        np.float32,
    )
    want = np.asarray(
        paged_attention_ref(
            q, entry, table, lens, q_lens, block_size=bs, window=window
        ),
        np.float32,
    )
    tol = 3e-2 if kv_dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    # the empty slot (trash table, q_lens 0) must come back exactly zero,
    # and so must every row past a slot's q_lens
    np.testing.assert_array_equal(got[0], np.zeros_like(got[0]))
    dead = np.arange(W)[None] >= np.asarray(q_lens)[:, None]  # (B, W)
    np.testing.assert_array_equal(got[dead], np.zeros_like(got[dead]))


@pytest.mark.parametrize(
    "G, W, want",
    [
        (2, 256, (0, 8, 512)),  # sizes 8, 128, ..., 512
        (16, 256, (0, 16, 4096)),  # sizes 16, 512, ..., 4096
        (2, 1, (0, 2, 2)),  # a W = 1 step: the one size G * W
        (16, 1, (0, 16, 16)),
    ],
)
def test_row_sizes_rule(G, W, want):
    """Rows per KV head computed on a live tile for q_len 0, 1 and W: an
    idle slot none, a decode slot the small size, a full chunk all G*W;
    at most 8 sizes above the small one."""
    sizes = row_sizes(G, W)
    assert list(sizes) == sorted(set(sizes)) and sizes[-1] == G * W
    assert len(sizes) <= 9
    assert tuple(rows_computed(G, W, [0, 1, W])) == want


@pytest.mark.parametrize(
    "window, want",
    [
        # first live tile, live tiles, next live slot, ordinal of the first
        (0, [[0, 0, 0, 0, 0], [0, 2, 0, 3, 1], [1, 3, 3, 4, 5], [0, 0, 2, 2, 5]]),
        # the window drops slot 3's tile 0 (its first query sees keys 51-70)
        (20, [[0, 0, 0, 1, 0], [0, 2, 0, 2, 1], [1, 3, 3, 4, 5], [0, 0, 2, 2, 4]]),
    ],
)
def test_sweep_schedule(window, want):
    """The kernel's sweep over slots idle, decode, idle, prefill, prefill:
    live tiles only, each live slot pointing at the next one past the idle
    slot between them, and tile ordinals that alternate the buffers."""
    lens = jnp.asarray([0, 37, 50, 70, 5], jnp.int32)
    q_lens = jnp.asarray([0, 1, 0, 3, 2], jnp.int32)
    got = sweep_schedule(lens, q_lens, tile=32, n_tiles=4, window=window)
    assert np.asarray(got).tolist() == want


def test_tile_sweep_invariance():
    """Plan knob contract (paper C2 analog): pages_per_tile changes the VMEM
    schedule, never the numbers."""
    q, entry, table, lens, q_lens = _mk_case(2, 4, 4, 2, 32, 4, 16, "float32")
    outs = [
        np.asarray(
            paged_attention(
                q, entry, table, lens, q_lens,
                block_size=4, pages_per_tile=ppt,
            )
        )
        for ppt in (1, 2, 4, 8, 16)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-6, atol=1e-6)


def test_swa_wraparound_ignores_pages_below_window():
    """With a sliding window, pages wholly below every row's window must not
    influence the output: corrupting them changes nothing (the kernel skips
    those tiles outright)."""
    B, W, H, KH, D, bs, MB, window = 1, 1, 2, 1, 32, 4, 8, 8
    q, entry, table, lens, q_lens = _mk_case(B, W, H, KH, D, bs, MB, "float32")
    lens = jnp.array([28], jnp.int32)  # deep context, window covers 21..28
    q_lens = jnp.array([1], jnp.int32)
    table = jnp.arange(MB, dtype=jnp.int32)[None] + 1
    base = np.asarray(
        paged_attention(
            q, entry, table, lens, q_lens,
            block_size=bs, window=window, pages_per_tile=2,
        )
    )
    smashed = dict(entry)
    smashed["k"] = entry["k"].at[1:4].set(1e3)  # positions 0..11, all dead
    smashed["v"] = entry["v"].at[1:4].set(-1e3)
    got = np.asarray(
        paged_attention(
            q, smashed, table, lens, q_lens,
            block_size=bs, window=window, pages_per_tile=2,
        )
    )
    np.testing.assert_allclose(got, base, rtol=1e-6, atol=1e-6)


def test_matches_model_fallback_path():
    """The kernel and the model's gather fallback
    (models/layers.paged_attention over models/cache.paged_gather) are the
    same op on live rows."""
    from repro.models.cache import paged_gather
    from repro.models.layers import paged_attention as gather_attn

    B, W, H, KH, D, bs, MB = 2, 4, 4, 2, 32, 4, 8
    q, entry, table, lens, q_lens = _mk_case(B, W, H, KH, D, bs, MB, "float32", seed=3)
    got = np.asarray(
        paged_attention(q, entry, table, lens, q_lens, block_size=bs)
    )
    kf, vf = paged_gather(entry, table, bs, max_blocks=MB)
    pos = np.asarray(lens)[:, None] + np.arange(W)[None]
    want = np.asarray(gather_attn(q, kf, vf, jnp.asarray(pos)))
    live = np.arange(W)[None] < np.asarray(q_lens)[:, None]  # (B, W)
    np.testing.assert_allclose(
        got[live], want[live], rtol=2e-5, atol=2e-5
    )

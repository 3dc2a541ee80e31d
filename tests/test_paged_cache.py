"""Paged KV cache: block allocator, pool write/gather, int8 round-trip."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import derive_plan, derive_serve_plan
from repro.models.cache import (
    init_paged_cache,
    paged_flat_slots,
    paged_gather,
    paged_update,
)
from repro.serve.scheduler import BlockAllocator, Request, Scheduler

MESH1 = {"data": 1, "model": 1}


def _serve(cfg, **kw):
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("decode_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("kv_dtype", "fp32")
    kw.setdefault("prefill_chunk", 4)
    return derive_serve_plan(cfg, MESH1, **kw)


# ---------------------------------------------------------------- allocator
def test_allocator_alloc_free_wraparound():
    a = BlockAllocator(6)  # blocks 1..5 allocatable, 0 is trash
    assert a.available == 5
    got = a.alloc(3)
    assert sorted(got) == [1, 2, 3]
    assert a.alloc(3) is None  # only 2 left
    a.free(got)
    assert a.available == 5
    # wraparound: freed ids come back out
    again = a.alloc(5)
    assert sorted(again) == [1, 2, 3, 4, 5]
    a.free(again)


def test_allocator_rejects_bad_frees():
    a = BlockAllocator(4)
    blocks = a.alloc(2)
    with pytest.raises(ValueError):
        a.free([0])  # trash block is never allocatable
    with pytest.raises(ValueError):
        a.free([9])
    a.free(blocks)
    # double free: counted, warned no-op — with refcounts a trusted second
    # free would silently steal a sharer's block, so the allocator defends
    with pytest.warns(RuntimeWarning):
        assert a.free([blocks[0]]) == []
    assert a.double_frees == 1
    assert a.available == 3  # pool unchanged by the bad free


def test_allocator_refcounts_share_and_release():
    a = BlockAllocator(6)
    got = a.alloc(2)
    a.share(got)  # second owner
    assert a.free(got) == []  # first free: still shared, nothing released
    assert a.available == 3
    assert sorted(a.free(got)) == sorted(got)  # last owner releases
    assert a.available == 5
    assert a.peak_in_use == 2
    with pytest.raises(ValueError):
        a.share(got)  # unowned blocks cannot gain sharers


# ------------------------------------------------------------------- pools
def test_paged_write_gather_round_trip(key):
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=2, seq_len=8, training=False)
    serve = _serve(cfg)
    pools = init_paged_cache(cfg, plan, serve)
    # stack: tuple over the layer pattern, leaves stacked (n_groups, N, ...)
    e0 = jax.tree.map(lambda x: x[0], pools["layers"]["stack"][0])["paged"]

    B, S, KV, Dh = 2, 6, cfg.n_kv_heads, cfg.d_head
    k = jax.random.normal(key, (B, S, KV, Dh), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, Dh), jnp.float32)
    # slot 0 owns blocks 1,2; slot 1 owns blocks 3,4
    table = jnp.array([[1, 2, 0, 0, 0, 0, 0, 0], [3, 4, 0, 0, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    e0 = paged_update(e0, k, v, pos, table, serve.block_size)
    kf, vf = paged_gather(e0, table, serve.block_size)
    np.testing.assert_allclose(np.asarray(kf[:, :S]), np.asarray(k), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vf[:, :S]), np.asarray(v), rtol=1e-6)

    # block reuse (wraparound): slot 1's blocks handed to a new request on
    # slot 0 — fresh writes must fully shadow the stale pages
    table2 = jnp.array([[3, 4, 0, 0, 0, 0, 0, 0], [0, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
    k2 = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, Dh), jnp.float32)
    e0 = paged_update(e0, k2, v, pos, table2, serve.block_size)
    kf2, _ = paged_gather(e0, table2, serve.block_size)
    np.testing.assert_allclose(np.asarray(kf2[0, :S]), np.asarray(k2[0]), rtol=1e-6)


def test_paged_flat_slots_mapping():
    table = jnp.array([[5, 9], [7, 2]], jnp.int32)
    pos = jnp.array([[0, 3, 4], [1, 5, 7]], jnp.int32)  # block_size 4
    got = np.asarray(paged_flat_slots(table, pos, 4))
    assert got.tolist() == [[20, 23, 36], [29, 9, 11]]


def test_int8_kv_round_trip_tolerance(key):
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=2, seq_len=8, training=False)
    serve = _serve(cfg, kv_dtype="int8")
    pools = init_paged_cache(cfg, plan, serve)
    e0 = jax.tree.map(lambda x: x[0], pools["layers"]["stack"][0])["paged"]
    assert e0["k"].dtype == jnp.int8 and "k_scale" in e0

    B, S, KV, Dh = 1, 8, cfg.n_kv_heads, cfg.d_head
    k = 3.0 * jax.random.normal(key, (B, S, KV, Dh), jnp.float32)
    v = 0.1 * jax.random.normal(jax.random.fold_in(key, 3), (B, S, KV, Dh), jnp.float32)
    table = jnp.array([[1, 2, 0, 0, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.arange(S)[None]
    e0 = paged_update(e0, k, v, pos, table, serve.block_size)
    kf, vf = paged_gather(e0, table, serve.block_size)
    # per-(token, head) grid: worst case half a quantization step of the
    # vector max => ~0.5/127 relative to each vector's own scale
    for got, want in ((kf[:, :S], k), (vf[:, :S], v)):
        scale = np.abs(np.asarray(want)).max(axis=-1, keepdims=True)
        err = np.abs(np.asarray(got) - np.asarray(want)) / (scale + 1e-12)
        assert err.max() < 1.0 / 127.0, err.max()


# ---------------------------------------------------- gather high-water mark
def test_paged_gather_clamps_to_live_high_water_mark(key):
    """The fallback gather must materialize only up to the last live block
    column, not always the full cache_len (satellite fix: the eager /
    interpreter path keeps working, just smaller)."""
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=2, seq_len=8, training=False)
    serve = _serve(cfg, max_seq_len=64, block_size=4)  # 16-wide tables
    pools = init_paged_cache(cfg, plan, serve)
    e0 = jax.tree.map(lambda x: x[0], pools["layers"]["stack"][0])["paged"]
    # only blocks in columns 0..1 are live -> gather stops at 2 blocks
    table = jnp.zeros((2, serve.max_blocks_per_seq), jnp.int32)
    table = table.at[0, :2].set(jnp.array([1, 2]))
    table = table.at[1, :1].set(jnp.array([3]))
    kf, vf = paged_gather(e0, table, serve.block_size)
    assert kf.shape[1] == 2 * serve.block_size
    # all-trash tables (idle batch) still yield one block, not zero
    kt, _ = paged_gather(e0, jnp.zeros_like(table), serve.block_size)
    assert kt.shape[1] == serve.block_size
    # explicit override and the jit path keep the full extent available
    kx, _ = paged_gather(e0, table, serve.block_size, max_blocks=4)
    assert kx.shape[1] == 4 * serve.block_size


def test_paged_update_valid_mask_routes_dead_rows_to_trash(key):
    """Mixed-slab writes: rows past a slot's ``kinds`` count must land in
    the trash block, never in the slot's own (or anyone else's) pages."""
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=2, seq_len=8, training=False)
    serve = _serve(cfg)
    pools = init_paged_cache(cfg, plan, serve)
    e0 = jax.tree.map(lambda x: x[0], pools["layers"]["stack"][0])["paged"]
    B, S, KV, Dh = 2, 4, cfg.n_kv_heads, cfg.d_head
    k = jax.random.normal(key, (B, S, KV, Dh), jnp.float32)
    table = jnp.array([[1, 2, 0, 0, 0, 0, 0, 0], [3, 4, 0, 0, 0, 0, 0, 0]], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    valid = jnp.array([[True] * 4, [True, False, False, False]])
    flat = np.asarray(paged_flat_slots(table, pos, serve.block_size, valid))
    bs = serve.block_size
    assert (flat[1, 1:] < bs).all()  # dead rows -> trash block extent
    assert (flat[0] >= bs).all() and flat[1, 0] >= bs  # live rows -> own pages
    # a full masked update leaves the dead rows' would-be pages untouched
    e1 = paged_update(e0, k, k, pos, table, bs, valid)
    np.testing.assert_array_equal(
        np.asarray(e1["k"])[4, 1:], np.zeros((bs - 1, KV, Dh))
    )
    # positions past the table extent (a decode row's dead tail) must clamp,
    # not index out of range
    far = pos + serve.max_blocks_per_seq * bs
    paged_flat_slots(table, far, bs, jnp.zeros_like(valid))


# --------------------------------------------------------------- scheduler
def _drive_slab(s, serve, token=7):
    """One host-side engine iteration against a fake device step."""
    s.admit(10**9)
    s.grow_for_decode()
    kinds = s.slab_view(serve.mixed_slab_width).kinds
    s.slab_done(np.full((serve.decode_batch,), token, np.int64), kinds)
    return kinds


def test_scheduler_eviction_and_recovery():
    """Pool too small for both runners: youngest is evicted (recompute
    preemption), re-admitted after the elder finishes, stream still drains."""
    cfg = get_config("smollm-135m").reduced()
    serve = _serve(cfg, decode_batch=2, block_size=2, prefill_chunk=4, max_seq_len=16)
    serve = dataclasses.replace(serve, n_blocks=1 + 8)  # 8 allocatable blocks
    s = Scheduler(serve)
    r0 = Request(rid="a", prompt=[1, 2, 3, 4], max_new_tokens=9)
    r1 = Request(rid="b", prompt=[5, 6, 7, 8], max_new_tokens=9)
    s.submit(r0)
    s.submit(r1)
    s.admit(0)
    assert {r0.state, r1.state} == {"prefill"}
    for _ in range(40):
        if s.idle:
            break
        _drive_slab(s, serve)
    assert s.n_evictions >= 1
    assert {len(r.out) for r in (r0, r1)} == {9}
    assert r0.state == "done" and r1.state == "done"
    assert s.alloc.available == 8  # everything returned to the pool


def test_grow_preempts_mid_prefill_holder_instead_of_crashing():
    """Oversubscribed pool, one runner + one mid-prefill block holder: the
    runner must preempt the younger prefill slot, not raise pool-exhausted
    (regression: victims used to be drawn from running() only)."""
    cfg = get_config("smollm-135m").reduced()
    serve = _serve(
        cfg, decode_batch=2, block_size=2, prefill_chunk=4, max_seq_len=16,
        mixed_slab_width=4,
    )
    serve = dataclasses.replace(serve, n_blocks=1 + 7)
    s = Scheduler(serve)
    r0 = Request(rid="a", prompt=[1, 2, 3, 4], max_new_tokens=8, arrival=0)
    r1 = Request(rid="b", prompt=[5, 6, 7, 8, 9, 10, 11, 12], max_new_tokens=2,
                 arrival=1)
    s.submit(r0)
    s.submit(r1)
    s.admit(0)  # r0 admitted alone: 2 blocks
    kinds = s.slab_view(4).kinds
    s.slab_done(np.full((2,), 3, np.int64), kinds)  # r0 RUNNING
    s.admit(1)  # r1 takes 4 blocks, 1 free; stays mid-prefill (8 > slab 4)
    s.slab_view(4)
    assert r1.state == "prefill" and r1.blocks
    for _ in range(4):  # r0 decodes until the pool runs dry
        s.grow_for_decode()
        kinds = s.slab_view(4).kinds
        s.slab_done(np.full((2,), 7, np.int64), kinds)
    assert s.n_evictions == 1
    assert r1.state == "waiting" and not r1.blocks
    assert r0.state == "running" and len(r0.out) == 5


def test_slab_view_masks_idle_and_mid_prefill_rows():
    """Packing invariants: an idle slot has no rows (kinds 0, table
    all-trash); a mid-prefill slot carries its own chunk at its own
    positions; the padding rows past the live tokens resolve to the trash
    block, never a slot's pages."""
    from repro.models.transformer import slab_index

    cfg = get_config("smollm-135m").reduced()
    serve = _serve(
        cfg, decode_batch=3, block_size=4, prefill_chunk=4, max_seq_len=32
    )
    s = Scheduler(serve)
    r0 = Request(rid="run", prompt=[1, 2, 3, 4], max_new_tokens=4)
    r1 = Request(rid="pre", prompt=[5, 6, 7, 8, 9, 10, 11, 12], max_new_tokens=4)
    s.submit(r0)
    s.submit(r1)
    s.admit(0)
    kinds = s.slab_view(4).kinds
    s.slab_done(np.full((3,), 3, np.int64), kinds)  # r0 RUNNING, r1 pos=4
    assert r0.state == "running" and r1.state == "prefill" and r1.pos == 4
    s.grow_for_decode()  # r0's next position opens its second block
    slab = s.slab_view(4)
    kinds, T = slab.kinds, serve.step_tokens
    assert slab.tokens.shape == slab.slot.shape == slab.pos.shape == (T,)
    assert kinds[r0.slot] == 1 and kinds[r1.slot] == 4 and slab.lens[r1.slot] == 4
    n = int(kinds.sum())
    mine = lambda r: slab.slot[:n] == r.slot  # noqa: E731
    assert slab.tokens[:n][mine(r0)].tolist() == [3]
    assert slab.pos[:n][mine(r0)].tolist() == [4]
    assert slab.tokens[:n][mine(r1)].tolist() == [9, 10, 11, 12]
    assert slab.pos[:n][mine(r1)].tolist() == [4, 5, 6, 7]
    assert (slab.pos[n:] == -1).all() and (slab.tokens[n:] == 0).all()
    idle = next(b for b in range(3) if s.slots[b] is None)
    assert kinds[idle] == 0 and slab.tables[idle].tolist() == [0] * slab.tables.shape[1]
    # live rows land in their slot's slab rows; padding rows write to the
    # trash block, not to any slot's pages
    flat, _ = slab_index(
        *(jnp.asarray(x) for x in (slab.slot, slab.pos, slab.lens, kinds)), 4
    )
    live = np.asarray(flat) < 3 * 4
    assert live.tolist() == [True] * n + [False] * (T - n)
    pool = np.asarray(
        paged_flat_slots(
            jnp.asarray(slab.tables[slab.slot]), jnp.asarray(slab.pos[:, None]),
            serve.block_size, jnp.asarray(live[:, None]),
        )
    )[:, 0]
    bs = serve.block_size
    assert (pool[n:] < bs).all()
    assert all(f // bs in r1.blocks for f in pool[:n][mine(r1)])
    assert pool[:n][mine(r0)][0] // bs in r0.blocks


def test_scheduler_rejects_oversized_request():
    cfg = get_config("smollm-135m").reduced()
    s = Scheduler(_serve(cfg, max_seq_len=16, prefill_chunk=4, block_size=4))
    with pytest.raises(ValueError):
        s.submit(Request(rid="x", prompt=list(range(14)), max_new_tokens=8))

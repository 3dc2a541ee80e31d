"""Property-based fuzz of the host-side serving state machine.

Six PRs of scheduler features (refcounted blocks, radix prefix sharing,
copy-on-write forks, eviction, rolled spans) share a handful of conserved
invariants.  The hand-picked scenario tests exercise each feature's happy
path; this file drives *randomized* admit/prefill/decode/rolled/evict/
finish sequences against the real :class:`Scheduler` (pure numpy — no
device, no model) and asserts every invariant after every operation:

* **conservation** — free + resident blocks always partition the pool;
* **refcount exactness** — each block's refcount equals the number of live
  requests holding it (so no block is reachable from two block tables
  without refcount > 1, and the free list is exactly the refcount-0 set);
* **index liveness** — every radix-indexed block is owned by some live
  request (``forget`` leaves no dangling node or subtree) and the trie's
  parent/child links stay bidirectionally consistent;
* **table mirroring** — each slot's device-visible block-table row is its
  request's block list (then trash), and every pre-reserved rolled span
  is fully covered before dispatch.

The chaos variant layers the fault machinery on the same churn: a
:class:`FaultInjector` squeezing the free list, zero-deadline expiry,
random cancels and admission shedding — the invariants must hold with the
injector holding blocks, and every request must end finished or shed.

Strategies come from ``hypothesis`` when installed (CI) or the
deterministic stub in ``_hypothesis_stub.py`` otherwise; either way the
sequence is derived from drawn integer seeds, so failures reproduce.
"""
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.plan import derive_serve_plan
from repro.serve.faults import FaultInjector
from repro.serve.scheduler import PREFILL, RUNNING, Request, Scheduler

pytestmark = pytest.mark.slow

MESH1 = {"data": 1, "model": 1}


def _serve_plan(n_blocks=None, decode_batch=3, block_size=4):
    cfg = get_config("smollm-135m").reduced()
    sp = derive_serve_plan(
        cfg, MESH1, max_seq_len=32, decode_batch=decode_batch,
        block_size=block_size, kv_dtype="fp32", prefill_chunk=8,
    )
    if n_blocks is not None:
        import dataclasses

        sp = dataclasses.replace(sp, n_blocks=n_blocks)
    return sp


def _check_invariants(s: Scheduler, held=()) -> None:
    alloc, serve = s.alloc, s.serve
    # conservation: free + resident == allocatable pool
    assert alloc.available + alloc.in_use == serve.n_blocks - 1
    # refcount exactness vs the live holders (slot owners are the only
    # block-holding requests; waiting/finished/evicted hold none —
    # plus whatever blocks a chaos injector is squeezing out of the pool)
    holders: dict[int, int] = {}
    for b in held:
        holders[b] = holders.get(b, 0) + 1
    for r in s.slots:
        if r is None:
            continue
        assert len(set(r.blocks)) == len(r.blocks), f"{r.rid} duplicate block"
        for b in r.blocks:
            holders[b] = holders.get(b, 0) + 1
    for b in range(1, serve.n_blocks):
        assert alloc.refcount(b) == holders.get(b, 0), (
            f"block {b}: refcount {alloc.refcount(b)} != "
            f"{holders.get(b, 0)} holders"
        )
        assert (alloc.refcount(b) == 0) == (b in alloc._free)
    assert alloc.double_frees == 0
    # block tables mirror the block lists exactly (trash elsewhere)
    for r in s.slots:
        if r is None:
            continue
        row = s.table[r.slot]
        assert list(row[: len(r.blocks)]) == r.blocks
        assert not row[len(r.blocks):].any()
    # radix index: every node's block is live, links are consistent
    if s.index is not None:
        for b, node in s.index._by_block.items():
            assert node.block == b
            assert alloc.refcount(b) >= 1, f"indexed block {b} is free"
            assert node.parent is not None
            assert node.parent.children.get(node.key) is node
        # no dangling subtree: everything reachable from the root is in
        # _by_block, and nothing else (forget() removed whole subtrees)
        reachable = set()
        stack = list(s.index._root.children.values())
        while stack:
            n = stack.pop()
            reachable.add(n.block)
            stack.extend(n.children.values())
        assert reachable == set(s.index._by_block)
    # pending fork copies read from still-resident sources
    for src, _dst in s.pending_copies:
        assert alloc.refcount(src) >= 1


def _random_request(rng, i: int, t: int) -> Request:
    # small token alphabet -> frequent prefix collisions (shares + forks)
    n = int(rng.integers(1, 17))
    return Request(
        rid=f"r{i:04d}",
        prompt=[int(x) for x in rng.integers(0, 6, n)],
        max_new_tokens=int(rng.integers(1, 7)),
        arrival=t,
        priority=int(rng.integers(0, 3)),
    )


def _host_step(s: Scheduler, rng) -> None:
    """One engine iteration minus the device: the K=1 slab path with
    fabricated sampled tokens (content never matters to the invariants)."""
    if not s.busy():
        return
    W = s.serve.mixed_slab_width
    kinds = s._slab_view(W).kinds
    sampled = rng.integers(0, 6, s.serve.decode_batch).astype(np.int32)
    s._slab_done(sampled, kinds)


def _rolled_span(s: Scheduler, rng, t: int) -> int:
    """The rolled path: horizon + pre-reservation, then the span's
    bookkeeping with fabricated device output.  Returns iterations used."""
    cap = int(rng.integers(2, 9))
    k, steps = s.plan_rolled(t, cap)
    if k <= 1:
        return 0
    # pre-reservation invariant: every runner's table already covers its span
    for r in s.running():
        need = -(-(int(s.lens[r.slot]) + int(steps[r.slot])) // s.serve.block_size)
        assert len(r.blocks) >= need, (r.rid, len(r.blocks), need)
    out = rng.integers(0, 6, (s.serve.decode_batch, k)).astype(np.int32)
    s._rolled_done(out, steps)
    return int(steps.max())


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_scheduler_invariants_under_random_churn(seed):
    """Randomized admit/prefill/decode/rolled/evict/finish sequences keep
    every conserved invariant, with prefix sharing on and a pool small
    enough that eviction and admission-blocking actually occur."""
    rng = np.random.default_rng(seed)
    s = Scheduler(_serve_plan(n_blocks=1 + 14))
    t, n_submitted = 0, 0
    for _ in range(60):
        op = rng.random()
        if op < 0.35 and n_submitted < 24:
            s.submit(_random_request(rng, n_submitted, t))
            n_submitted += 1
        s.admit(t)
        s.drain_copies()  # engine applies the page copies here
        _check_invariants(s)
        if op < 0.08:  # adversarial preemption of a random holder
            active = s._active()
            if active:
                s.evict(active[int(rng.integers(len(active)))])
                _check_invariants(s)
        if op > 0.75:
            adv = _rolled_span(s, rng, t)
            if adv:
                t += adv
                _check_invariants(s)
                continue
        s._grow_for_decode()
        _check_invariants(s)
        _host_step(s, rng)
        _check_invariants(s)
        t += 1
    # drain to idle: every submitted request must terminate cleanly
    guard = 0
    while not s.idle and guard < 500:
        s.admit(t)
        s.drain_copies()
        s._grow_for_decode()
        _host_step(s, rng)
        _check_invariants(s)
        t += 1
        guard += 1
    assert s.idle, "stream failed to drain"
    assert len(s.finished) == n_submitted
    # a drained scheduler owns nothing: the pool is whole again
    assert s.alloc.in_use == 0
    assert s.index is not None and len(s.index) == 0


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**32 - 1),
    sharing=st.sampled_from([True, False]),
)
def test_allocator_and_index_survive_tiny_pools(seed, sharing):
    """The degenerate pools: barely more blocks than one request needs.
    Admission blocking, self-preemption and forget-on-release must still
    conserve the pool (regression net for the eviction/refcount corners)."""
    import dataclasses

    rng = np.random.default_rng(seed)
    sp = _serve_plan(decode_batch=2, block_size=4)
    sp = dataclasses.replace(sp, n_blocks=1 + 6, prefix_sharing=sharing)
    s = Scheduler(sp)
    t = 0
    for i in range(20):
        if rng.random() < 0.5:
            n = int(rng.integers(1, 9))
            s.submit(Request(
                rid=f"t{i:03d}",
                prompt=[int(x) for x in rng.integers(0, 4, n)],
                max_new_tokens=int(rng.integers(1, 5)),
                arrival=t,
            ))
        s.admit(t)
        s.drain_copies()
        _check_invariants(s)
        try:
            s._grow_for_decode()
        except RuntimeError:
            # "pool exhausted by a single request" is a legal terminal
            # diagnosis for adversarial streams; state must stay consistent
            _check_invariants(s)
            return
        _host_step(s, rng)
        _check_invariants(s)
        t += 1


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_scheduler_invariants_under_chaos_churn(seed):
    """The random churn with the fault machinery layered on: injector
    pool squeezes, zero-deadline expiry, random cancels and admission
    shedding.  The conserved invariants must hold with the injector
    holding blocks (they count as one extra holder each), every request
    must terminate as finished *or* shed — never lost — and releasing the
    squeeze must make the pool whole again."""
    rng = np.random.default_rng(seed)
    inj = FaultInjector(
        int(seed) % 2**31, pressure_rate=0.3, pressure_frac=0.5,
        pressure_steps=3,
    )
    import dataclasses

    sp = dataclasses.replace(
        _serve_plan(n_blocks=1 + 14), admission_patience=6
    )
    s = Scheduler(sp)
    t, n_submitted = 0, 0

    def tick():
        inj.pressure(t, s.alloc)
        s.expire_deadlines(time.perf_counter())
        s.admit(t)
        s.shed_starved(t)
        s.drain_copies()
        _check_invariants(s, held=inj.held)

    for _ in range(60):
        op = rng.random()
        if op < 0.35 and n_submitted < 24:
            r = _random_request(rng, n_submitted, t)
            if rng.random() < 0.15:
                r.deadline_ms = 0.0  # expires the moment it is checked
            s.submit(r)
            n_submitted += 1
        tick()
        if op < 0.10:
            live = s._active() + list(s.waiting)
            if live:
                s.cancel(live[int(rng.integers(len(live)))])
                _check_invariants(s, held=inj.held)
        try:
            s._grow_for_decode()
        except RuntimeError:
            # a squeeze can leave too little pool for a single request's
            # growth: legal terminal diagnosis, state must stay consistent
            _check_invariants(s, held=inj.held)
            return
        _host_step(s, rng)
        _check_invariants(s, held=inj.held)
        t += 1
    guard = 0
    while not s.idle and guard < 500:
        tick()
        try:
            s._grow_for_decode()
        except RuntimeError:
            _check_invariants(s, held=inj.held)
            return
        _host_step(s, rng)
        _check_invariants(s, held=inj.held)
        t += 1
        guard += 1
    assert s.idle, "chaotic stream failed to drain"
    # nothing vanished: every submission is accounted finished or shed
    assert len(s.finished) + len(s.shed) == n_submitted
    for r in s.shed:
        assert r.status in ("shed", "expired", "cancelled", "poisoned")
    inj.release(s.alloc)
    _check_invariants(s)
    assert s.alloc.in_use == 0
    if s.index is not None:
        assert len(s.index) == 0


def test_prefill_then_rolled_spans_preserve_state():
    """Deterministic mixed sequence touching every transition at least once
    (collectable without hypothesis; the seeded tests above generalize it)."""
    rng = np.random.default_rng(0)
    s = Scheduler(_serve_plan(n_blocks=1 + 20, decode_batch=2))
    for i in range(4):
        s.submit(_random_request(rng, i, 0))
    t = 0
    for _ in range(80):
        if s.idle:
            break
        s.admit(t)
        s.drain_copies()
        if not s.prefilling() and s.running():
            adv = _rolled_span(s, rng, t)
            if adv:
                _check_invariants(s)
                t += adv
                continue
        s._grow_for_decode()
        _host_step(s, rng)
        _check_invariants(s)
        t += 1
    assert s.idle and s.alloc.in_use == 0

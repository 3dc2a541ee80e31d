"""Copy-on-write prefix sharing: radix index, refcounted blocks, and the
byte-parity anchor — greedy outputs identical with sharing on vs off across
staggered arrivals, eviction, int8 pages, forks and speculation."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_traces_bounded

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import derive_plan, derive_serve_plan
from repro.serve import Request, ServingEngine, greedy_generate, make_draft_source
from repro.serve.prefix import PrefixIndex
from repro.serve.scheduler import Scheduler

MESH1 = {"data": 1, "model": 1}


def _setup(key, arch="smollm-135m", **serve_kw):
    cfg = get_config(arch).reduced()
    plan = derive_plan(cfg, MESH1, batch=4, seq_len=16, training=False)
    serve_kw.setdefault("max_seq_len", 64)
    serve_kw.setdefault("decode_batch", 4)
    serve_kw.setdefault("block_size", 8)
    serve_kw.setdefault("kv_dtype", "fp32")
    serve_kw.setdefault("prefill_chunk", 8)
    serve = derive_serve_plan(cfg, MESH1, **serve_kw)
    from repro.models.params import init_params

    params = init_params(key, cfg, plan, dtype=jnp.float32)
    return cfg, plan, serve, params


def _oracle(params, cfg, plan, prompt, gen):
    out = greedy_generate(
        params, cfg, plan, {"tokens": jnp.asarray(prompt)[None]},
        n_steps=gen, cache_len=len(prompt) + gen, cache_dtype=jnp.float32,
    )
    return list(np.asarray(out)[0])


def _ab(params, cfg, plan, serve, make_reqs, **engine_kw):
    """Run the same stream with sharing on and off; returns both engines'
    (outputs, engine) pairs.  Fresh Request objects per run — the scheduler
    mutates them."""
    runs = {}
    for sharing in (True, False):
        s = dataclasses.replace(serve, prefix_sharing=sharing)
        eng = ServingEngine(params, cfg, plan, s, **engine_kw)
        runs[sharing] = (eng.run(make_reqs()), eng)
    return runs


# ------------------------------------------------------------- radix index
def test_prefix_index_full_partial_and_cap():
    ix = PrefixIndex(4)
    ix.register(list(range(12)), [5, 6, 7])
    assert len(ix) == 3
    # exact full-block prefix, capped at len-1: a fully resident prompt
    # still leaves its last token to prefill
    full, partial, n = ix.match(list(range(12)))
    assert full == [5, 6] and partial == (7, 3) and n == 11
    # block-aligned shorter prompt
    full, partial, n = ix.match(list(range(8)) + [99])
    assert full == [5, 6] and partial is None and n == 8
    # mid-block divergence: partial head of the next resident block
    full, partial, n = ix.match([0, 1, 2, 3, 4, 5, 99, 98, 97])
    assert full == [5] and partial == (6, 2) and n == 6
    # no match at all
    assert ix.match([99, 98, 97, 96, 95]) == ([], None, 0)
    # too short to share anything (cap = len-1 < block)
    assert ix.match([0, 1])[2] <= 1


def test_prefix_index_register_dedup_and_forget():
    ix = PrefixIndex(4)
    assert ix.register(list(range(8)), [3, 4]) == 2
    # same content in different physical blocks: first resident copy wins
    assert ix.register(list(range(8)), [8, 9]) == 0
    assert ix.match(list(range(8)) + [0])[0] == [3, 4]
    # forgetting an interior block drops its subtree too
    ix.register(list(range(12)), [3, 4, 5])
    ix.forget(4)
    full, partial, n = ix.match(list(range(12)))
    assert full == [3] and partial is None and n == 4
    ix.forget(4)  # idempotent
    ix.forget(77)  # never-indexed blocks tolerated


def test_scheduler_shares_blocks_and_skips_prefill():
    """Host-side: the second request on a registered prefix holds the same
    physical blocks (refcount 2) and prefills only its tail."""
    cfg = get_config("smollm-135m").reduced()
    serve = derive_serve_plan(
        cfg, MESH1, max_seq_len=32, decode_batch=2, block_size=4,
        kv_dtype="fp32", prefill_chunk=4,
    )
    s = Scheduler(serve)
    base = list(range(2, 10))  # two full blocks
    r0 = Request(rid="a", prompt=base + [40, 41], max_new_tokens=4)
    s.submit(r0)
    s.admit(0)
    while r0.state == "prefill":
        kinds = s._slab_view(serve.mixed_slab_width).kinds
        s._slab_done(np.full((2,), 7, np.int64), kinds)
    assert r0.registered == 2  # base blocks indexed once resident
    r1 = Request(rid="b", prompt=base + [50, 51], max_new_tokens=4, arrival=0)
    s.submit(r1)
    s.admit(1)
    assert r1.blocks[:2] == r0.blocks[:2]  # same physical blocks
    assert all(s.alloc.refcount(b) == 2 for b in r0.blocks[:2])
    assert r1.pos == 8 and r1.shared == 2  # only the tail left to prefill
    assert s.n_prefix_hits == 1 and s.prefix_tokens_saved == 8
    # finishing r0 must NOT release the shared blocks under r1
    s.evict(r0)
    assert all(s.alloc.refcount(b) == 1 for b in r1.blocks[:2])
    assert s.index is not None and len(s.index) >= 2


# ------------------------------------------------- engine byte-parity suite
def test_shared_system_prompt_staggered_parity(key):
    """N staggered requests on one system prompt: byte-identical outputs
    with sharing on vs off, against the eager oracle, with prefill tokens
    and peak pool blocks strictly reduced."""
    cfg, plan, serve, params = _setup(key)
    rng = np.random.default_rng(0)
    sysp = [int(t) for t in rng.integers(0, cfg.vocab_size, 19)]
    tails = [list(map(int, rng.integers(0, cfg.vocab_size, n))) for n in (5, 9, 3, 7)]

    def reqs():
        return [
            Request(rid=f"r{i}", prompt=sysp + t, max_new_tokens=6, arrival=2 * i)
            for i, t in enumerate(tails)
        ]

    runs = _ab(params, cfg, plan, serve, reqs)
    (on, eng_on), (off, eng_off) = runs[True], runs[False]
    assert on == off
    for i, t in enumerate(tails):
        assert on[f"r{i}"] == _oracle(params, cfg, plan, sysp + t, 6)
    assert_traces_bounded(eng_on.trace_counts)
    p = eng_on.summary()["prefix"]
    assert p["hits"] >= 3 and p["tokens_saved"] > 0
    assert eng_on.stats["prefill_tokens"] < eng_off.stats["prefill_tokens"]
    assert p["peak_blocks"] < eng_off.summary()["prefix"]["peak_blocks"]


def test_fork_on_write_non_block_aligned_divergence(key):
    """A prompt diverging *inside* a resident block forks it (device page
    copy) and still matches the oracle byte-for-byte."""
    cfg, plan, serve, params = _setup(key)
    rng = np.random.default_rng(1)
    p0 = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
    # diverges at token 10 — two tokens into p0's second block (block 8)
    p1 = p0[:10] + [int(t) + 1 if int(t) + 1 < cfg.vocab_size else 0
                    for t in p0[10:12]] + [3, 5]

    def reqs():
        return [
            Request(rid="own", prompt=p0, max_new_tokens=8, arrival=0),
            Request(rid="div", prompt=p1, max_new_tokens=8, arrival=8),
        ]

    runs = _ab(params, cfg, plan, serve, reqs)
    (on, eng_on), (off, _) = runs[True], runs[False]
    assert on == off
    assert on["own"] == _oracle(params, cfg, plan, p0, 8)
    assert on["div"] == _oracle(params, cfg, plan, p1, 8)
    p = eng_on.summary()["prefix"]
    assert p["forks"] >= 1 and p["fork_copies"] >= 1
    assert_traces_bounded(eng_on.trace_counts)


def test_shared_prefix_eviction_while_sharer_decodes(key):
    """Pool pressure evicts one sharer mid-stream; the survivor keeps
    reading the shared pages (eviction must not release them) and both
    finish oracle-exact."""
    cfg, plan, serve, params = _setup(
        key, decode_batch=2, block_size=2, prefill_chunk=4, max_seq_len=16
    )
    serve = dataclasses.replace(serve, n_blocks=1 + 9)
    rng = np.random.default_rng(2)
    base = [int(t) for t in rng.integers(0, cfg.vocab_size, 4)]
    p0 = base + [int(t) for t in rng.integers(0, cfg.vocab_size, 2)]
    p1 = base + [int(t) for t in rng.integers(0, cfg.vocab_size, 2)]

    def reqs():
        return [
            Request(rid="e0", prompt=p0, max_new_tokens=8, arrival=0),
            Request(rid="e1", prompt=p1, max_new_tokens=8, arrival=3),
        ]

    runs = _ab(params, cfg, plan, serve, reqs)
    (on, eng_on), (off, _) = runs[True], runs[False]
    assert eng_on.sched.n_evictions >= 1
    assert on == off
    assert on["e0"] == _oracle(params, cfg, plan, p0, 8)
    assert on["e1"] == _oracle(params, cfg, plan, p1, 8)
    # everything returned to the pool at the end (no leaked refcounts)
    assert eng_on.sched.alloc.available == 9
    assert len(eng_on.sched.index) == 0


def test_int8_pages_shared_then_forked(key):
    """int8 pool: sharing quantized pages (and forking them, scales
    included) is byte-deterministic — same tokens as the unshared int8
    engine."""
    cfg, plan, serve, params = _setup(key, kv_dtype="int8")
    rng = np.random.default_rng(3)
    p0 = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
    p1 = p0[:10] + [(int(p0[10]) + 1) % cfg.vocab_size, 7, 2, 4]
    p2 = p0[:8] + [int(t) for t in rng.integers(0, cfg.vocab_size, 4)]

    def reqs():
        # i0 must still be resident (blocks registered, not yet released)
        # when i1/i2 arrive: its block 1 fills at written length 16 =
        # 12 prompt + 4 outputs, around iteration 5
        return [
            Request(rid="i0", prompt=p0, max_new_tokens=10, arrival=0),
            Request(rid="i1", prompt=p1, max_new_tokens=6, arrival=7),
            Request(rid="i2", prompt=p2, max_new_tokens=6, arrival=8),
        ]

    runs = _ab(params, cfg, plan, serve, reqs)
    (on, eng_on), (off, _) = runs[True], runs[False]
    assert on == off
    p = eng_on.summary()["prefix"]
    assert p["hits"] >= 2 and p["forks"] >= 1


def test_speculative_decode_over_shared_prefix_parity(key):
    """gamma > 0 (prompt-lookup drafting) over a shared prefix: outputs
    stay byte-identical to both the unshared speculative engine and the
    plain (no-draft) engine."""
    cfg, plan, serve, params = _setup(
        key, mixed_slab_width=8, spec_len=3, draft="ngram"
    )
    assert serve.spec_len == 3
    rng = np.random.default_rng(4)
    sysp = [int(t) for t in rng.integers(0, cfg.vocab_size, 17)]
    tails = [list(map(int, rng.integers(0, cfg.vocab_size, n))) for n in (4, 6, 9)]

    def reqs():
        # accepted drafts finish requests in few iterations: arrivals stay
        # tight so the prefix owner is still resident when sharers land
        return [
            Request(rid=f"g{i}", prompt=sysp + t, max_new_tokens=9, arrival=2 * i)
            for i, t in enumerate(tails)
        ]

    draft = lambda: make_draft_source("ngram", cfg, serve, hw=TPU_V5E)
    runs = _ab(params, cfg, plan, serve, reqs, draft=draft())
    (on, eng_on), (off, _) = runs[True], runs[False]
    assert on == off
    plain = ServingEngine(
        params, cfg, plan, dataclasses.replace(serve, spec_len=0, draft="none")
    )
    assert plain.run(reqs()) == on
    assert eng_on.summary()["prefix"]["hits"] >= 2
    assert_traces_bounded(eng_on.trace_counts)


def test_plan_prefix_sharing_flag_reaches_engine(key):
    cfg, plan, serve, params = _setup(key)
    assert serve.prefix_sharing  # derived plans default to sharing on
    off = dataclasses.replace(serve, prefix_sharing=False)
    assert ServingEngine(params, cfg, plan, off).sched.index is None
    assert "prefix_sharing" in serve.to_record()
    with pytest.raises(dataclasses.FrozenInstanceError):
        serve.prefix_sharing = False

"""Speculative decoding: greedy-token parity for ANY draft source (drafts
change speed, never tokens), slab-native verification through the one jitted
step, length-vector rollback, plan-derived draft depth, and the scheduler
edge cases speculation stresses (mid-speculation eviction, slot reuse after
full rejection, slab-width degradation)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import derive_plan, derive_serve_plan
from repro.serve import Request, ServingEngine, greedy_generate, make_draft_source
from repro.serve.speculative import ModelDraft, NGramDraft, prompt_lookup

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


def _mixed_prompts(cfg, seed=0, lengths=(5, 8, 12, 12, 3, 9)):
    """Half random, half repetitive (so prompt-lookup actually fires)."""
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in lengths]
    for i in range(1, len(prompts), 2):
        pat = prompts[i][:3]
        prompts[i] = (pat * len(prompts[i]))[: len(prompts[i])]
    return prompts


def _self_draft(cfg, serve, params):
    """The target drafting for itself: acceptance == 1, the full-accept path."""
    base = cfg.name[: -len("-reduced")]
    return make_draft_source(base, cfg, serve, params=params, reduced=True)


def _garbage_draft(cfg, serve):
    """Independent random weights: exact parity whatever they propose."""
    base = cfg.name[: -len("-reduced")]
    return make_draft_source(base, cfg, serve, seed=123, reduced=True)


class _OffByOneDraft:
    """Adversarial source: proposes (last+1+i) mod V — random-init targets
    collapse to repeat-token attractors, so these are reliably rejected and
    the rollback path runs every single step."""

    def __init__(self, vocab):
        self.vocab = vocab

    def propose(self, asks):
        return {
            rid: [(seq[-1] + 1 + i) % self.vocab for i in range(n)]
            for rid, seq, n in asks
        }


# ---------------------------------------------------------------- unit level
def test_prompt_lookup_copies_after_last_match():
    assert prompt_lookup([1, 2, 3, 9, 1, 2, 3], 4) == [9, 1, 2, 3]
    # longest n-gram wins; most recent occurrence wins
    assert prompt_lookup([5, 1, 2, 7, 1, 2, 8, 1, 2], 1) == [8]
    assert prompt_lookup([1, 2, 3, 4], 3) == []  # nothing recurs
    # a tail-adjacent match only has a truncated window: an earlier
    # occurrence with the full n tokens of continuation is preferred
    assert prompt_lookup([4, 4, 4], 2) == [4, 4]
    # ... and the truncated draft is still better than none
    assert prompt_lookup([4, 4], 2) == [4]
    # a loop whose earlier occurrence has room yields the full depth
    assert prompt_lookup([1, 2, 3, 1, 2, 3, 1, 2], 3) == [3, 1, 2]


# ----------------------------------------------------------- tentpole parity
@pytest.mark.parametrize("gamma", [1, 2, 4])
def test_spec_parity_ngram_staggered(key, gamma):
    """Staggered mixed-length stream with prompt-lookup drafting at every
    gamma: byte-identical to the eager greedy path, ONE trace of the one
    unified step (no retrace per gamma — depth varies only in `kinds`
    values)."""
    cfg, plan, serve, params = _setup(key, spec_len=gamma, draft="ngram")
    prompts = _mixed_prompts(cfg)
    reqs = [
        Request(rid=f"r{i}", prompt=p, max_new_tokens=6, arrival=2 * i)
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve, draft=NGramDraft())
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        want = _oracle(params, cfg, plan, p, 6)
        assert got[f"r{i}"] == want, (gamma, i, got[f"r{i}"], want)
    assert engine.trace_counts == {"step": 1}
    assert engine.stats["draft_rows"] > 0  # speculation actually engaged


@pytest.mark.parametrize("gamma", [1, 2, 4])
def test_spec_parity_model_draft_full_accept(key, gamma):
    """Self-drafting oracle (drafter == target): every draft accepted, and
    tokens are still byte-identical — the accept path changes speed only.
    The drafter's own step traces exactly once too."""
    cfg, plan, serve, params = _setup(key, spec_len=gamma, draft="smollm-135m")
    prompts = _mixed_prompts(cfg, seed=1, lengths=(5, 9, 12))
    reqs = [
        Request(rid=f"a{i}", prompt=p, max_new_tokens=7) for i, p in enumerate(prompts)
    ]
    draft = _self_draft(cfg, serve, params)
    engine = ServingEngine(params, cfg, plan, serve, draft=draft)
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"a{i}"] == _oracle(params, cfg, plan, p, 7)
    s = engine.summary()
    assert s["spec"]["acceptance_rate"] == 1.0
    assert s["spec"]["tokens_per_spec_step"] > 1.0
    assert engine.trace_counts == {"step": 1}
    assert draft.trace_counts == {"draft_step": 1}


def test_spec_parity_model_draft_independent_weights(key):
    """A drafter with its own (differently seeded) weights: tokens are
    byte-identical to the oracle whatever it proposes — acceptance is a
    speed observation, never a correctness input."""
    cfg, plan, serve, params = _setup(key, spec_len=2, draft="smollm-135m")
    prompts = _mixed_prompts(cfg, seed=2, lengths=(6, 11, 4))
    reqs = [
        Request(rid=f"g{i}", prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(
        params, cfg, plan, serve, draft=_garbage_draft(cfg, serve)
    )
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"g{i}"] == _oracle(params, cfg, plan, p, 6)
    assert engine.stats["draft_rows"] > 0


def test_spec_parity_full_rejection_rollback(key):
    """Adversarial drafts rejected at row 0 every step: pure rollback —
    lens retreats past every draft row, and emitted tokens stay exact."""
    cfg, plan, serve, params = _setup(key, spec_len=3)
    prompts = _mixed_prompts(cfg, seed=2, lengths=(6, 11, 4))
    reqs = [
        Request(rid=f"x{i}", prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(
        params, cfg, plan, serve, draft=_OffByOneDraft(cfg.vocab_size)
    )
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"x{i}"] == _oracle(params, cfg, plan, p, 6)
    s = engine.summary()
    assert s["spec"]["draft_rows"] > 0
    assert s["spec"]["acceptance_rate"] == 0.0
    assert s["spec"]["tokens_per_spec_step"] == 1.0  # rollback to plain pace


def test_spec_swa_wraparound_parity(key):
    """Sliding-window arch past its window: draft rows land beyond the
    window boundary and the kernel's per-row window mask must keep parity."""
    cfg, plan, serve, params = _setup(key, arch="mixtral-8x7b", spec_len=2)
    assert cfg.sliding_window == 16
    prompts = _mixed_prompts(cfg, seed=5, lengths=(20, 7, 25))
    reqs = [
        Request(rid=f"w{i}", prompt=p, max_new_tokens=8)
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve, draft=NGramDraft())
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"w{i}"] == _oracle(params, cfg, plan, p, 8)
    assert engine.trace_counts == {"step": 1}


def test_spec_int8_pages_match_spec_off(key):
    """Int8 KV pages: speculation must reproduce the spec-off engine's
    tokens exactly (draft rows quantize into the pool the same way the
    serial path would have)."""
    cfg, plan, serve, params = _setup(key, kv_dtype="int8", spec_len=2)
    prompts = _mixed_prompts(cfg, seed=3, lengths=(6, 9, 6))
    reqs = lambda pre: [
        Request(rid=f"{pre}{i}", prompt=p, max_new_tokens=5)
        for i, p in enumerate(prompts)
    ]
    want = ServingEngine(params, cfg, plan, serve).run(reqs("q"))
    got = ServingEngine(
        params, cfg, plan, serve, draft=_self_draft(cfg, serve, params)
    ).run(reqs("q"))
    assert got == want


def test_spec_gather_fallback_matches_fused(key):
    """Both attention engines verify the same slab: identical tokens."""
    cfg, plan, serve, params = _setup(key, spec_len=2)
    prompts = _mixed_prompts(cfg, seed=6, lengths=(9, 9, 9))
    reqs = lambda: [
        Request(rid=f"f{i}", prompt=p, max_new_tokens=5)
        for i, p in enumerate(prompts)
    ]
    fused = ServingEngine(
        params, cfg, plan, serve, fused=True, draft=NGramDraft()
    )
    fallback = ServingEngine(
        params, cfg, plan, serve, fused=False, draft=NGramDraft()
    )
    assert fused.run(reqs()) == fallback.run(reqs())


# ------------------------------------------------- scheduler edge cases
def test_spec_eviction_mid_speculation_preserves_tokens(key):
    """A pool too small for the stream forces recompute-preemption while
    slots hold in-flight draft rows; evicted requests still return
    oracle-exact tokens and the drafter state self-heals."""
    cfg, plan, serve, params = _setup(
        key, decode_batch=2, block_size=2, prefill_chunk=4, max_seq_len=16,
        spec_len=2,
    )
    serve = dataclasses.replace(serve, n_blocks=1 + 8)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, 4)) for _ in range(2)]
    reqs = [
        Request(rid=f"e{i}", prompt=p, max_new_tokens=9) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(
        params, cfg, plan, serve, draft=_self_draft(cfg, serve, params)
    )
    got = engine.run(reqs)
    assert engine.sched.n_evictions >= 1
    for i, p in enumerate(prompts):
        assert got[f"e{i}"] == _oracle(params, cfg, plan, p, 9)


def test_spec_slot_reuse_after_full_rejection(key):
    """More requests than slots + a drafter whose every draft is rejected:
    completed slots recycle cleanly (no stale draft rows leak into the next
    occupant) and late requests still match the oracle."""
    cfg, plan, serve, params = _setup(key, decode_batch=2, spec_len=2)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, cfg.vocab_size, 7)) for _ in range(5)]
    reqs = [
        Request(rid=f"s{i}", prompt=p, max_new_tokens=4) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(
        params, cfg, plan, serve, draft=_OffByOneDraft(cfg.vocab_size)
    )
    got = engine.run(reqs)
    assert len(got) == 5
    for i, p in enumerate(prompts):
        assert got[f"s{i}"] == _oracle(params, cfg, plan, p, 4)


def test_spec_degrades_to_plain_decode_when_slab_too_narrow(key):
    """gamma+1 > mixed_slab_width must degrade to plain decode, not
    deadlock: a slab of width 1 has no room for draft rows, so the engine
    never asks the drafter and the stream still drains with exact tokens."""
    cfg, plan, serve, params = _setup(
        key, prefill_chunk=1, mixed_slab_width=1, spec_len=4
    )
    assert serve.spec_len == 0  # the plan already clamps gamma to the slab
    serve = dataclasses.replace(serve, spec_len=4)  # hand-built hostile plan
    prompts = _mixed_prompts(cfg, seed=4, lengths=(4, 6))
    reqs = [
        Request(rid=f"n{i}", prompt=p, max_new_tokens=4) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve, draft=NGramDraft())
    got = engine.run(reqs)
    assert engine.stats["draft_rows"] == 0  # degraded: no speculation at all
    for i, p in enumerate(prompts):
        assert got[f"n{i}"] == _oracle(params, cfg, plan, p, 4)


def test_spec_partial_slab_room_truncates_gamma(key):
    """gamma larger than the slab leaves W-1 draft rows, not a deadlock."""
    cfg, plan, serve, params = _setup(
        key, prefill_chunk=3, mixed_slab_width=3, spec_len=8
    )
    assert serve.spec_len == 2  # clamped to W - 1
    prompts = _mixed_prompts(cfg, seed=7, lengths=(5, 5))
    reqs = [
        Request(rid=f"t{i}", prompt=p, max_new_tokens=6) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(
        params, cfg, plan, serve, draft=_self_draft(cfg, serve, params)
    )
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"t{i}"] == _oracle(params, cfg, plan, p, 6)
    assert engine.stats["draft_rows"] > 0


# ---------------------------------------------------------- plan derivation
def test_serve_plan_spec_len_from_roofline_slack():
    cfg = get_config("smollm-135m")
    # no draft source named -> no speculation
    off = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=2048)
    assert off.spec_len == 0 and off.draft == "none"
    # small decode batch = bandwidth-bound decode = compute slack -> gamma > 0
    small = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, decode_batch=4, draft="ngram"
    )
    assert small.spec_len > 0
    # at/above the machine-balance batch the step is compute-bound: gamma = 0
    big = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, decode_batch=4096, draft="ngram"
    )
    assert big.spec_len == 0
    # gamma never blows the slab width
    narrow = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, decode_batch=4,
        prefill_chunk=2, mixed_slab_width=2, draft="ngram",
    )
    assert narrow.spec_len <= narrow.mixed_slab_width - 1 == 1
    # explicit override still clamps
    forced = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, decode_batch=4,
        mixed_slab_width=4, draft="smollm-135m", spec_len=64,
    )
    assert forced.spec_len == 3 and forced.draft == "smollm-135m"
    assert "spec_len" in forced.to_record()


# -------------------------------------------------------- stats and latency
def test_engine_counts_accepted_tokens_not_slab_rows(key):
    """Throughput counts emitted output tokens: prompt rows live in
    prefill_tokens, rejected draft rows are invisible, and the per-request
    latency percentiles ride the summary."""
    cfg, plan, serve, params = _setup(key, spec_len=2)
    prompts = _mixed_prompts(cfg, seed=8, lengths=(6, 9, 5))
    reqs = [
        Request(rid=f"c{i}", prompt=p, max_new_tokens=5) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(
        params, cfg, plan, serve, draft=_OffByOneDraft(cfg.vocab_size)
    )
    got = engine.run(reqs)
    s = engine.summary()
    n_out = sum(len(v) for v in got.values())
    assert s["generated_tokens"] == n_out == 3 * 5
    assert s["prefill_tokens"] == sum(len(p) for p in prompts)
    assert s["tok_per_s"] == pytest.approx(n_out / s["wall_s"])
    for pkey in ("latency_s", "ttft_s"):
        pct = s[pkey]
        assert pct and pct["p50"] <= pct["p90"] <= pct["p99"]
    assert s["ttft_s"]["p50"] <= s["latency_s"]["p50"]


def test_model_draft_caps_proposals_to_target_vocab(key):
    """A drafter with a bigger vocab than the target must stop at the first
    unverifiable id instead of handing the target an out-of-range token."""
    cfg, plan, serve, params = _setup(key, spec_len=3)
    base = cfg.name[: -len("-reduced")]
    draft = make_draft_source(base, cfg, serve, seed=5, reduced=True)
    assert isinstance(draft, ModelDraft)
    draft.target_vocab = 1  # pathological target: only token 0 verifiable
    out = draft.propose([("x", [0, 0, 0], 3)])
    assert all(t == 0 for t in out["x"])

@pytest.mark.parametrize("source", ["ngram", "self"])
def test_speculation_keeps_parity_when_the_budget_binds(key, source):
    """Slots prefilling at once ask for more rows than the step's budget
    (T = 128 < 12 x ~29 prompt rows): runners keep their gamma+1 rows,
    prompts wait their turn, and the tokens still match the oracle — for
    prompt lookup and for a model drafter whose own catch-up outgrows its
    own budget."""
    cfg, plan, serve, params = _setup(
        key, decode_batch=16, prefill_chunk=16, spec_len=2, draft="ngram",
        rolled_steps=1,
    )
    assert serve.spec_len == 2 and serve.step_tokens == 128
    draft = NGramDraft() if source == "ngram" else _self_draft(cfg, serve, params)
    prompts = _mixed_prompts(
        cfg, seed=4, lengths=(30, 28, 31, 29, 30, 27, 26, 30, 29, 31, 28, 30, 25, 30)
    )
    reqs = [
        Request(rid=f"b{i}", prompt=p, max_new_tokens=6, arrival=2 * (i // 12))
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve, draft=draft)
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"b{i}"] == _oracle(params, cfg, plan, p, 6), i
    st = engine.stats
    assert st["budget_bound_steps"] > 0 and st["prefill_rows_deferred"] > 0
    assert st["spec_slots"] > 0
    assert st["rows_computed"] == st["steps"] * serve.step_tokens
    assert engine.trace_counts == {"step": 1}
    if source == "self":
        assert draft.trace_counts == {"draft_step": 1}

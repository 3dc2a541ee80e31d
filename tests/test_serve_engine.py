"""Continuous-batching engine: greedy-token parity with the eager path,
ONE static-shape unified mixed step under request churn, plan-driven knobs,
sharded serving, and the no-dense-gather guarantee of the fused kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_traces_bounded

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import derive_plan, derive_serve_plan, serve_feasible
from repro.serve import Request, ServingEngine, greedy_generate

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
    """Per-request eager greedy decode (B=1), fp32 cache."""
    out = greedy_generate(
        params, cfg, plan, {"tokens": jnp.asarray(prompt)[None]},
        n_steps=gen, cache_len=len(prompt) + gen, cache_dtype=jnp.float32,
    )
    return list(np.asarray(out)[0])


def test_engine_matches_greedy_generate_staggered(key):
    """Mixed prompt lengths + staggered arrivals through the scheduler must
    produce exactly the eager path's greedy tokens — and ONE trace of the
    single unified step, however the stream churns (the no-retrace +
    one-step-kind acceptance bar)."""
    cfg, plan, serve, params = _setup(key)
    rng = np.random.default_rng(0)
    lengths = [5, 8, 12, 12, 3, 9]
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in lengths]
    reqs = [
        Request(rid=f"r{i}", prompt=p, max_new_tokens=6, arrival=2 * i)
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve)
    assert engine.fused  # single-device default is the Pallas kernel path
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        want = _oracle(params, cfg, plan, p, 6)
        assert got[f"r{i}"] == want, (i, got[f"r{i}"], want)
    assert_traces_bounded(engine.trace_counts)
    assert engine.summary()["mean_occupancy"] > 0.3


def test_engine_swa_wraparound_matches_oracle(key):
    """Sliding-window arch (mixtral-reduced, window 16) with contexts past
    the window: the kernel's window masking must skip the slot's own oldest
    pages and still match the eager path exactly."""
    cfg, plan, serve, params = _setup(key, arch="mixtral-8x7b")
    assert cfg.sliding_window == 16
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (20, 7, 25)]
    reqs = [
        Request(rid=f"w{i}", prompt=p, max_new_tokens=8)
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve)
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"w{i}"] == _oracle(params, cfg, plan, p, 8)
    assert_traces_bounded(engine.trace_counts)


def test_engine_slot_reuse_keeps_parity(key):
    """More requests than slots: completed slots are reused in place
    (padding-free) and late requests still match the oracle."""
    cfg, plan, serve, params = _setup(key, decode_batch=2)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(0, cfg.vocab_size, 7)) for _ in range(5)]
    reqs = [
        Request(rid=f"s{i}", prompt=p, max_new_tokens=4) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve)
    got = engine.run(reqs)
    assert len(got) == 5
    for i, p in enumerate(prompts):
        assert got[f"s{i}"] == _oracle(params, cfg, plan, p, 4)
    assert_traces_bounded(engine.trace_counts)


def test_engine_eviction_preserves_tokens(key):
    """A pool too small for the whole stream forces recompute-preemption;
    evicted requests still return oracle-exact tokens."""
    cfg, plan, serve, params = _setup(
        key, decode_batch=2, block_size=2, prefill_chunk=4, max_seq_len=16
    )
    serve = dataclasses.replace(serve, n_blocks=1 + 8)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab_size, 4)) for _ in range(2)]
    reqs = [
        Request(rid=f"e{i}", prompt=p, max_new_tokens=9) for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve)
    got = engine.run(reqs)
    assert engine.sched.n_evictions >= 1
    for i, p in enumerate(prompts):
        assert got[f"e{i}"] == _oracle(params, cfg, plan, p, 9)


def test_engine_int8_kv_runs_and_is_deterministic(key):
    cfg, plan, serve, params = _setup(key, kv_dtype="int8")
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, 6)) for _ in range(3)]

    def run_once():
        engine = ServingEngine(params, cfg, plan, serve)
        return engine.run(
            Request(rid=f"q{i}", prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)
        )

    a, b = run_once(), run_once()
    assert a == b
    assert all(len(v) == 5 for v in a.values())


def test_engine_fallback_gather_path_matches_fused(key):
    """The jnp gather fallback (model-sharded meshes) and the fused kernel
    are the same op: identical greedy tokens, still one step kind."""
    cfg, plan, serve, params = _setup(key)
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(0, cfg.vocab_size, 9)) for _ in range(3)]
    reqs = lambda: (
        Request(rid=f"f{i}", prompt=p, max_new_tokens=5)
        for i, p in enumerate(prompts)
    )
    fused = ServingEngine(params, cfg, plan, serve, fused=True)
    fallback = ServingEngine(params, cfg, plan, serve, fused=False)
    assert fused.run(reqs()) == fallback.run(reqs())
    assert_traces_bounded(fallback.trace_counts)


def test_engine_sharded_mesh_matches_single(key):
    """Decode through dist.Shardings on whatever host mesh exists (CI runs
    4 fake devices -> (data=1, model=4)): tokens must equal the unsharded
    engine's."""
    from repro.dist.sharding import Shardings
    from repro.launch.mesh import make_host_mesh

    cfg, plan_1, serve, params = _setup(key)
    mesh = make_host_mesh()
    plan = derive_plan(cfg, dict(mesh.shape), batch=4, seq_len=16, training=False)
    sh = Shardings(mesh, plan, cfg)
    sharded_params = jax.device_put(params, sh.param_shardings(params))
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(0, cfg.vocab_size, 9)) for _ in range(3)]
    reqs = lambda: (
        Request(rid=f"m{i}", prompt=p, max_new_tokens=5)
        for i, p in enumerate(prompts)
    )
    got = ServingEngine(sharded_params, cfg, plan, serve, shardings=sh).run(reqs())
    want = ServingEngine(params, cfg, plan_1, serve).run(reqs())
    assert got == want


def _dense_cache_gathers(jaxpr, cache_len):
    """Gather eqns producing a (B, cache_len, ...) dense-cache buffer — the
    signature of ``paged_gather`` materializing the whole table."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "gather":
                for ov in eqn.outvars:
                    shp = ov.aval.shape
                    if len(shp) >= 3 and shp[1] == cache_len:
                        found.append(shp)
            for sub in eqn.params.values():
                subs = sub if isinstance(sub, (list, tuple)) else [sub]
                for s in subs:
                    if hasattr(s, "jaxpr"):
                        walk(s.jaxpr)

    walk(jaxpr)
    return found


def test_unified_step_jaxpr_has_no_dense_gather(key):
    """The acceptance bar of the fused kernel: no dense (B, cache_len, ...)
    gather is ever materialized inside the unified step — the only gathers
    left are the stream-sized embedding/table lookups.  The gather fallback
    is the positive control: its jaxpr must show the dense buffer."""
    cfg, plan, serve, params = _setup(key)
    B, T = serve.decode_batch, serve.step_tokens
    args = (
        params,
        ServingEngine(params, cfg, plan, serve).pools,
        jnp.zeros((3, T), jnp.int32),
        jnp.zeros((B, serve.max_blocks_per_seq), jnp.int32),
        jnp.zeros((B,), jnp.int32),
        jnp.ones((B,), jnp.int32),
        jnp.zeros((B,), jnp.float32),
    )

    def jaxpr_of(engine):
        return jax.make_jaxpr(lambda *a: engine._step.__wrapped__(*a))(*args).jaxpr

    fused = ServingEngine(params, cfg, plan, serve, fused=True)
    assert _dense_cache_gathers(jaxpr_of(fused), serve.max_seq_len) == [], (
        "dense cache_len gather in the unified fused step"
    )
    fallback = ServingEngine(params, cfg, plan, serve, fused=False)
    assert _dense_cache_gathers(jaxpr_of(fallback), serve.max_seq_len)


# ----------------------------------------------------------- rolled loop
def test_rolled_loop_parity_and_span_accounting(key):
    """K>1 rolled spans: byte-identical tokens to the K=1 engine, genuine
    multi-iteration dispatches, and at most ONE compile of each program."""
    cfg, plan, serve, params = _setup(key)
    assert serve.rolled_steps > 1  # tiny weights -> big dispatch slack
    rng = np.random.default_rng(8)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 9, 12)]
    reqs = lambda: [
        Request(rid=f"k{i}", prompt=p, max_new_tokens=8)
        for i, p in enumerate(prompts)
    ]
    rolled = ServingEngine(params, cfg, plan, serve)
    got = rolled.run(reqs())
    k1 = ServingEngine(
        params, cfg, plan, dataclasses.replace(serve, rolled_steps=1)
    )
    assert got == k1.run(reqs())
    assert k1.trace_counts == {"step": 1}
    assert_traces_bounded(rolled.trace_counts)
    assert rolled.trace_counts["rolled_step"] == 1
    r = rolled.summary()["rolled"]
    assert r["enabled"] and r["dispatches"] >= 1 and r["mean_span"] > 1
    # the span really replaced host round-trips: device iterations advanced
    # the clock identically, but the rolled engine dispatched fewer times
    assert rolled.iteration == k1.iteration
    assert rolled.stats["rolled_steps"] > rolled.stats["rolled_dispatches"]
    # a rolled span counts the kernel's live rows as its K=1 steps would
    assert rolled.stats["attn_live_rows"] == k1.stats["attn_live_rows"] > 0


def test_attn_row_counters_follow_the_packed_slab(key):
    """``attn_live_rows`` is G x the live rows each step packed and
    ``attn_rows`` the kernel's row rule over them, at least as many; a
    step with decode slots computes fewer rows than the whole slab."""
    from repro.kernels.paged_attention.kernel import rows_computed

    cfg, plan, serve, params = _setup(key, rolled_steps=1)
    G, W = cfg.n_heads // cfg.n_kv_heads, serve.mixed_slab_width
    engine = ServingEngine(params, cfg, plan, serve)
    packed = []
    step = engine._step
    engine._step = lambda *a: (packed.append(np.asarray(a[5])), step(*a))[1]
    rng = np.random.default_rng(3)
    for i, n in enumerate((5, 20, 3, 17)):
        engine.submit(Request(f"c{i}", list(rng.integers(0, cfg.vocab_size, n)), 6))
    mixed = 0
    while not engine.sched.idle:
        before, n = dict(engine.stats), len(packed)
        engine.step()
        kinds = packed[-1] if len(packed) > n else np.zeros(1, int)
        live = G * int(kinds.sum())
        got = engine.stats["attn_live_rows"] - before["attn_live_rows"]
        rows = engine.stats["attn_rows"] - before["attn_rows"]
        assert got == live
        assert rows == int(rows_computed(G, W, kinds).sum()) >= live
        if (kinds == 1).any():
            assert rows < G * W * int((kinds > 0).sum())
        mixed += bool((kinds == 1).any() and (kinds > 1).any())
    assert mixed  # some step carried decode and prefill slots together
    assert_traces_bounded(engine.trace_counts)


@pytest.mark.parametrize("arch", ["smollm-135m", "mixtral-8x7b"])
def test_attn_tile_counters_follow_the_slot_lengths(key, arch):
    """``attn_tiles``, ``attn_keys_fetched`` and ``attn_live_keys`` are the
    kernel's live tiles, their keys and the keys the live rows attend,
    counted slot by slot from the lengths and live rows each step
    dispatched: three requests on four slots, so steps mix decode, prefill
    and idle slots; mixtral's sliding window drops its slots' oldest
    tiles."""
    cfg, plan, serve, params = _setup(key, arch, rolled_steps=1, pages_per_tile=2)
    T, n_tiles = 2 * serve.block_size, serve.max_blocks_per_seq // 2
    window = cfg.sliding_window
    engine = ServingEngine(params, cfg, plan, serve)
    seen = []
    step = engine._step
    engine._step = lambda *a: (seen.append((np.asarray(a[4]), np.asarray(a[5]))), step(*a))[1]
    rng = np.random.default_rng(5)
    for i, n in enumerate((30, 9, 22)):
        engine.submit(Request(f"t{i}", list(rng.integers(0, cfg.vocab_size, n)), 12))
    kinds_seen = set()
    while not engine.sched.idle:
        before, n = dict(engine.stats), len(seen)
        engine.step()
        want = {"attn_tiles": 0, "attn_keys_fetched": 0, "attn_live_keys": 0}
        for lens, kinds in seen[n:]:
            for ln, q in zip(lens.tolist(), kinds.tolist()):
                kinds_seen.add(min(q, 2))
                if q == 0:
                    continue
                first = max(ln - window + 1, 0) if window else 0
                tiles = sum(t * T <= ln + q - 1 and t * T + T - 1 >= first
                            for t in range(n_tiles))
                want["attn_tiles"] += tiles
                want["attn_keys_fetched"] += tiles * T
                want["attn_live_keys"] += ln + q - first
        for k, v in want.items():
            assert engine.stats[k] - before[k] == v, k
    assert kinds_seen == {0, 1, 2}  # idle, decode and prefill slots
    assert engine.stats["attn_keys_fetched"] >= engine.stats["attn_live_keys"] > 0


def test_packed_step_keeps_parity_when_the_budget_binds(key):
    """More prompt rows than the step's budget (14 slots x ~25 rows against
    T = 128): the prompts are served over several steps, decode slots keep
    decoding meanwhile, the counters see the budget bind, and every token
    matches the eager oracle."""
    cfg, plan, serve, params = _setup(
        key, decode_batch=16, prefill_chunk=16, rolled_steps=1
    )
    assert serve.step_tokens == 128
    rng = np.random.default_rng(11)
    lengths = [30, 3, 28, 31, 6, 29, 30, 27, 26, 30, 25, 31, 22, 30, 12, 29]
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in lengths]
    reqs = [
        Request(rid=f"t{i}", prompt=p, max_new_tokens=5, arrival=0 if i < 14 else 3)
        for i, p in enumerate(prompts)
    ]
    engine = ServingEngine(params, cfg, plan, serve)
    kinds = []
    step = engine._step
    engine._step = lambda *a: (kinds.append(np.asarray(a[5])), step(*a))[1]
    got = engine.run(reqs)
    for i, p in enumerate(prompts):
        assert got[f"t{i}"] == _oracle(params, cfg, plan, p, 5), i
    st = engine.stats
    assert st["budget_bound_steps"] > 0 and st["prefill_rows_deferred"] > 0
    assert st["rows_computed"] == st["steps"] * serve.step_tokens
    assert all(int(k.sum()) <= serve.step_tokens for k in kinds)
    # some step carried decode rows next to prompt chunks
    assert any((k == 1).any() and (k > 1).any() for k in kinds)
    assert engine.trace_counts == {"step": 1}


def test_packed_step_compiles_once_for_every_live_row_count(key):
    """One compile serves a stream of any number of live tokens, 0 to T,
    spread over the slots: only the values of the stream change."""
    from repro.serve.scheduler import pack

    cfg, plan, serve, params = _setup(key, rolled_steps=1)
    engine = ServingEngine(params, cfg, plan, serve)
    B, W, T = serve.decode_batch, serve.mixed_slab_width, serve.step_tokens
    assert T == B * W
    table = np.zeros((B, serve.max_blocks_per_seq), np.int32)
    zero = jnp.zeros((B,), jnp.float32)
    for n in range(T + 1):
        rows, left = {}, n
        for b in range(B):
            k = min(W, left)
            rows[b] = (3 * b, list(range(1, k + 1)))
            left -= k
        slab = pack(rows, table, T)
        assert int(slab.kinds.sum()) == n
        tok, _, finite, engine.pools = engine._step(
            params, engine.pools, slab.stream, slab.tables, slab.lens,
            slab.kinds, zero,
        )
        assert bool(np.asarray(finite).all())
    assert_traces_bounded(engine.trace_counts)
    assert engine.trace_counts == {"step": 1}


def test_budget_serves_runners_first_then_the_oldest_prefill(key):
    """Host-only budget rules: every running slot gets its row (and its
    drafts), prefilling slots share the rest oldest admitted first, a slot
    the budget leaves out gets no rows and does not advance."""
    from repro.serve.scheduler import RUNNING, Scheduler

    cfg = get_config("smollm-135m").reduced()
    serve = derive_serve_plan(
        cfg, MESH1, max_seq_len=64, decode_batch=16, block_size=8,
        kv_dtype="fp32", prefill_chunk=16,
    )
    T, W = serve.step_tokens, serve.mixed_slab_width
    assert (T, W) == (128, 16)
    s = Scheduler(serve)
    reqs = [Request(rid=f"q{i}", prompt=[1 + i] * 40, max_new_tokens=4)
            for i in range(16)]
    for r in reqs:
        s.submit(r)
        s.admit(0)  # one at a time: admission order is t_admit order
    runners = reqs[9:11]
    for r in runners:
        r.state, r.out, r.pos = RUNNING, [9], 40
        s.lens[r.slot] = 40
    s._grow_for_decode({runners[0].rid: 2})
    slab = s._slab_view(W, {runners[0].rid: [5, 6]})
    k = slab.kinds
    assert k[runners[0].slot] == 3 and k[runners[1].slot] == 1
    prefill = [r for r in reqs if r not in runners]  # admitted in this order
    # 128 - 4 runner rows = 124 = 7 x 16 + 12, the rest get none
    assert [int(k[r.slot]) for r in prefill] == [16] * 7 + [12] + [0] * 6
    assert slab.deferred == 4 + 16 * 6
    assert int(k.sum()) <= T and (slab.pos[int(k.sum()):] == -1).all()
    left_out = prefill[-1]
    assert slab.tables[left_out.slot].tolist() == [0] * serve.max_blocks_per_seq
    s._slab_done(np.full((16,), 7, np.int64), k, drafts={runners[0].rid: [5, 6]},
                 vtok=np.full((16, 3), 7, np.int64))
    assert [r.pos for r in prefill] == [16] * 7 + [12] + [0] * 6
    assert left_out.state == "prefill" and not left_out.out


def test_plan_rolled_event_horizon_and_reservation(key):
    """Host-only scheduler checks: the horizon stops at each kind of host
    event, and a granted span is always fully block-covered up front."""
    from repro.serve.scheduler import RUNNING, Scheduler

    cfg = get_config("smollm-135m").reduced()
    serve = derive_serve_plan(
        cfg, MESH1, max_seq_len=64, decode_batch=2, block_size=8,
        kv_dtype="fp32", prefill_chunk=8,
    )

    def runner(s, rid, gen, arrival=0):
        r = Request(rid=rid, prompt=list(range(1, 9)), max_new_tokens=gen,
                    arrival=arrival)
        s.submit(r)
        s.admit(arrival)
        assert r.state == "prefill"
        r.state, r.out = RUNNING, [7]  # first token already emitted
        s.lens[r.slot] = len(r.prompt)
        return r

    # free horizon: cap and the runner's own remaining budget
    s = Scheduler(serve)
    r = runner(s, "a", gen=10)
    k, steps = s.plan_rolled(0, 8)
    assert k == 8 and steps[r.slot] == 8
    assert len(r.blocks) >= -(-(8 + 8) // serve.block_size)  # pre-reserved

    # an unarrived waiter bounds the span by its arrival (admission event)
    s = Scheduler(serve)
    runner(s, "a", gen=10)
    s.submit(Request(rid="w", prompt=[1] * 8, max_new_tokens=4, arrival=3))
    assert s.plan_rolled(0, 8)[0] == 3

    # an arrived-but-blocked waiter: earliest completion is its admission
    s = Scheduler(serve)
    runner(s, "a", gen=3)  # 2 steps of budget left
    runner(s, "b", gen=10)
    s.submit(Request(rid="w", prompt=[1] * 8, max_new_tokens=4, arrival=0))
    assert s.plan_rolled(0, 8)[0] == 2

    # a mid-prefill slot is host work every iteration: K=1
    s = Scheduler(serve)
    runner(s, "a", gen=10)
    p = Request(rid="p", prompt=[1] * 16, max_new_tokens=4)
    s.submit(p)
    s.admit(0)
    assert p.state == "prefill"
    assert s.plan_rolled(0, 8) == (1, None)

    # pool pressure the reservation cannot cover -> K=1 (eviction is the
    # K=1 path's job); nothing is allocated on the refused span
    tiny = dataclasses.replace(serve, n_blocks=2)  # trash + 1
    s = Scheduler(tiny)
    r = runner(s, "a", gen=20)
    held = list(r.blocks)
    assert s.plan_rolled(0, 8) == (1, None)
    assert r.blocks == held and s.alloc.available == 0


def test_summary_safe_at_zero_and_one_sample(key):
    """Regression (PR 7 satellite): summary() used to report None
    throughput for step-driven engines and count-less one-sample
    percentiles.  Cold, one-request and step-driven engines must all
    report sane numbers without run()."""
    cfg, plan, serve, params = _setup(key)
    engine = ServingEngine(params, cfg, plan, serve)
    s = engine.summary()  # cold: zero steps, zero finished requests
    assert s["tok_per_s"] is None and s["wall_s"] is None
    assert s["latency_s"] is None and s["ttft_s"] is None
    assert s["step_ms"] is None and s["tenants"] == {}

    engine.submit(Request(rid="one", prompt=[1, 2, 3], max_new_tokens=2))
    while not engine.sched.idle:
        engine.step()
    s = engine.summary()
    assert s["wall_s"] is None  # run() never measured a wall clock
    assert s["generated_tokens"] == 2
    assert s["device_s"] > 0
    assert s["tok_per_s"] == pytest.approx(2 / s["device_s"])
    lat = s["latency_s"]
    assert lat["n"] == 1  # a 1-sample p99 must be recognizable as such
    assert lat["p50"] == lat["p90"] == lat["p99"] == lat["mean"]
    assert s["step_ms"] is None or s["step_ms"] > 0
    # latency and TTFT as the client sees them, from submission; admission
    # to first token under its own name; the histograms agree
    r = engine.sched.finished[0]
    assert lat["p50"] == pytest.approx(r.t_done - r.t_submit)
    assert s["ttft_s"]["p50"] == pytest.approx(r.t_first - r.t_submit)
    assert s["prefill_s"]["p50"] == pytest.approx(r.t_first - r.t_admit)
    assert s["tenants"][r.tenant]["ttft_s"] == s["ttft_s"]
    m = engine.obs.metrics.snapshot()
    for name, t in (("serve_ttft_ms", r.t_first), ("serve_latency_ms", r.t_done)):
        assert m[name]["samples"][0]["sum"] == pytest.approx((t - r.t_submit) * 1e3)


# ----------------------------------------------------------- plan-driven
def test_serve_plan_derivation_roofline_and_capacity():
    cfg = get_config("smollm-135m")
    sp = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=2048)
    # roofline batch: machine balance ~240 -> pow2 floor, capped by HBM
    assert sp.decode_batch == 128
    assert sp.block_size == TPU_V5E.mxu_dim // 8
    assert sp.kv_dtype == "bf16"
    assert sp.n_blocks == 1 + sp.decode_batch * sp.max_blocks_per_seq
    assert sp.max_concurrency == sp.decode_batch

    # starved HBM must push the KV pages to the paper's int8 grid
    tiny = dataclasses.replace(TPU_V5E, hbm_bytes=1 * 1024**3)
    sp8 = derive_serve_plan(cfg, MESH1, tiny, max_seq_len=2048)
    assert sp8.kv_dtype == "int8"
    assert sp8.decode_batch < sp.decode_batch


def test_serve_plan_kernel_knobs():
    """pages-per-tile comes from the VMEM budget (and divides the table);
    the mixed-slab width defaults to the prefill chunk."""
    cfg = get_config("smollm-135m")
    sp = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=2048)
    assert sp.mixed_slab_width == sp.prefill_chunk
    assert sp.max_blocks_per_seq % sp.pages_per_tile == 0
    assert sp.fused_attention
    # a VMEM-starved chip must take more, smaller tile sweeps
    small = dataclasses.replace(TPU_V5E, vmem_bytes=64 * 1024)
    sp_small = derive_serve_plan(cfg, MESH1, small, max_seq_len=2048)
    assert sp_small.pages_per_tile < sp.pages_per_tile
    # knobs are overridable
    sp_o = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, mixed_slab_width=4, pages_per_tile=2
    )
    assert sp_o.mixed_slab_width == 4 and sp_o.pages_per_tile == 2


def test_serve_plan_rolled_steps_from_dispatch_overhead():
    """K comes from the dispatch-overhead roofline: roll until the host
    round-trip is under ~10% of the span, capped at 32 and clamped by a
    TTFT SLO (an arrival must not wait out a long span)."""
    cfg = get_config("smollm-135m")
    sp = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=2048)
    assert sp.rolled_steps >= 1
    assert sp.rolled_steps & (sp.rolled_steps - 1) == 0  # power of two
    # zero dispatch overhead: nothing to amortize, rolling stays off
    free = dataclasses.replace(TPU_V5E, dispatch_overhead_s=0.0)
    assert derive_serve_plan(cfg, MESH1, free, max_seq_len=2048).rolled_steps == 1
    # pathological dispatch cost saturates the cap
    slow = dataclasses.replace(TPU_V5E, dispatch_overhead_s=1.0)
    assert derive_serve_plan(cfg, MESH1, slow, max_seq_len=2048).rolled_steps == 32
    # a TTFT target clamps the span an in-flight dispatch may hold
    slo = derive_serve_plan(
        cfg, MESH1, slow, max_seq_len=2048, slo_ttft_ms=4.0
    )
    assert slo.rolled_steps < 32
    # explicit override wins and lands in the record
    sp_o = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=2048, rolled_steps=4)
    assert sp_o.rolled_steps == 4
    assert sp_o.to_record()["rolled_steps"] == 4
    assert "rolled_steps=4" in sp_o.describe()


def test_serve_plan_gather_tax_caps_fallback_batch():
    """The roofline's gather-bytes term only exists on the fallback path:
    the dense write+read of a full-context cache per slot per step stops
    the gather engine's batch from amortizing the weight stream, so the
    fused plan must admit at least as many decode slots."""
    cfg = get_config("smollm-135m")
    fused = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=32768)
    gather = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=32768, fused_attention=False
    )
    assert gather.decode_batch < fused.decode_batch


def test_serve_plan_model_axis_scales_batch():
    cfg = get_config("smollm-135m")
    a = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=1024)
    b = derive_serve_plan(cfg, {"data": 1, "model": 4}, TPU_V5E, max_seq_len=1024)
    # TP shards the weight stream: per-chip balance point comes down
    assert b.decode_batch <= a.decode_batch


def test_serve_feasibility_gates():
    ok, _ = serve_feasible(get_config("smollm-135m"))
    assert ok
    for arch in ("rwkv6-1.6b", "recurrentgemma-9b", "whisper-small", "paligemma-3b"):
        ok, reason = serve_feasible(get_config(arch))
        assert not ok and reason
    with pytest.raises(ValueError):
        derive_serve_plan(get_config("rwkv6-1.6b"), MESH1)

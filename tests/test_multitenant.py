"""Multi-tenant scheduling: priority classes, per-tenant fair shares,
SLO-aware chunk sizing, the SLO plan feedback, trace workloads and the
ServeArgs CLI record."""
import argparse
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_traces_bounded

from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import derive_plan, derive_serve_plan
from repro.launch.serve import ServeArgs, build_parser
from repro.serve import (
    Request,
    ServingEngine,
    WORKLOADS,
    make_trace,
    parse_mix,
    per_class_report,
)
from repro.serve.scheduler import Scheduler

MESH1 = {"data": 1, "model": 1}


def _sched(cfg, **kw):
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("decode_batch", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("kv_dtype", "fp32")
    kw.setdefault("prefill_chunk", 4)
    serve = derive_serve_plan(cfg, MESH1, **kw)
    return Scheduler(serve), serve


def _drive(s, serve, token=7):
    s.admit(10**9)
    s.drain_copies()
    s._grow_for_decode()
    kinds = s._slab_view(serve.mixed_slab_width).kinds
    s._slab_done(np.full((serve.decode_batch,), token, np.int64), kinds)


# ----------------------------------------------------------- admission policy
def test_priority_class_admission_order():
    """One free slot, two arrived waiters: the higher priority class is
    admitted first regardless of arrival order."""
    cfg = get_config("smollm-135m").reduced()
    s, serve = _sched(cfg, decode_batch=1)
    lo = Request(rid="lo", prompt=[1, 2, 3], max_new_tokens=2, arrival=0)
    hi = Request(
        rid="hi", prompt=[4, 5, 6], max_new_tokens=2, arrival=1, priority=5
    )
    s.submit(lo)
    s.submit(hi)
    s.admit(5)
    assert hi.state == "prefill" and lo.state == "waiting"


def test_tenant_fair_share_breaks_priority_ties():
    """Equal priority: the tenant holding fewer slots wins the free slot
    even when the loaded tenant's request arrived first."""
    cfg = get_config("smollm-135m").reduced()
    s, serve = _sched(cfg, decode_batch=3)
    a1 = Request(rid="a1", prompt=[1, 2], max_new_tokens=4, tenant="a")
    a2 = Request(rid="a2", prompt=[3, 4], max_new_tokens=4, tenant="a")
    s.submit(a1)
    s.submit(a2)
    s.admit(0)
    assert {a1.state, a2.state} == {"prefill"}
    a3 = Request(rid="a3", prompt=[5, 6], max_new_tokens=4, arrival=0, tenant="a")
    b1 = Request(rid="b1", prompt=[7, 8], max_new_tokens=4, arrival=1, tenant="b")
    s.submit(a3)
    s.submit(b1)
    s.admit(1)  # one slot left: tenant b (0 active) beats tenant a (2 active)
    assert b1.state == "prefill" and a3.state == "waiting"


def test_priority_eviction_and_no_livelock():
    """A senior (higher-priority) runner evicts a junior to grow; a junior
    must never evict a senior — it self-preempts instead."""
    cfg = get_config("smollm-135m").reduced()
    s, serve = _sched(cfg, decode_batch=2, block_size=2, max_seq_len=16)
    serve = dataclasses.replace(serve, n_blocks=1 + 6)
    s = Scheduler(serve)
    hi = Request(rid="hi", prompt=[1, 2, 3, 4], max_new_tokens=9, priority=5)
    lo = Request(rid="lo", prompt=[5, 6, 7, 8], max_new_tokens=9)
    s.submit(hi)
    s.submit(lo)
    for _ in range(40):
        if s.idle:
            break
        _drive(s, serve)
    assert s.n_evictions >= 1
    assert hi.t_done is not None and lo.t_done is not None
    # the high-priority request never lost its slot: one continuous run
    assert hi.t_done < lo.t_done or s.n_evictions == 0
    assert s.alloc.available == 6


def test_slo_chunk_sizing_throttles_sloless_prefills():
    """With an SLO'd prefill at risk (measured step time vs TTFT target),
    SLO-less prefills throttle to one block per step; the SLO'd request
    keeps the full slab width."""
    cfg = get_config("smollm-135m").reduced()
    s, serve = _sched(cfg, decode_batch=2, block_size=4, prefill_chunk=8,
                      max_seq_len=64)
    urgent = Request(
        rid="u", prompt=list(range(24)), max_new_tokens=2, slo_ttft_ms=1.0
    )
    bulk = Request(rid="b", prompt=list(range(24)), max_new_tokens=2)
    s.submit(urgent)
    s.submit(bulk)
    s.admit(0)
    assert not s._slo_pressure()  # no measured step time yet -> no pressure
    s.step_ms = 50.0  # measured steps are slow; 1ms TTFT is at risk
    assert s._slo_pressure()
    kinds = s._slab_view(serve.mixed_slab_width).kinds
    assert kinds[urgent.slot] == 8  # full width
    assert kinds[bulk.slot] == 4  # throttled to one block
    s.step_ms = None
    kinds = s._slab_view(serve.mixed_slab_width).kinds
    assert kinds[bulk.slot] == 8  # no pressure signal -> full width again


# -------------------------------------------------------------- plan feedback
def test_plan_slo_widens_slab_and_reins_in_gamma():
    cfg = get_config("smollm-135m")
    base = derive_serve_plan(cfg, MESH1, TPU_V5E, max_seq_len=2048, draft="ngram")
    slo = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, draft="ngram",
        slo_ttft_ms=1.0, typical_prompt_len=2048,
    )
    # a 1ms TTFT budget at ~0.3ms/step leaves ~3 steps for 2048 tokens
    assert slo.mixed_slab_width > base.mixed_slab_width
    assert slo.slo_ttft_ms == 1.0 and base.slo_ttft_ms is None
    # gamma under SLO: slack//2 - 1 at the derived batch
    assert slo.spec_len <= base.spec_len
    b64 = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, decode_batch=64, draft="ngram"
    )
    s64 = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, decode_batch=64, draft="ngram",
        slo_ttft_ms=200.0, typical_prompt_len=256,
    )
    assert b64.spec_len == 2  # slack 240/64 ~ 3.75 -> gamma 2
    assert s64.spec_len == 0  # slack//2 - 1 = 0 under a TTFT target
    # a loose SLO must not shrink an explicitly wider slab
    wide = derive_serve_plan(
        cfg, MESH1, TPU_V5E, max_seq_len=2048, mixed_slab_width=512,
        slo_ttft_ms=10_000.0, typical_prompt_len=256,
    )
    assert wide.mixed_slab_width == 512


# ------------------------------------------------------------------ workloads
def test_parse_mix_and_classes():
    assert parse_mix("chat:4,summarize:2") == {"chat": 4, "summarize": 2}
    assert parse_mix("classify") == {"classify": 1}
    with pytest.raises(ValueError):
        parse_mix("nosuch:3")
    with pytest.raises(ValueError):
        parse_mix("")
    assert set(WORKLOADS) == {"chat", "summarize", "classify"}
    assert WORKLOADS["classify"].priority > WORKLOADS["chat"].priority
    assert WORKLOADS["summarize"].slo_ttft_ms is None


def test_make_trace_shapes_and_tenancy():
    cfg = get_config("smollm-135m").reduced()
    reqs = make_trace(
        cfg, {"chat": 3, "classify": 3}, tenants=2, system_prompt_len=16,
        stagger=2, seed=0, max_tokens=64,
    )
    assert len(reqs) == 6
    assert [r.arrival for r in reqs] == [0, 2, 4, 6, 8, 10]
    by_tenant = {}
    for r in reqs:
        by_tenant.setdefault(r.tenant, []).append(r)
        wc = WORKLOADS[r.tag]
        assert r.priority == wc.priority and r.slo_ttft_ms == wc.slo_ttft_ms
        assert len(r.prompt) + r.max_new_tokens <= 64
    assert set(by_tenant) == {"tenant0", "tenant1"}
    for rs in by_tenant.values():
        sys0 = rs[0].prompt[:16]
        assert all(r.prompt[:16] == sys0 for r in rs)  # shared system prompt
    # same seed -> same trace (replayable); different seed -> different
    again = make_trace(
        cfg, {"chat": 3, "classify": 3}, tenants=2, system_prompt_len=16,
        stagger=2, seed=0, max_tokens=64,
    )
    assert [r.prompt for r in again] == [r.prompt for r in reqs]


def test_trace_replay_engine_parity_and_report(key):
    """End-to-end trace replay on the real engine: byte parity sharing on
    vs off, prefix hits from the shared system prompts, per-class report."""
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=4, seq_len=16, training=False)
    serve = derive_serve_plan(
        cfg, MESH1, max_seq_len=64, decode_batch=4, block_size=8,
        kv_dtype="fp32", prefill_chunk=8,
    )
    from repro.models.params import init_params

    params = init_params(key, cfg, plan, dtype=jnp.float32)
    mix = {"chat": 3, "classify": 2}

    def trace():
        return make_trace(cfg, mix, tenants=2, system_prompt_len=24,
                          stagger=1, seed=3, max_tokens=64)

    outs = {}
    for sharing in (True, False):
        eng = ServingEngine(
            params, cfg, plan,
            dataclasses.replace(serve, prefix_sharing=sharing),
        )
        outs[sharing] = eng.run(trace())
        if sharing:
            summ = eng.summary()
            assert_traces_bounded(summ["traces"])
            assert summ["prefix"]["hits"] > 0
            assert set(summ["tenants"]) == {"tenant0", "tenant1"}
            report = per_class_report(eng.sched.finished)
            assert set(report) == set(mix)
            assert all(v["count"] == mix[k] for k, v in report.items())
    assert outs[True] == outs[False]


# ---------------------------------------------------------------- ServeArgs
def test_serve_args_maps_one_to_one_onto_plan_overrides():
    ns = build_parser().parse_args(
        [
            "--arch", "smollm-135m", "--fix-batch", "--batch", "4",
            "--max-seq", "128", "--slab-width", "16", "--pages-per-tile", "2",
            "--no-fused", "--kv-dtype", "int8", "--draft", "ngram",
            "--spec-len", "2", "--no-prefix-sharing", "--slo-ttft-ms", "250",
            "--deadline-ms", "1500", "--retry-limit", "2", "--stall-limit", "64",
        ]
    )
    a = ServeArgs.from_namespace(ns)
    ov = a.plan_overrides()
    assert ov == {
        "max_seq_len": 128, "decode_batch": 4, "prefill_chunk": None,
        "mixed_slab_width": 16, "pages_per_tile": 2, "fused_attention": False,
        "kv_dtype": "int8", "draft": "ngram", "spec_len": 2,
        "prefix_sharing": False, "slo_ttft_ms": 250.0,
        "typical_prompt_len": 32, "rolled_steps": None,
        "deadline_ms": 1500.0, "retry_limit": 2, "stall_limit": 64,
    }
    cfg = get_config("smollm-135m")
    sp = derive_serve_plan(cfg, MESH1, TPU_V5E, **ov)
    assert sp.decode_batch == 4 and sp.kv_dtype == "int8"
    assert not sp.prefix_sharing and sp.slo_ttft_ms == 250.0
    assert sp.mixed_slab_width == 16 and not sp.fused_attention
    assert sp.deadline_ms == 1500.0 and sp.retry_limit == 2
    assert sp.stall_limit == 64


def test_serve_args_old_spellings_and_trace_flags():
    # every pre-existing flag spelling still parses
    ns = build_parser().parse_args(
        [
            "--arch", "smollm-135m", "--engine", "eager", "--batch", "2",
            "--requests", "5", "--prompt-len", "16", "--gen", "4",
            "--stagger", "3", "--prefill-chunk", "8",
        ]
    )
    a = ServeArgs.from_namespace(ns)
    assert (a.engine, a.batch, a.requests, a.prompt_len, a.gen, a.stagger) == (
        "eager", 2, 5, 16, 4, 3
    )
    assert a.prefill_chunk == 8 and a.trace is None
    # new trace flags
    ns2 = build_parser().parse_args(
        ["--arch", "smollm-135m", "--trace", "chat:2,classify:1",
         "--tenant-mix", "3"]
    )
    a2 = ServeArgs.from_namespace(ns2)
    assert a2.trace == "chat:2,classify:1" and a2.tenant_mix == 3
    cfg = get_config("smollm-135m").reduced()
    reqs = a2.request_stream(cfg)
    assert len(reqs) == 3 and len({r.tenant for r in reqs}) == 3


def test_request_new_fields_are_keyword_only():
    with pytest.raises(TypeError):
        Request("r", [1, 2], 4, 0, "tenant")  # tenant not positional
    r = Request(rid="r", prompt=[1, 2], max_new_tokens=4, tenant="t",
                priority=3, slo_ttft_ms=50.0, tag="chat")
    assert (r.tenant, r.priority, r.slo_ttft_ms, r.tag) == ("t", 3, 50.0, "chat")
    d = Request(rid="d", prompt=[1], max_new_tokens=1)
    assert (d.tenant, d.priority, d.slo_ttft_ms, d.tag) == ("default", 0, None, "")

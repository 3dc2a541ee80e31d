"""Unified observability: metrics registry and lifecycle tracing.

Three groups:

* **unit** — registry semantics (labels, histograms, Prometheus round-trip
  as an exact parse-of-exposition == flat-samples oracle), tracer ring
  buffer + Chrome trace_event structure.  Pure host code, no jax.
* **engine integration** — one real serving run with the full bundle on:
  golden Chrome-trace validity (monotone timestamps, >= 1 complete request
  lifecycle nested under step spans), metric/summary back-compat
  agreement, and the disabled-mode no-op guarantee (tracing off leaves the ring empty).
* **launcher** — ``--replay-trace`` is the canonical replay spelling and
  ``--trace`` keeps working as a deprecation alias (both spellings, plus
  the conflict error).
"""
import json

import jax.numpy as jnp
import pytest

from conftest import assert_traces_bounded

from repro.configs import get_config
from repro.core.plan import derive_plan, derive_serve_plan
from repro.obs import (
    PID_ENGINE,
    TID_PHASES,
    Observability,
    Tracer,
    parse_prometheus_text,
    prometheus_roundtrip_ok,
    validate_chrome_trace,
)
from repro.obs.metrics import MetricsRegistry
from repro.serve import ServingEngine
from repro.serve.scheduler import Request, random_stream

MESH1 = {"data": 1, "model": 1}


# ---------------------------------------------------------------- metrics
def test_counter_gauge_histogram_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests", ("tenant",))
    c.inc(tenant="a")
    c.inc(2, tenant="b")
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.set(3)
    h = reg.histogram("lat_ms", "latency", ("tenant",))
    h.observe(0.4, tenant="a")
    h.observe(12.0, tenant="a")
    h.observe(1e9, tenant="a")  # beyond the last bucket -> +Inf only
    snap = reg.snapshot()
    assert snap["reqs_total"]["type"] == "counter"
    assert snap["depth"]["samples"][0]["value"] == 3
    # exact round-trip: parse(exposition) == flat_samples
    assert prometheus_roundtrip_ok(reg)
    parsed = parse_prometheus_text(reg.to_prometheus())
    assert parsed[("reqs_total", (("tenant", "b"),))] == 2
    assert parsed[("lat_ms_count", (("tenant", "a"),))] == 3
    assert parsed[("lat_ms_bucket", (("le", "+Inf"), ("tenant", "a")))] == 3


def test_metrics_registry_rejects_mismatches():
    reg = MetricsRegistry()
    reg.counter("x_total", "x")
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")  # type mismatch on re-registration
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", ("tenant",))  # label mismatch
    with pytest.raises(ValueError):
        reg.counter("bad name", "x")
    c = reg.counter("y_total", "y")
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same object
    assert reg.counter("x_total", "x") is reg.counter("x_total", "x")


# ---------------------------------------------------------------- tracer
def test_tracer_ring_buffer_and_chrome_structure():
    import time

    tr = Tracer(buffer=4, enabled=True)
    base = time.perf_counter()
    for i in range(10):
        tr.instant(f"e{i}", PID_ENGINE, 0, base + i * 1e-3)
    doc = tr.chrome_trace()
    events = validate_chrome_trace(doc)
    assert len(events) == 4  # ring kept the newest 4
    assert [e["name"] for e in events] == ["e6", "e7", "e8", "e9"]
    assert doc["otherData"]["dropped_events"] == 6
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    tr.instant("x", PID_ENGINE, 0, 1.0)
    tr.complete("y", PID_ENGINE, 0, 1.0, 2.0)
    tr.request_span("z", "r0", 1.0, 2.0)
    assert len(validate_chrome_trace(tr.chrome_trace())) == 0


# ---------------------------------------------------- engine integration
@pytest.fixture(scope="module")
def obs_run(key):
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=3, seq_len=16, training=False)
    from repro.models.params import init_params

    params = init_params(key, cfg, plan, dtype=jnp.float32)
    serve = derive_serve_plan(
        cfg, MESH1, max_seq_len=64, decode_batch=3, block_size=8,
        prefill_chunk=8, mixed_slab_width=8, rolled_steps=4,
    )
    stream = lambda: random_stream(cfg, 5, 8, 10, stagger=1, seed=11)
    obs = Observability(tracing=True)
    engine = ServingEngine(params, cfg, plan, serve, obs=obs)
    out_on = engine.run(stream())
    plain = ServingEngine(params, cfg, plan, serve)
    out_off = plain.run(stream())
    return engine, plain, obs, out_on, out_off


def test_obs_parity_and_trace_contract(obs_run):
    engine, plain, obs, out_on, out_off = obs_run
    assert out_on == out_off, "observability changed the engine's bytes"
    assert_traces_bounded(engine.trace_counts)
    assert engine.trace_counts == plain.trace_counts


def test_golden_chrome_trace(obs_run, tmp_path):
    engine, _, obs, out_on, _ = obs_run
    path = tmp_path / "trace.json"
    n = obs.tracer.write(str(path))
    doc = json.loads(path.read_text())
    events = validate_chrome_trace(doc)  # structure + monotone timestamps
    assert len(events) == n > 0
    names = {e["name"] for e in events}
    # >= one COMPLETE lifecycle: queued -> admitted -> first-token ->
    # finished, plus the whole-request span, nested under step spans
    for required in ("queued", "admitted", "first-token", "finished",
                     "request", "prefill-chunk"):
        assert required in names, f"missing {required!r} in {sorted(names)}"
    assert {"step", "rolled_step"} & names, "no dispatch spans exported"
    # every per-request event rides the requests track with its rid
    reqs = [e for e in events if e.get("pid") == 2]
    assert reqs and all("rid" in e.get("args", {}) for e in reqs)
    # lifecycle nests under the dispatch spans' wall-clock envelope
    steps = [e for e in events
             if e["name"] in ("step", "rolled_step") and e["ph"] == "X"]
    t_lo = min(e["ts"] for e in steps)
    t_hi = max(e["ts"] + e["dur"] for e in steps)
    fin = [e for e in events if e["name"] == "finished"]
    assert fin and all(t_lo <= e["ts"] <= t_hi + 1e6 for e in fin)


def test_metrics_agree_with_summary(obs_run):
    engine, _, obs, out_on, _ = obs_run
    s = engine.summary()
    m = obs.metrics.snapshot()

    def total(name):
        return sum(x["value"] for x in m[name]["samples"])

    assert total("serve_requests_submitted_total") == len(out_on)
    assert total("serve_requests_finished_total") == len(out_on)
    assert total("serve_tokens_total") >= s["generated_tokens"]
    # the steps counter counts DISPATCHES (a rolled span is one), while
    # stats["steps"] counts device iterations (a rolled span adds K)
    dispatches = (s["steps"] - engine.stats["rolled_steps"]
                  + engine.stats["rolled_dispatches"])
    assert total("serve_steps_total") == dispatches
    assert prometheus_roundtrip_ok(obs.metrics)


def test_default_obs_keeps_tracing_off(obs_run):
    _, plain, _, _, _ = obs_run
    # the default bundle: metrics on, tracer disabled and EMPTY
    assert plain.obs.tracer.enabled is False
    assert len(validate_chrome_trace(plain.obs.tracer.chrome_trace())) == 0


PHASES = ("engine.admit", "engine.pack", "engine.launch", "engine.sync",
          "engine.harvest")


def test_step_phases_tile_the_call(key):
    """Every step() that dispatches is tiled by the five phases, in order,
    with no gap or overlap (one clock read per boundary), inside the call;
    the phase counters hold the phases' seconds, the dispatch wall time is
    launch + sync, and reset_stats() zeroes them."""
    import time

    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=2, seq_len=16, training=False)
    from repro.models.params import init_params

    params = init_params(key, cfg, plan, dtype=jnp.float32)
    serve = derive_serve_plan(
        cfg, MESH1, max_seq_len=64, decode_batch=2, block_size=8,
        prefill_chunk=8, mixed_slab_width=8, rolled_steps=4,
    )
    obs = Observability(tracing=True)
    engine = ServingEngine(params, cfg, plan, serve, obs=obs)
    tr = obs.tracer
    engine.submit(Request(rid="a", prompt=list(range(1, 12)), max_new_tokens=9))
    seen = 0
    totals = dict.fromkeys(PHASES, 0.0)
    while not engine.sched.idle:
        t_a = time.perf_counter()
        engine.step()
        t_b = time.perf_counter()
        events = [e for e in validate_chrome_trace(tr.chrome_trace())
                  if e["pid"] == PID_ENGINE and e["tid"] == TID_PHASES]
        new, seen = events[seen:], len(events)
        assert [e["name"] for e in new] == list(PHASES)
        assert tr._ts(t_a) <= new[0]["ts"]
        assert new[-1]["ts"] + new[-1]["dur"] <= tr._ts(t_b)
        for a, b in zip(new, new[1:]):
            assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1e-3)  # 1 ns
        for e in new:
            totals[e["name"]] += e["dur"] / 1e6
    assert engine.stats["rolled_dispatches"] > 0 and engine.stats["steps"] > 1
    for name, total in totals.items():
        assert engine.stats[name.split(".")[1] + "_s"] == pytest.approx(total, rel=1e-6)
    dispatch = [e for e in validate_chrome_trace(tr.chrome_trace())
                if e["name"] in ("step", "rolled_step")]
    assert engine.summary()["device_s"] == pytest.approx(
        sum(e["dur"] for e in dispatch) / 1e6, rel=1e-6)
    engine.reset_stats()
    assert all(engine.stats[p.split(".")[1] + "_s"] == 0.0 for p in PHASES)


def test_fault_events_carry_determinism_key(key):
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, MESH1, batch=2, seq_len=16, training=False)
    from repro.models.params import init_params
    from repro.serve import FaultInjector

    params = init_params(key, cfg, plan, dtype=jnp.float32)
    serve = derive_serve_plan(
        cfg, MESH1, max_seq_len=64, decode_batch=2, prefill_chunk=8,
        mixed_slab_width=8,
    )
    obs = Observability(tracing=True)
    inj = FaultInjector(3, transient_rate=0.2, nan_rate=0.1, horizon=30)
    engine = ServingEngine(params, cfg, plan, serve, injector=inj, obs=obs)
    engine.run(random_stream(cfg, 3, 8, 8, stagger=1, seed=2))
    events = validate_chrome_trace(obs.tracer.chrome_trace())
    faults = [e for e in events if e["name"].startswith("fault:")]
    assert faults, "chaos run traced no fault events"
    for e in faults:
        assert e["args"]["seed"] == 3
        assert e["args"]["salt"] in (1, 2, 3, 4)
        assert e["args"]["iteration"] >= 0
    kinds = {e["name"].split(":", 1)[1] for e in faults}
    assert kinds <= {"transient", "nan", "pressure", "spike"}
    if inj.counts["transient"]:
        assert "transient" in kinds
    if engine.stats["injected_nans"]:
        assert "nan" in kinds


# ------------------------------------------------------------- launcher
def test_replay_trace_flag_spellings():
    from repro.launch.serve import ServeArgs, build_parser

    ns = build_parser().parse_args(["--arch", "x", "--replay-trace", "chat:2"])
    a = ServeArgs.from_namespace(ns)
    assert a.replay_trace == a.trace == "chat:2"
    # deprecated spelling still lands in BOTH fields
    ns2 = build_parser().parse_args(["--arch", "x", "--trace", "chat:3"])
    a2 = ServeArgs.from_namespace(ns2)
    assert a2.replay_trace == a2.trace == "chat:3"
    with pytest.raises(ValueError):
        ServeArgs(arch="x", trace="a:1", replay_trace="b:1")
    a3 = ServeArgs(arch="x")
    assert a3.trace is None and a3.replay_trace is None
    assert a3.make_observability().tracer.enabled is False
    assert ServeArgs(arch="x", trace_out="t.json").make_observability(
    ).tracer.enabled is True

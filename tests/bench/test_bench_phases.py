"""The engine's phase spans as the benchmark reads them: in a profiler trace
taken on the CPU, each step's five phases lie inside the harness's
``engine.step`` span and name the idle gaps; the readers of the phase
counters and of the scheduler's stamps give numbers on a traced run of the
small cells, and None where there is nothing to read."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import jax
import jax.numpy as jnp
import pytest

import tinybench
from bench import harness, registry, trace
from bench.window import Rec

PHASES = ["engine.admit", "engine.pack", "engine.launch", "engine.sync", "engine.harvest"]


def _engine(key):
    from repro.configs import get_config
    from repro.core.plan import derive_plan, derive_serve_plan
    from repro.models.params import init_params
    from repro.serve import ServingEngine

    mesh = {"data": 1, "model": 1}
    cfg = get_config("smollm-135m").reduced()
    plan = derive_plan(cfg, mesh, batch=2, seq_len=16, training=False)
    serve = derive_serve_plan(cfg, mesh, max_seq_len=64, decode_batch=2, block_size=8,
                              prefill_chunk=8, mixed_slab_width=8, rolled_steps=1)
    return ServingEngine(init_params(key, cfg, plan, dtype=jnp.float32), cfg, plan, serve)


def test_profiler_trace_nests_the_phases_in_engine_step(key, tmp_path):
    from repro.serve.scheduler import Request

    engine = _engine(key)
    engine.submit(Request("a", list(range(1, 20)), 6))
    engine.step()  # compiles outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            with jax.profiler.TraceAnnotation("engine.step"):
                engine.step()
    finally:
        jax.profiler.stop_trace()
    rec = trace.extract(trace.latest_xplane(str(tmp_path)))

    steps = [e for e in rec["host"] if e[0] == "engine.step"]
    assert len(steps) == 2
    for _, s0, d0 in steps:
        inside = [e for e in rec["host"] if e[0] != "engine.step" and s0 <= e[1] < s0 + d0]
        assert [e[0] for e in inside] == PHASES
        assert all(s + d <= s0 + d0 for _, s, d in inside)

    # a device idle inside the second step's pack phase is named by it
    pack = [e for e in rec["host"] if e[0] == "engine.pack"][1]
    lo, hi = steps[0][1], steps[1][1] + steps[1][2]
    gap0, gap1 = pack[1] + pack[2] // 4, pack[1] + 3 * pack[2] // 4
    rec["devices"] = {"/device:TPU:0": [["fusion.1 fusion", lo, gap0 - lo, False],
                                        ["fusion.2 fusion", gap1, hi - gap1, False]]}
    red = trace.reduce(rec)
    assert red["idle_gaps"][0] == ["engine.pack", pytest.approx((gap1 - gap0) / 1e9)]


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tinybench.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell, names", [
    ("tiny.code", {"host_ms.code", "prefill_ms_p85.code"}),
    ("tiny.batch", {"host_ms.batch"}),
])
def test_new_readers_report_on_a_traced_run(reg, monkeypatch, cell, names):
    run_cell = harness.run_cell
    monkeypatch.setattr(harness, "run_cell",
                        lambda w, seed, s, trace, **kw: run_cell(w, seed, s, True, **kw))
    out = tinybench.run(reg, cell)
    assert out["correct"], out["check"]
    for name in names:
        assert out["metrics"][name]["value"] > 0, name
    # host time per dispatch is a part of the step time
    step = out["metrics"][f"iter_ms.{cell.split('.')[1]}"]["value"]
    assert out["metrics"][f"host_ms.{cell.split('.')[1]}"]["value"] < step


class _Window:
    def __init__(self, stats, recs=()):
        self._stats, self.recs = stats, list(recs)

    def stats(self):
        return self._stats


def test_new_readers_read_none_where_nothing_is_there():
    from repro.serve.scheduler import Request

    reg = registry.Registry()
    host, prefill = reg.metric("host_ms.code"), reg.metric("prefill_ms_p85.code")
    # an engine without phase counters
    no_phases = {"steps": 10, "rolled_steps": 0, "rolled_dispatches": 0}
    assert host.read({"window": _Window(no_phases)}) is None
    zero = dict(no_phases, steps=0, admit_s=0.0, pack_s=0.0, launch_s=0.0, harvest_s=0.0)
    assert host.read({"window": _Window(zero)}) is None
    # no request due in the window got a first token
    waiting = Rec("r0", 1.0, [1], 1, counted=True, req=Request("r0", [1], 1))
    waiting.req.t_admit = 1.5
    early = Rec("r1", -1.0, [1], 1, counted=False, req=Request("r1", [1], 1))
    early.req.t_admit, early.req.t_first = 0.0, 0.5
    assert prefill.read({"window": _Window({}, [waiting, early])}) is None
    waiting.req.t_first = 2.0
    assert prefill.read({"window": _Window({}, [waiting, early])}) == pytest.approx(500.0)

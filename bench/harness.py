"""One run of one cell: set-up, the measured window, the correctness check
and the result line.  ``bench/run.py`` is its command line."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

from bench import readers
from bench import registry as R
from bench import weights as W

OUT = R.BENCH / "out"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips and JAX finds {len(devs)}")
    return devs[:n]


def enable_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it (in
    the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise);
    every program is cached, however quick its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compilations JAX reports while ``armed``."""

    def __init__(self):
        import jax.monitoring as mon

        self.n, self.armed = 0, False
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and "backend_compile" in event:
            self.n += 1


def warm_up(engine) -> None:
    """Run every program the window can run once, through the engine's own
    calls: the unified step (prompt and decode rows), the rolled span where
    the plan rolls decode steps, and the copy-on-write page copy (a second
    prompt that diverges inside a resident block); then clear the counters."""
    from repro.serve.scheduler import Request

    bs = engine.serve.block_size
    first = list(range(1, 2 * bs + 9))
    a = Request("warm-up-0", first, 3 + engine.rolled_cap)
    engine.submit(a)
    while not a.out:
        engine.step()
    engine.submit(Request("warm-up-1", first[: bs + 4] + [7] * 4, 2))
    while not engine.sched.idle:
        engine.step()
    engine.reset_stats()


def pick_sample(recs, seed: int, check: dict) -> list:
    """Requests to compare with the reference: the one with the longest
    context served, then others drawn from the seed until ``tokens`` served
    tokens or ``max_requests`` requests.  Finished requests come first;
    requests still in flight at the close are drawn from when the finished
    ones hold too few tokens."""
    from bench import lengths

    served = [r for r in recs if r.tokens]
    if not served:
        return []
    done = [r for r in served if r.status == "ok"]
    longest = max(served, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    gen = lengths.rng(seed, 5)
    pools = [gen.permutation(len(done)), gen.permutation(len(served))]
    out, n = [longest], len(longest.tokens)
    for pool, order in zip((done, served), pools):
        for i in order:
            r = pool[i]
            if n >= check["tokens"] or len(out) >= check["max_requests"]:
                return out
            if r not in out:
                out.append(r)
                n += len(r.tokens)
    return out


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *, reg=None,
             devices=None, t_start=None, program_cfg=None, serve_overrides=None,
             control: bool = False, fault=None, keep_trace: bool = False) -> dict:
    """Run one cell; returns the result line as a dict.

    ``devices`` skips the look for a chip (the CPU tests pass the CPU
    device); ``program_cfg`` and ``serve_overrides`` let them serve a small
    model with a small plan; ``fault(engine)`` breaks the timed path
    underneath.  ``control`` runs the control: the int8 reference's picks
    are judged in place of the served tokens, by the same limits, and the
    program's own gaps go under ``program_gaps``."""
    import jax
    import numpy as np

    from bench import model, reference, window

    t_start = time.time() if t_start is None else t_start
    reg = reg or R.Registry()
    cell = reg.cell(workload)
    c = reg.config(cell["config"])
    arch = reg.arch(c)
    traffic = reg.traffic(cell["traffic"])
    check = R.load_json(reg.base / "checks" / f"{workload}.json")
    if devices is None:
        devices = require_chips(int(cell["chips"]))
    if devices[0].platform == "tpu":
        enable_cache()
    counter = CompileCounter()

    engine, cfg, plan, serve = model.build(
        arch, c, seed, cfg=program_cfg, serve_overrides=serve_overrides)
    log(serve.describe())
    if fault is not None:
        fault(engine)
    warm_up(engine)
    traces0 = dict(engine.trace_counts)
    gen = reg.generator(traffic["generator"]).Generator(traffic, seed, c["vocab_size"], seconds)

    tracer = Tracer(workload, seed, traffic) if trace else None
    win = window.Window(engine, gen, arch, c, seconds, drain_s=traffic.get("drain_s", 0.0),
                        annotate=tracer.annotate if tracer else None,
                        on_step=tracer.on_step if tracer else None)
    t_setup = time.time()
    counter.armed = True
    win.run()
    counter.armed = False
    if tracer:
        tracer.stop()
    compiles = counter.n + sum(engine.trace_counts.values()) - sum(traces0.values())
    peak = memory_peak(devices)
    log(f"window {win.window_s():.3f} s, {win.window_steps} steps in it, "
        f"{len(win.steps)} with the drain; traces {engine.trace_counts}")
    log("window: output tokens/s in its first and second half %.2f, %.2f; "
        "requests admitted %d, finished %d" % (*win.halves(), *win.turnover()))

    sample = pick_sample(win.recs, seed, check)
    sample_io = [(r.prompt, r.tokens) for r in sample]
    model.release(engine)
    del engine
    hi, lo = W.seed_words(seed)
    wts = jax.jit(lambda a, b: W.draw(arch, c, a, b))(np.uint32(hi), np.uint32(lo))
    t_ref = time.time()
    got = reference.gaps(arch, c, wts, sample_io, int(c["max_seq_len"]), control=control)
    log(f"reference over {len(sample)} requests, {got['tokens']} served tokens: "
        f"{time.time() - t_ref:.1f} s")
    del wts

    record = {
        "cell": cell, "config": c, "traffic": traffic, "seed": seed,
        "setup_s": t_setup - t_start + win.lead_in_s, "window": win, "serve": serve.to_record(),
        "peaks": None, "trace": None,
    }
    dev = devices[0]
    if dev.platform == "tpu":
        from bench import flops
        record["peaks"] = flops.peaks(dev.device_kind)
    if tracer:
        record["trace"] = tracer.reduce(win, keep_trace)
        record["profiler_s"] = sum(max(min(b, win.t_end) - max(a, win.t_open), 0.0)
                                   for a, b in tracer.pauses)
        least = readers.attn_least_s(record)
        if least is not None:
            log(f"paged attention least time: {least[0]:.4f} s by operations, "
                f"{least[1]:.4f} s by bytes; kernel {record['trace']['kernel_s']:.4f} s")

    metrics = {}
    for m in reg.metrics_for(workload, traced=trace):
        value = reg.metric(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    attempted, failed = counts(win)
    program = None
    if control:  # the int8 pass's picks stand where the served tokens stood
        program = {k: got[k] for k in check["limits"]}
        got = dict(got, **{k: got["control_" + k] for k in check["limits"]})
    correct, checks = decide(got, check, compiles)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if tracer and record["trace"]:
        t = record["trace"]
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
    if program is not None:
        out["program_gaps"] = program
    out["check"] = checks
    return out


def decide(got: dict, check: dict, compiles: int) -> tuple[bool, dict]:
    """``correct``, and each number compared beside its limit: each gap of
    the cell's check file within its limit, at least half the sample's
    served tokens compared, and nothing compiled inside the window."""
    checks = {name: {"value": got[name], "limit": limit} for name, limit in check["limits"].items()}
    checks["tokens_missing"] = {"value": max(check["tokens"] // 2 - got["tokens"], 0), "limit": 0}
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    return all(v["value"] <= v["limit"] for v in checks.values()), checks


def counts(win) -> tuple[int, int]:
    """(attempted, failed).  Open loop: the requests due in the window, and
    those of them that never got a first token or were refused.  Closed
    loop: the requests admitted or refused, and those refused or cancelled."""
    if win.gen.closed:
        recs = [r for r in win.recs if r.t_admit is not None or r.status != "pending"]
        bad = [r for r in recs if r.status not in ("ok", "pending")]
    else:
        recs = [r for r in win.recs if r.counted]
        bad = [r for r in recs if not r.stamps or r.status not in ("ok", "pending")]
    return len(recs), len(bad)


class Tracer:
    """Profiles ``trace_s`` seconds of the window, starting ``trace_after_s``
    into it, between two steps; host spans go into the same trace."""

    def __init__(self, workload: str, seed: int, traffic: dict):
        import jax

        self.jax = jax
        self.dir = OUT / f"trace.{workload}.{seed}"
        shutil.rmtree(self.dir, ignore_errors=True)
        self.after = float(traffic.get("trace_after_s", 5.0))
        self.length = float(traffic.get("trace_s", 8.0))
        self.on = False
        self.done = False
        self.t0 = None
        self.pauses = []  # (from, to): spent starting and stopping the profiler

    def annotate(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    def on_step(self, i: int) -> None:
        now = time.perf_counter()
        if self.t0 is None:
            self.t0 = now
        if not self.on and not self.done and now - self.t0 >= self.after:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans come from TraceAnnotation alone
            self.jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.on, self.started = True, time.perf_counter()
            self.pauses.append((now, self.started))
        elif self.on and now - self.started >= self.length:
            self.stop()

    def stop(self) -> None:
        if self.on:
            self.stopped = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.pauses.append((self.stopped, time.perf_counter()))
            self.on, self.done = False, True

    def reduce(self, win, keep: bool) -> dict:
        from bench import trace

        if not self.done:
            return None
        rec = trace.extract(trace.latest_xplane(str(self.dir)))
        OUT.mkdir(exist_ok=True)
        trace.save(rec, str(self.dir) + ".events.json.gz")
        if not keep:
            shutil.rmtree(self.dir, ignore_errors=True)
        red = trace.reduce(rec)
        if red:
            # the steps the trace holds, by the host clock
            red["steps"] = [s for s in win.steps
                            if s.t0 >= self.started and s.t1 <= self.stopped]
        return red


def write_json(obj, path) -> None:
    pathlib.Path(path).parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)

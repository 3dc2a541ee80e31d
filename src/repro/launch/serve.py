"""Serving launcher: continuous batching by default, eager lockstep as
fallback.

    # continuous batching (paged KV cache, plan-derived knobs):
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --requests 8 --prompt-len 32 --gen 16 --stagger 2

    # speculative decoding: a small model drafts, the big model verifies
    # gamma+1 rows per slot in the same mixed slab (tokens are identical
    # to plain decode; only the speed changes):
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --draft smollm-135m --requests 8 --prompt-len 32 --gen 16

    # multi-tenant trace replay: two tenants, each with a shared system
    # prompt, driving a heterogeneous class mix with per-class latency
    # percentiles (prefix sharing makes the shared prompts one prefill):
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --replay-trace chat:4,summarize:2,classify:2 --tenant-mix 2 --max-seq 512

    # observability: Prometheus-format metrics + a Chrome trace_event
    # export of the whole run (open in https://ui.perfetto.dev):
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --requests 8 --gen 16 --metrics-out m.prom --trace-out trace.json

    # eager whole-batch greedy decode (non-attention archs serve here):
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b-reduced \
        --engine eager --batch 4 --prompt-len 32 --gen 16

All serving knobs live in one :class:`ServeArgs` record whose
``plan_overrides()`` maps 1:1 onto :func:`repro.core.plan.derive_serve_plan`
keyword arguments — the CLI flags are just its spellings (old flag names
all keep working).  The batched path derives an :class:`ExecutionPlan`
(mesh decisions) *and* a :class:`ServePlan` from the same (arch, mesh,
hardware) triple, places params through ``dist.Shardings`` so a
model-sharded mesh serves correctly, and prints the plan + engine summary
(tokens/s, batch occupancy, prefix-sharing hit rates) at the end.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.hardware import resolve_hardware
from repro.core.plan import (
    SERVE_WEIGHT_DTYPE,
    derive_plan,
    derive_serve_plan,
    serve_feasible,
)
from repro.dist.sharding import Shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.params import init_params
from repro.serve import (
    ServingEngine,
    greedy_generate,
    make_draft_source,
    make_trace,
    parse_mix,
    per_class_report,
    random_stream,
)


@dataclasses.dataclass
class ServeArgs:
    """Every serving-launcher knob, CLI-independent.

    The fields group into (a) workload shape (requests / prompt-len / gen /
    stagger, or a ``trace`` workload-mix spec with ``tenant_mix`` tenants)
    and (b) plan overrides — the latter map 1:1 onto
    :func:`derive_serve_plan` keywords via :meth:`plan_overrides`, so
    adding a plan knob means adding a field + one mapping entry, not new
    plumbing."""

    arch: str
    engine: str = "batched"
    batch: int = 4
    fix_batch: bool = False
    requests: int = 8
    prompt_len: int = 32
    gen: int = 16
    stagger: int = 2
    # ---- ServePlan overrides (1:1 with derive_serve_plan keywords) ----
    max_seq: int = 2048
    prefill_chunk: Optional[int] = None
    slab_width: Optional[int] = None
    pages_per_tile: Optional[int] = None
    no_fused: bool = False
    kv_dtype: Optional[str] = None
    draft: Optional[str] = None
    spec_len: Optional[int] = None
    no_prefix_sharing: bool = False
    slo_ttft_ms: Optional[float] = None
    rolled_steps: Optional[int] = None
    deadline_ms: Optional[float] = None
    retry_limit: int = 3
    stall_limit: int = 256
    # ---- chaos injection (serve/faults.py; docs/ROBUSTNESS.md) ----
    chaos_seed: Optional[int] = None  # None = no injector
    chaos_transient: float = 0.0
    chaos_burst: int = 1
    chaos_nan: float = 0.0
    chaos_pressure: float = 0.0
    chaos_spike_ms: float = 0.0
    chaos_horizon: Optional[int] = None
    # ---- device + family pick ----
    # registered HardwareSpec the plans derive from off-TPU; on a TPU the
    # chip's device_kind decides (core/hardware.resolve_hardware)
    hardware: str = "tpu_v5e"
    # Pick the serving plan off the design-space Pareto frontier instead of
    # deriving one: "throughput" | "cost" | "energy" (core/search.py;
    # docs/PLANNER.md).  Individual plan-override flags are ignored then.
    from_family: Optional[str] = None
    # ---- multi-tenant trace replay ----
    # ``replay_trace`` is the canonical spelling (PR 10 freed ``--trace``
    # for execution tracing); ``trace`` remains a deprecation alias and the
    # two fields are kept mirrored in ``__post_init__`` so old callers and
    # old flags keep working unchanged.
    replay_trace: Optional[str] = None  # workload mix, e.g. "chat:4,classify:2"
    trace: Optional[str] = None  # deprecated alias of replay_trace
    tenant_mix: int = 2  # tenants sharing per-tenant system prompts
    # ---- observability (repro.obs; docs/OBSERVABILITY.md) ----
    metrics_out: Optional[str] = None  # metrics dump path (.prom = text format)
    trace_out: Optional[str] = None  # Chrome trace_event JSON path (Perfetto)
    trace_buffer: int = 65536  # tracer ring-buffer capacity (events)

    def __post_init__(self):
        if self.replay_trace is None and self.trace is not None:
            self.replay_trace = self.trace
        elif self.trace is None and self.replay_trace is not None:
            self.trace = self.replay_trace
        elif (
            self.trace is not None
            and self.replay_trace is not None
            and self.trace != self.replay_trace
        ):
            raise ValueError(
                "--trace (deprecated) and --replay-trace disagree: "
                f"{self.trace!r} vs {self.replay_trace!r}"
            )

    @classmethod
    def from_namespace(cls, ns: argparse.Namespace) -> "ServeArgs":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in vars(ns).items() if k in names})

    def plan_overrides(self) -> dict:
        """Keyword arguments for :func:`derive_serve_plan`."""
        return {
            "max_seq_len": self.max_seq,
            "decode_batch": self.batch if self.fix_batch else None,
            "prefill_chunk": self.prefill_chunk,
            "mixed_slab_width": self.slab_width,
            "pages_per_tile": self.pages_per_tile,
            "fused_attention": not self.no_fused,
            "kv_dtype": self.kv_dtype,
            "draft": self.draft or "none",
            "spec_len": self.spec_len,
            "prefix_sharing": not self.no_prefix_sharing,
            "slo_ttft_ms": self.slo_ttft_ms,
            "rolled_steps": self.rolled_steps,
            "typical_prompt_len": self.prompt_len,
            "deadline_ms": self.deadline_ms,
            "retry_limit": self.retry_limit,
            "stall_limit": self.stall_limit,
        }

    def make_injector(self):
        """Build the chaos injector when any --chaos-* flag asks for one."""
        if self.chaos_seed is None:
            return None
        from repro.serve import FaultInjector

        return FaultInjector(
            self.chaos_seed,
            transient_rate=self.chaos_transient,
            transient_burst=self.chaos_burst,
            nan_rate=self.chaos_nan,
            pressure_rate=self.chaos_pressure,
            spike_rate=1.0 if self.chaos_spike_ms > 0 else 0.0,
            spike_ms=self.chaos_spike_ms,
            horizon=self.chaos_horizon,
        )

    def make_observability(self):
        """The engine's observability bundle: metrics + drift always on,
        lifecycle tracing only when ``--trace-out`` asks for the export."""
        from repro.obs import Observability

        return Observability(
            tracing=self.trace_out is not None, trace_buffer=self.trace_buffer
        )

    def request_stream(self, cfg) -> list:
        if self.replay_trace:
            return make_trace(
                cfg,
                parse_mix(self.replay_trace),
                tenants=self.tenant_mix,
                stagger=self.stagger,
                seed=1,
                max_tokens=self.max_seq,
            )
        return random_stream(
            cfg, self.requests, self.prompt_len, self.gen, self.stagger, seed=1
        )


def pick_from_family(a: ServeArgs, cfg, mesh, hw):
    """Resolve the ServePlan from the Pareto frontier (--from-family).

    The search is restricted to the launcher's actual model-axis degree so
    the picked plan is runnable on this mesh; the criterion selects the
    frontier's throughput-, cost-, or energy-optimal point."""
    import dataclasses as _dc

    from repro.core.search import default_space, search_family

    ma = dict(mesh.shape).get("model", 1)
    space = _dc.replace(
        default_space(hw, max_seq_len=a.max_seq), mesh_models=(ma,)
    )
    result = search_family(cfg, hw, space)
    if not result.frontier:
        raise SystemExit(f"empty family frontier for {cfg.name} on {hw.name}")
    key = {
        "throughput": lambda p: -p.tokens_per_s,
        "cost": lambda p: p.usd_per_mtok,
        "energy": lambda p: p.mj_per_tok,
    }[a.from_family]
    point = min(result.frontier, key=key)
    print(
        f"family pick ({a.from_family}-optimal of {len(result.frontier)} "
        f"frontier points on {hw.name}): {point.tokens_per_s:.0f} tok/s, "
        f"${point.usd_per_mtok:.3f}/Mtok, {point.mj_per_tok:.2f} mJ/tok, "
        f"tile {point.tile}"
    )
    return point.plan


def run_batched(a: ServeArgs, cfg, mesh):
    """Serve ``a``'s request stream on ``mesh``; returns the drained
    engine (its ``summary()`` is printed)."""
    hw = resolve_hardware(a.hardware)
    plan = derive_plan(
        cfg, dict(mesh.shape), hw,
        batch=a.batch, seq_len=a.prompt_len, training=False,
    )
    if a.from_family:
        serve = pick_from_family(a, cfg, mesh, hw)
    else:
        serve = derive_serve_plan(cfg, dict(mesh.shape), hw, **a.plan_overrides())
    print(plan.describe())
    print(serve.describe())
    sh = Shardings(mesh, plan, cfg)
    # one program draws the weights straight into their placement (eager
    # init holds every layer twice, and then a placed copy)
    init = lambda: init_params(  # noqa: E731
        jax.random.PRNGKey(0), cfg, plan, dtype=SERVE_WEIGHT_DTYPE
    )
    params = jax.jit(init, out_shardings=sh.param_shardings(jax.eval_shape(init)))()
    draft = None
    draft_name = a.draft
    if a.from_family and not draft_name and serve.spec_len > 0:
        draft_name = serve.draft  # the frontier point decided to speculate
    if draft_name and serve.spec_len == 0:
        print("roofline slack leaves no free verification rows at this "
              "decode batch: speculation stays off (gamma = 0)")
    elif draft_name:
        draft = make_draft_source(draft_name, cfg, serve, hw=hw, seed=2)
    injector = a.make_injector()
    if injector is not None:
        print(f"chaos injection on: {injector.to_record()}")
    obs = a.make_observability()
    engine = ServingEngine(
        params, cfg, plan, serve, shardings=sh, draft=draft,
        injector=injector, obs=obs, hw=hw,
    )
    if engine.fused != serve.fused_attention:
        print("multi-device mesh: unified step falls back to the gather path "
              "(Pallas kernel is single-device for now)")
    out = engine.run(a.request_stream(cfg))
    summary = engine.summary()
    if injector is not None:
        print(f"engine health after chaos: {json.dumps(engine.health())}")
    first = next(iter(out))
    print(f"served {len(out)} requests; {first} -> {out[first]}")
    if a.replay_trace:
        summary["classes"] = per_class_report(engine.sched.finished)
    if a.trace_out:
        n = obs.tracer.write(a.trace_out)
        print(f"wrote {n} trace events to {a.trace_out} "
              f"(load in Perfetto: https://ui.perfetto.dev)")
    if a.metrics_out:
        if a.metrics_out.endswith((".prom", ".txt")):
            with open(a.metrics_out, "w") as f:
                f.write(obs.metrics.to_prometheus())
        else:
            with open(a.metrics_out, "w") as f:
                json.dump(obs.metrics.snapshot(), f, indent=1)
        print(f"wrote metrics to {a.metrics_out}")
    cal = summary["calibration"]
    if cal.get("overall_ratio"):
        print(f"planner calibration: {cal['note']}")
    print(json.dumps(summary, indent=1, default=str))
    return engine


def run_eager(a: ServeArgs, cfg, mesh) -> dict:
    plan = derive_plan(
        cfg, dict(mesh.shape), resolve_hardware(a.hardware),
        batch=a.batch, seq_len=a.prompt_len, training=False,
    )
    params = init_params(jax.random.PRNGKey(0), cfg, plan, dtype=SERVE_WEIGHT_DTYPE)
    key = jax.random.PRNGKey(1)
    batch = {
        "tokens": jax.random.randint(key, (a.batch, a.prompt_len), 0, cfg.vocab_size)
    }
    if cfg.frontend != "none":
        batch["prefix_embeds"] = jax.random.normal(
            key, (a.batch, cfg.n_prefix_embeds, cfg.d_model), jnp.float32
        )
    if cfg.enc_dec:
        batch["enc_embeds"] = jax.random.normal(
            key, (a.batch, cfg.enc_seq, cfg.d_model), jnp.float32
        )
    t0 = time.time()
    out = greedy_generate(
        params, cfg, plan, batch, n_steps=a.gen,
        cache_len=a.prompt_len + a.gen,
    )
    dt = time.time() - t0
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({a.batch * a.gen / dt:.1f} tok/s)")
    print(out[0])
    return {"tok_per_s": a.batch * a.gen / dt}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--engine", default="batched", choices=["batched", "eager"])
    ap.add_argument("--batch", type=int, default=4,
                    help="eager batch / batched decode slots (with --fix-batch)")
    ap.add_argument("--fix-batch", action="store_true",
                    help="pin decode_batch to --batch instead of deriving it")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--stagger", type=int, default=2,
                    help="engine iterations between request arrivals")
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--slab-width", type=int, default=None,
                    help="mixed-slab query rows per slot (default: prefill chunk)")
    ap.add_argument("--pages-per-tile", type=int, default=None,
                    help="KV pages per VMEM tile of the fused kernel "
                         "(default: derived from the VMEM budget)")
    ap.add_argument("--no-fused", action="store_true",
                    help="use the dense gather path instead of the fused "
                         "Pallas paged-attention kernel")
    ap.add_argument("--kv-dtype", default=None,
                    choices=[None, "bf16", "int8", "fp32"])
    ap.add_argument("--draft", default=None,
                    help="speculative draft source: 'ngram' (prompt-lookup "
                         "self-drafting) or a config name (e.g. smollm-135m "
                         "drafting for a larger --arch)")
    ap.add_argument("--spec-len", type=int, default=None,
                    help="draft depth gamma per decode slot (default: derived "
                         "from the roofline's compute slack; 0 disables)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable copy-on-write prefix sharing (A/B baseline; "
                         "outputs are byte-identical either way)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="fleet TTFT target fed back into the plan "
                         "(slab width, draft depth)")
    ap.add_argument("--rolled-steps", type=int, default=None,
                    help="cap K of the rolled on-device decode loop (decode "
                         "iterations per dispatch; default: derived from the "
                         "dispatch-overhead roofline; 1 disables)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="fleet-default per-request deadline (wall-clock ms "
                         "from submit); expiry cancels the request and "
                         "releases its blocks")
    ap.add_argument("--retry-limit", type=int, default=3,
                    help="transient-dispatch retries per degradation-ladder "
                         "rung before stepping down rolled -> mixed -> gather")
    ap.add_argument("--stall-limit", type=int, default=256,
                    help="consecutive no-progress iterations before run() "
                         "raises StallError with an engine health dump")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="enable the deterministic fault injector with this "
                         "seed (pair with --chaos-* rates; docs/ROBUSTNESS.md)")
    ap.add_argument("--chaos-transient", type=float, default=0.0,
                    help="per-iteration probability of a transient dispatch "
                         "failure burst")
    ap.add_argument("--chaos-burst", type=int, default=1,
                    help="consecutive dispatch attempts each transient fault "
                         "kills (longer than --retry-limit forces ladder "
                         "escalation)")
    ap.add_argument("--chaos-nan", type=float, default=0.0,
                    help="per-slot per-iteration probability of non-finite "
                         "logits (quarantine + replay keeps outputs "
                         "byte-identical)")
    ap.add_argument("--chaos-pressure", type=float, default=0.0,
                    help="per-iteration probability of a temporary block-pool "
                         "squeeze")
    ap.add_argument("--chaos-spike-ms", type=float, default=0.0,
                    help="artificial per-dispatch latency spike (stresses the "
                         "SLO/EMA feedback); 0 disables")
    ap.add_argument("--chaos-horizon", type=int, default=None,
                    help="iteration after which no new fault fires (lets a "
                         "chaotic stream drain deterministically)")
    ap.add_argument("--hardware", default="tpu_v5e",
                    help="registered HardwareSpec the plans derive from when "
                         "the backend is not a TPU (variants: "
                         "repro.core.hardware.HARDWARE_VARIANTS); on a TPU "
                         "the chip's device_kind decides")
    ap.add_argument("--from-family", default=None,
                    choices=[None, "throughput", "cost", "energy"],
                    help="pick the serving plan off the design-space Pareto "
                         "frontier (core/search.py) instead of deriving one; "
                         "the criterion chooses the frontier point")
    ap.add_argument("--replay-trace", default=None, dest="replay_trace",
                    help="multi-tenant trace replay: workload mix spec like "
                         "'chat:4,summarize:2,classify:2' (replaces "
                         "--requests/--prompt-len/--gen)")
    ap.add_argument("--trace", default=None,
                    help="(deprecated alias for --replay-trace; --trace-out "
                         "is the lifecycle-trace export)")
    ap.add_argument("--tenant-mix", type=int, default=2,
                    help="tenants in the trace; each gets a shared system "
                         "prompt its requests all carry")
    ap.add_argument("--metrics-out", default=None,
                    help="write the metrics registry after the run: *.prom/"
                         "*.txt -> Prometheus text exposition, anything "
                         "else -> JSON snapshot")
    ap.add_argument("--trace-out", default=None,
                    help="write the lifecycle + dispatch trace as Chrome "
                         "trace_event JSON (open in https://ui.perfetto.dev); "
                         "tracing is enabled only when this is set")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="trace ring-buffer capacity in events; older events "
                         "drop first (dropped count recorded in the export)")
    return ap


def main():
    a = ServeArgs.from_namespace(build_parser().parse_args())
    enable_compile_cache()
    cfg = get_config(a.arch)
    mesh = make_host_mesh()
    if a.engine == "batched" and not serve_feasible(cfg)[0]:
        print(f"{a.arch}: {serve_feasible(cfg)[1]}; falling back to --engine eager")
        a.engine = "eager"
    if a.engine == "batched":
        run_batched(a, cfg, mesh)
    else:
        run_eager(a, cfg, mesh)


if __name__ == "__main__":
    main()

"""Smoke run of the continuous-batching server on a TPU, at the full width of
qwen3-1.7b (28 layers, random weights from a fixed seed).

    python3 chip_smoke.py               # one chip: the served path
    python3 chip_smoke.py --four-chips  # four chips: the model-sharded path

One chip: 8 staggered requests (prompt 128, gen 32) go through
``launch/serve.run_batched`` with the plan ``derive_serve_plan`` derives
for the chip.  The run must finish every request with its token count, use
the fused paged-attention kernel compiled by Mosaic (a ``tpu_custom_call``
in the step's HLO that carries the kernel's name) and compile each engine
program at most once.  When the derived plan does not roll decode steps,
the same requests run again with the rolled loop (4 steps per dispatch), so
both engine programs run.  The same requests then run on a gather-path
engine (dense attention) and the tokens are compared, as are the first-step
logits of the two attention paths (tolerance below).

Four chips: the same requests on a (data=1, model=4) mesh and on a
one-device mesh with the same plan, tokens and first-step logits compared.

Timings printed on the way are smoke timings (compilation included), not a
benchmark.  The last line of stdout is a JSON object naming the device.
Exits non-zero, with no such line, when JAX finds no TPU or a phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.dist.sharding import Shardings  # noqa: E402
from repro.kernels.paged_attention.kernel import NAME as KERNEL  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import ServeArgs, run_batched  # noqa: E402
from repro.models.cache import init_paged_cache  # noqa: E402
from repro.models.transformer import forward, logits_fn  # noqa: E402
from repro.serve import ServingEngine  # noqa: E402
from repro.serve.engine import traces_bounded  # noqa: E402

ARCH = "qwen3-1.7b"
REQUESTS, PROMPT, GEN, STAGGER = 8, 128, 32, 2
# First-step logits of two paths may differ by this fraction of the largest
# logit.  On a TPU, default-precision f32 matmuls take bf16 operands
# (8-bit mantissa, ~0.4% rounding); the two attention paths round in
# different places and 28 layers compound it to a few percent at most.  A
# wrong page, scale or mask gives errors of the order of the logits.
LOGIT_RTOL = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def log_hbm(label: str) -> None:
    """Device memory of the first chip as its allocator reports it."""
    s = jax.devices()[0].memory_stats() or {}
    gb = lambda k: s.get(k, 0) / 1e9  # noqa: E731
    log(f"hbm ({label}): {gb('bytes_in_use'):.2f} GB in use, peak "
        f"{gb('peak_bytes_in_use'):.2f} GB of {gb('bytes_limit'):.2f} GB")


def run_stream(engine, args, cfg, label: str) -> dict:
    """Run the request stream on ``engine``; returns {rid: tokens}."""
    t0 = time.perf_counter()
    out = engine.run(args.request_stream(cfg))
    log(f"smoke timing ({label}): {time.perf_counter() - t0:.1f} s for "
        f"{engine.stats['steps']} steps, compilation included")
    log_hbm(label)
    check(len(out) == REQUESTS, f"{label}: {len(out)} of {REQUESTS} requests finished")
    for rid, toks in out.items():
        check(len(toks) == GEN, f"{label}: {rid} emitted {len(toks)} of {GEN} tokens")
    check(traces_bounded(engine.trace_counts), f"{label}: traces {engine.trace_counts}")
    return out


def release(engine) -> None:
    """Free an engine's KV pool now: weights and the pools of two engines
    do not fit in one chip's HBM together."""
    for x in jax.tree.leaves(engine.pools):
        x.delete()


def token_agreement(a: dict, b: dict) -> tuple[int, int]:
    """(identical requests, tokens before the first divergence summed)."""
    same = sum(a[r] == b[r] for r in a)
    lead = 0
    for r in a:
        for x, y in zip(a[r], b[r]):
            if x != y:
                break
            lead += 1
    return same, lead


def first_logits(params, cfg, plan, serve_plan, prompts, *, fused, shardings=None):
    """Logits after each prompt's last token from one paged prefill pass of
    the model's forward, on either attention path."""
    n, length = prompts.shape
    mb = serve_plan.max_blocks_per_seq
    sp = dataclasses.replace(serve_plan, decode_batch=n, n_blocks=1 + n * mb)
    pools = init_paged_cache(cfg, plan, sp)
    shard = lambda x, name=None: x
    if shardings is not None:
        pools = jax.device_put(pools, shardings.cache_shardings(pools))
        shard = shardings.constrain
    table = jnp.asarray(1 + np.arange(n * mb).reshape(n, mb), jnp.int32)

    @jax.jit
    def f(params, pools, tokens, table):
        page_state = {
            "block_size": sp.block_size, "fused": fused,
            "pages_per_tile": sp.pages_per_tile, "table": table,
            "q_lens": jnp.full((n,), length, jnp.int32),
        }
        cache = {"layers": pools["layers"], "t": jnp.zeros((n,), jnp.int32)}
        x, _, _ = forward(
            params, {"tokens": tokens}, cfg=cfg, plan=plan, cache=cache,
            shard=shard, page_state=page_state,
        )
        return logits_fn(params, x[:, -1:], cfg)[:, -1].astype(jnp.float32)

    return np.asarray(f(params, pools, jnp.asarray(prompts, jnp.int32), table))


def compare_logits(ref, got, label: str) -> None:
    """Relative max error within LOGIT_RTOL, and the greedy token equal
    wherever the reference's top-2 margin exceeds that error."""
    ref, got = np.asarray(ref, np.float64), np.asarray(got, np.float64)
    check(np.isfinite(ref).all() and np.isfinite(got).all(), f"{label}: non-finite logits")
    scale = np.abs(ref).max()
    err = np.abs(ref - got).max()
    top2 = np.sort(ref, axis=-1)[:, -2:]
    clear = (top2[:, 1] - top2[:, 0]) > 2 * err
    agree = ref.argmax(-1) == got.argmax(-1)
    log(f"{label}: first-step logits max |diff| {err:.4g} = {err / scale:.3g} of "
        f"max |logit| {scale:.4g} (limit {LOGIT_RTOL}); greedy token agrees "
        f"for {int(agree.sum())}/{len(agree)} prompts")
    check(err <= LOGIT_RTOL * scale, f"{label}: logits differ by {err / scale:.3g}")
    check(bool(np.all(agree | ~clear)), f"{label}: greedy token differs at a clear margin")


def one_device_mesh():
    """A (data=1, model=1) mesh on the first device."""
    auto = (jax.sharding.AxisType.Auto,) * 2
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=auto)


def prompts_of(args, cfg):
    return np.asarray([r.prompt for r in args.request_stream(cfg)], np.int32)


def one_chip(cfg) -> None:
    mesh = one_device_mesh()
    args = ServeArgs(arch=ARCH, requests=REQUESTS, prompt_len=PROMPT, gen=GEN,
                     stagger=STAGGER)
    t0 = time.perf_counter()
    engine = run_batched(args, cfg, mesh)
    log(f"smoke timing (derived plan, run_batched): {time.perf_counter() - t0:.1f} s, "
        f"weight init and compilation included")
    log_hbm("derived plan")
    out = {r.rid: list(r.out) for r in engine.sched.finished}
    check(len(out) == REQUESTS, f"{len(out)} of {REQUESTS} requests finished")
    for rid, toks in out.items():
        check(len(toks) == GEN, f"{rid} emitted {len(toks)} of {GEN} tokens")
    check(engine.fused, "the engine took the gather path on one chip")
    check(traces_bounded(engine.trace_counts), f"traces {engine.trace_counts}")
    hlo = step_hlo(engine)
    check("tpu_custom_call" in hlo, "no Mosaic kernel in the served step's HLO")
    check(any("tpu_custom_call" in line and f"/{KERNEL}/pallas_call" in line
              for line in hlo.splitlines()),
          f"the served step's Mosaic kernel is not named {KERNEL!r}")
    log(f"served step: fused={engine.fused} traces={engine.trace_counts} "
        f"tpu_custom_call named {KERNEL!r} in HLO: True")
    params, plan, sp, hw = engine.params, engine.plan, engine.serve, engine.hw
    sh = Shardings(mesh, plan, cfg)  # place new pools as run_batched did
    release(engine)

    if sp.rolled_steps == 1:
        sp4 = dataclasses.replace(sp, rolled_steps=4)
        rolled = ServingEngine(params, cfg, plan, sp4, shardings=sh, hw=hw)
        out_r = run_stream(rolled, args, cfg, "rolled loop, K=4")
        check(rolled.stats["rolled_dispatches"] > 0, "the rolled loop never dispatched")
        same, lead = token_agreement(out, out_r)
        log(f"rolled vs mixed: {same}/{REQUESTS} requests identical, "
            f"{lead}/{REQUESTS * GEN} tokens before the first divergence; "
            f"traces {rolled.trace_counts}")
        release(rolled)

    # one slot per request: at the derived batch the gather step's dense
    # per-slot context copies leave under 0.5 GB of HBM spare
    mb = sp.max_blocks_per_seq
    sp_g = dataclasses.replace(sp, decode_batch=REQUESTS, n_blocks=1 + REQUESTS * mb)
    gather = ServingEngine(params, cfg, plan, sp_g, shardings=sh, fused=False, hw=hw)
    out_g = run_stream(gather, args, cfg, "gather path")
    release(gather)
    same, lead = token_agreement(out_g, out)
    log(f"fused vs gather: {same}/{REQUESTS} requests identical, "
        f"{lead}/{REQUESTS * GEN} tokens before the first divergence")

    prompts = prompts_of(args, cfg)
    ref = first_logits(params, cfg, plan, sp, prompts, fused=False, shardings=sh)
    got = first_logits(params, cfg, plan, sp, prompts, fused=True, shardings=sh)
    compare_logits(ref, got, "fused vs gather")


def four_chips(cfg) -> None:
    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, found {len(jax.devices())}")
    mesh4 = make_host_mesh()
    # one slot per request: the plan then also fits a single chip, which
    # has to hold every weight and page the four chips split
    args = ServeArgs(arch=ARCH, requests=REQUESTS, prompt_len=PROMPT, gen=GEN,
                     stagger=STAGGER, batch=REQUESTS, fix_batch=True)
    t0 = time.perf_counter()
    engine = run_batched(args, cfg, mesh4)
    log(f"smoke timing (model-sharded, run_batched): {time.perf_counter() - t0:.1f} s, "
        f"weight init and compilation included")
    log_hbm("model-sharded")
    out4 = {r.rid: list(r.out) for r in engine.sched.finished}
    check(len(out4) == REQUESTS and all(len(t) == GEN for t in out4.values()),
          "sharded engine did not finish every request")
    check(traces_bounded(engine.trace_counts), f"traces {engine.trace_counts}")
    log(f"sharded engine: mesh {dict(mesh4.shape)} fused={engine.fused}")
    plan, sp, hw = engine.plan, engine.serve, engine.hw
    params4 = engine.params
    sh4 = Shardings(mesh4, plan, cfg)
    release(engine)

    mesh1 = one_device_mesh()
    sh1 = Shardings(mesh1, plan, cfg)
    params1 = jax.device_put(params4, sh1.param_shardings(params4))
    single = ServingEngine(params1, cfg, plan, sp, shardings=sh1, fused=False, hw=hw)
    out1 = run_stream(single, args, cfg, "one device, same plan")
    release(single)
    same, lead = token_agreement(out1, out4)
    log(f"model=4 vs one device: {same}/{REQUESTS} requests identical, "
        f"{lead}/{REQUESTS * GEN} tokens before the first divergence")

    prompts = prompts_of(args, cfg)
    ref = first_logits(params1, cfg, plan, sp, prompts, fused=False, shardings=sh1)
    got = first_logits(params4, cfg, plan, sp, prompts, fused=False, shardings=sh4)
    compare_logits(ref, got, "model=4 vs one device")


def step_hlo(engine) -> str:
    """Optimized HLO of the engine's unified step, as compiled for its
    pools and parameters."""
    sp = engine.serve
    B, T, MB = sp.decode_batch, sp.step_tokens, sp.max_blocks_per_seq
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    lowered = engine._step.lower(
        jax.tree.map(spec, engine.params), jax.tree.map(spec, engine.pools),
        i32(3, T), i32(B, MB), i32(B), i32(B), jax.ShapeDtypeStruct((B,), jnp.float32),
    )
    return lowered.compile().as_text()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the model-sharded path on four chips")
    a = ap.parse_args()

    cache = enable_compile_cache()  # before the first compile
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    log(f"device: {device['kind']} x{device['count']}; compile cache {cache}")
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if a.four_chips:
        four_chips(cfg)
    else:
        one_chip(cfg)
    log(f"smoke timing (all phases): {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

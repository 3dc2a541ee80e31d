"""Fused paged-attention kernel vs the gather-path attention microbenchmark.

Times one decode-attention layer step the two ways the serve engine can run
it, over (batch, context, block_size, kv_dtype):

* **gather** — what the engine did before the fused kernel: materialize the
  dense (B, cache_len, KH, D) cache from the block pool in HBM
  (``models/cache.paged_gather`` at full table width, exactly like the old
  jitted step), then ``models/layers.paged_attention`` over it.
* **fused** — ``kernels/paged_attention``: walk the block table, stream
  pages into VMEM tiles, online-softmax in place.  Slots only pay for their
  own live context (tiles past a slot's high-water mark are skipped), while
  the gather path always pays ``cache_len``.

Slots carry a realistic mixed decode state (live lengths drawn between half
and full context).  Emits ``BENCH_paged_attn.json`` and registers in
``benchmarks/run.py``; CI uploads the JSON next to BENCH_serve.json.

    PYTHONPATH=src:. python -m benchmarks.paged_attn_bench \
        --out BENCH_paged_attn.json
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_record, emit
from repro.core.hardware import TPU_V5E
from repro.core.plan import int8_scale_lanes
from repro.kernels.paged_attention.ops import paged_attention as fused_attn
from repro.models.cache import paged_gather
from repro.models.layers import paged_attention as gather_attn


def _build(B, ctx, bs, kv_dtype, H=4, KH=2, D=64, seed=0):
    key = jax.random.PRNGKey(seed)
    MB = ctx // bs
    N = 1 + B * MB
    q = jax.random.normal(key, (B, 1, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (N, bs, KH, D), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (N, bs, KH, D), jnp.float32)
    if kv_dtype == "int8":
        def q8(x):
            s = jnp.maximum(jnp.abs(x).max(-1, keepdims=True), 1e-12) / 127.0
            q = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
            rows = s.reshape(N, 1, bs * KH)  # pool layout (N, 1, P)
            pad = int8_scale_lanes(KH, bs) - bs * KH
            return q, jnp.pad(rows, ((0, 0), (0, 0), (0, pad)))

        qk, sk = q8(k)
        qv, sv = q8(v)
        entry = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    else:
        entry = {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}
    rng = np.random.default_rng(seed)
    lens = rng.integers(ctx // 2, ctx, B).astype(np.int32)  # mixed decode state
    table = np.zeros((B, MB), np.int32)
    for b in range(B):
        nb = -(-(int(lens[b]) + 1) // bs)
        table[b, :nb] = 1 + b * MB + np.arange(nb)
    return q, entry, jnp.asarray(table), jnp.asarray(lens)


@functools.partial(jax.jit, static_argnames=("bs",))
def _gather_step(q, entry, table, lens, *, bs):
    # the old engine's jitted path: dense gather at full table width (the
    # trace sees a Tracer table, so no high-water clamp applies — exactly
    # the over-materialization the fused kernel removes)
    kf, vf = paged_gather(entry, table, bs)
    return gather_attn(q, kf, vf, lens[:, None])


def _time(fn, *args, iters=10, repeats=5):
    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(repeats):  # min over repeats rejects scheduler noise
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def bench_case(B, ctx, bs, kv_dtype, ppt=None):
    q, entry, table, lens = _build(B, ctx, bs, kv_dtype)
    q_lens = jnp.ones((B,), jnp.int32)
    MB = table.shape[1]
    ppt = ppt or max(1, MB // 8)

    def fused(q, entry, table, lens, q_lens):
        return fused_attn(
            q, entry, table, lens, q_lens, block_size=bs, pages_per_tile=ppt
        )

    t_fused = _time(fused, q, entry, table, lens, q_lens)
    t_gather = _time(
        functools.partial(_gather_step, bs=bs), q, entry, table, lens
    )
    return {
        "batch": B,
        "context": ctx,
        "block_size": bs,
        "kv_dtype": kv_dtype,
        "pages_per_tile": ppt,
        "fused_us": t_fused * 1e6,
        "gather_us": t_gather * 1e6,
        "fused_decode_tok_s": B / t_fused,
        "gather_decode_tok_s": B / t_gather,
        "speedup": t_gather / t_fused,
    }


SWEEP = [
    # (batch, context, block_size, kv_dtype).  At the largest context the
    # derived plans flip KV pages to int8 (the bf16 pool cannot host the
    # roofline batch at full context — test_serve_plan_derivation), so the
    # headline cases carry the plan's own dtype; bf16 covers the small end.
    (4, 512, 16, "bf16"),
    (4, 2048, 16, "bf16"),
    (8, 2048, 32, "bf16"),
    (4, 2048, 16, "int8"),
    (8, 4096, 64, "bf16"),
    (8, 8192, 64, "int8"),
    (16, 8192, 64, "int8"),
]


def sweep(out: str = "BENCH_paged_attn.json") -> dict:
    t0 = time.perf_counter()
    cases = [bench_case(*c) for c in SWEEP]
    max_ctx = max(c["context"] for c in cases)
    at_largest = [c for c in cases if c["context"] == max_ctx]
    record = bench_record("paged_attn", {
        "hardware": TPU_V5E.name + " (cpu interpret timings)",
        "cases": cases,
        "largest_context": max_ctx,
        "fused_beats_gather_at_largest_context": bool(
            all(
                c["fused_decode_tok_s"] > c["gather_decode_tok_s"]
                for c in at_largest
            )
        ),
    }, config={"sweep": SWEEP}, seed=0, elapsed_s=time.perf_counter() - t0)
    with open(out, "w") as f:
        json.dump(record, f, indent=1)
    for c in cases:
        print(
            f"B{c['batch']} ctx{c['context']} bs{c['block_size']} "
            f"{c['kv_dtype']}: fused {c['fused_us']:.0f}us vs gather "
            f"{c['gather_us']:.0f}us ({c['speedup']:.2f}x)"
        )
    print(f"wrote {out}")
    return record


def run() -> list[str]:
    """benchmarks/run.py hook: the small end of the sweep as CSV rows."""
    rows = []
    for B, ctx, bs, kvd in SWEEP[:3]:
        c = bench_case(B, ctx, bs, kvd)
        rows.append(
            emit(
                f"paged_attn/b{B}_ctx{ctx}_{kvd}",
                c["fused_us"],
                f"gather_us={c['gather_us']:.0f};speedup={c['speedup']:.2f}",
            )
        )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_paged_attn.json")
    a = ap.parse_args()
    sweep(a.out)

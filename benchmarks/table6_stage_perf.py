"""Paper Table VI — per-stage performance (MHA Stage vs FFN Stage vs system)
for the paper's own models (BERT-Base L=256, ViT-Base L=197).

CPU wall time per stage + derived v5e TOPS from the roofline model; the
paper's structural claims replicated: system sits between the two stages,
ViT's MHA throughput suffers from L=197 padding.

Also emits ``BENCH_dist.json``: the gradient-exchange bytes-on-wire
comparison (fp32 baseline vs the bf16/int8 ``compressed_psum`` wire
formats from ``dist/collectives.py``) plus the measured int8 round-trip
error of the exchange on a tiny gradient tree.
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

from benchmarks.common import bench_record, emit, time_fn
from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import derive_plan
from repro.core.pu import pick_pu
from repro.kernels.mm_pu.ops import pad_overhead
from repro.models import init_params
from repro.models import transformer as T
from repro.models.layers import apply_norm


def _stage_fns(cfg, plan, params):
    lp = jax.tree.map(lambda x: x[0], params["blocks"]["stack"])[0]
    positions = jnp.arange(256)[None]

    @jax.jit
    def mha_stage(x):
        h = apply_norm(lp["attn"]["ln"], x, cfg.norm)
        out, _, _ = T.attention_stage(
            lp["attn"], h, cfg=cfg, plan=plan, kind="attn",
            positions=positions[:, : x.shape[1]], cache=None, prefix_len=0,
        )
        return x + out

    @jax.jit
    def ffn_stage(x):
        from repro.models.layers import mlp

        h = apply_norm(lp["ffn"]["ln"], x, cfg.norm)
        return x + mlp(lp["ffn"], h, cfg.activation)

    return mha_stage, ffn_stage


def _v5e_tops(cfg, L, stage: str) -> float:
    """Roofline-derived achievable TOPS for one stage on one chip."""
    hw = TPU_V5E
    D, H, F = cfg.d_model, cfg.n_heads, cfg.d_ff
    if stage == "mha":
        flops = 2 * L * D * 3 * D + 2 * 2 * L * L * D + 2 * L * D * D
        spec = pick_pu(L, 3 * D, D, hw)
        t = hw.matmul_time_s(L, 3 * D, D) * (1 + max(pad_overhead(L, 3 * D, D, spec), 0))
        t += 2 * 2 * L * L * D / hw.peak_flops_bf16 + hw.matmul_time_s(L, D, D)
    else:
        flops = 2 * L * D * F * 2
        t = hw.matmul_time_s(L, F, D) + hw.matmul_time_s(L, D, F)
    return flops / t / 1e12


def grad_exchange_report(archs=("bert-base", "vit-base"), out_path="BENCH_dist.json"):
    """Bytes-on-wire per gradient exchange, compressed vs uncompressed.

    Analytic per full-size model (one replica's payload per all-reduce, from
    the parameter count), plus a measured int8 exchange error on the
    reduced config so the number is grounded in the real collective.
    """
    from repro.core.plan import derive_plan
    from repro.dist.collectives import compressed_psum, wire_bytes
    from repro.models.params import param_count_tree

    import time as _time

    _t0 = _time.perf_counter()
    report = {"benchmark": "grad_exchange_bytes_on_wire", "archs": {}}
    for arch in archs:
        cfg = get_config(arch)
        n = cfg.param_count()
        per_mode = {m: wire_bytes(n, m) for m in ("none", "bf16", "int8")}
        report["archs"][arch] = {
            "params": n,
            "bytes_on_wire": per_mode,
            "reduction_vs_fp32": {
                m: round(per_mode["none"] / b, 2) for m, b in per_mode.items()
            },
        }
    # measured: int8 exchange on a reduced-config gradient tree (1 device:
    # psum over a size-1 axis still runs the full quantize/sum/dequant path)
    cfg = get_config("bert-base-reduced")
    plan = derive_plan(cfg, {"data": 1, "model": 1}, batch=2, seq_len=16)
    params = init_params(jax.random.PRNGKey(0), cfg, plan, dtype=jnp.float32)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(1), p.shape) * 1e-2, params
    )
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh(
        (1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,)
    )
    exchanged = shard_map(
        lambda g: jax.tree.map(lambda x: compressed_psum(x, "data", "int8"), g),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
    )(grads)
    errs = [
        float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
        for a, b in zip(jax.tree.leaves(exchanged), jax.tree.leaves(grads))
    ]
    report["int8_exchange_max_rel_err"] = max(errs)
    report["grad_leaves_measured"] = len(errs)
    report["params_measured"] = param_count_tree(params)
    report = bench_record(
        "grad_exchange", report, config={"archs": list(archs)}, seed=0,
        elapsed_s=_time.perf_counter() - _t0,
    )
    pathlib.Path(out_path).write_text(json.dumps(report, indent=1))
    print(f"wrote {out_path} ({len(report['archs'])} archs)", flush=True)
    return report


def run() -> list[str]:
    out = []
    for arch, L in (("bert-base", 256), ("vit-base", 197)):
        cfg = get_config(arch)
        plan = derive_plan(cfg, {"data": 1, "model": 1}, batch=2, seq_len=L)
        params = init_params(jax.random.PRNGKey(0), cfg, plan, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, L, cfg.d_model), jnp.float32)
        mha, ffn = _stage_fns(cfg, plan, params)
        t_mha = time_fn(mha, x)
        t_ffn = time_fn(ffn, x)
        tops_mha = _v5e_tops(cfg, L, "mha")
        tops_ffn = _v5e_tops(cfg, L, "ffn")
        out.append(emit(f"table6/{arch}/mha_stage", t_mha, f"v5e_tops={tops_mha:.1f}"))
        out.append(emit(f"table6/{arch}/ffn_stage", t_ffn, f"v5e_tops={tops_ffn:.1f}"))
        sys_tops = (tops_mha * t_mha + tops_ffn * t_ffn) / (t_mha + t_ffn)
        out.append(
            emit(f"table6/{arch}/system", t_mha + t_ffn, f"v5e_tops={sys_tops:.1f}")
        )
    rep = grad_exchange_report()
    for arch, r in rep["archs"].items():
        out.append(
            emit(
                f"table6/{arch}/grad_wire_int8_reduction",
                r["bytes_on_wire"]["int8"] / 1e6,
                f"x{r['reduction_vs_fp32']['int8']}_vs_fp32",
            )
        )
    return out


if __name__ == "__main__":
    run()

"""Parameter initialization.

The param tree mirrors the EDPU structure: ``blocks.stack`` holds n_full
pattern-groups stacked on a leading axis (scanned), ``blocks.tail`` the
remainder layers.  Whether QKV is one fused matrix (C5 Independent-Linear)
is a *plan* decision, so init takes the plan.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.plan import ExecutionPlan

PyTree = Any


def _norm_params(cfg: ArchConfig, d: int) -> dict:
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


class _Init:
    """Deterministic per-leaf initializer (fold_in counter keys)."""

    def __init__(self, key: jax.Array, dtype):
        self.key = key
        self.count = 0
        self.dtype = dtype

    def normal(self, shape, scale=0.02):
        self.count += 1
        k = jax.random.fold_in(self.key, self.count)
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(self.dtype)

    def zeros(self, shape):
        return jnp.zeros(shape, self.dtype)


def _attn_params(init: _Init, cfg: ArchConfig, plan: ExecutionPlan, cross: bool = False) -> dict:
    d, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p: dict = {"ln": _norm_params(cfg, d)}
    if plan.fuse_qkv and not cross:
        p["wqkv"] = init.normal((d, (H + 2 * KV) * Dh))
    else:
        p["wq"] = init.normal((d, H * Dh))
        p["wk"] = init.normal((d, KV * Dh))
        p["wv"] = init.normal((d, KV * Dh))
    p["wo"] = init.normal((H * Dh, d), scale=0.02 / max(1, cfg.n_layers) ** 0.5)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((Dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((Dh,), jnp.float32)
    return p


def _ffn_params(init: _Init, cfg: ArchConfig) -> dict:
    d = cfg.d_model
    p: dict = {"ln": _norm_params(cfg, d)}
    if cfg.is_moe:
        E, F = cfg.n_experts, cfg.moe_d_ff
        p["router"] = init.normal((d, E))
        p["w1"] = init.normal((E, d, F))
        if cfg.activation in ("swiglu", "geglu"):
            p["w3"] = init.normal((E, d, F))
        p["w2"] = init.normal((E, F, d))
    elif cfg.activation == "rwkv":
        F = cfg.d_ff
        p["mix_k"] = init.zeros((d,))
        p["mix_r"] = init.zeros((d,))
        p["w1"] = init.normal((d, F))
        p["w_r"] = init.normal((d, d))
        p["w2"] = init.normal((F, d))
    else:
        F = cfg.d_ff
        p["w1"] = init.normal((d, F))
        if cfg.activation in ("swiglu", "geglu"):
            p["w3"] = init.normal((d, F))
        p["w2"] = init.normal((F, d))
    return p


def _rglru_params(init: _Init, cfg: ArchConfig) -> dict:
    d, W, Hn = cfg.d_model, cfg.lru_width or cfg.d_model, max(cfg.rnn_heads, 1)
    bh = W // Hn
    return {
        "ln": _norm_params(cfg, d),
        "w_x": init.normal((d, W)),
        "w_g": init.normal((d, W)),
        "conv_w": init.normal((cfg.conv_width, W), scale=0.1),
        "w_gate_a": init.normal((Hn, bh, bh)),
        "b_gate_a": init.zeros((W,)),
        "w_gate_x": init.normal((Hn, bh, bh)),
        "b_gate_x": init.zeros((W,)),
        # softplus(lam) ~ U[...] so a = exp(-8 softplus(lam)) spans (0.7, 0.999)
        "lam": jnp.linspace(-2.0, 1.0, W, dtype=jnp.float32),
        "w_out": init.normal((W, d)),
    }


def _rwkv6_params(init: _Init, cfg: ArchConfig) -> dict:
    d, H, Dh = cfg.d_model, cfg.rnn_heads, cfg.d_head
    hd = H * Dh
    lora = max(32, d // 32)
    p = {
        "ln": _norm_params(cfg, d),
        "w_r": init.normal((d, hd)),
        "w_k": init.normal((d, hd)),
        "w_v": init.normal((d, hd)),
        "w_g": init.normal((d, hd)),
        "w_o": init.normal((hd, d), scale=0.02 / max(1, cfg.n_layers) ** 0.5),
        "lora_a": init.normal((d, lora)).astype(jnp.float32),
        "lora_b": init.normal((lora, hd)).astype(jnp.float32),
        "w0": jnp.full((hd,), -0.6, jnp.float32),  # decay ~ exp(-exp(-0.6)) ~ .58
        "u": init.normal((H, Dh)).astype(jnp.float32),
        "gn_scale": jnp.ones((hd,), jnp.float32),
        "gn_bias": jnp.zeros((hd,), jnp.float32),
    }
    for name in ("mix_r", "mix_k", "mix_v", "mix_g", "mix_w"):
        p[name] = init.zeros((d,))
    return p


def layer_params(init: _Init, cfg: ArchConfig, plan: ExecutionPlan, kind: str,
                 with_cross: bool = False) -> dict:
    if kind in ("attn", "swa", "local"):
        core = {"attn": _attn_params(init, cfg, plan)}
    elif kind == "rglru":
        core = {"attn": _rglru_params(init, cfg)}
    elif kind == "rwkv6":
        core = {"attn": _rwkv6_params(init, cfg)}
    else:
        raise ValueError(kind)
    if with_cross:
        core["cross"] = _attn_params(init, cfg, plan, cross=True)
    core["ffn"] = _ffn_params(init, cfg)
    return core


def init_params(
    key: jax.Array,
    cfg: ArchConfig,
    plan: ExecutionPlan,
    dtype=jnp.bfloat16,
) -> PyTree:
    init = _Init(key, dtype)
    pattern = cfg.layer_pattern
    n_full, rem = divmod(cfg.n_layers, len(pattern))
    with_cross = cfg.enc_dec

    def one_group(_):
        return tuple(
            layer_params(init, cfg, plan, kind, with_cross) for kind in pattern
        )

    groups = [one_group(i) for i in range(n_full)]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *groups) if n_full else None
    tail = tuple(
        layer_params(init, cfg, plan, pattern[i], with_cross) for i in range(rem)
    )

    params: dict = {"blocks": {"stack": stack, "tail": tail}}
    if cfg.vocab_size > 1:
        params["embed"] = init.normal((cfg.vocab_size, cfg.d_model))
    if cfg.pos_embedding == "learned":
        params["pos"] = init.normal((cfg.max_seq_len, cfg.d_model))
    params["final_norm"] = _norm_params(cfg, cfg.d_model)
    if not cfg.tie_embeddings and cfg.vocab_size > 1:
        params["lm_head"] = init.normal((cfg.d_model, cfg.vocab_size))
    if cfg.n_classes:
        params["cls_head"] = init.normal((cfg.d_model, cfg.n_classes))

    if cfg.enc_dec:
        enc_groups = [
            (layer_params(init, cfg, plan, "attn"),) for _ in range(cfg.n_enc_layers)
        ]
        params["encoder"] = {
            "stack": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_groups),
            "tail": (),
            "final_norm": _norm_params(cfg, cfg.d_model),
        }
    return params

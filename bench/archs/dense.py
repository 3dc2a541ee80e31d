"""Dense decoder: grouped-query attention with RoPE over the whole head
(and optionally RMSNorm on q and k), RMSNorm, SwiGLU and a tied output
head.  A configuration file without ``"arch_module"`` is served as this.

An architecture module holds what the benchmark knows of one shape of
model, in five hooks, each a function of the configuration file ``c``:

- ``shapes(c)``: the weights the seed draws, by name (``bench/weights.py``);
- ``hidden(c, w, tokens, quantized)``: the plain float32 forward pass to
  the final hidden states (``bench/reference.py``); it imports nothing of
  the program;
- ``check_program(c, cfg)``: refuses a program configuration that departs
  from the file, since the reference follows the file (``bench/model.py``);
- ``to_program_tree(w, cfg, plan)``: the drawn weights in the program's
  parameter tree (``bench/model.py``);
- the work counts ``dense_flops_per_row``, ``attn_flops``, ``head_flops``,
  ``kv_bytes`` and ``qo_bytes`` (``bench/flops.py``).

``check_program`` alone imports the program, inside its body."""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import KV_ITEMSIZE, QO_ITEMSIZE, SCALE_BYTES
from bench.reference import HI, _mm, _q8
from bench.weights import STD

# ------------------------------------------------------------------ weights


def dims(c: dict) -> dict:
    """Sizes a configuration file states, under short names."""
    return {
        "L": c["num_hidden_layers"], "d": c["hidden_size"],
        "H": c["num_attention_heads"], "KV": c["num_key_value_heads"],
        "Dh": c["head_dim"], "F": c["intermediate_size"], "V": c["vocab_size"],
        "qk_norm": bool(c.get("qk_norm", False)),
    }


def shapes(c: dict) -> dict:
    """name -> (shape, std); a std of None marks a norm gain's offset."""
    m = dims(c)
    L, d, H, KV, Dh, F, V = (m[k] for k in ("L", "d", "H", "KV", "Dh", "F", "V"))
    out_std = STD / (2 * L) ** 0.5
    s = {
        "embed": ((V, d), STD),
        "final_norm": ((d,), None),
        "attn_norm": ((L, d), None),
        "wq": ((L, d, H * Dh), STD),
        "wk": ((L, d, KV * Dh), STD),
        "wv": ((L, d, KV * Dh), STD),
        "wo": ((L, H * Dh, d), out_std),
        "ffn_norm": ((L, d), None),
        "w_gate": ((L, d, F), STD),
        "w_up": ((L, d, F), STD),
        "w_down": ((L, F, d), out_std),
    }
    if m["qk_norm"]:
        s["q_norm"] = ((L, Dh), None)
        s["k_norm"] = ((L, Dh), None)
    return s


# ---------------------------------------------------------------- reference


def _rms(x, offset, eps):
    g = 1.0 + offset
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta, fraction):
    """Rotate the first ``fraction`` of each head by position, pairing
    dimension i with i + rot/2 (the published models' layout)."""
    Dh = x.shape[-1]
    rot = int(Dh * fraction)
    freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2 : rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def hidden(c: dict, w: dict, tokens, quantized: bool = False):
    """Final hidden states (S, d) of one sequence ``tokens`` (S,)."""
    m = dims(c)
    H, KV, Dh = m["H"], m["KV"], m["Dh"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    frac = float(c.get("partial_rotary_factor", 1.0))
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens]

    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], eps)
        q = _mm(h, lw["wq"], quantized).reshape(S, H, Dh)
        k = _mm(h, lw["wk"], quantized).reshape(S, KV, Dh)
        v = _mm(h, lw["wv"], quantized).reshape(S, KV, Dh)
        if m["qk_norm"]:
            q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
        q, k = _rope(q, pos, theta, frac), _rope(k, pos, theta, frac)
        if quantized:
            k, v = _q8(k, -1), _q8(v, -1)
        k = jnp.repeat(k, H // KV, axis=1)  # query head i reads kv head i // (H/KV)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / np.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, H * Dh)
        x = x + _mm(o, lw["wo"], quantized)
        h = _rms(x, lw["ffn_norm"], eps)
        f = jax.nn.silu(_mm(h, lw["w_gate"], quantized)) * _mm(h, lw["w_up"], quantized)
        return x + _mm(f, lw["w_down"], quantized), None

    per_layer = {k: v for k, v in w.items() if k not in ("embed", "final_norm")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    return _rms(x, w["final_norm"], eps)


# ------------------------------------------------------------------ program

# configuration-file key -> the program's ArchConfig field
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "d_head",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "qk_norm": "qk_norm",
    "tie_word_embeddings": "tie_embeddings",
}


def check_program(c: dict, cfg) -> None:
    """Any size of the program's ArchConfig ``cfg`` that differs from the
    file is an error, not an override; so is a program this module's
    reference does not cover."""
    for key, field in PROGRAM_KEYS.items():
        want, got = c[key], getattr(cfg, field)
        if (float(want) if isinstance(want, float) else want) != got:
            raise ValueError(f"{c['arch']}: {key}={want} in the file, {field}={got} in the program")
    if cfg.activation != "swiglu" or cfg.norm != "rmsnorm" or cfg.layer_pattern != ("attn",):
        raise ValueError(f"{cfg.name}: the reference covers rmsnorm/swiglu/full-attention models only")
    # the program's RMSNorm epsilon and RoPE are fixed in its layers: the
    # file may state only what they do, since the reference follows the file
    from repro.models import layers
    eps = inspect.signature(layers.rmsnorm).parameters["eps"].default
    if float(c["rms_norm_eps"]) != eps:
        raise ValueError(f"{c['arch']}: rms_norm_eps={c['rms_norm_eps']} in the file, {eps} in the program")
    if float(c.get("partial_rotary_factor", 1.0)) != 1.0 or c.get("rope_scaling") is not None:
        raise ValueError(f"{c['arch']}: the program rotates the whole head with plain RoPE")


def to_program_tree(w: dict, cfg, plan) -> dict:
    """Named weights -> the program's parameter tree (one scanned group of
    one attention layer per model layer; norm scales are offsets from 1)."""
    attn = {"ln": {"scale": w["attn_norm"]}, "wo": w["wo"]}
    if plan.fuse_qkv:
        attn["wqkv"] = jax.numpy.concatenate([w["wq"], w["wk"], w["wv"]], axis=-1)
    else:
        attn.update(wq=w["wq"], wk=w["wk"], wv=w["wv"])
    if cfg.qk_norm:
        attn.update(q_norm=w["q_norm"], k_norm=w["k_norm"])
    ffn = {"ln": {"scale": w["ffn_norm"]}, "w1": w["w_gate"], "w3": w["w_up"], "w2": w["w_down"]}
    return {
        "blocks": {"stack": ({"attn": attn, "ffn": ffn},), "tail": ()},
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
    }


# -------------------------------------------------------------- work counts


def dense_flops_per_row(c: dict) -> float:
    """Matmul operations of one token through every layer (no attention
    scores, no output head)."""
    m = dims(c)
    d, H, KV, Dh, F = m["d"], m["H"], m["KV"], m["Dh"], m["F"]
    per_layer = d * (H + 2 * KV) * Dh + H * Dh * d + 3 * d * F
    return 2.0 * m["L"] * per_layer


def attn_flops(c: dict, ctx: float) -> float:
    """Score and value operations of one query row over ``ctx`` keys, all
    layers (QK^T and PV, 2 operations per multiply-add)."""
    m = dims(c)
    return 4.0 * m["L"] * m["H"] * m["Dh"] * ctx


def head_flops(c: dict) -> float:
    m = dims(c)
    return 2.0 * m["d"] * m["V"]


def kv_bytes(c: dict, ctx: float, kv_dtype: str) -> float:
    """Bytes of ``ctx`` cached tokens' keys and values, all layers."""
    m = dims(c)
    per = 2 * m["KV"] * m["Dh"] * KV_ITEMSIZE[kv_dtype]
    if kv_dtype == "int8":
        per += 2 * m["KV"] * SCALE_BYTES
    return float(m["L"] * per * ctx)


def qo_bytes(c: dict, rows: float) -> float:
    """Bytes of ``rows`` query rows read and output rows written, all layers."""
    m = dims(c)
    return float(m["L"] * 2 * rows * m["H"] * m["Dh"] * QO_ITEMSIZE)

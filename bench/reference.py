"""Plain float32 forward pass of the configurations the benchmark serves,
written from the published description (RMSNorm, RoPE, grouped-query
attention, SwiGLU, tied output head) and from nothing of the program.

Every matmul runs at ``HIGHEST`` precision, so on a TPU it is float32 and
not the chip's default of bfloat16 operands.  ``quantized=True`` is the
control: the same pass with every matmul operand and the cached keys and
values rounded to int8 (weights per output channel, activations per row,
keys and values per token and head), the precision below the stated
bfloat16.  Departures of the served model from the published one are
stated in each configuration file; this pass follows the file."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Round to int8 with one scale per slice along ``axis`` (symmetric,
    max-abs), back to float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quantized):
    if quantized:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


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
    m = W.dims(c)
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


@functools.partial(jax.jit, static_argnames=("c_key", "quantized", "block"))
def _readings(w, tokens, served, c_key, quantized=False, block=256):
    """Per position: the reference's best logit, its logit of the served
    token, and (control) its logit of the token the quantized pass puts
    first.  Logits are formed ``block`` positions at a time."""
    c = dict(c_key)
    x = hidden(c, w, tokens)
    xq = hidden(c, w, tokens, quantized=True) if quantized else x
    head = w["embed"].T
    S = tokens.shape[0]

    def one(i):
        xs = jax.lax.dynamic_slice_in_dim(x, i * block, block)
        ss = jax.lax.dynamic_slice_in_dim(served, i * block, block)
        logits = jnp.matmul(xs, head, precision=HI)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, ss[:, None], -1)[:, 0]
        if quantized:
            xqs = jax.lax.dynamic_slice_in_dim(xq, i * block, block)
            pick = jnp.argmax(_mm(xqs, head, True), -1)
            ctl = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
        else:
            ctl = got
        return best, got, ctl

    best, got, ctl = jax.lax.map(one, jnp.arange(S // block))
    return best.reshape(S), got.reshape(S), ctl.reshape(S)


def _hashable(c: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "qk_norm", "rms_norm_eps",
            "rope_theta", "partial_rotary_factor")
    return tuple((k, c[k]) for k in keys if k in c)


BUCKET = 512


def gaps(c: dict, w: dict, sample: list[tuple[list[int], list[int]]], length: int,
         control: bool = False) -> dict:
    """The gap by which a served token's reference logit lies below the
    reference's best, over ``sample`` = [(prompt, served tokens)], each
    sequence padded to the next multiple of 512 (at most ``length``): the
    widest (``max_logit_gap``) and the mean over every compared token
    (``mean_logit_gap``).  With ``control`` the same two of the token the
    int8 pass puts first at the same positions (``control_...``)."""
    worst, worst_ctl, total, total_ctl, n = 0.0, 0.0, 0.0, 0.0, 0
    for prompt, served in sample:
        seq = (list(prompt) + list(served))[:-1]
        if len(seq) > length:
            raise ValueError(f"sequence of {len(seq)} tokens exceeds the reference length {length}")
        padded = min(-(-len(seq) // BUCKET) * BUCKET, length)
        block = 256 if padded % 256 == 0 else padded
        tokens = np.zeros((padded,), np.int32)
        tokens[: len(seq)] = seq
        target = np.zeros((padded,), np.int32)
        first = len(prompt) - 1
        target[first : first + len(served)] = served
        best, got, ctl = (
            np.asarray(a) for a in _readings(w, jnp.asarray(tokens), jnp.asarray(target),
                                             c_key=_hashable(c), quantized=control, block=block)
        )
        sl = slice(first, first + len(served))
        gap, gap_ctl = best[sl] - got[sl], best[sl] - ctl[sl]
        worst, worst_ctl = max(worst, float(gap.max())), max(worst_ctl, float(gap_ctl.max()))
        total, total_ctl = total + float(gap.sum()), total_ctl + float(gap_ctl.sum())
        n += len(served)
    out = {"max_logit_gap": worst, "mean_logit_gap": total / max(n, 1), "tokens": n}
    if control:
        out["control_max_logit_gap"] = worst_ctl
        out["control_mean_logit_gap"] = total_ctl / max(n, 1)
    return out

"""Plain float32 reference of the configurations the benchmark serves: the
final logits, formed from the hidden states that the configuration's
architecture module computes (``hidden`` in ``bench/archs/<name>.py``,
written from the published description and from nothing of the program).

Every matmul runs at ``HIGHEST`` precision, so on a TPU it is float32 and
not the chip's default of bfloat16 operands.  ``quantized=True`` is the
control: the same pass with every matmul operand and the cached keys and
values rounded to int8 (weights per output channel, activations per row,
keys and values per token and head), the precision below the stated
bfloat16.  Departures of the served model from the published one are
stated in each configuration file; this pass follows the file."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

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


@functools.partial(jax.jit, static_argnames=("arch", "c_key", "quantized", "block"))
def _readings(w, tokens, served, arch, c_key, quantized=False, block=256):
    """Per position: the reference's best logit, its logit of the served
    token, and (control) its logit of the token the quantized pass puts
    first.  Logits are formed ``block`` positions at a time from the hidden
    states of architecture module ``arch``; ``c_key`` is the whole
    configuration file, as ``_hashable`` gives it."""
    c = json.loads(c_key)
    x = arch.hidden(c, w, tokens)
    xq = arch.hidden(c, w, tokens, quantized=True) if quantized else x
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


def _hashable(c: dict) -> str:
    """The whole configuration file, every key and nested value of it, as
    a static argument of ``_readings``."""
    return json.dumps(c, sort_keys=True)


BUCKET = 512


def gaps(arch, c: dict, w: dict, sample: list[tuple[list[int], list[int]]], length: int,
         control: bool = False) -> dict:
    """The gap by which a served token's reference logit lies below the
    reference's best (configuration ``c``, architecture module ``arch``),
    over ``sample`` = [(prompt, served tokens)], each sequence padded to
    the next multiple of 512 (at most ``length``): the widest (``max_logit_gap``) and the mean over every compared token
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
                                             arch=arch, c_key=_hashable(c), quantized=control,
                                             block=block)
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

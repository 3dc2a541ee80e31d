"""Operations and bytes the served model needs, counted from its shapes.

Only useful work counts: rows that carry a prompt or generated token,
attention over each row's live context, and the output head only where a
token is sampled.  Dead slab rows and padded pages are the implementation's
waste and are never counted."""
from __future__ import annotations

import json
import pathlib

from bench import weights as W

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
KV_ITEMSIZE = {"int8": 1, "bf16": 2, "fp32": 4}
SCALE_BYTES = 4  # one float32 scale per (token, kv head) of an int8 page
QO_ITEMSIZE = 2  # query rows and outputs at the stated precision, bfloat16


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def dense_flops_per_row(c: dict) -> float:
    """Matmul operations of one token through every layer (no attention
    scores, no output head)."""
    m = W.dims(c)
    d, H, KV, Dh, F = m["d"], m["H"], m["KV"], m["Dh"], m["F"]
    per_layer = d * (H + 2 * KV) * Dh + H * Dh * d + 3 * d * F
    return 2.0 * m["L"] * per_layer


def attn_flops(c: dict, ctx: float) -> float:
    """Score and value operations of one query row over ``ctx`` keys, all
    layers (QK^T and PV, 2 operations per multiply-add)."""
    m = W.dims(c)
    return 4.0 * m["L"] * m["H"] * m["Dh"] * ctx


def head_flops(c: dict) -> float:
    m = W.dims(c)
    return 2.0 * m["d"] * m["V"]


def kv_bytes(c: dict, ctx: float, kv_dtype: str) -> float:
    """Bytes of ``ctx`` cached tokens' keys and values, all layers."""
    m = W.dims(c)
    per = 2 * m["KV"] * m["Dh"] * KV_ITEMSIZE[kv_dtype]
    if kv_dtype == "int8":
        per += 2 * m["KV"] * SCALE_BYTES
    return float(m["L"] * per * ctx)


def qo_bytes(c: dict, rows: float) -> float:
    """Bytes of ``rows`` query rows read and output rows written, all layers."""
    m = W.dims(c)
    return float(m["L"] * 2 * rows * m["H"] * m["Dh"] * QO_ITEMSIZE)


def step_work(c: dict, slots: list[tuple[int, int, int]], kv_dtype: str) -> dict:
    """Work of one engine step from its live slots, each ``(start, rows,
    sampled)``: the slot's first live position, its live query rows and
    whether a token was sampled from it.  Row ``i`` of a slot sits at
    position ``start + i`` and attends to ``start + i + 1`` keys; the
    attention kernel reads the slot's ``start + rows`` cached tokens once."""
    model = attn = kbytes = 0.0
    for start, rows, sampled in slots:
        keys = rows * start + rows * (rows + 1) / 2  # sum over rows of (start + i + 1)
        model += rows * dense_flops_per_row(c) + attn_flops(c, keys) + sampled * head_flops(c)
        attn += attn_flops(c, keys)
        kbytes += kv_bytes(c, start + rows, kv_dtype) + qo_bytes(c, rows)
    return {"model_flops": model, "attn_flops": attn, "attn_bytes": kbytes}

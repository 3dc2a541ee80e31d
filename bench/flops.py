"""Operations and bytes the served model needs, counted from its shapes by
the configuration's architecture module (``bench/archs/``).

Only useful work counts: rows that carry a prompt or generated token,
attention over each row's live context, and the output head only where a
token is sampled.  Dead slab rows and padded pages are the implementation's
waste and are never counted."""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
KV_ITEMSIZE = {"int8": 1, "bf16": 2, "fp32": 4}
SCALE_BYTES = 4  # one float32 scale per (token, kv head) of an int8 page
QO_ITEMSIZE = 2  # query rows and outputs at the stated precision, bfloat16


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return table[device_kind]


def step_work(arch, c: dict, slots: list[tuple[int, int, int]], kv_dtype: str) -> dict:
    """Work of one engine step of configuration ``c`` (architecture module
    ``arch``) from its live slots, each ``(start, rows, sampled)``: the
    slot's first live position, its live query rows and whether a token was
    sampled from it.  Row ``i`` of a slot sits at
    position ``start + i`` and attends to ``start + i + 1`` keys; the
    attention kernel reads the slot's ``start + rows`` cached tokens once."""
    model = attn = kbytes = 0.0
    for start, rows, sampled in slots:
        keys = rows * start + rows * (rows + 1) / 2  # sum over rows of (start + i + 1)
        model += (rows * arch.dense_flops_per_row(c) + arch.attn_flops(c, keys)
                  + sampled * arch.head_flops(c))
        attn += arch.attn_flops(c, keys)
        kbytes += arch.kv_bytes(c, start + rows, kv_dtype) + arch.qo_bytes(c, rows)
    return {"model_flops": model, "attn_flops": attn, "attn_bytes": kbytes}

"""Operation and byte counts against hand counts at a tiny configuration."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import pytest

from bench import flops, registry

dense = registry.Registry().arch({})

C = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
     "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
     "vocab_size": 10}


def test_dense_flops_per_row():
    # per layer: qkv 8*(4+2*2)*2 = 128, out 4*2*8 = 64, ffn 3*8*16 = 384
    assert dense.dense_flops_per_row(C) == 2 * 2 * (128 + 64 + 384)


def test_attention_and_head():
    assert dense.attn_flops(C, 5) == 4 * 2 * 4 * 2 * 5
    assert dense.head_flops(C) == 2 * 8 * 10


def test_kv_and_qo_bytes():
    # int8: 2 layers * (2 (k,v) * 2 heads * 2 dims * 1 B + 2 * 2 heads * 4 B scales)
    assert dense.kv_bytes(C, 3, "int8") == 2 * (8 + 16) * 3
    assert dense.kv_bytes(C, 3, "bf16") == 2 * (2 * 2 * 2 * 2) * 3
    assert dense.qo_bytes(C, 5) == 2 * 2 * 5 * 4 * 2 * 2


def test_step_work_counts_live_rows_only():
    # a 3-row prompt chunk from position 0 that samples, and a decode row at 7
    w = flops.step_work(dense, C, [(0, 3, 1), (7, 1, 1)], "int8")
    keys = (1 + 2 + 3) + 8
    assert w["attn_flops"] == dense.attn_flops(C, keys)
    assert w["model_flops"] == pytest.approx(
        4 * dense.dense_flops_per_row(C) + dense.attn_flops(C, keys) + 2 * dense.head_flops(C))
    assert w["attn_bytes"] == dense.kv_bytes(C, 3 + 8, "int8") + dense.qo_bytes(C, 4)
    assert flops.step_work(dense, C, [], "int8") == {
        "model_flops": 0, "attn_flops": 0, "attn_bytes": 0}


def test_peaks_table():
    p = flops.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")

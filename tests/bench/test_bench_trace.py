"""The reduction from a profiler trace to device busy time, kernel time,
top operations and idle gaps: on a hand-made record with known answers, and
on a record kept from a chip run of ``qwen3-1.7b.batch`` (three steps)."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_reduce_by_hand():
    ms = 1_000_000
    rec = {
        "devices": {"/device:TPU:0": [
            ["while.1 while", 0, 10 * ms, False],          # a container: not a top op
            ["fusion.1 fusion", 0, 4 * ms, False],
            ["paged_attention.1 custom-call", 3 * ms, 3 * ms, True],  # overlaps the fusion
            ["fusion.2 fusion", 8 * ms, 2 * ms, False],
            ["fusion.3 fusion", 14 * ms, 2 * ms, False],
        ]},
        "host": [["engine.step", 0, 11 * ms], ["bench.record", 11 * ms, 2 * ms],
                 ["engine.step", 13 * ms, 4 * ms]],
    }
    r = trace.reduce(rec)
    assert r["window_s"] == pytest.approx(0.017)
    # the while spans 0-10 ms; then 14-16 ms
    assert r["busy_s"] == pytest.approx(0.012)
    assert r["kernel_s"] == pytest.approx(0.003)
    assert [n for n, _ in r["device_ops"]] == [
        "fusion.1 fusion", "paged_attention.1 custom-call", "fusion.2 fusion", "fusion.3 fusion"]
    assert r["idle_gaps"][0] == ["bench.record", pytest.approx(0.004)]
    assert r["idle_gaps"][1] == ["engine.step", pytest.approx(0.001)]


def test_reduce_with_nothing_to_read():
    assert trace.reduce({"devices": {}, "host": []}) == {}


def test_short_name():
    name = ('%paged_attention.12 = f32[32,8,512,128]{3,2,1,0:T(8,128)} custom-call('
            's32[32,128]{1,0:T(8,128)S(1)} %copy-done.5), custom_call_target="tpu_custom_call"')
    assert trace.short_name(name) == "paged_attention.12 custom-call"


def test_recorded_chip_trace():
    rec = trace.load(DATA / "qwen3-1.7b.batch.events.json.gz")
    r = trace.reduce(rec)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["kernel_s"] < r["busy_s"]
    assert any(n.startswith("paged_attention") for n, _ in r["device_ops"])
    assert all(not n.endswith(" while") for n, _ in r["device_ops"])
    assert 1 <= len(r["idle_gaps"]) <= 10
    assert all(g[0].startswith(("engine.", "bench.", "host-")) and g[1] >= 1e-6
               for g in r["idle_gaps"])

"""The end-to-end readers: percentiles over every request, tokens per
second over the window, and the counting of undrained or failed requests."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import numpy as np
import pytest

from bench import harness, readers
from bench.window import Rec, Step


class FakeGen:
    def __init__(self, closed):
        self.closed = closed


class FakeWindow:
    def __init__(self, recs, closed=False, t_open=100.0, seconds=10.0, t_end=110.5, t_stop=130.0):
        self.recs, self.gen = recs, FakeGen(closed)
        self.t_open, self.t_close, self.t_end, self.t_stop = t_open, t_open + seconds, t_end, t_stop
        self.steps, self.window_steps = [], 0
        self._stats = {"steps": 0, "rolled_steps": 0, "prefill_tokens": 0, "generated_tokens": 0}

    def window_s(self):
        return self.t_end - self.t_open

    def stats(self):
        return self._stats


def rec(rid, due, stamps, counted=True, status="ok", t_admit=None):
    r = Rec(rid, due, [1, 2, 3], 4, counted)
    r.stamps, r.tokens, r.status, r.t_admit = list(stamps), [0] * len(stamps), status, t_admit
    return r


def test_ttft_counts_every_request_due_and_charges_the_unserved():
    w = FakeWindow([rec("a", 101.0, [101.5, 102.0]), rec("b", 102.0, [103.0]),
                    rec("c", 105.0, [], status="pending"),  # never served: waits till the stop
                    rec("d", 111.0, [111.2], counted=False)])
    got = readers.ttft_ms(w)
    assert got == pytest.approx([500.0, 1000.0, 25000.0])
    assert readers.percentile(got, 90) == pytest.approx(np.percentile([500, 1000, 25000], 90))
    assert harness.counts(w) == (3, 1)


def test_refused_requests_fail_in_both_loops():
    recs = [rec("a", 101.0, [101.5], t_admit=101.1), rec("b", 101.0, [], status="shed"),
            rec("c", 101.0, [], status="pending")]  # never admitted: not attempted when closed
    assert harness.counts(FakeWindow(recs, closed=True)) == (2, 1)
    assert harness.counts(FakeWindow(recs, closed=False)) == (3, 2)


def test_itl_takes_gaps_whose_later_token_is_inside_the_window():
    w = FakeWindow([rec("a", 100.0, [101.0, 101.3, 101.3, 109.9, 110.4]), rec("b", 100.0, [102.0])])
    assert readers.itl_ms(w) == pytest.approx([300.0, 0.0, 8600.0])


def test_output_tok_s_is_tokens_in_window_over_window_time():
    w = FakeWindow([rec("a", 99.0, [100.5, 101.0, 110.5, 111.0]), rec("b", 99.0, [99.9, 105.0])])
    assert readers.output_tok_s({"window": w}) == pytest.approx(4 / 10.5)


def test_slab_live_share_and_iter_ms():
    w = FakeWindow([rec("a", 99.0, [100.5, 101.0])])
    w._stats = {"steps": 4, "rolled_steps": 0, "prefill_tokens": 10, "generated_tokens": 5}
    w.steps = [Step(100.0 + i, 100.25 + i, {}) for i in range(4)]
    w.window_steps = 4
    rec_ = {"window": w, "serve": {"decode_batch": 2, "mixed_slab_width": 8}}
    # live rows: 10 prompt rows + 5 tokens, less the one first token that a prompt row sampled
    assert readers.slab_live_share(rec_) == pytest.approx(100.0 * 14 / (4 * 2 * 8))
    assert readers.iter_ms(rec_) == pytest.approx(250.0)


def test_roofline_takes_the_larger_bound_over_kernel_time():
    steps = [Step(0.0, 1.0, {"attn_flops": 197e9, "attn_bytes": 819e6 * 3}),
             Step(1.0, 2.0, {"attn_flops": 197e9, "attn_bytes": 819e6})]
    rec_ = {"trace": {"kernel_s": 0.008, "steps": steps},
            "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    assert readers.attn_least_s(rec_) == pytest.approx((0.002, 0.004))
    assert readers.roofline(rec_) == pytest.approx(50.0)


def test_readers_with_nothing_to_read_return_none():
    w = FakeWindow([])
    assert readers.percentile([], 90) is None
    assert readers.device_idle({"trace": None}) is None
    assert readers.roofline({"trace": {}, "peaks": {"flops_bf16": 1}}) is None
    assert readers.mfu({"window": w, "peaks": None, "cell": {"chips": 1}}) is None

"""Arithmetic shared by the metric readers in ``bench/metrics/``.  Each
reader takes the run's record and returns a number, or None where the run
has nothing to read."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """Linear-interpolated percentile over every value (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


def win(record):
    return record["window"]


def output_tokens_in_window(w) -> int:
    return sum(1 for r in w.recs for t in r.stamps if w.t_open < t <= w.t_end)


def output_tok_s(record):
    w = win(record)
    return output_tokens_in_window(w) / w.window_s() if w.window_s() > 0 else None


def ttft_ms(w) -> list:
    """First token minus due time for every request due in the window; one
    that never got a first token counts as waiting until the run stopped."""
    return [((r.stamps[0] if r.stamps else w.t_stop) - r.due) * 1e3 for r in w.recs if r.counted]


def itl_ms(w) -> list:
    """Every gap between consecutive tokens of a request, as its client saw
    them, where the later token came inside the window."""
    out = []
    for r in w.recs:
        for a, b in zip(r.stamps, r.stamps[1:]):
            if w.t_open < b <= w.t_close:
                out.append((b - a) * 1e3)
    return out


def queue_wait_ms(w) -> list:
    return [(r.t_admit - r.due) * 1e3 for r in w.recs if r.counted and r.t_admit is not None]


def window_steps(w):
    return w.steps[: w.window_steps]


def iter_ms(record):
    """Mean time per device iteration of the window: the time spent in
    ``step()`` calls that did work over the iterations they ran (a rolled
    dispatch counts each of its iterations)."""
    w = win(record)
    n = w.stats()["steps"]
    busy = sum(s.t1 - s.t0 for s in window_steps(w))
    return busy / n * 1e3 if n else None


def slab_live_share(record):
    """Query rows that carried a token over the rows the steps computed:
    B*W for each mixed step, B for each rolled iteration."""
    w, serve = win(record), record["serve"]
    st = w.stats()
    first = sum(1 for r in w.recs for t in r.stamps[:1] if w.t_open < t <= w.t_end)
    live = st["prefill_tokens"] + st["generated_tokens"] - first
    mixed = st["steps"] - st["rolled_steps"]
    rows = mixed * serve["decode_batch"] * serve["mixed_slab_width"] + st["rolled_steps"] * serve["decode_batch"]
    return 100.0 * live / rows if rows else None


def mfu(record):
    """Useful model operations in the window over the window times the
    chip's peak (bfloat16).  A traced run's window leaves out the time the
    profiler took to start and stop."""
    w, peaks = win(record), record["peaks"]
    span = w.window_s() - record.get("profiler_s", 0.0)
    if not peaks or span <= 0:
        return None
    flops = sum(s.work["model_flops"] for s in window_steps(w))
    n = record["cell"]["chips"]
    return 100.0 * flops / (span * n * peaks["flops_bf16"])


def attn_least_s(record):
    """The paged-attention work of the steps the trace holds, as seconds at
    the chip's peaks: ``(by operations, by bytes)``, or None."""
    t, peaks = record["trace"], record["peaks"]
    if not t or not peaks or not t.get("kernel_s") or not t.get("steps"):
        return None
    f = sum(s.work["attn_flops"] for s in t["steps"])
    b = sum(s.work["attn_bytes"] for s in t["steps"])
    return f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"]


def roofline(record):
    """The paged-attention kernel's least time on this chip (the larger of
    its operations over peak FLOP/s and its bytes over peak bandwidth) over
    its measured time, for the steps the trace holds."""
    least = attn_least_s(record)
    return None if least is None else 100.0 * max(least) / record["trace"]["kernel_s"]


def device_idle(record):
    t = record["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

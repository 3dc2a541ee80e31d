"""From a profiler trace to the numbers the benchmark reads.

``extract`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small record: the operations of each device with their start and duration,
whether each is a Pallas kernel (a ``tpu_custom_call``), and the harness's
own host spans.  ``reduce`` computes device busy time, kernel time, the
operations that took most time and the longest idle gaps, each named by the
host span it fell in.  Tests check ``reduce`` on a record kept from a chip
run."""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

HOST_SPANS = ("bench.", "engine.")
# control flow around other operations: their time is their children's
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
OPS_LINE = "XLA Ops"
KERNEL_MARK = "tpu_custom_call"
MIN_GAP_NS = 1000  # shorter gaps between two operations are the clock's rounding


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``fusion.12 fusion``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _stats(ev) -> dict:
    try:
        return {str(k): v for k, v in ev.stats}
    except Exception:
        return {}


def _is_kernel(name: str, stats: dict) -> bool:
    if KERNEL_MARK in name:
        return True
    return any(isinstance(v, str) and KERNEL_MARK in v for v in stats.values())


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def extract(path: str) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns, is_kernel], ...]},
    "host": [[name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = devices.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append([short_name(ev.name), int(ev.start_ns), int(ev.duration_ns),
                                _is_kernel(ev.name, _stats(ev))])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPANS):
                        host.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def describe(path: str, per_line: int = 6) -> dict:
    """Planes, lines, event counts and a few events with their stats: what
    a reader looks at before trusting ``extract`` on a new chip."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "n": len(evs),
                "sample": [[e.name, int(e.start_ns), int(e.duration_ns),
                            {k: str(v)[:300] for k, v in _stats(e).items()}]
                           for e in evs[:per_line]],
            }
        out[plane.name] = lines
    return out


def save(record: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(record, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_label(spans, t: float) -> str:
    """The innermost harness span covering time ``t``, or ``host-other``."""
    best = None
    for name, s, d in spans:
        if s > t:
            break
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "host-other"


def reduce(record: dict, top: int = 10) -> dict:
    """Device time within the traced window: from the start of the first
    ``engine.step`` span to the end of the last one.

    Returns ``busy_s`` and ``kernel_s`` (averaged over devices),
    ``window_s``, ``device_ops`` (the ``top`` operation names by summed
    time) and ``idle_gaps`` (the ``top`` longest gaps between device
    operations, each named by the host span it fell in)."""
    steps = [e for e in record["host"] if e[0] == "engine.step"]
    if not steps or not record["devices"]:
        return {}
    lo = steps[0][1]
    hi = max(s + d for _, s, d in steps)
    busy = kernel = 0.0
    by_name: dict = {}
    gaps = []
    for evs in record["devices"].values():
        inside = []
        for name, s, d, is_kernel in evs:
            s2, e2 = max(s, lo), min(s + d, hi)
            if e2 <= s2:
                continue
            inside.append((s2, e2))
            if name.split(" ")[-1] not in CONTAINERS:
                by_name[name] = by_name.get(name, 0.0) + (e2 - s2)
            if is_kernel:
                kernel += e2 - s2
        merged = _union(inside)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= MIN_GAP_NS:
                gaps.append((b - a, (a + b) / 2))
    n = len(record["devices"])
    gaps.sort(reverse=True)
    spans = record["host"]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "kernel_s": kernel / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_label(spans, mid), g / 1e9] for g, mid in gaps[:top]],
    }

"""One run of a cell with extras the benchmark's own runs leave out:
``--control`` also reads the int8 control on the same sample, and
``--describe`` keeps the raw trace and writes what its planes, lines and
events look like.  Output goes to ``--out`` (a JSON file).

    python3 bench/tools/probe.py --workload qwen3-1.7b.code --seed 5 --seconds 20 \
        --trace 1 --control --describe --out bench/out/probe.json"""
import argparse
import json
import pathlib
import sys
import time

T_START = time.time()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    out = harness.run_cell(a.workload, a.seed, a.seconds, bool(a.trace), t_start=T_START,
                           control=a.control, keep_trace=a.describe)
    rec = {"args": vars(a), "result": out}
    if a.describe and a.trace:
        d = harness.OUT / f"trace.{a.workload}.{a.seed}"
        rec["describe"] = trace.describe(trace.latest_xplane(str(d)))
    harness.write_json(rec, a.out)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

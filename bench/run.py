"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload qwen3-1.7b.code --seed 7 --seconds 45 --trace 0

Prints one JSON object as the last line of standard output (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``check``: each number compared with its limit), and
the same comparison as the last lines of standard error.  Exits non-zero
with no result line when JAX finds no TPU or fewer chips than the cell
needs."""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# libtpu would otherwise write its logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args()
    try:
        out = harness.run_cell(a.workload, a.seed, a.seconds, bool(a.trace), t_start=T_START)
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 3
    for name, v in out["check"].items():
        harness.log(f"check {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of a cell, with where the engine's step time went: host
milliseconds per dispatch in each phase of ``ServingEngine.step()``
(``engine.stats``' ``admit_s`` ... ``harvest_s`` over the window), the share
of the time spent in ``step()`` calls that the phases cover, and, traced,
the idle gaps as the breakdown names them.  A program without phase
counters reports no phases.

    python3 bench/tools/phases.py --workload qwen3-1.7b.batch --seed 5 --seconds 51 \
        --trace 1 --out bench/out/phases.batch.5.json

Prints the result line, then this summary, as JSON; writes both to
``--out``."""
import argparse
import json
import pathlib
import sys
import time

T_START = time.time()
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, window  # noqa: E402

PHASES = ("admit_s", "pack_s", "launch_s", "sync_s", "harvest_s")


def summary(win) -> dict:
    st = win.stats()
    steps = win.steps[: win.window_steps]
    in_step = sum(s.t1 - s.t0 for s in steps)
    dispatches = st["steps"] - st["rolled_steps"] + st["rolled_dispatches"]
    out = {"dispatches": dispatches, "step_calls": len(steps), "in_step_s": in_step,
           "window_s": win.window_s()}
    if all(k in st for k in PHASES) and dispatches:
        out["phase_ms_per_dispatch"] = {k: 1e3 * st[k] / dispatches for k in PHASES}
        out["phase_share_of_step"] = sum(st[k] for k in PHASES) / in_step
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    wins = []
    run = window.Window.run

    def keep(self):
        wins.append(self)
        return run(self)

    window.Window.run = keep
    out = harness.run_cell(a.workload, a.seed, a.seconds, bool(a.trace), t_start=T_START)
    rec = {"args": vars(a), "result": out, "phases": summary(wins[-1])}
    harness.write_json(rec, a.out)
    print(json.dumps(out), flush=True)
    print(json.dumps(rec["phases"]), flush=True)


if __name__ == "__main__":
    main()

"""Sweep offered rates of an open-loop cell in one process, to find the
knee: the highest rate the server sustains without a growing queue.

    python3 bench/tools/knee.py --workload qwen3-1.7b.code --rates 3,4,5,6,7 \
        --seconds 30 --seed 11 --out bench/out/knee.json

For each rate: requests due, first tokens within the drain, TTFT median and
90th percentile, the median TTFT of the first and the last third of the
window (a growing queue shows as the last third waiting longer), and the
waiting queue at the close."""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


from bench import harness, model, readers, registry, window  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    reg = registry.Registry()
    cell = reg.cell(a.workload)
    c = reg.config(cell["config"])
    arch = reg.arch(c)
    traffic = reg.traffic(cell["traffic"])
    harness.require_chips(int(cell["chips"]))
    harness.enable_cache()
    engine, cfg, plan, serve = model.build(arch, c, a.seed)
    harness.warm_up(engine)
    rows = []
    for rate in [float(r) for r in a.rates.split(",")]:
        t = dict(traffic, rate_per_s=rate)
        gen = reg.generator(t["generator"]).Generator(t, a.seed, c["vocab_size"], a.seconds)
        w = window.Window(engine, gen, arch, c, a.seconds, drain_s=t.get("drain_s", 60.0)).run()
        queued = sum(1 for r in w.recs if r.counted and r.t_admit is None)
        ttft = readers.ttft_ms(w)
        counted = [r for r in w.recs if r.counted]
        third = a.seconds / 3
        early = [(r.stamps[0] - r.due) * 1e3 for r in counted if r.stamps and r.due - w.t_open < third]
        late = [(r.stamps[0] - r.due) * 1e3 for r in counted if r.stamps and r.due - w.t_open >= 2 * third]
        row = {"rate": rate, "due": len(counted), "first_tokens": sum(1 for r in counted if r.stamps),
               "ttft_p50_ms": readers.percentile(ttft, 50), "ttft_p90_ms": readers.percentile(ttft, 90),
               "ttft_p50_first_third_ms": readers.percentile(early, 50),
               "ttft_p50_last_third_ms": readers.percentile(late, 50),
               "itl_p95_ms": readers.percentile(readers.itl_ms(w), 95),
               "iter_ms": readers.iter_ms({"window": w}),
               "not_admitted_at_drain_end": queued, "drain_s": w.t_stop - w.t_close}
        rows.append(row)
        harness.log(json.dumps(row))
        t0 = time.time()
        while not engine.sched.idle:  # finish what is in flight before the next rate
            engine.step()
        engine.reset_stats()
        harness.log(f"emptied in {time.time() - t0:.1f} s")
    harness.write_json({"workload": a.workload, "seconds": a.seconds, "rows": rows}, a.out)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()

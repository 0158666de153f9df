"""Readings that the cell's correctness limits are set from, in one
process: the program's served-token logit gaps (widest and mean) on each
seed, and the control's (the reference with its weights one precision
step lower, its first choices put in the served tokens' place) on the
first ``--control`` seeds, each at the cell's own sizes and load; and
on every seed the widest gap had one request's last token been moved to
its neighbour where it was produced.  The control's and the altered
token's readings go through the run's own ``verdict`` with the cell's
limits, which has to find them not correct.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \\
        --seconds 15 --control 3 [--out chiprun_out/calib.json]

Each seed gets its own weights; the engine, its pool and its compiled
programs are built once and reused.  Not run by the benchmark itself.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time

import jax
import numpy as np

from run import find_devices, log, served_ok, use_cache, verdict

from bench import cells, serving
from bench.arrivals import Traffic


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = cells.load_cell(args.workload)
    use_cache()
    config, mix = cell.config, cell.traffic
    arch = config["arch"]
    where = serving.placement(config, find_devices(cell.chips))
    weights = cells.bench_module(config, "weights")
    reference = cells.bench_module(config, "reference")
    seeds = [int(s) for s in args.seeds.split(",")]
    with where.scope():
        model, params = serving.build(config, seeds[0], where)
        engine = serving.engine_for(model, params, config, where)
        serving.warm(engine, config, mix, arch["vocab"])
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, params) \
        if where.mesh is not None else None
    rec = serving.Recorder(engine, annotate=False)
    sess = serving.Session(engine, rec, config["engine"]["max_queued"],
                           where)
    drive = serving.open_loop if mix["kind"] == "open_loop" \
        else serving.backlog
    rows = []
    for j, seed in enumerate(seeds):
        t0 = time.perf_counter()
        with where.scope():
            if j:
                engine.params = weights.make_params(seed, arch, shardings)
            rec.steps.clear()       # a backlog window waits for new steps
            win = asyncio.run(drive(sess, mix,
                                    Traffic(mix, seed, arch["vocab"]),
                                    args.seconds))
            sched = engine.scheduler
            for r in list(sched.running.values()):
                engine._release(r)  # drop what the halted window left
            sched.waiting.clear()
        _, failed, served = served_ok(win, mix["kind"], arch["vocab"])
        engine.params = None
        del params
        params = None
        gc.collect()
        rng = np.random.default_rng([seed, 7])
        sample = reference.sample(served, int(config["check"]["sample"]),
                                  rng)
        ctrl = config["control"] if j < args.control else None
        g = reference.served_gaps(seed, arch, sample, control=ctrl)
        _, ok = verdict(g, failed, len(sample), config["check"])
        row = {"seed": seed, "judged": len(win.judged), "failed": failed,
               "served": len(served), "compared": len(sample), **g,
               "correct": ok, "wall_s": time.perf_counter() - t0}
        _, row["altered_correct"] = verdict(
            dict(g, max_logit_gap=g["altered_max_logit_gap"]), failed,
            len(sample), config["check"])
        if ctrl is not None:
            _, row["control_correct"] = verdict(
                {k: g[f"control_{k}"] for k in ("max_logit_gap",
                                                "mean_logit_gap")},
                0, len(sample), config["check"])
        log(json.dumps(row))
        rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())

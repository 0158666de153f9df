"""Find the highest arrival rate an open-loop cell sustains: one process
builds the cell once and offers its mix at each rate in turn.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
        --rates 1.0,1.5,2.0 [--out chiprun_out/sweep.json]

Per rate it reports the requests due in the window, how many finished,
the p90 time to first token and per output token, the p90 queue wait,
and the requests still waiting for a slot when the window closed (a
backlog that grows through the window means the rate is past the knee).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

from run import find_devices, log, use_cache

from bench import cells, serving, stats
from bench.arrivals import Traffic


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    cell = cells.load_cell(args.workload)
    use_cache()
    config = cell.config
    where = serving.placement(config, find_devices(cell.chips))
    with where.scope():
        rows = _sweep(cell, where, args)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    print(json.dumps(rows))
    return 0


def _sweep(cell, where, args):
    config = cell.config
    model, params = serving.build(config, args.seed, where)
    engine = serving.engine_for(model, params, config, where)
    serving.warm(engine, config, cell.traffic, config["arch"]["vocab"])
    rec = serving.Recorder(engine, annotate=False)
    sess = serving.Session(engine, rec, config["engine"]["max_queued"],
                           where)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic)
        mix["arrivals"] = dict(mix["arrivals"], rate_per_s=rate)
        traffic = Traffic(mix, args.seed, config["arch"]["vocab"])
        backlog = {}

        def on_window(edge, backlog=backlog):
            if edge == "end":
                sch = engine.scheduler
                backlog["waiting"] = len(sch.waiting)
                backlog["running"] = len(sch.running)

        t0 = time.perf_counter()
        win = asyncio.run(serving.open_loop(sess, mix, traffic,
                                            args.seconds, on_window))
        came = [r for r in win.judged if r.tokens is not None]
        row = {"rate_per_s": rate, "due": len(win.judged),
               "finished": len(came), **backlog,
               "not_admitted_at_close": sum(
                   1 for r in win.judged if r.request is None
                   or r.request.t_admitted is None
                   or r.request.t_admitted > win.w1)}
        if came:
            row["ttft_p90_ms"] = 1e3 * stats.p90(
                [r.request.t_tokens[0] - r.due for r in came])
            row["tpot_p90_ms"] = 1e3 * stats.p90(
                [stats.tpot(r.request.t_tokens) for r in came])
            row["queue_wait_p90_ms"] = 1e3 * stats.p90(
                [r.request.t_admitted - r.due for r in came])
            row["finished_per_s"] = sum(
                1 for r in came if r.request.t_finished <= win.w1) \
                / args.seconds
        engine.run()            # finish what the last window left running
        row["wall_s"] = time.perf_counter() - t0
        log(json.dumps(row))
        rows.append(row)
    return rows


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json``.  Set-up builds the weights from the seed on the
device, warms every shape the cell's traffic uses and brings the traffic
to steady state; then the window runs for ``--seconds``.  With ``--trace
0`` the result carries the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and the result carries its per-layer
metrics, the device's busy time and a breakdown.  A configuration with a
``mesh`` runs over that many chips, its weights placed as the program
places them.  Afterwards a sample of the served requests, drawn from the
seed, is compared with the float32 reference the configuration names
(``bench/reference.py`` unless it names another); the last line of
standard output is the JSON result.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# libtpu logs under /tmp/tpu_logs unless told otherwise: keep them in the
# checkout, so two checkouts on one machine share nothing
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".tpu_logs"))
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import cells, serving, stats  # noqa: E402
from bench.arrivals import Traffic  # noqa: E402
from bench.flops import peaks  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
# a traced run profiles the window's last TRACE_S seconds: long enough
# for several decode windows of every cell, short enough that the trace
# stays tens of MB and stopping the profiler falls after the window
TRACE_S = 12.0


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``.jax_cache/`` in the
    checkout.  Every program is cached, however quickly it compiled."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def find_devices(chips: int, allow_cpu: bool = False):
    import jax
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's default backend is "
                     f"{devs[0].platform!r}; the benchmark does not fall "
                     f"back")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


class RunRecord:
    """What a per-layer metric reader reads (``bench/metrics/*.py``)."""

    def __init__(self, cell, window, steps, compiles, peak, trace=None,
                 trace_bounds=None):
        self.cell = cell
        self.config = cell.config
        self.arch = cell.config["arch"]
        self.policy = cell.config["policy"]
        self.slots = cell.config["engine"]["max_slots"]
        self.window = window
        self.steps = steps
        self.in_window = serving.window_steps(steps, window.w0, window.w1)
        self.counters = serving.counter_delta(steps, window.w0, window.w1)
        self.compiles = compiles
        self.peak = peak
        self.trace = trace                  # tracefile.Trace or None
        self.trace_bounds = trace_bounds    # (t0_ns, t1_ns) of the window

    def device_ops(self):
        """The first chip's ops in the traced window, or None.  On a mesh
        this is one chip of several: the others run the same program."""
        if self.trace is None or not self.trace.devices:
            return None
        return self.trace.devices[sorted(self.trace.devices)[0]]


def end_to_end(cell, win, rec: RunRecord, setup_s: float):
    """The cell's end-to-end metrics by name."""
    out = {"setup_s": setup_s}
    came = [r for r in win.judged if r.tokens is not None
            and r.error is None and len(r.tokens) >= 2]
    if cell.traffic["kind"] == "open_loop" and came:
        out["ttft_p90_ms"] = 1e3 * stats.p90(
            [r.request.t_tokens[0] - r.due for r in came])
        out["tpot_p90_ms"] = 1e3 * stats.p90(
            [stats.tpot(r.request.t_tokens) for r in came])
    steps = rec.in_window
    if steps:
        span = steps[-1].t1 - steps[0].t0
        out["out_tokens_per_s"] = sum(s.emitted for s in steps) / span
    return out


def served_ok(win, kind: str, vocab: int):
    """(attempted, failed, served): a judged request fails when it never
    came, was quarantined, or came with a token out of the vocabulary or
    (open loop, where every judged request is waited for) with another
    number of tokens than it asked for.  ``served`` lists the (prompt,
    tokens) of the rest."""
    failed, served = 0, []
    for r in win.judged:
        t = r.tokens
        bad = t is None or r.error is not None or len(t) == 0 \
            or bool(np.any((t < 0) | (t >= vocab))) \
            or (kind == "open_loop" and len(t) != r.max_new_tokens)
        if bad:
            failed += 1
        else:
            served.append((r.prompt, t))
    return len(win.judged), failed, served


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_process: float = None, allow_cpu: bool = False,
             mutate=None) -> dict:
    """One run of ``cell``; returns the result object.  ``mutate(session)``
    may break the system before the window (the harness's own tests)."""
    t_process = time.perf_counter() if t_process is None else t_process
    devs = find_devices(cell.chips, allow_cpu)
    config = cell.config
    where = serving.placement(config, devs)
    with where.scope():
        result, served = _serve(cell, devs, where, seed, seconds, trace,
                                t_process, allow_cpu, mutate)
    # the comparison runs once the program's state is freed, off the mesh
    gc.collect()
    check = config["check"]
    reference = cells.bench_module(config, "reference")
    rng = np.random.default_rng([int(seed), 7])
    sample = reference.sample(served, int(check["sample"]), rng)
    t_ref = time.perf_counter()
    gaps = reference.served_gaps(seed, config["arch"], sample)
    log(f"reference over {len(sample)} requests, {int(gaps['tokens'])} "
        f"served tokens in {time.perf_counter() - t_ref:.3f}s; one last "
        f"token moved to its neighbour would read max_logit_gap "
        f"{gaps['altered_max_logit_gap']}")
    checks, result["correct"] = verdict(gaps, result["failed"], len(sample),
                                        check)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


def _serve(cell, devs, where, seed, seconds, trace, t_process, allow_cpu,
           mutate):
    """Build, warm and drive the system on ``where``; (the result without
    its comparison, the served (prompt, tokens) pairs)."""
    import jax
    kind = devs[0].device_kind
    peak = peaks(kind) if not allow_cpu else peaks("TPU v5 lite")
    clock = serving.CompileClock(jax)
    config, mix = cell.config, cell.traffic
    arch = config["arch"]

    model, params = serving.build(config, seed, where)
    engine = serving.engine_for(model, params, config, where)
    n_warm = serving.warm(engine, config, mix, arch["vocab"])
    rec = serving.Recorder(engine, annotate=trace)
    sess = serving.Session(engine, rec, config["engine"]["max_queued"],
                           where)
    if mutate is not None:
        mutate(sess)
    traffic = Traffic(mix, seed, arch["vocab"])
    marks = {}

    def on_window(edge):
        marks[edge] = (time.perf_counter(), clock.programs)
        if trace and edge == "trace":
            TRACE_DIR.mkdir(exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            marks["ann"] = jax.profiler.TraceAnnotation("bench.window")
            marks["ann"].__enter__()
        if trace and edge == "end":
            marks["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    drive = serving.open_loop if mix["kind"] == "open_loop" \
        else serving.backlog
    win = asyncio.run(drive(sess, mix, traffic, seconds, on_window,
                            min(seconds, TRACE_S) if trace else 0.0))
    setup_s = win.w0 - t_process
    compiles = marks["end"][1] - marks["start"][1]
    mem = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
           for d in devs]
    log(f"window {win.w1 - win.w0:.3f}s: {len(win.judged)} judged, "
        f"{len(rec.steps)} steps, {compiles} compiles in the window; "
        f"setup {setup_s:.3f}s with {n_warm} warm-up requests and "
        f"{clock.seconds:.3f}s of compiles ({clock.programs} programs, "
        f"{clock.hits} from the persistent cache)")
    lateness = [r.sent - r.due for r in win.reqs if r.sent]
    if mix["kind"] == "open_loop" and lateness:
        log(f"generator lateness p90 {1e3 * stats.p90(lateness):.3f} ms, "
            f"max {1e3 * max(lateness):.3f} ms over {len(lateness)} sends")

    tr, bounds = None, None
    if trace:
        from bench import tracefile
        tr = tracefile.load(tracefile.find_xplane(str(TRACE_DIR)))
        wins = tracefile.span_bounds(tr.spans, "bench.window")
        bounds = wins[0] if wins else None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    record = RunRecord(cell, win, rec.steps, compiles, peak, tr, bounds)
    attempted, failed, served = served_ok(win, mix["kind"], arch["vocab"])

    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        result["metrics"] = per_layer(cell, record)
    else:
        e2e = end_to_end(cell, win, record, setup_s)
        result["metrics"] = {m.name: {"value": e2e[m.name], "unit": m.unit}
                             for m in cell.end_to_end if m.name in e2e}
    result["device"] = {"platform": devs[0].platform, "kind": kind,
                        "count": len(devs), "memory_peak_bytes": max(mem),
                        "memory_peak_bytes_per_device": mem}
    ops = record.device_ops()
    if ops is not None and bounds is not None:
        from bench import tracefile
        t0, t1 = bounds
        busy = [tracefile.busy_ns(o, t0, t1) for o in tr.devices.values()]
        result["device"]["busy_s"] = sum(busy) / len(busy) / 1e9
        result["device"]["window_s"] = (t1 - t0) / 1e9
        result["breakdown"] = {
            "device_ops": tracefile.top_ops(ops, t0, t1),
            "idle_gaps": tracefile.idle_gaps(ops, tr.spans, t0, t1)}
    return result, served


def verdict(gaps: dict, failed: int, compared: int, check: dict):
    """(the numbers compared, each beside its limit; whether every one
    holds).  ``gaps`` is what ``reference.served_gaps`` reads for the
    served tokens, or for the tokens a control put in their place."""
    checks = {
        "max_logit_gap": {"value": gaps["max_logit_gap"],
                          "limit": check["max_logit_gap"]},
        "mean_logit_gap": {"value": gaps["mean_logit_gap"],
                           "limit": check["mean_logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
        "compared_requests": {"value": compared, "limit": 1},
    }
    ok = (gaps["max_logit_gap"] <= check["max_logit_gap"]
          and gaps["mean_logit_gap"] <= check["mean_logit_gap"]
          and failed == 0 and compared >= 1)
    return checks, bool(ok)


def per_layer(cell, record) -> dict:
    out = {}
    for m in cell.per_layer:
        v = cells.metric_reader(m.name)(record)
        if v is not None:
            out[m.name] = {"value": v, "unit": m.unit}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.load_cell(args.workload)
        use_cache()
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          T_PROCESS)
    except (NoChip, KeyError, FileNotFoundError) as e:
        log(f"FAIL: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

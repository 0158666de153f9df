"""Drive the serving system through one cell: build it from the
configuration file, warm every shape the cell's traffic uses, then offer
the mix through ``serve.frontend.AsyncServer`` and record what happened.

The system under test is the program's ``Model``,
``ContinuousBatchingEngine`` and ``AsyncServer``.  The benchmark takes from
it only what it serves, its counters, the request stamps and the spans the
wrappers below open around its calls (``bench.*``, on the profiler's clock
when tracing).
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.arrivals import Traffic


# the longest a backlog may take to fill the engine before the run fails
FILL_S = 120.0


class CompileClock:
    """Backend compiles (persistent-cache reads included) and programs read
    from the persistent cache, from JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0

        def on_duration(name, secs, **_):
            if name == self.COMPILE:
                self.seconds += secs
                self.programs += 1

        def on_event(name, **_):
            if name == self.HIT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class StepRec:
    """One engine step (host sync cycle) as the wrappers saw it."""
    t0: float
    t1: float = 0.0
    emitted: int = 0
    groups: List[List[int]] = dataclasses.field(default_factory=list)
    decode_n: int = 0
    decode_ctx: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)       # (cache length at start, live steps)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    saturated: bool = False         # admission left every slot live or
                                    # refused a waiting request its pages


@dataclasses.dataclass
class ReqRec:
    """One request of the mix: when it was due and sent, and what came."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float
    sent: float = 0.0
    request: Any = None             # the engine's Request (stamps)
    tokens: Optional[np.ndarray] = None
    error: Optional[str] = None


def program_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file: its
    ``family`` (a decoder unless it says otherwise) and every key of its
    ``arch``."""
    from repro.models.config import ModelConfig, QuantPolicy
    arch = config["arch"]
    return ModelConfig(name=config["name"],
                       family=config.get("family", "decoder"),
                       mx=QuantPolicy.parse(config["policy"]),
                       attn_impl=config["attn_impl"], **arch)


@dataclasses.dataclass
class Placement:
    """Where the cell's program runs: the default device, or the
    configuration's ``mesh`` with the program's decode-posture rules
    (weights resident and tensor-parallel)."""
    mesh: Any = None
    rules: Optional[Dict] = None

    def scope(self):
        """The mesh as JAX's context mesh (the program's sharding
        constraints resolve against it), or nothing."""
        import jax
        return contextlib.nullcontext() if self.mesh is None \
            else jax.set_mesh(self.mesh)


def placement(config: Dict, devs) -> Placement:
    """The configuration's ``mesh`` ({axis: size}, in mesh order) over
    ``devs``, whose number it has to match; one device without it."""
    shape = config.get("mesh")
    if shape is None:
        return Placement()
    import jax
    from jax.sharding import AxisType
    from repro.dist.sharding import make_rules
    from repro.models.config import QuantPolicy
    axes = tuple(shape)
    mesh = jax.make_mesh(tuple(int(shape[a]) for a in axes), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=list(devs))
    rules = make_rules(axes, fsdp_params=False,
                       quant=QuantPolicy.parse(config["policy"]))
    return Placement(mesh, rules)


def build(config: Dict, seed: int, where: Placement = Placement()):
    """(model, params) with weights from ``seed`` made on the device, or
    on ``where``'s mesh as the program places its parameters, by the
    weights module the configuration names."""
    import jax
    from repro.models import Model
    from bench.cells import bench_module
    model = Model(program_config(config))
    expect = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shardings = None
    if where.mesh is not None:
        from repro.launch.cells import shardings_for_params
        shardings = shardings_for_params(expect, where.mesh)
    params = bench_module(config, "weights").make_params(
        seed, config["arch"], shardings)
    got = jax.eval_shape(lambda: params)
    if jax.tree_util.tree_structure(expect) != \
            jax.tree_util.tree_structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                    jax.tree_util.tree_leaves(expect),
                    jax.tree_util.tree_leaves(got))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")
    jax.block_until_ready(params)
    return model, params


def engine_for(model, params, config: Dict, where: Placement = Placement()):
    from repro.serve import ContinuousBatchingEngine
    e = config["engine"]
    return ContinuousBatchingEngine(
        model, params, max_slots=e["max_slots"], page_size=e["page_size"],
        max_len=e["max_len"], num_pages=e["num_pages"],
        sync_every=e["sync_every"], prefill_bucket=e["prefill_bucket"],
        rules=where.rules)


def prefill_shapes(config: Dict, mix: Dict) -> List[Tuple[int, int]]:
    """Every (group size, padded length) prefill the cell can run: the
    front end lets at most ``max_queued`` requests wait for admission, so
    a step prefills at most that many of one bucket."""
    e = config["engine"]
    b = -(-e["prefill_bucket"] // e["page_size"]) * e["page_size"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    buckets = range(-(-lo // b) * b, -(-hi // b) * b + 1, b)
    return [(g, lp) for lp in buckets
            for g in range(1, e["max_queued"] + 1)]


def warm(engine, config: Dict, mix: Dict, vocab: int) -> int:
    """Run every prefill shape and every decode-window length of the cell
    once through the engine itself, so nothing compiles in the window.
    Returns the number of warm-up requests."""
    rng = np.random.default_rng(0)
    n = 0
    for g, lp in prefill_shapes(config, mix):
        for _ in range(g):
            engine.add_request(rng.integers(0, vocab, lp, dtype=np.int32), 1)
        engine.run()
        n += g
    w = 1
    while w <= engine.sync_every:
        engine.add_request(rng.integers(0, vocab, 16, dtype=np.int32),
                           w + 1)
        engine.run()
        n += 1
        w *= 2
    engine.reset_metrics()
    return n


class Recorder:
    """Wraps the engine's step, prefill, decode-window and admission calls
    (instance attributes only; the program is unchanged) to record one
    ``StepRec`` per step and, when ``annotate``, to open ``bench.*``
    profiler spans around each call."""

    def __init__(self, engine, annotate: bool):
        import jax
        self.engine = engine
        self.steps: List[StepRec] = []
        self._cur: Optional[StepRec] = None
        ann = (lambda name: jax.profiler.TraceAnnotation(name)) \
            if annotate else (lambda name: contextlib.nullcontext())
        bucket = engine.prefill_bucket
        step, prefill = engine.step, engine._batched_prefill
        multi, admit = engine._multi, engine.scheduler.admit

        def step_w():
            rec = StepRec(time.perf_counter())
            self._cur = rec
            with ann("bench.step"):
                out = step()
            rec.t1 = time.perf_counter()
            rec.emitted = len(out)
            rec.counters = self.counters()
            self.steps.append(rec)
            return out

        def prefill_w(admitted, emitted):
            groups: Dict[int, List[int]] = {}
            for r in admitted:
                lp = -(-r.prompt_len // bucket) * bucket
                groups.setdefault(lp, []).append(r.prompt_len)
            self._cur.groups = [v for _, v in sorted(groups.items())]
            with ann("bench.prefill"):
                return prefill(admitted, emitted)

        def multi_w(*args):
            n = int(args[7])
            run = engine.scheduler.running.values()
            self._cur.decode_n = n
            self._cur.decode_ctx = [
                (r.prompt_len + len(r.out) - 1, min(n, r.remaining))
                for r in run]
            with ann("bench.decode_dispatch"):
                return multi(*args)

        def admit_w():
            with ann("bench.admit"):
                out = admit()
            # admission takes every waiting request that fits, so one
            # still waiting was refused a slot or pages
            sched = engine.scheduler
            self._cur.saturated = bool(sched.waiting) \
                or len(sched.running) >= sched.max_slots
            return out

        engine.step = step_w
        engine._batched_prefill = prefill_w
        engine._multi = multi_w
        engine.scheduler.admit = admit_w

    def counters(self) -> Dict[str, float]:
        e = self.engine
        ph = e.phase
        return {"decode_s": ph["decode"], "prefill_s": ph["prefill"],
                "sync_s": ph["sync"], "steps": e.n_steps,
                "prefill_tokens": e.prefill_tokens_computed,
                "generated": e.n_generated}


@dataclasses.dataclass
class Window:
    t_start: float                  # when traffic began
    w0: float                       # measured window
    w1: float
    reqs: List[ReqRec]
    judged: List[ReqRec]            # the requests the window answers for


@dataclasses.dataclass
class Session:
    """The system under test with its recorder."""
    engine: Any
    recorder: Recorder
    max_queued: int
    where: Placement = dataclasses.field(default_factory=Placement)


class _Server:
    """An AsyncServer with a private one-thread executor, so that the
    harness can stop it at a step boundary and wait for the step.  JAX's
    context mesh is per thread, so the executor's thread, which runs the
    engine's steps, enters the session's mesh itself: the steps then find
    the programs the warm-up compiled under it."""

    def __init__(self, sess: Session):
        from repro.serve.frontend import AsyncServer
        self.pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, initializer=sess.where.scope)
        self.server = AsyncServer(sess.engine, max_queued=sess.max_queued,
                                  use_executor=True)

    def start(self):
        asyncio.get_running_loop().set_default_executor(self.pool)
        self.server.start()

    async def halt(self):
        """Stop stepping now (no drain) and wait for the running step."""
        task = self.server._task
        if task is not None:
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)
        self.pool.shutdown(wait=True)


async def _window(on_window, w0: float, w1: float, trace_s: float) -> None:
    """Call ``on_window`` at the window's start, ``trace_s`` seconds
    before its end (where the profiler starts in a traced run, so that
    stopping it falls after the window) and at its end."""
    async def at(t):
        await asyncio.sleep(max(0.0, t - time.perf_counter()))

    await at(w0)
    on_window("start")
    await at(max(w0, w1 - trace_s))
    on_window("trace")
    await at(w1)
    on_window("end")


def _noop(edge):
    return None


async def _serve_one(server, rec: ReqRec) -> None:
    from repro.serve.frontend import QuarantinedError
    rec.sent = time.perf_counter()
    stream = await server.submit(rec.prompt, rec.max_new_tokens)
    rec.request = stream.request
    try:
        rec.tokens = await stream.tokens()
    except QuarantinedError as e:
        rec.error = str(e)


async def open_loop(sess: Session, mix: Dict, traffic: Traffic,
                    seconds: float, on_window=_noop,
                    trace_s: float = 0.0) -> Window:
    """Requests due on the mix's schedule; the first ``ramp_s`` seconds
    bring queue and slots to steady state, the next ``seconds`` are the
    window.  Every request due in the window is waited for, up to
    ``drain_s`` past its close."""
    srv = _Server(sess)
    srv.start()
    ramp, drain = float(mix["ramp_s"]), float(mix["drain_s"])
    plan = traffic.until(ramp + seconds)
    t_start = time.perf_counter() + 0.05
    recs = [ReqRec(r.index, r.prompt, r.max_new_tokens, t_start + r.due_s)
            for r in plan]

    async def due(rec):
        await asyncio.sleep(max(0.0, rec.due - time.perf_counter()))
        await _serve_one(srv.server, rec)

    tasks = [asyncio.create_task(due(r)) for r in recs]
    w0, w1 = t_start + ramp, t_start + ramp + seconds
    await _window(on_window, w0, w1, trace_s)
    judged = [r for r in recs if w0 <= r.due < w1]
    pending = [t for t, r in zip(tasks, recs) if w0 <= r.due < w1]
    if pending:
        await asyncio.wait(pending,
                           timeout=max(0.0, w1 + drain - time.perf_counter()))
    await srv.halt()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return Window(t_start, w0, w1, recs, judged)


async def backlog(sess: Session, mix: Dict, traffic: Traffic,
                  seconds: float, on_window=_noop,
                  trace_s: float = 0.0) -> Window:
    """A saturated queue: twice as many closed-loop clients as slots keep
    the front end full.  The window opens once the engine has been full
    (admission left every slot live, or refused a waiting request its
    pages) for the
    mix's ``full_steps`` steps in a row, so the slots' fill lies before
    it, and lasts ``seconds``; it answers for every request that produced
    tokens in it, finished or not (a request of this mix can outlast the
    window)."""
    srv = _Server(sess)
    srv.start()
    t_start = time.perf_counter()
    recs: List[ReqRec] = []
    stop = asyncio.Event()
    nxt = [0]

    async def client():
        while not stop.is_set():
            r = traffic.take(nxt[0] + 1)[nxt[0]]
            nxt[0] += 1
            rec = ReqRec(r.index, r.prompt, r.max_new_tokens,
                         time.perf_counter())
            recs.append(rec)
            await _serve_one(srv.server, rec)

    tasks = [asyncio.create_task(client())
             for _ in range(2 * sess.engine.scheduler.max_slots)]
    steps = sess.recorder.steps
    hold = int(mix["full_steps"])
    while len(steps) < hold or not all(s.saturated for s in steps[-hold:]):
        if time.perf_counter() - t_start > FILL_S:
            await srv.halt()
            raise RuntimeError(f"the backlog did not keep the engine full "
                               f"for {hold} steps within {FILL_S} s")
        await asyncio.sleep(0.005)
    w0 = time.perf_counter()
    w1 = w0 + seconds
    await _window(on_window, w0, w1, trace_s)
    stop.set()
    await srv.halt()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    judged = []
    for r in recs:
        q = r.request
        if q is None or q.t_admitted is None or q.t_admitted > w1 \
                or not q.t_tokens or q.t_tokens[-1] < w0:
            continue
        r.tokens = np.asarray(q.out, np.int32)   # finished or not
        judged.append(r)
    return Window(t_start, w0, w1, recs, judged)


def window_steps(steps: List[StepRec], w0: float, w1: float) -> List[StepRec]:
    """Steps run wholly inside the window."""
    return [s for s in steps if s.t0 >= w0 and s.t1 <= w1]


def counter_delta(steps: List[StepRec], w0: float, w1: float
                  ) -> Optional[Dict[str, float]]:
    """Engine counters over the window's whole steps: the snapshot after
    its last step less the one after the step before its first."""
    inside = [i for i, s in enumerate(steps) if s.t0 >= w0 and s.t1 <= w1]
    if not inside or inside[0] == 0:
        return None
    a, b = inside[0], inside[-1]
    return {k: steps[b].counters[k] - steps[a - 1].counters[k]
            for k in steps[b].counters}

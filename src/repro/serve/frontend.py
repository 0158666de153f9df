"""Asyncio serving front end over ``ContinuousBatchingEngine``.

``AsyncServer`` accepts requests as they arrive (coroutines calling
:meth:`submit`), streams each request's tokens back through its own
``asyncio.Queue``, and drives the engine from a **single background step
loop** — the engine itself stays synchronous and single-threaded, so all
of PR 2-7's token-identity guarantees carry over verbatim.

Concurrency model:

* Submissions land in a pending deque; the step loop applies them to the
  scheduler *between* engine steps, always on the loop task — the
  scheduler is never touched concurrently with a step, even when the
  step itself runs in a worker thread (``use_executor=True``).
* Each accepted request gets a :class:`RequestStream`; the step loop
  pushes ``(token, final)`` pairs into its queue as ``engine.step()``
  emits them, and the caller consumes them with ``async for``.
* Backpressure: ``max_queued`` bounds the number of requests waiting for
  admission; ``submit`` blocks (``admission="block"``) until the backlog
  drains, or raises :class:`RejectedError` (``admission="reject"``) when
  the request could not *start immediately* — the reject-on-full baseline
  the bench's preempt-and-swap claim is measured against.
* ``use_executor=True`` runs each engine step in the default thread-pool
  executor so the event loop stays responsive while the device computes;
  the engine is still only ever stepped by one caller at a time.

Latency accounting is carried by the ``Request`` objects themselves
(``arrival_t`` is stamped at submission, first-token / per-token stamps by
the engine); :func:`latency_summary` aggregates a population of finished
requests into the p50/p99 TTFT + ITL numbers ``bench_serve`` schema v4
reports.
"""
from __future__ import annotations

import asyncio
import collections
import time
from typing import AsyncIterator, Dict, List, Optional, Sequence

import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.metrics import percentile as percentile  # noqa: F401
from repro.serve import snapshot as snapshot_mod
from repro.serve.engine import ContinuousBatchingEngine
from repro.serve.scheduler import Request, RequestState


class RejectedError(RuntimeError):
    """Raised by ``submit`` under ``admission="reject"`` when the request
    cannot start immediately (no free slot/pages, or a backlog exists)."""


class QuarantinedError(RuntimeError):
    """A request was quarantined by a numeric-health guard and no retry
    budget remains (``retries=0``).  Raised out of the request's stream;
    the message carries the engine's diagnostic."""


class RetriesExhausted(QuarantinedError):
    """A quarantined request failed every attempt of its retry budget."""


# ``percentile`` is re-exported above from repro.obs.metrics — the single
# nearest-rank implementation the launcher, bench, and registry share
# (this module used to carry its own copy, one of three that disagreed
# on empty/singleton windows).


def latency_summary(finished: Sequence[Request]) -> Dict[str, float]:
    """p50/p99 TTFT and ITL (milliseconds) plus SLO attainment over a
    population of finished requests.  Requests lacking stamps (none
    finished, or an engine driven without arrival times) are skipped."""
    ttft = [r.ttft_s for r in finished if r.ttft_s is not None]
    itl: List[float] = []
    for r in finished:
        itl.extend(r.itl_s)
    out: Dict[str, float] = {"n_requests": float(len(finished))}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
        out["ttft_p99_ms"] = percentile(ttft, 99) * 1e3
    if itl:
        out["itl_p50_ms"] = percentile(itl, 50) * 1e3
        out["itl_p99_ms"] = percentile(itl, 99) * 1e3
    met = [r.deadline_met for r in finished if r.deadline_met is not None]
    if met:
        out["slo_attainment"] = sum(met) / len(met)
    return out


class RequestStream:
    """One request's token stream: ``async for tok in stream`` yields
    generated token ids as the engine emits them; :meth:`tokens` collects
    the full output.  ``request`` exposes the live ``Request`` (latency
    stamps, preemption count) once finished."""

    def __init__(self, rid: int, request: Request):
        self.rid = rid
        self.request = request
        self._q: asyncio.Queue = asyncio.Queue()
        self._out: List[int] = []
        self._done = False
        # tokens the server has put into the queue — the replay-dedupe
        # baseline for retry and snapshot recovery
        self.n_pushed = 0

    def __aiter__(self) -> AsyncIterator[int]:
        return self._gen()

    async def _gen(self) -> AsyncIterator[int]:
        # once the final token is consumed the stream is exhausted —
        # iterating again (e.g. tokens() after an async-for) must stop
        # instead of awaiting a queue nothing will ever fill
        while not self._done:
            item = await self._q.get()
            if isinstance(item, Exception):
                # terminal failure (QuarantinedError / RetriesExhausted)
                self._done = True
                raise item
            tok, final = item
            self._out.append(tok)
            self._done = final
            yield tok

    async def tokens(self) -> np.ndarray:
        """Drain the stream to completion; returns all generated tokens
        (including any consumed earlier through ``async for``)."""
        async for _ in self:
            pass
        return np.asarray(self._out, np.int32)


class _Pending:
    """One submission awaiting application by the step loop."""

    __slots__ = ("future", "prompt", "max_new_tokens", "priority",
                 "deadline_s", "arrival_t")

    def __init__(self, future, prompt, max_new_tokens, priority,
                 deadline_s, arrival_t):
        self.future = future
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.priority = priority
        self.deadline_s = deadline_s
        self.arrival_t = arrival_t


class AsyncServer:
    """Single-loop asyncio front end over a ``ContinuousBatchingEngine``.

    ``admission`` — ``"block"`` queues submissions (awaiting when more
    than ``max_queued`` are waiting for admission) or ``"reject"`` raises
    :class:`RejectedError` unless the request can start immediately.
    ``use_executor`` — run each engine step in the default thread-pool
    executor so jitted device work doesn't block the event loop.

    Fault tolerance (engine ``health_checks`` quarantines feed these):

    ``retries``        — per-request retry budget: a quarantined request
                         re-enters the queue after a jittered exponential
                         backoff (``retry_backoff_s * 2**attempt``), same
                         rid — the replay is token-identical, and tokens
                         the stream already delivered are deduplicated.
                         After ``retries`` failed attempts the stream
                         raises :class:`RetriesExhausted` (``retries=0``
                         raises :class:`QuarantinedError` immediately).
    ``watchdog_s``     — stalled-step watchdog (requires
                         ``use_executor=True``): when one engine step
                         exceeds this wall time, the server aborts the
                         stall cooperatively (``engine.abort_stall``) and
                         restores the last snapshot; streams resume
                         token-identically (already-delivered tokens are
                         skipped on replay).
    ``snapshot_every`` — take an engine snapshot
                         (``serve.snapshot.capture``) every N completed
                         steps (an initial one is always taken when this
                         or ``watchdog_s`` is set).

    Use as an async context manager (starts/stops the step loop), or call
    :meth:`start` / :meth:`stop` explicitly.
    """

    def __init__(self, engine: ContinuousBatchingEngine, *,
                 admission: str = "block", max_queued: int = 64,
                 use_executor: bool = False,
                 retries: int = 0, retry_backoff_s: float = 0.05,
                 watchdog_s: Optional[float] = None,
                 snapshot_every: Optional[int] = None):
        if admission not in ("block", "reject"):
            raise ValueError(f"admission must be 'block' or 'reject', "
                             f"got {admission!r}")
        if max_queued < 1:
            raise ValueError(f"max_queued must be >= 1, got {max_queued}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        if watchdog_s is not None and not use_executor:
            raise ValueError(
                "watchdog_s requires use_executor=True: without the "
                "executor the step blocks the event loop and a stalled "
                "step could never be timed out")
        if snapshot_every is not None and snapshot_every < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {snapshot_every}")
        self.engine = engine
        self.admission = admission
        self.max_queued = int(max_queued)
        self.use_executor = bool(use_executor)
        self.retries = int(retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.watchdog_s = watchdog_s
        self.snapshot_every = snapshot_every
        self._pending: collections.deque = collections.deque()
        self._requeue: collections.deque = collections.deque()
        self._streams: Dict[int, RequestStream] = {}
        self._skip: Dict[int, int] = {}     # replay-dedupe counters
        self._task: Optional[asyncio.Task] = None
        self._wake: Optional[asyncio.Event] = None
        self._space: Optional[asyncio.Condition] = None
        self._stopping = False
        self._snap = None                   # last EngineSnapshot
        self._snap_pushed: Dict[int, int] = {}
        self._steps_since_snap = 0
        # the server keeps its *own* registry: engine.reset_metrics()
        # restarts the engine's measurement window without zeroing the
        # server's accept/reject/retry history (exactly the pre-registry
        # behavior); obs_snapshot() exports both side by side
        self.metrics = MetricsRegistry()
        self._c_accepted = self.metrics.counter(
            "server.accepted", "submissions applied to the scheduler")
        self._c_rejected = self.metrics.counter(
            "server.rejected", "admission='reject' turn-aways")
        self._c_retried = self.metrics.counter(
            "server.retried", "retry attempts dispatched")
        self._c_failed = self.metrics.counter(
            "server.failed", "terminal quarantines")
        self._c_recoveries = self.metrics.counter(
            "server.recoveries", "watchdog snapshot restores")

    # ------------------------------------ registry-backed counter views
    @property
    def n_accepted(self) -> int:
        return int(self._c_accepted.value())

    @n_accepted.setter
    def n_accepted(self, v: int) -> None:
        self._c_accepted.set(int(v))

    @property
    def n_rejected(self) -> int:
        return int(self._c_rejected.value())

    @n_rejected.setter
    def n_rejected(self, v: int) -> None:
        self._c_rejected.set(int(v))

    @property
    def n_retried(self) -> int:
        return int(self._c_retried.value())

    @n_retried.setter
    def n_retried(self, v: int) -> None:
        self._c_retried.set(int(v))

    @property
    def n_failed(self) -> int:
        return int(self._c_failed.value())

    @n_failed.setter
    def n_failed(self, v: int) -> None:
        self._c_failed.set(int(v))

    @property
    def n_recoveries(self) -> int:
        return int(self._c_recoveries.value())

    @n_recoveries.setter
    def n_recoveries(self, v: int) -> None:
        self._c_recoveries.set(int(v))

    def obs_snapshot(self) -> Dict[str, object]:
        """One structured view of the whole serving process: the server's
        own counters, the engine registry (every engine / scheduler /
        paging / prefix / swap / mx series), and the latency summary over
        the current measurement window.  JSON-serializable — the
        launcher's ``--metrics-json`` writes exactly this."""
        return {"server": self.metrics.snapshot(),
                "engine": self.engine.metrics.snapshot(),
                "latency": latency_summary(self.engine.finished_in_window)}

    # ------------------------------------------------------------ lifecycle
    async def __aenter__(self) -> "AsyncServer":
        self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    def start(self) -> None:
        if self._task is not None:
            raise RuntimeError("server already started")
        self._wake = asyncio.Event()
        self._space = asyncio.Condition()
        self._stopping = False
        self._task = asyncio.get_running_loop().create_task(self._loop())

    async def stop(self) -> None:
        """Drain in-flight work, then stop the step loop."""
        await self.drain()
        self._stopping = True
        self._wake.set()
        await self._task
        self._task = None

    # ------------------------------------------------------------ submission
    def _backlog(self) -> int:
        """Requests accepted but not yet admitted into a slot."""
        return len(self._pending) + len(self.engine.scheduler.waiting)

    async def submit(self, prompt, max_new_tokens: int, *,
                     priority: int = 0,
                     deadline_s: Optional[float] = None,
                     arrival_t: Optional[float] = None) -> RequestStream:
        """Accept one request; resolves to its :class:`RequestStream` once
        the step loop has applied the submission (or raises
        :class:`RejectedError` under ``admission="reject"``).

        The arrival timestamp is taken *here* unless the caller passes
        ``arrival_t`` (a ``time.perf_counter`` stamp, e.g. when the
        request was due under timed traffic) — queueing delay (backlog
        under ``"block"``, scheduler wait, preemption) all counts against
        the request's TTFT and its ``scheduler.queue_wait_s``.
        """
        if self._task is None:
            raise RuntimeError("server is not running")
        arrival = time.perf_counter() if arrival_t is None else arrival_t
        if self.admission == "block":
            async with self._space:
                await self._space.wait_for(
                    lambda: self._backlog() < self.max_queued)
        future = asyncio.get_running_loop().create_future()
        self._pending.append(_Pending(
            future, np.asarray(prompt, np.int32).reshape(-1),
            int(max_new_tokens), int(priority), deadline_s, arrival))
        self._wake.set()
        return await future

    async def drain(self) -> None:
        """Wait until every accepted request has finished streaming (or
        failed terminally)."""
        while (self._pending or self._requeue or self._streams
               or self.engine.scheduler.has_work()):
            self._wake.set()
            await asyncio.sleep(0.001)

    # ------------------------------------------------------------ step loop
    def _apply_pending(self) -> None:
        """Apply queued submissions to the scheduler — always on the loop
        task, between engine steps, so scheduler state is single-writer.
        Backoff-expired retries re-enter first: they keep their original
        arrival rank, so a retried request isn't starved by later
        arrivals."""
        while self._requeue:
            req = self._requeue.popleft()
            if req.state is not RequestState.FAILED:
                continue        # a snapshot restore rewound the failure
            stream = self._streams.get(req.rid)
            if stream is not None:
                # the healthy prefix already streamed is replayed
                # token-identically — skip it on delivery
                self._skip[req.rid] = stream.n_pushed
            self.engine.retry_request(req)
            self._c_retried.inc()
        while self._pending:
            p = self._pending.popleft()
            if p.future.cancelled():
                continue
            if self.admission == "reject" \
                    and not self.engine.scheduler.can_admit_now(
                        p.prompt, p.max_new_tokens):
                self._c_rejected.inc()
                p.future.set_exception(RejectedError(
                    "cannot start immediately: admission='reject'"))
                continue
            try:
                rid = self.engine.add_request(
                    p.prompt, p.max_new_tokens, priority=p.priority,
                    deadline_s=p.deadline_s, arrival_t=p.arrival_t)
            except ValueError as e:        # can never fit slot/pool
                p.future.set_exception(e)
                continue
            req = next(r for r in self.engine.scheduler.waiting
                       if r.rid == rid)
            stream = RequestStream(rid, req)
            self._streams[rid] = stream
            self._c_accepted.inc()
            p.future.set_result(stream)

    async def _loop(self) -> None:
        loop = asyncio.get_running_loop()
        if self.watchdog_s is not None or self.snapshot_every is not None:
            self._take_snapshot()
        while True:
            self._apply_pending()
            if self.engine.scheduler.has_work():
                recovered = False
                if self.use_executor:
                    step = loop.run_in_executor(None, self.engine.step)
                    if self.watchdog_s is not None:
                        try:
                            emitted = await asyncio.wait_for(
                                asyncio.shield(step), self.watchdog_s)
                        except asyncio.TimeoutError:
                            # a stalled step: cut it short cooperatively,
                            # then roll the engine back to the snapshot —
                            # the aborted step's partial work is discarded
                            # and replayed token-identically
                            self.engine.abort_stall()
                            await step
                            self._recover_from_snapshot()
                            recovered = True
                    else:
                        emitted = await step
                else:
                    emitted = self.engine.step()
                    await asyncio.sleep(0)  # let submitters interleave
                if not recovered:
                    self._publish(emitted)
                    self._handle_quarantines(loop)
                    self._steps_since_snap += 1
                    if self.snapshot_every is not None \
                            and self._steps_since_snap \
                            >= self.snapshot_every:
                        self._take_snapshot()
            async with self._space:
                self._space.notify_all()
            if not self.engine.scheduler.has_work() \
                    and not self._pending and not self._requeue:
                if self._stopping:
                    return
                self._wake.clear()
                if self._pending or self._requeue:  # raced with a submit
                    continue
                await self._wake.wait()

    def _publish(self, emitted) -> None:
        done_rids = {r.rid for r in self.engine.scheduler.finished}
        last: Dict[int, int] = {}
        for i, (rid, _) in enumerate(emitted):
            last[rid] = i
        for i, (rid, tok) in enumerate(emitted):
            stream = self._streams.get(rid)
            if stream is None:
                continue
            final = rid in done_rids and i == last[rid]
            skip = self._skip.get(rid, 0)
            if skip > 0:
                # replayed token the stream already delivered (a live
                # stream's final token is never in the skipped prefix:
                # delivering final deletes the stream)
                self._skip[rid] = skip - 1
                continue
            stream._q.put_nowait((tok, final))
            stream.n_pushed += 1
            if final:
                del self._streams[rid]
                self._skip.pop(rid, None)

    # ------------------------------------------------- retry + recovery
    def _backoff_delay(self, req: Request) -> float:
        """Exponential backoff with deterministic per-(rid, attempt)
        jitter in [1.0, 1.25) — decorrelates same-step quarantines
        without a nondeterministic RNG."""
        j = ((req.rid * 2654435761 + req.n_retries * 40503) % 997) / 997.0
        return self.retry_backoff_s * (2 ** req.n_retries) * (1 + 0.25 * j)

    def _handle_quarantines(self, loop) -> None:
        """Route this step's quarantined requests: schedule a backoff'd
        retry while budget remains, otherwise fail the stream."""
        for req in self.engine.quarantined_in_step:
            stream = self._streams.get(req.rid)
            if req.n_retries < self.retries:
                loop.call_later(self._backoff_delay(req),
                                self._requeue_later, req)
                continue
            self._c_failed.inc()
            tr = self.engine.tracer
            if tr is not None and tr.open_spans(req.rid):
                # the engine unwound the track to its root at quarantine
                # time; a spent retry budget is the terminal close
                tr.close_track(req.rid, status="failed")
            if stream is None:
                continue
            if self.retries:
                err: Exception = RetriesExhausted(
                    f"request {req.rid} quarantined after "
                    f"{req.n_retries} retries: {req.error}")
            else:
                err = QuarantinedError(
                    f"request {req.rid} quarantined: {req.error}")
            stream._q.put_nowait(err)
            del self._streams[req.rid]
            self._skip.pop(req.rid, None)

    def _requeue_later(self, req: Request) -> None:
        """call_later target: hand the request back to the loop task (the
        scheduler is single-writer — mutation happens in _apply_pending)."""
        self._requeue.append(req)
        self._wake.set()

    def _take_snapshot(self) -> None:
        self._snap = snapshot_mod.capture(self.engine)
        self._snap_pushed = {rid: st.n_pushed
                             for rid, st in self._streams.items()}
        self._steps_since_snap = 0

    def _recover_from_snapshot(self) -> None:
        """Roll the engine back to the last snapshot and reconcile the
        live streams: tokens delivered since the snapshot will be
        re-emitted token-identically, so each stream skips exactly that
        many; requests the snapshot never saw are resubmitted whole."""
        assert self._snap is not None, "watchdog recovery needs a snapshot"
        snapshot_mod.restore(self.engine, self._snap)
        self.engine._stall_abort.clear()    # no stale abort latch
        tr = self.engine.tracer
        if tr is not None:
            # reconcile the rolled-back request tracks *before* replay:
            # whatever spans opened since the snapshot no longer
            # happened — unwind each live track to its root, and re-open
            # "queued" for requests the restore put back in the queues
            tr.instant("snapshot_restore",
                       recoveries=self.n_recoveries + 1)
            for req, _ in self._snap.requests:
                if not tr.open_spans(req.rid):
                    continue
                tr.unwind(req.rid, keep=1)
                if req.state in (RequestState.WAITING,
                                 RequestState.SWAPPED):
                    tr.begin("queued", cat="request", rid=req.rid)
        known = {r.rid for r, _ in self._snap.requests}
        for rid, stream in list(self._streams.items()):
            if rid in known:
                self._skip[rid] = \
                    stream.n_pushed - self._snap_pushed.get(rid, 0)
            else:
                # submitted after the snapshot: restore dropped it from
                # the queues — re-enter it whole and skip everything the
                # stream already got
                self.engine.resubmit(stream.request)
                self._skip[rid] = stream.n_pushed
        self._c_recoveries.inc()

"""Observability layer: metrics registry, percentiles, trace spans.

Three layers of coverage:

* **Pure unit tests** (no jax): ``percentile`` boundary semantics (the
  satellite bugfix — single sample returns itself at every q, empty
  raises ValueError not IndexError), ``rate`` zero-duration guard,
  Counter/Gauge/Histogram labeled series, registry get-or-create /
  kind-conflict / reset / merge, Tracer span lifecycle (end-mismatch
  raises, unwind, close_track), ``validate_nesting`` re-derivation, and
  the Chrome trace_event export.
* **Engine integration**: the legacy ``n_*`` counters are property
  views over the registry, so the engine's numbers and
  ``metrics.snapshot()`` must agree bit-for-bit; ``reset_metrics``
  must zero every registered series; ``obs_interval`` publishes the
  ``mx.*`` health gauges per KV role.
* **Trace lifecycle property**: a seeded fault plan served through the
  asyncio front end (one request retried to success, one driven to
  ``RetriesExhausted``) must leave every track well-formed — spans nest
  and close exactly once across quarantine/retry — with exactly one
  completed root ``request`` span per rid and the right terminal
  status.
"""
import asyncio
import json

import jax
import numpy as np
import pytest

from repro.kernels import backend
from repro.models import Model, load_reduced
from repro.models.config import QuantPolicy
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       Tracer, chrome_events, percentile, rate,
                       validate_nesting)
from repro.obs.trace import EVENT_FIELDS, TRACE_SCHEMA
from repro.serve import (AsyncServer, ContinuousBatchingEngine,
                         FaultPlan, GenerationConfig, RetriesExhausted)

PAGE = 8
NEW = 6
TIMEOUT = 180


@pytest.fixture(autouse=True)
def _clean_backend():
    backend.reset_degradation()
    yield
    backend.reset_degradation()


@pytest.fixture(scope="module")
def served():
    cfg = load_reduced("chatglm3_6b",
                       mx=QuantPolicy.parse("kv=int8@32:ocp"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens=(7, 12, 9), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
            for n in lens]


def _engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 40)
    kw.setdefault("sync_every", 4)
    kw.setdefault("gen", GenerationConfig(max_new_tokens=NEW))
    return ContinuousBatchingEngine(model, params, page_size=PAGE, **kw)


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, TIMEOUT))


# =============================================================================
# percentile / rate: the deduplicated single implementations
# =============================================================================
def test_percentile_empty_raises_value_error():
    """The satellite bugfix: an empty sample set must raise ValueError
    with a clear message, never IndexError from ``s[-1]``."""
    with pytest.raises(ValueError, match="empty sample set"):
        percentile([], 50)


def test_percentile_single_sample_is_itself():
    for q in (0.001, 1, 50, 99, 100):
        assert percentile([42.5], q) == 42.5


def test_percentile_rejects_q_out_of_range():
    for q in (0, -1, 101):
        with pytest.raises(ValueError, match="q must be in"):
            percentile([1.0], q)


def test_percentile_nearest_rank_goldens():
    s = list(range(1, 11))           # 1..10
    assert percentile(s, 10) == 1
    assert percentile(s, 50) == 5
    assert percentile(s, 51) == 6
    assert percentile(s, 99) == 10
    assert percentile(s, 100) == 10
    # input order must not matter
    assert percentile([3, 1, 2], 50) == 2


def test_percentile_reexports_are_the_same_function():
    """The three former hand-rolled copies now resolve to one object."""
    from repro.obs.metrics import percentile as obs_p
    from repro.serve.frontend import percentile as fe_p
    assert fe_p is obs_p


def test_rate_zero_duration_guard():
    assert rate(5, 0) == 0.0
    assert rate(5, -1) == 0.0
    assert rate(10, 2) == 5.0
    from repro.launch.serve import safe_rate
    assert safe_rate is rate


# =============================================================================
# Counter / Gauge / Histogram
# =============================================================================
def test_counter_labeled_series_and_snapshot():
    c = Counter("c")
    assert c.snapshot() == 0                 # empty -> scalar zero
    c.inc(2)
    assert c.value() == 2 and c.snapshot() == 2
    c2 = Counter("c2")
    c2.inc(1, phase="prefill")
    c2.inc(0.5, phase="decode")
    c2.inc(1, phase="prefill")
    assert c2.value(phase="prefill") == 2
    assert c2.snapshot() == {"phase=decode": 0.5, "phase=prefill": 2}


def test_counter_rejects_negative_but_set_rewinds():
    c = Counter("c")
    c.inc(3)
    with pytest.raises(ValueError, match="negative increment"):
        c.inc(-1)
    c.set(1)                                 # snapshot restore path
    assert c.value() == 1


def test_counter_merge_adds():
    a, b = Counter("c"), Counter("c")
    a.inc(1, k="x")
    b.inc(2, k="x")
    b.inc(5, k="y")
    a.merge(b)
    assert a.value(k="x") == 3 and a.value(k="y") == 5


def test_gauge_set_max_and_default():
    g = Gauge("g")
    assert g.value() == 0 and g.value(default=7) == 7
    g.set_max(4)
    g.set_max(2)
    assert g.value() == 4
    g.set(1)
    assert g.value() == 1


def test_histogram_stats_and_time():
    h = Histogram("h")
    for v in (3.0, 1.0, 2.0):
        h.observe(v)
    assert h.count() == 3 and h.sum() == 6.0
    assert h.percentile(50) == 2.0
    snap = h.snapshot()
    assert snap == {"count": 3, "sum": 6.0, "min": 1.0, "max": 3.0,
                    "p50": 2.0, "p99": 3.0}
    assert Histogram("e").snapshot() == {"count": 0, "sum": 0.0}
    with h.time(op="x"):
        pass
    assert h.count(op="x") == 1 and h.values(op="x")[0] >= 0.0


# =============================================================================
# MetricsRegistry
# =============================================================================
def test_registry_get_or_create_and_kind_conflict():
    m = MetricsRegistry()
    c = m.counter("a.b", "help")
    assert m.counter("a.b") is c
    with pytest.raises(TypeError, match="already registered as counter"):
        m.gauge("a.b")
    assert m.names() == ["a.b"]


def test_registry_reset_zeroes_everything():
    m = MetricsRegistry()
    m.counter("c").inc(5, k="x")
    m.gauge("g").set(3)
    m.histogram("h").observe(1.0)
    m.reset()
    assert m.counter("c").value(k="x") == 0
    assert m.gauge("g").value() == 0
    assert m.histogram("h").count() == 0
    # metrics stay registered after reset
    assert m.names() == ["c", "g", "h"]


def test_registry_merge_and_snapshot_shape():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c").inc(1)
    b.counter("c").inc(2)
    b.gauge("g").set(9)
    b.histogram("h").observe(4.0)
    a.merge(b)
    snap = a.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 9
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)                         # JSON-serializable throughout


# =============================================================================
# Tracer: span lifecycle, export, nesting validation
# =============================================================================
def test_tracer_span_lifecycle_and_mismatch():
    tr = Tracer()
    tr.begin("request", cat="request", rid=0)
    tr.begin("queued", cat="request", rid=0)
    assert tr.open_spans(0) == ["request", "queued"]
    assert tr.top(0) == "queued"
    with pytest.raises(ValueError, match="innermost open span"):
        tr.end("request", rid=0)
    assert tr.open_spans(0) == ["request", "queued"]   # stack intact
    tr.end("queued", rid=0)
    tr.end("request", rid=0)
    with pytest.raises(ValueError, match="no open span"):
        tr.end("request", rid=0)
    assert tr.open_tracks() == []
    roots = validate_nesting(tr.events)
    assert roots == {0: ["request"]}


def test_tracer_end_clamps_to_begin_time():
    """An E stamped before its B (clock jitter at us resolution) clamps
    to the begin time, keeping the track clock monotone."""
    tr = Tracer()
    t = tr.t0 + 1.0
    tr.begin("s", ts=t)
    tr.end("s", ts=t - 0.5)
    b, e = tr.events
    assert e["t_us"] == b["t_us"] == 1_000_000
    validate_nesting(tr.events)


def test_tracer_unwind_and_close_track():
    tr = Tracer()
    tr.begin("request", rid=3)
    tr.begin("queued", rid=3)
    tr.begin("inner", rid=3)
    assert tr.unwind(3, keep=1) == 2
    assert tr.open_spans(3) == ["request"]
    tr.close_track(3, status="failed")
    assert tr.open_tracks() == []
    last = tr.events[-1]
    assert last["ph"] == "E" and last["name"] == "request"
    assert last["args"] == {"status": "failed"}
    validate_nesting(tr.events)


def test_tracer_event_schema_and_determinism():
    tr = Tracer(meta={"seed": 7})
    tr.begin("a", ts=tr.t0)
    tr.instant("mark", ts=tr.t0, k=1)
    tr.end("a", ts=tr.t0)
    assert tr.header() == {"schema": TRACE_SCHEMA, "meta": {"seed": 7}}
    for i, ev in enumerate(tr.events):
        assert ev["seq"] == i                # dense, emission-ordered
        assert set(ev) - {"args"} == set(EVENT_FIELDS)
    # the same operations replayed on a fresh tracer yield the same
    # events modulo nothing (timestamps pinned to t0 here)
    tr2 = Tracer(meta={"seed": 7})
    tr2.begin("a", ts=tr2.t0)
    tr2.instant("mark", ts=tr2.t0, k=1)
    tr2.end("a", ts=tr2.t0)
    assert tr2.events == tr.events


def test_tracer_write_jsonl_roundtrip(tmp_path):
    tr = Tracer(meta={"arch": "t"})
    tr.begin("request", cat="request", rid=1, ts=tr.t0)
    tr.end("request", rid=1, ts=tr.t0)
    p = tmp_path / "t.jsonl"
    tr.write_jsonl(p)
    lines = [json.loads(x) for x in p.read_text().splitlines()]
    assert lines[0] == {"schema": "trace/v1", "meta": {"arch": "t"}}
    assert lines[1:] == tr.events
    validate_nesting(lines[1:])


def test_chrome_export_maps_tracks_to_threads(tmp_path):
    tr = Tracer()
    tr.span("decode_window", t0=tr.t0, t1=tr.t0, steps=4)
    tr.begin("request", cat="request", rid=2, ts=tr.t0)
    tr.instant("admitted", cat="request", rid=2, ts=tr.t0)
    tr.end("request", rid=2, ts=tr.t0)
    evs = chrome_events(tr.events)
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["tid"]: m["args"]["name"] for m in meta} == {
        0: "engine", 3: "request 2"}
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst[0]["s"] == "t" and inst[0]["tid"] == 3
    assert all(e["pid"] == 1 for e in evs)
    p = tmp_path / "t.json"
    tr.write_chrome(p)
    doc = json.loads(p.read_text())
    assert doc["traceEvents"] == evs


def test_validate_nesting_rejects_malformed_streams():
    def ev(seq, ph, name, rid, t):
        return {"seq": seq, "ph": ph, "name": name, "cat": "x",
                "rid": rid, "t_us": t}
    with pytest.raises(ValueError, match="does not close"):
        validate_nesting([ev(0, "B", "a", 0, 0), ev(1, "E", "b", 0, 1)])
    with pytest.raises(ValueError, match="clock moved backwards"):
        validate_nesting([ev(0, "I", "a", 0, 5), ev(1, "I", "b", 0, 1)])
    with pytest.raises(ValueError, match="tracks left open"):
        validate_nesting([ev(0, "B", "a", 0, 0)])
    # independent tracks do not interleave-break each other
    roots = validate_nesting([
        ev(0, "B", "a", 0, 0), ev(1, "B", "b", 1, 0),
        ev(2, "E", "a", 0, 2), ev(3, "E", "b", 1, 3)])
    assert roots == {0: ["a"], 1: ["b"]}


# =============================================================================
# Engine integration: counters == registry snapshot, reset, mx gauges
# =============================================================================
def test_engine_counters_equal_registry_snapshot(served):
    cfg, model, params = served
    eng = _engine(model, params, obs_interval=2)
    for p in _prompts(cfg):
        eng.add_request(p, NEW)
    out = eng.run()
    assert sum(len(v) for v in out.values()) == 3 * NEW
    snap = eng.metrics.snapshot()
    c = snap["counters"]
    assert c["engine.steps"] == eng.n_steps > 0
    assert c["engine.syncs"] == eng.n_syncs > 0
    assert c["engine.generated_tokens"] == eng.n_generated == 3 * NEW
    assert c["engine.prefill_tokens"] == eng.prefill_tokens_computed \
        == 7 + 12 + 9
    assert c["engine.cow_forks"] == eng.n_cow_forks
    assert c["engine.preemptions"] == eng.n_preemptions == 0
    assert c["engine.quarantined"] == eng.n_quarantined == 0
    assert c["engine.phase_s"] == {
        f"phase={k}": v for k, v in eng.phase.items()}
    g = snap["gauges"]
    assert g["pages.peak_mapped"] == eng.peak_mapped_pages > 0
    assert g["pages.peak_shared"] == eng.peak_shared_pages
    assert snap["histograms"]["engine.window_steps"]["count"] \
        == eng.n_syncs
    # obs_interval=2 sampled the MX health gauges per KV role
    for name in ("mx.scale_bytes", "mx.poison_markers",
                 "mx.saturation_rate", "mx.clip_rate",
                 "mx.underflow_rate"):
        assert set(g[name]) == {"role=kv_key", "role=kv_value"}, name
    assert g["mx.poison_markers"]["role=kv_key"] == 0


def test_live_slot_steps_and_queue_wait_in_the_snapshot(served):
    """``engine.live_slot_steps`` counts one slot-step per token a decode
    window hands a live request; ``scheduler.queue_wait_s`` takes each
    admission's wait from the ``arrival_t`` the front end forwarded."""
    import time
    cfg, model, params = served
    eng = _engine(model, params, max_slots=2)
    prompts = _prompts(cfg)

    async def go():
        async with AsyncServer(eng) as srv:
            due = time.perf_counter() - 0.25
            streams = [await srv.submit(p, NEW, arrival_t=due)
                       for p in prompts]
            for st in streams:
                await st.tokens()
            return srv.obs_snapshot(), [st.request for st in streams], due

    snap, reqs, due = asyncio.run(asyncio.wait_for(go(), TIMEOUT))
    assert all(r.arrival_t == due for r in reqs)
    c, h = snap["engine"]["counters"], snap["engine"]["histograms"]
    # every token but each request's first (the prefill's) came from a
    # live slot-step of a decode window
    assert c["engine.live_slot_steps"] == 3 * NEW - 3
    waits = h["scheduler.queue_wait_s"]
    assert waits["count"] == 3
    assert waits["min"] >= 0.25
    assert waits["sum"] == pytest.approx(
        sum(r.t_admitted - due for r in reqs))


def test_engine_reset_metrics_zeroes_registry(served):
    cfg, model, params = served
    eng = _engine(model, params)
    for p in _prompts(cfg):
        eng.add_request(p, NEW)
    eng.run()
    assert eng.n_steps > 0
    eng.reset_metrics()
    assert eng.n_steps == eng.n_syncs == eng.n_generated == 0
    assert eng.phase == {"prefill": 0.0, "decode": 0.0,
                         "sync": 0.0, "swap": 0.0}
    assert eng.swap_store.bytes_out == eng.swap_store.bytes_in == 0
    snap = eng.metrics.snapshot()
    assert snap["counters"]["engine.steps"] == 0
    assert snap["histograms"]["engine.window_steps"]["count"] == 0
    assert eng.finished_in_window == []


# =============================================================================
# Trace lifecycle property: spans close exactly once across faults
# =============================================================================
def test_trace_lifecycle_under_faults_and_retry(served):
    """One request quarantined once and retried to success, one poisoned
    on every attempt until RetriesExhausted — every track must stay
    well-formed (validate_nesting raises otherwise) and finish with
    exactly one completed root ``request`` span carrying the right
    terminal status."""
    cfg, model, params = served
    plan = FaultPlan.parse("prefill_nan:rid=1,prefill_nan:rid=2:always",
                           seed=3)
    tracer = Tracer(meta={"plan": str(plan.faults)})
    eng = _engine(model, params, faults=plan, tracer=tracer,
                  metrics=MetricsRegistry())
    prompts = _prompts(cfg)

    async def go():
        async with AsyncServer(eng, retries=1,
                               retry_backoff_s=0.01) as srv:
            streams = [await srv.submit(p, NEW) for p in prompts]
            res = await asyncio.gather(
                *(s.tokens() for s in streams), return_exceptions=True)
            return srv, streams, res

    srv, streams, res = _run(go())
    assert isinstance(res[2], RetriesExhausted)      # rid 2: exhausted
    assert streams[1].request.n_retries == 1         # rid 1: retried ok
    assert len(res[0]) == len(res[1]) == NEW

    eng.finalize_trace()
    roots = validate_nesting(tracer.events)
    # every request track completes exactly one root "request" span
    for rid in (0, 1, 2):
        assert roots[rid] == ["request"], rid

    def terminal(rid):
        ends = [e for e in tracer.events
                if e["rid"] == rid and e["ph"] == "E"
                and e["name"] == "request"]
        assert len(ends) == 1
        return (ends[0].get("args") or {}).get("status")

    assert terminal(0) == "finished"
    assert terminal(1) == "finished"
    assert terminal(2) == "failed"

    names = {(e["rid"], e["name"], e["ph"]) for e in tracer.events}
    assert (1, "quarantine", "I") in names
    assert (1, "retry", "I") in names
    assert (1, "prefill", "B") in names
    assert (0, "decode", "B") in names
    assert (None, "prefill_batch", "B") in names
    assert (None, "decode_window", "B") in names
    assert (None, "fault:stall", "I") not in names

    # finalize_trace is idempotent
    n = len(tracer.events)
    eng.finalize_trace()
    assert len(tracer.events) == n

    # engine counters agree with what the trace recorded
    assert eng.n_quarantined == eng.metrics.counter(
        "engine.quarantined").value() == 3   # rid1 once + rid2 twice
    snap = srv.obs_snapshot()
    assert set(snap) == {"server", "engine", "latency"}
    assert snap["server"]["counters"]["server.retried"] \
        == srv.n_retried == 2                # rid1 + rid2 first retry
    assert snap["server"]["counters"]["server.failed"] \
        == srv.n_failed == 1
    assert snap["engine"] == eng.metrics.snapshot()
    assert snap["latency"]["n_requests"] == 2.0
    assert snap["latency"]["ttft_p99_ms"] > 0


def test_trace_preempt_restore_spans(served):
    """Preempt-and-swap leaves well-formed tracks: the preempted
    request re-queues (preempt instant + fresh queued span), its
    restore is a span on its own track, and it still completes exactly
    one root request span."""
    cfg, model, params = served
    tracer = Tracer()
    eng = _engine(model, params, max_slots=2, preempt=True,
                  tracer=tracer)
    rng = np.random.default_rng(3)
    # low-priority victim mid-generation, then two high-priority
    # arrivals oversubscribe the 2 slots -> deterministic swap-out
    victim = eng.add_request(
        rng.integers(1, cfg.vocab, size=9).astype(np.int32), 12,
        priority=5)
    eng.step()
    others = [eng.add_request(
        rng.integers(1, cfg.vocab, size=17).astype(np.int32), 6,
        priority=0) for _ in range(2)]
    out = eng.run()
    assert eng.n_preemptions >= 1 and eng.n_restores >= 1
    assert len(out[victim]) == 12
    eng.finalize_trace()
    roots = validate_nesting(tracer.events)
    for rid in (victim, *others):
        assert roots[rid] == ["request"], rid
    engine_spans = {(e["name"], e["ph"]) for e in tracer.events
                    if e["rid"] is None}
    assert ("swap_out", "B") in engine_spans
    assert ("swap_restore", "B") in engine_spans
    victim_evs = {(e["name"], e["ph"]) for e in tracer.events
                  if e["rid"] == victim}
    assert ("preempt", "I") in victim_evs
    assert ("restore", "B") in victim_evs
    # the victim re-queued: two completed queued spans on its track
    queued = [e for e in tracer.events
              if e["rid"] == victim and e["name"] == "queued"
              and e["ph"] == "B"]
    assert len(queued) >= 2

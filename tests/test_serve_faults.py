"""Fault injection + recovery: the serving stack under seeded failures.

PR 9's load-bearing guarantee: every recovery path is *provable* because
the fault plan is deterministic — the same seeded plan yields the same
quarantine set, the same retry outcomes, the same restored tokens.  The
engine-level tests all follow one shape: run a scripted workload clean,
run it again under a ``FaultPlan``, and assert that (a) exactly the
targeted requests are quarantined with a diagnostic, (b) every *healthy*
request's token stream is array-equal to the clean run (batch rows are
independent; a poisoned neighbor must not perturb them), and (c) where a
recovery exists (retry, swap restore, kernel degradation, watchdog
snapshot restore) the recovered stream is token-identical too.

Coverage by site:

* ``prefill_nan``  — quarantined at admission, healthy slots stream on;
                     an engine-level retry replays token-identically.
* ``page_corrupt`` — mid-decode scale-marker corruption caught by the
                     next window's poison scan.
* ``alloc_fail``   — page-grant failure degrades to preempt-and-swap
                     (token-identical resume), never a crash.
* ``swap_corrupt`` — corrupted host payload detected *after* restore by
                     the first post-restore health scan.
* ``kernel_fail``  — Pallas launch failure demotes paged attention to
                     the dense fallback (logged once, tokens unchanged).
* ``stall``        — a hung step is cut short by the front-end watchdog
                     and replayed from the last snapshot.

Unit tests cover ``FaultPlan`` parse/counting/rid-target semantics, the
pool corruption/scrub helpers, and the trace loader's timestamp
validation (satellite: reject, never silently repair).
"""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.formats import SCALE_NAN
from repro.kernels import backend
from repro.models import Model, load_reduced
from repro.models.config import QuantPolicy
from repro.serve import (AsyncServer, ContinuousBatchingEngine, Fault,
                         FaultPlan, GenerationConfig, RetriesExhausted,
                         load_trace, save_trace)
from repro.serve.faults import (corrupt_swap_payload, poison_pool_pages,
                                scrub_pool_pages)
from repro.serve.traffic import Arrival

MIXED = QuantPolicy.parse("kv_key=int8@32:paper,kv_value=e4m3@32:paper")
PAGE = 8
NEW = 10


@pytest.fixture(autouse=True)
def _clean_backend():
    """Kernel degradation is process-global state; isolate every test."""
    backend.reset_degradation()
    yield
    backend.reset_degradation()


@pytest.fixture(scope="module")
def mixed():
    cfg = load_reduced("chatglm3_6b", mx=MIXED)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens=(7, 12, 9), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
            for n in lens]


def _engine(model, params, faults=None, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 40)
    kw.setdefault("sync_every", 4)
    kw.setdefault("gen", GenerationConfig(max_new_tokens=NEW))
    return ContinuousBatchingEngine(model, params, page_size=PAGE,
                                    faults=faults, **kw)


def _failed_rids(eng):
    return {r.rid: r.error for r in eng.scheduler.failed}


# =============================================================================
# FaultPlan: parse / counting / rid-target semantics
# =============================================================================
def test_fault_plan_parse():
    plan = FaultPlan.parse("prefill_nan:rid=2,page_corrupt:nth=1,"
                           "stall:stall_s=0.5,kernel_fail:always", seed=7)
    assert plan.seed == 7
    assert [f.site for f in plan.faults] == [
        "prefill_nan", "page_corrupt", "stall", "kernel_fail"]
    assert plan.faults[0].rid == 2 and plan.faults[0].nth == 0
    assert plan.faults[1].nth == 1 and plan.faults[1].rid is None
    assert plan.faults[2].stall_s == 0.5
    assert plan.faults[3].always


def test_fault_plan_parse_rejects_bad_input():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan.parse("page_corupt")
    with pytest.raises(ValueError, match="bad fault modifier"):
        FaultPlan.parse("stall:speed=9")
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan([]).should_fire("nope")


def test_fault_plan_nth_counts_consultations():
    plan = FaultPlan([Fault("stall", nth=2)])
    assert [plan.should_fire("stall") is not None
            for _ in range(5)] == [False, False, True, False, False]
    assert plan.fired == [("stall", None, 2)]


def test_fault_plan_always_fires_every_time():
    plan = FaultPlan([Fault("kernel_fail", always=True)])
    assert all(plan.should_fire("kernel_fail") is not None
               for _ in range(3))
    assert len(plan.fired) == 3


def test_fault_plan_rid_target_semantics():
    """A fault's rid filters rid-scoped consultations (per-rid count);
    at a site-wide consultation it is a *target hint* the caller reads
    off the returned fault, matched against the site-wide count."""
    plan = FaultPlan([Fault("prefill_nan", rid=7, nth=1)])
    assert plan.should_fire("prefill_nan", rid=3) is None   # wrong rid
    assert plan.should_fire("prefill_nan", rid=7) is None   # rid count 0
    f = plan.should_fire("prefill_nan", rid=7)              # rid count 1
    assert f is not None and f.rid == 7
    # site-wide consultations use the site-wide count
    plan2 = FaultPlan([Fault("page_corrupt", rid=7, nth=1)])
    assert plan2.should_fire("page_corrupt") is None        # site count 0
    assert plan2.should_fire("page_corrupt") is not None    # site count 1


def test_fault_plan_rng_is_deterministic():
    a, b = FaultPlan(seed=5), FaultPlan(seed=5)
    a.should_fire("page_corrupt"), b.should_fire("page_corrupt")
    assert (a.rng("page_corrupt").integers(1 << 30)
            == b.rng("page_corrupt").integers(1 << 30))
    c = FaultPlan(seed=6)
    c.should_fire("page_corrupt")
    assert (a.rng("page_corrupt").integers(1 << 30)
            != c.rng("page_corrupt").integers(1 << 30))


# =============================================================================
# Corruption / scrub helpers over pool pytrees
# =============================================================================
def _fake_pool():
    return {"layers": {
        "ks_pages": jnp.zeros((6, 4, 2, 3), jnp.uint8),
        "k_pages": jnp.ones((2, 6, 4, 2, 3), jnp.float32),
    }}


def test_poison_then_scrub_roundtrip():
    pool = poison_pool_pages(_fake_pool(), [1, 4])
    ks = np.asarray(pool["layers"]["ks_pages"])
    kf = np.asarray(pool["layers"]["k_pages"])
    assert (ks[[1, 4]] == SCALE_NAN).all() and not ks[[0, 2, 3, 5]].any()
    assert np.isnan(kf[:, [1, 4]]).all()         # stacked rank hit too
    assert np.isfinite(kf[:, [0, 2, 3, 5]]).all()

    pool = scrub_pool_pages(pool, [1, 4])
    assert not np.asarray(pool["layers"]["ks_pages"]).any()
    assert not np.asarray(pool["layers"]["k_pages"])[:, [1, 4]].any()
    # pages never poisoned keep their payload
    assert (np.asarray(pool["layers"]["k_pages"])[:, [0, 2]] == 1).all()


def test_poison_single_offset_hits_one_token():
    pool = poison_pool_pages(_fake_pool(), [2], offset=3)
    ks = np.asarray(pool["layers"]["ks_pages"])
    assert (ks[2, 3] == SCALE_NAN).all() and ks[2, :3].sum() == 0


def test_corrupt_swap_payload_replaces_readonly_views():
    dev = _fake_pool()["layers"]
    host = {"layers": {k: np.asarray(v) for k, v in dev.items()}}
    for v in host["layers"].values():
        v.setflags(write=False)          # gather_pages returns r/o views
    assert corrupt_swap_payload(host) == 2
    assert (host["layers"]["ks_pages"] == SCALE_NAN).all()
    assert np.isnan(host["layers"]["k_pages"]).all()


# =============================================================================
# Trace loader: validate timestamps, never silently repair
# =============================================================================
def _write_trace(path, ts):
    arr = [Arrival(t=t, prompt=np.asarray([1, 2], np.int32),
                   max_new_tokens=2) for t in ts]
    with open(path, "w") as f:
        for a in arr:
            f.write('{"t": %r, "prompt": [1, 2], "max_new_tokens": 2}\n'
                    % a.t)
    return str(path)


def test_load_trace_rejects_negative_time(tmp_path):
    p = _write_trace(tmp_path / "t.jsonl", [0.0, -1.0])
    with pytest.raises(ValueError, match=r"t\.jsonl:2.*>= 0"):
        load_trace(p)


def test_load_trace_rejects_nonfinite_time(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text('{"t": NaN, "prompt": [1], "max_new_tokens": 1}\n')
    with pytest.raises(ValueError, match=r"t\.jsonl:1.*finite"):
        load_trace(str(p))


def test_load_trace_rejects_nonmonotonic_time(tmp_path):
    p = _write_trace(tmp_path / "t.jsonl", [0.0, 2.0, 1.0])
    with pytest.raises(ValueError,
                       match=r"t\.jsonl:3.*non-monotonic.*line 2"):
        load_trace(p)


def test_load_trace_roundtrip_valid(tmp_path):
    p = tmp_path / "t.jsonl"
    save_trace(str(p), [Arrival(t=float(i), prompt=np.arange(1, 4,
                                dtype=np.int32), max_new_tokens=3)
                        for i in range(3)])
    got = load_trace(str(p))
    assert [a.t for a in got] == [0.0, 1.0, 2.0]


# =============================================================================
# Engine recovery, site by site
# =============================================================================
def _run_clean(mixed, **kw):
    cfg, model, params = mixed
    eng = _engine(model, params, **kw)
    rids = [eng.add_request(p, NEW) for p in _prompts(cfg)]
    return rids, eng.run()


def test_prefill_nan_quarantines_only_target(mixed):
    cfg, model, params = mixed
    rids, want = _run_clean(mixed)
    plan = FaultPlan.parse("prefill_nan:rid=1:always", seed=1)
    eng = _engine(model, params, faults=plan)
    got_rids = [eng.add_request(p, NEW) for p in _prompts(cfg)]
    out = eng.run()
    assert got_rids == rids
    failed = _failed_rids(eng)
    assert set(failed) == {1} and "prefill" in failed[1]
    assert eng.n_quarantined == 1
    assert 1 not in out
    for r in (0, 2):                     # healthy rows: token-identical
        np.testing.assert_array_equal(out[r], want[r])


def test_page_corrupt_quarantined_mid_decode(mixed):
    cfg, model, params = mixed
    rids, want = _run_clean(mixed)
    plan = FaultPlan.parse("page_corrupt:nth=2:rid=2", seed=2)
    eng = _engine(model, params, faults=plan)
    for p in _prompts(cfg):
        eng.add_request(p, NEW)
    out = eng.run()
    failed = _failed_rids(eng)
    # either guard may report first: the marker scale both trips the
    # poison scan and drives the same window's logits non-finite
    assert set(failed) == {2}
    assert "poison" in failed[2] or "non-finite logits" in failed[2]
    assert ("page_corrupt", None, 2) in plan.fired
    for r in (0, 1):
        np.testing.assert_array_equal(out[r], want[r])


def test_quarantined_request_retries_token_identical(mixed):
    """Same rid -> same per-slot PRNG key -> a clean replay after
    ``retry_request`` emits exactly the clean run's tokens."""
    cfg, model, params = mixed
    _, want = _run_clean(mixed)
    plan = FaultPlan([Fault("prefill_nan", rid=1, nth=0)], seed=3)
    eng = _engine(model, params, faults=plan)
    for p in _prompts(cfg):
        eng.add_request(p, NEW)
    out = eng.run()
    assert set(_failed_rids(eng)) == {1} and 1 not in out
    req = eng.scheduler.failed[0]
    eng.retry_request(req)               # second admission: rid count 1,
    out2 = eng.run()                     # fault stays quiet
    assert not eng.scheduler.failed and req.n_retries == 1
    np.testing.assert_array_equal(out2[1], want[1])
    for r in (0, 2):
        np.testing.assert_array_equal(out[r], want[r])


def test_alloc_fail_degrades_to_swap_out(mixed):
    """A failed page grant preempts the requesting slot instead of
    crashing; the swap restore resumes token-identically."""
    cfg, model, params = mixed
    rids, want = _run_clean(mixed, preempt=True)
    # nth counts non-trivial mid-decode page grants only (admission's
    # reserved allocations never consult the hook); this workload makes
    # roughly four such grants, so target the second one
    plan = FaultPlan.parse("alloc_fail:nth=1", seed=4)
    eng = _engine(model, params, faults=plan, preempt=True)
    for p in _prompts(cfg):
        eng.add_request(p, NEW)
    out = eng.run()
    assert not eng.scheduler.failed      # recovered, not quarantined
    assert eng.n_preemptions >= 1 and eng.n_restores == eng.n_preemptions
    assert plan.fired and plan.fired[0][0] == "alloc_fail"
    for r in rids:
        np.testing.assert_array_equal(out[r], want[r])


def test_swap_corrupt_detected_after_restore(mixed):
    """Corrupt the host payload at swap-out; the poison scan flags the
    victim at its first post-restore window, healthy requests are
    untouched."""
    cfg, model, params = mixed

    def drive(eng):
        rng = np.random.default_rng(3)
        victim = eng.add_request(
            rng.integers(1, cfg.vocab, size=9).astype(np.int32), 12,
            priority=5)
        eng.step()                       # victim is mid-generation
        others = [eng.add_request(
            rng.integers(1, cfg.vocab, size=17).astype(np.int32), 6,
            priority=0) for _ in range(2)]
        return victim, others, eng.run()

    v0, o0, want = drive(_engine(model, params, max_slots=2,
                                 preempt=True))
    plan = FaultPlan([Fault("swap_corrupt", rid=v0, always=True)], seed=5)
    eng = _engine(model, params, max_slots=2, preempt=True, faults=plan)
    v, o, out = drive(eng)
    assert (v, o) == (v0, o0)
    assert eng.n_preemptions >= 1        # the fault actually ran
    failed = _failed_rids(eng)
    assert set(failed) == {v}
    assert "poison" in failed[v] or "non-finite logits" in failed[v]
    for r in o:
        np.testing.assert_array_equal(out[r], want[r])


def test_kernel_fail_degrades_to_dense(caplog):
    """An injected Pallas launch failure mid-serve demotes paged
    attention to the dense path — logged once, token streams unchanged
    (the kernel and dense paths are bit-identical by construction)."""
    cfg = load_reduced("chatglm3_6b", mx=MIXED, attn_impl="flash")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = _engine(model, params)
    rids = [eng.add_request(p, NEW) for p in _prompts(cfg)]
    want = eng.run()
    assert not backend.is_degraded("paged_attn")     # kernel path ran

    plan = FaultPlan.parse("kernel_fail:nth=1", seed=6)
    eng = _engine(model, params, faults=plan)
    for p in _prompts(cfg):
        eng.add_request(p, NEW)
    with caplog.at_level("WARNING", logger="repro.kernels"):
        out = eng.run()
    assert backend.is_degraded("paged_attn")
    assert "injected" in backend.degraded_ops()["paged_attn"]
    assert sum("degrading" in r.message for r in caplog.records) == 1
    assert not eng.scheduler.failed
    for r in rids:
        np.testing.assert_array_equal(out[r], want[r])


def test_real_kernel_error_propagates():
    """Only an injected fault degrades an op: an exception raised by the
    kernel itself reaches the caller, and the op stays on the kernel path
    (a kernel the chip's compiler refuses must fail the run, not serve it
    at dense speed)."""
    backend.reset_degradation()

    def broken():
        raise ValueError("Mosaic refused the block shape")

    with pytest.raises(ValueError, match="Mosaic refused"):
        backend.supervised("paged_attn", broken)
    assert not backend.is_degraded("paged_attn")
    backend.inject_failure("paged_attn")
    assert backend.supervised("paged_attn", broken) is None
    assert backend.is_degraded("paged_attn")
    backend.reset_degradation()


def test_combined_plan_is_deterministic(mixed):
    """One plan exercising four sites at once: the healthy request is
    token-identical to the clean run, exactly the targeted requests are
    quarantined, and a replay of the same plan text fires identically."""
    cfg, model, params = mixed
    _, want = _run_clean(mixed)
    text = ("prefill_nan:rid=1:always,page_corrupt:nth=1:rid=2,"
            "kernel_fail:nth=0,stall:nth=0:stall_s=0.01")

    def run():
        plan = FaultPlan.parse(text, seed=9)
        eng = _engine(model, params, faults=plan)
        for p in _prompts(cfg):
            eng.add_request(p, NEW)
        out = eng.run()
        return plan, out, _failed_rids(eng)

    plan, out, failed = run()
    assert set(failed) == {1, 2}
    np.testing.assert_array_equal(out[0], want[0])
    sites = [s for s, _, _ in plan.fired]
    assert {"prefill_nan", "page_corrupt", "kernel_fail",
            "stall"} <= set(sites)

    backend.reset_degradation()
    plan2, out2, failed2 = run()
    assert plan2.fired == plan.fired and set(failed2) == set(failed)
    np.testing.assert_array_equal(out2[0], out[0])


# =============================================================================
# Async front end: retry budget, exhaustion, watchdog + snapshot restore
# =============================================================================
async def _serve(eng, prompts, **kw):
    out, errs = {}, {}
    async with AsyncServer(eng, **kw) as srv:
        streams = [await srv.submit(p, NEW) for p in prompts]
        for i, st in enumerate(streams):
            try:
                out[i] = await st.tokens()
            except Exception as e:       # noqa: BLE001 — collected
                errs[i] = e
        return srv, out, errs


def test_async_retry_recovers_quarantine(mixed):
    cfg, model, params = mixed
    _, want = _run_clean(mixed)
    plan = FaultPlan([Fault("prefill_nan", rid=1, nth=0)], seed=11)
    srv, out, errs = asyncio.run(_serve(
        _engine(model, params, faults=plan), _prompts(cfg),
        retries=1, retry_backoff_s=0.01))
    assert not errs and srv.n_retried == 1 and srv.n_failed == 0
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])


def test_async_retries_exhausted_surfaces_error(mixed):
    cfg, model, params = mixed
    _, want = _run_clean(mixed)
    plan = FaultPlan([Fault("prefill_nan", rid=1, always=True)], seed=12)
    srv, out, errs = asyncio.run(_serve(
        _engine(model, params, faults=plan), _prompts(cfg),
        retries=1, retry_backoff_s=0.01))
    assert set(errs) == {1} and isinstance(errs[1], RetriesExhausted)
    assert srv.n_retried == 1 and srv.n_failed == 1
    for i in (0, 2):
        np.testing.assert_array_equal(out[i], want[i])


def test_async_watchdog_recovers_stalled_step(mixed):
    """A hung step (120 s injected stall) is cut short by the watchdog,
    the engine restored from the last snapshot, and every stream still
    finishes token-identical to the clean run.  ``watchdog_s`` must
    dominate first-trace compile time or slow-but-healthy steps trip
    spurious (sound, token-identical, wasteful) recoveries."""
    cfg, model, params = mixed
    _, want = _run_clean(mixed)
    plan = FaultPlan.parse("stall:nth=2:stall_s=120", seed=13)
    srv, out, errs = asyncio.run(_serve(
        _engine(model, params, faults=plan), _prompts(cfg),
        use_executor=True, watchdog_s=20, snapshot_every=1))
    assert not errs and srv.n_recoveries >= 1
    for i in range(3):
        np.testing.assert_array_equal(out[i], want[i])

"""The comparison that decides ``correct``, driven through a whole run
at a tiny size on the CPU (the look for a chip skipped): a sound run
passes, a run whose served tokens are altered where they are produced
fails, and the control (the reference with its weights one precision
step lower, its first choices in the served tokens' place) fails the
run's own verdict."""
from __future__ import annotations

import numpy as np
import pytest

import bench_tiny
from bench import reference, run  # noqa: E402


def _result(kind="open_loop", mutate=None, **kw):
    return run.run_cell(bench_tiny.cell(kind, **kw), 2 ** 31 + 11, 1.5,
                        False, allow_cpu=True, mutate=mutate)


def test_sound_run_is_correct():
    res = _result()
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert list(res["checks"])[0] == "max_logit_gap"
    assert list(res)[-1] == "checks"


def _alter_decode(sess):
    """Every token the decode window produces, moved to its neighbour."""
    eng = sess.engine
    multi = eng._multi

    def broken(*args):
        toks, *rest = multi(*args)
        return ((toks + 1) % eng.vocab, *rest)

    eng._multi = broken


def _alter_prefill(sess):
    """The first token each prefill samples, moved to its neighbour."""
    eng = sess.engine
    prefill = eng._prefill_scatter

    def broken(*args):
        first, *rest = prefill(*args)
        return ((first + 1) % eng.vocab, *rest)

    eng._prefill_scatter = broken


@pytest.mark.parametrize("fault", [_alter_decode, _alter_prefill],
                         ids=["decode_token", "prefill_token"])
def test_altered_token_is_caught(fault):
    res = _result("backlog", mutate=fault)
    assert res["correct"] is False
    c = res["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def test_control_fails_the_verdict():
    """Greedy continuations of the reference itself read a gap of 0 and
    pass; the control's first choices at the same positions, and one
    last token moved to its neighbour, fail the tiny cell's limits
    through ``run.verdict``."""
    cfg = bench_tiny.config()
    arch = cfg["arch"]
    rng = np.random.default_rng(0)
    outs = []
    for n in (40, 57, 33):
        seq = list(rng.integers(0, arch["vocab"], n).astype(np.int32))
        for _ in range(12):
            s = reference.Served(np.asarray(seq, np.int32),
                                 np.zeros(1, np.int32), 64)
            _, _, pick = reference.head(5, arch, reference.hidden(
                5, arch, s.tokens), s.served)
            seq.append(int(pick[s.rows[0]]))
        outs.append((np.asarray(seq[:n], np.int32),
                     np.asarray(seq[n:], np.int32)))
    g = reference.served_gaps(5, arch, outs, control="fp8_e4m3",
                              pad_to=64)
    assert g["max_logit_gap"] == pytest.approx(0.0, abs=1e-6)
    assert g["not_first_share"] == 0.0
    _, ok = run.verdict(g, 0, len(outs), cfg["check"])
    assert ok
    # one last token moved to its neighbour fails the widest gap
    checks, ok = run.verdict(
        dict(g, max_logit_gap=g["altered_max_logit_gap"]), 0, len(outs),
        cfg["check"])
    assert not ok
    assert checks["max_logit_gap"]["value"] \
        > checks["max_logit_gap"]["limit"]
    ctrl = {k: g[f"control_{k}"] for k in ("max_logit_gap",
                                           "mean_logit_gap")}
    checks, ok = run.verdict(ctrl, 0, len(outs), cfg["check"])
    assert not ok
    assert checks["mean_logit_gap"]["value"] \
        > checks["mean_logit_gap"]["limit"]


def test_backlog_window_opens_once_the_engine_is_full():
    """The backlog's window opens only after ``full_steps`` steps in a
    row in which admission left every slot live or refused a request."""
    from bench import serving
    got = {}
    drive = serving.backlog

    async def spy(sess, *args):
        got["steps"] = sess.recorder.steps
        got["win"] = await drive(sess, *args)
        return got["win"]

    serving.backlog = spy
    try:
        res = _result("backlog")
    finally:
        serving.backlog = drive
    assert res["correct"] is True, res["checks"]
    steps, w0 = got["steps"], got["win"].w0
    before = [s for s in steps if s.t1 <= w0]
    hold = bench_tiny.mix("backlog")["full_steps"]
    assert len(before) > hold
    assert all(s.saturated for s in before[-hold:])
    assert not before[0].saturated      # the fill lies before the window

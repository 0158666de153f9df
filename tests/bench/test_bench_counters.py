"""The program's own registry series equal what the harness now reads by
wrapping the engine's private methods: ``engine.live_slot_steps`` over a
window is the numerator ``Recorder.decode_ctx`` sums for
``slot_occupancy``, and ``scheduler.queue_wait_s`` holds each admitted
request's wait from its arrival stamp (a tiny backlog on the CPU)."""
from __future__ import annotations

import bench_tiny
from bench import run, serving


def test_live_slot_steps_and_queue_wait_match_the_harness():
    after = []                  # (StepRec, counter after the step)
    admitted = {}

    def spy(sess):
        eng = sess.engine
        step, admit = eng.step, eng.scheduler.admit   # the recorder's
        live = eng.metrics.counter("engine.live_slot_steps")

        def step_c():
            out = step()
            after.append((sess.recorder.steps[-1], live.value()))
            return out

        def admit_c():
            out = admit()
            admitted.update((r.rid, r) for r in out)
            return out

        eng.step, eng.scheduler.admit = step_c, admit_c
        spy.engine = eng

    res = run.run_cell(bench_tiny.cell("backlog"), 2 ** 31 + 23, 1.5, False,
                       allow_cpu=True, mutate=spy)
    assert res["correct"] is True, res["checks"]
    steps = [s for s, _ in after]
    # a window of every step but the first, as counter_delta takes it
    inside = serving.window_steps(steps, steps[1].t0, steps[-1].t1)
    assert inside == steps[1:] and len(inside) > 10
    delta = after[-1][1] - after[0][1]
    assert delta == sum(n for s in inside for _, n in s.decode_ctx) > 0

    waits = spy.engine.metrics.histogram("scheduler.queue_wait_s").values()
    want = sorted(r.t_admitted - r.arrival_t for r in admitted.values())
    assert len(waits) == len(want) > 0
    assert sorted(waits) == want

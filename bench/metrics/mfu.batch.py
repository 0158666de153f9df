"""Model step: model FLOPs of the window's whole steps (prefill and live
decode tokens, from the shapes) per second over the chip's bf16 peak (%)."""
from bench.flops import step_model_flops


def read(run):
    steps = run.in_window
    if not steps:
        return None
    span = steps[-1].t1 - steps[0].t0
    f = sum(step_model_flops(run.arch, s) for s in steps)
    return 100.0 * f / span / run.peak["bf16_flops_per_s"]

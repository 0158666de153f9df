"""Engine: decode-phase seconds per fused decode step over the window,
from the engine's own counters (ms)."""


def read(run):
    c = run.counters
    if not c or not c["steps"]:
        return None
    return 1e3 * c["decode_s"] / c["steps"]

"""Engine: prefill-phase milliseconds per 1000 prompt tokens over the
window, from the engine's own counters."""


def read(run):
    c = run.counters
    if not c or not c["prefill_tokens"]:
        return None
    return 1e3 * c["prefill_s"] / (c["prefill_tokens"] / 1e3)

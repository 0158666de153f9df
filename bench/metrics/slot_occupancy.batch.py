"""Front end and scheduler: live slots per decode step over the engine's
slots, across the window's decode steps (%)."""


def read(run):
    live = sum(n for s in run.in_window for _, n in s.decode_ctx)
    steps = sum(s.decode_n for s in run.in_window)
    return 100.0 * live / (steps * run.slots) if steps else None

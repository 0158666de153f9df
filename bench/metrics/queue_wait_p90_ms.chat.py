"""Front end and scheduler: p90 over the window's requests of the wait
from when a request was due to its admission into a slot (ms)."""
from bench import stats


def read(run):
    waits = [r.request.t_admitted - r.due for r in run.window.judged
             if r.request is not None and r.request.t_admitted is not None]
    return 1e3 * stats.p90(waits) if waits else None

"""Device: share of the traced window in which no operation ran on the
first chip, the only one a one-chip cell has (%)."""
from bench import tracefile


def read(run):
    ops = run.device_ops()
    if ops is None or run.trace_bounds is None:
        return None
    t0, t1 = run.trace_bounds
    return 100.0 * (1.0 - tracefile.busy_ns(ops, t0, t1) / (t1 - t0))

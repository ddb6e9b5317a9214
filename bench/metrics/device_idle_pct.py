"""The device's idle share of the traced window, in %: 1 - (union of its op
intervals / the window)."""
from bench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.busy:
        return None
    lo, hi = run.trace_window
    return 100.0 * (1.0 - T.busy_ns(tr, lo, hi) / (hi - lo))

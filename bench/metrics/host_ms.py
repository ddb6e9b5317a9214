"""Host time per batch in the engine's path (``repro.robust.runner`` and the
engine's batched executable: dispatch and result transfer): the wall time of
each ``bench.run_batch`` span in the traced window less the device's busy
time inside it, averaged over those spans, in ms."""
from bench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.busy:
        return None
    lo, hi = run.trace_window
    spans = [(s, e) for name, s, e in tr.spans
             if name == "bench.run_batch" and s >= lo and e <= hi]
    if not spans:
        return None
    host = [(e - s) - T.busy_ns(tr, s, e) for s, e in spans]
    return sum(host) / len(host) / 1e6

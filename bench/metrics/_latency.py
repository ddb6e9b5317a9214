"""Latency of every request due in the window, from when it was due to when
its answer reached the host, in ms. A request never answered counts with
the time it had waited when the run gave up on it."""
import math


def latencies_ms(run) -> list[float]:
    rec = run.record
    out = []
    for r in rec.requests:
        if math.isnan(r.due) or not rec.t0 <= r.due < rec.t_close:
            continue
        done = r.done if r.status and r.status != "error" else run.gave_up_at
        out.append((done - r.due) * 1e3)
    return out

"""Requests answered per second over the window ``[t0, t0 + seconds]``. A
batch answers its requests together; one that straddles the window's close
counts for the share of its run that lies inside the window, so the rate
does not jump by a whole batch (an AS batch runs for seconds) as the close
moves across one."""


def read(run):
    rec = run.record
    done = 0.0
    for b in rec.batches:
        inside = max(0.0, min(b.end, rec.t_close) - max(b.start, rec.t0))
        done += b.answered * inside / (b.end - b.start) if b.end > b.start else 0.0
    return done / (rec.t_close - rec.t0) if done else None

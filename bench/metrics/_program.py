"""What the program records of its own host path while a profiler session
collects (``repro.obs.trace.PROFILED``, on the host's ``perf_counter``, the
clock of the run's record): the runner's ``batch`` spans that started inside
the window, and spans nested in them. A program without that record gives
None, and the metrics that read it are left out."""


def batches(run):
    """The window's ``batch`` spans, or None where the program records none."""
    try:
        from repro.obs import trace as program
    except ImportError:
        return None
    sink = getattr(program, "PROFILED", None)
    if sink is None or run.trace is None:
        return None
    rec = run.record
    out = [sp for sp in sink.roots
           if sp.name == "batch" and rec.t0 <= sp.t0 < rec.t_close]
    return out or None


def nested(spans, name: str) -> list:
    """Every span called ``name`` inside ``spans``."""
    out, stack = [], list(spans)
    while stack:
        sp = stack.pop()
        if sp.name == name:
            out.append(sp)
        stack.extend(sp.children)
    return out


def mean_ms_per_batch(run, name: str):
    """Wall time of the ``name`` spans per window batch, in ms."""
    bs = batches(run)
    if bs is None:
        return None
    found = nested(bs, name)
    if not found:
        return None
    return sum(sp.wall_ms for sp in found) / len(bs)

"""Device time of the hop kernels (``gqfast_hop``, ``gqfast_fused_hops`` of
``repro.kernels``) in the traced window per request answered in it, in ms."""
import re

from bench import trace as T

KERNELS = re.compile(r"gqfast_(hop|fused_hops)(\.\d+)?$")


def read(run):
    tr = run.trace
    n = run.traced_requests()
    if tr is None or not n:
        return None
    ns = T.op_ns(tr, KERNELS, *run.trace_window)
    return ns / n / 1e6 if ns > 0 else None

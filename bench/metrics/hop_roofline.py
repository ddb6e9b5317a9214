"""The hops' share of the HBM roofline, in %: the least time the chip's HBM
needs to read the useful bytes of the requests answered in the traced window
(``bench/bytes.py``; semantic FLOPs, about 2 per edge, are negligible, so
bytes bound it), over the device's busy time in that window. The busy time
counts every device op, not only the hop kernels, so work moved out of the
kernels cannot raise the share."""
from bench import trace as T


def read(run):
    tr = run.trace
    if tr is None or not tr.busy:
        return None
    busy_s = T.busy_ns(tr, *run.trace_window) / 1e9
    if busy_s <= 0:
        return None
    return 100.0 * run.useful_bytes() / run.peaks["hbm_bytes_per_s"] / busy_s

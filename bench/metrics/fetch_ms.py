"""Time per batch the engine's host path spends copying the finished result
to host memory (the program's ``fetch`` span: ``np.asarray`` of the
``[bucket, out_dom]`` result once the device is done), over the batches that
started in the traced window, in ms."""
from bench.metrics._program import mean_ms_per_batch


def read(run):
    return mean_ms_per_batch(run, "fetch")

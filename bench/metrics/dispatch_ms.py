"""Time per batch the engine's host path spends in the call of the batched
executable until it returns (the program's ``dispatch`` span, inside the
runner's ``attempt``), over the batches that started in the traced window,
in ms: argument transfer and launch, not the device's work."""
from bench.metrics._program import mean_ms_per_batch


def read(run):
    return mean_ms_per_batch(run, "dispatch")

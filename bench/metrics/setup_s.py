"""Process start to the window's start: data, database build and placement,
prepare, compile-cache loads and warm-up, and the loop's warm-up batches."""


def read(run):
    return run.setup_s

"""One reader per metric, found by name: the metric ``<base>`` or
``<base>.<split>`` (the same quantity, split by the end-to-end metric it
moves) is read by ``read(run)`` in ``bench/metrics/<base>.py``. ``run`` is a
:class:`bench.run.RunView`. A reader that finds nothing to read returns
``None`` and the metric is left out of the result line."""

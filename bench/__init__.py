"""The chip benchmark of the relationship-query engine (``bench/run.py``)."""

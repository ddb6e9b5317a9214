"""95th percentile (nearest rank, exact) of the latencies of every request
due in the window (``bench/metrics/_latency.py``)."""
from bench.metrics._latency import latencies_ms
from bench.stats import percentile


def read(run):
    lat = latencies_ms(run)
    return percentile(lat, 95) if lat else None

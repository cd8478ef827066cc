"""Median latency of every query answered in the window, submit to
result, on the client's clock."""
from chipbench.stats import percentile


def read(run):
    lat = [q.latency for q in run.records if q.error is None]
    return percentile(lat, 50) if lat else None

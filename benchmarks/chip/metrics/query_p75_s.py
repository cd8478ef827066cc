"""75th percentile latency of every query answered in the window: at
about 45 queries a window, the highest percentile with at least ten
samples beyond it."""
from chipbench.stats import percentile


def read(run):
    lat = [q.latency for q in run.records if q.error is None]
    return percentile(lat, 75) if lat else None

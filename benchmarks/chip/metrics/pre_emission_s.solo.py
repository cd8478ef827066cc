"""Mean time per query from submit to its first emission kernel on the
device: sampling, labels and bounds. One query runs at a time, so each
kernel event belongs to the query span around it."""
from chipbench.trace import mean, span_phases


def read(run):
    return mean([pre for pre, _ in span_phases(run.trace, "bench.query",
                                               "threshold_select")])

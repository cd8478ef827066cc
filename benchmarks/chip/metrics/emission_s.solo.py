"""Mean time per query from its first emission kernel on the device to
the result in the client's hands: the walk over every chunk, the copies
both ways and the host's stitching."""
from chipbench.trace import mean, span_phases


def read(run):
    return mean([post for _, post in span_phases(run.trace, "bench.query",
                                                 "threshold_select")])

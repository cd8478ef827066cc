"""Records taken from tokens to an installed, caught-up epoch, over the
window from its start to the last such epoch."""
from chipbench.stats import rate


def read(run):
    done = sum(a.scores.size for a in run.records if a.error is None)
    return rate(done, run.t_close - run.t0) if run.records else None

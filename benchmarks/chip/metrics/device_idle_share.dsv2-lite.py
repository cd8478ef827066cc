"""Share of the traced window in which no operation ran on the device."""
from chipbench.trace import idle_share


def read(run):
    return idle_share(run.trace)

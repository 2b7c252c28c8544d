"""Device: share of the traced window in which no operation ran."""
from chipbench.metrics._shared import idle_share


def read(rec, trace):
    return idle_share(trace)

"""SSD scan (models/layers._ssd_chunked): device ms a train step of the ops
under the ``ssd_scan`` scope, forward, backward and recompute."""
from chipbench.scopes import read_scopes


def read(rec, trace):
    got = read_scopes(rec, trace)
    return None if got is None else got.ms_per_step(("ssd_scan",))

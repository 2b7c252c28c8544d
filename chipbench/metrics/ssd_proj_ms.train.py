"""Block projections (models/layers.ssd_block): device ms a train step of the
ops under the ``ssd_in_proj`` and ``ssd_out_proj`` scopes, forward, backward
and recompute."""
from chipbench.scopes import read_scopes


def read(rec, trace):
    got = read_scopes(rec, trace)
    return (None if got is None
            else got.ms_per_step(("ssd_in_proj", "ssd_out_proj")))

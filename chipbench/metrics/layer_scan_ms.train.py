"""Pipeline stage (msl/pipeline._stage_apply): device ms a train step of the
ops under the ``stage`` scope and outside every block scope: the layer scan's
carries, per-layer parameter slices and stacked gradient writes."""
from chipbench.scopes import read_scopes


def read(rec, trace):
    got = read_scopes(rec, trace)
    return None if got is None else got.ms_per_step(("stage",))

"""AdamW (optim/optimizers.adamw): device ms a train step of the ops under
the ``optimizer`` scope."""
from chipbench.scopes import read_scopes


def read(rec, trace):
    got = read_scopes(rec, trace)
    return None if got is None else got.ms_per_step(("optimizer",))

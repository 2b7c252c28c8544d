"""Pipeline step (the ``cfg.remat`` policy): the share of the step's device
self time that the forward recomputed under ``jax.checkpoint`` takes, %."""
from chipbench.scopes import read_scopes


def read(rec, trace):
    got = read_scopes(rec, trace)
    if got is None or not got.sound() or got.share(direction="remat") == 0:
        return None
    return 100.0 * got.share(direction="remat")

"""MoE layers (models/layers.moe_ffn): device ms a train step of the ops
under the ``moe`` scope, whose parts are named ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine`` and ``moe_shared``;
forward, backward and recompute."""
from chipbench.scopes import read_scopes


def read(rec, trace):
    got = read_scopes(rec, trace)
    return None if got is None else got.ms_per_step(("moe",))

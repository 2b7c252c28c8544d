"""Attention (models/layers.attention_block, the attention-only block):
device ms a train step of the ops under the ``attn`` scope, forward,
backward and recompute."""
from chipbench.scopes import read_scopes


def read(rec, trace):
    got = read_scopes(rec, trace)
    return None if got is None else got.ms_per_step(("attn",))

"""Model assembly: embedding + repeated block pattern (scan) + head.

The layer stack is organized as `full_reps` repetitions of `cfg.pattern`
executed under one `lax.scan` with params stacked over repetitions (keeps HLO
size O(pattern) instead of O(L)), plus an unrolled remainder.  Whisper-style
encoders are a second (non-causal) stack over the modality memory.

The same stack is exposed to the *planner* (repro.core) through
`costmodel_profile` in profiles.py — every architecture is a layer list the
paper's splitting/placement/chaining optimizer can cut.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from .layers import Ctx
from .sharding import constrain

KINDS_WITH_KV = ("attn", "local_attn", "moe", "moe_dense", "dec_block",
                 "attn_only")


# ------------------------------------------------------------------- params --
def init_block(key, cfg: ModelConfig, kind: str) -> dict:
    ks = jax.random.split(key, 6)
    dt = L.pdt(cfg)
    D = cfg.d_model
    p: dict = {"ln1": jnp.zeros((D,), dt)}
    if kind in ("attn", "local_attn"):
        p["attn"] = L.init_attention(ks[0], cfg)
        p["ln2"] = jnp.zeros((D,), dt)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind in ("moe", "moe_dense"):
        p["attn"] = L.init_attention(ks[0], cfg)
        p["ln2"] = jnp.zeros((D,), dt)
        p["moe"] = L.init_moe(ks[1], cfg)
        if kind == "moe_dense":
            p["mlp"] = L.init_mlp(ks[2], cfg)
    elif kind == "xattn":
        p["attn"] = L.init_attention(ks[0], cfg, cross=True)
        p["ln2"] = jnp.zeros((D,), dt)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "dec_block":
        p["attn"] = L.init_attention(ks[0], cfg)
        p["ln2"] = jnp.zeros((D,), dt)
        p["xattn"] = L.init_attention(ks[1], cfg, cross=True)
        p["ln3"] = jnp.zeros((D,), dt)
        p["mlp"] = L.init_mlp(ks[2], cfg)
    elif kind == "rglru":
        p["rglru"] = L.init_rglru(ks[0], cfg)
        p["ln2"] = jnp.zeros((D,), dt)
        p["mlp"] = L.init_mlp(ks[1], cfg)
    elif kind == "ssd":
        p["ssd"] = L.init_ssd(ks[0], cfg)
    elif kind == "attn_only":
        p["attn"] = L.init_attention(ks[0], cfg)
    elif kind == "moe_only":
        p["moe"] = L.init_moe(ks[0], cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def _sp_gather(h):
    """Sequence-parallel entry: gather the (normed) sublayer input."""
    return constrain(h, ("batch", None, None))


def _sp_scatter(h):
    """Sequence-parallel exit: reduce-scatter the sublayer output back to the
    sequence-sharded residual layout."""
    return constrain(h, ("batch", "seq", None))


def apply_block(p, cfg: ModelConfig, kind: str, x, ctx: Ctx, cache):
    """Pre-norm block; returns (x, new_cache, stats), ``stats`` as
    ``layers.no_stats`` lays them out (the MoE's aux loss and counters).

    Sequence parallelism, Megatron-SP style: the residual stream (and thus the
    scan carry the backward pass saves per layer) stays sequence-sharded at all
    times; each sublayer gathers its *normed input* and reduce-scatters its
    output.  Constraining the residual itself at block entry instead makes the
    while-loop carry's fixed-point sharding replicated — full-sequence saved
    activations per layer (§Perf, hillclimb #1)."""

    def norm_in(scale_name: str):
        with jax.named_scope("block_norm"):
            return _sp_gather(L.rmsnorm(x, p[scale_name], cfg.norm_eps))

    def sub(scope: str, fn, *args, **kw):
        with jax.named_scope(scope):
            return fn(*args, **kw)

    stats = L.no_stats()
    if kind in ("attn", "local_attn", "moe", "moe_dense"):
        window = cfg.window if kind == "local_attn" else None
        h, cache = sub("attn", L.attention_block, p["attn"], cfg,
                       norm_in("ln1"), ctx, cache, window=window)
        x = x + _sp_scatter(h)
        hin = norm_in("ln2")
        if kind in ("moe", "moe_dense"):
            y, stats = sub("moe", L.moe_ffn, p["moe"], cfg, hin)
            if kind == "moe_dense":
                y = y + sub("mlp", L.mlp, p["mlp"], cfg, hin)
        else:
            y = sub("mlp", L.mlp, p["mlp"], cfg, hin)
        x = x + _sp_scatter(y)
    elif kind == "xattn":
        h, cache = sub("attn", L.attention_block, p["attn"], cfg,
                       norm_in("ln1"), ctx, cache, cross=True)
        x = x + _sp_scatter(h)
        x = x + _sp_scatter(sub("mlp", L.mlp, p["mlp"], cfg, norm_in("ln2")))
    elif kind == "dec_block":
        c_self = cache["self"] if cache else None
        c_cross = cache["cross"] if cache else None
        h, c_self = sub("attn", L.attention_block, p["attn"], cfg,
                        norm_in("ln1"), ctx, c_self)
        x = x + _sp_scatter(h)
        h, c_cross = sub("attn", L.attention_block, p["xattn"], cfg,
                         norm_in("ln2"), ctx, c_cross, cross=True)
        x = x + _sp_scatter(h)
        x = x + _sp_scatter(sub("mlp", L.mlp, p["mlp"], cfg, norm_in("ln3")))
        cache = ({"self": c_self, "cross": c_cross} if cache is not None
                 else None)
    elif kind == "rglru":
        h, cache = sub("rglru", L.rglru_block, p["rglru"], cfg,
                       norm_in("ln1"), ctx, cache)
        x = x + _sp_scatter(h)
        x = x + _sp_scatter(sub("mlp", L.mlp, p["mlp"], cfg, norm_in("ln2")))
    elif kind == "ssd":
        h, cache = L.ssd_block(p["ssd"], cfg, norm_in("ln1"), ctx, cache)
        x = x + _sp_scatter(h)
    elif kind == "attn_only":
        h, cache = sub("attn", L.attention_block, p["attn"], cfg,
                       norm_in("ln1"), ctx, cache)
        x = x + _sp_scatter(h)
    elif kind == "moe_only":
        y, stats = sub("moe", L.moe_ffn, p["moe"], cfg, norm_in("ln1"))
        x = x + _sp_scatter(y)
    else:
        raise ValueError(kind)
    x = constrain(x, ("batch", "seq", None))
    return x, cache, stats


def init_cross_cache(cfg: ModelConfig, batch: int):
    hd, Hkv = cfg.resolved_head_dim, max(1, cfg.n_kv_heads)
    return {
        "k": jnp.zeros((batch, cfg.memory_len, Hkv, hd), L.cdt(cfg)),
        "v": jnp.zeros((batch, cfg.memory_len, Hkv, hd), L.cdt(cfg)),
    }


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, length: int):
    if kind in ("attn", "moe", "moe_dense", "attn_only"):
        return L.init_kv_cache(cfg, batch, length)
    if kind == "dec_block":
        return {"self": L.init_kv_cache(cfg, batch, length),
                "cross": init_cross_cache(cfg, batch)}
    if kind == "local_attn":
        return L.init_kv_cache(cfg, batch, min(length, cfg.window or length))
    if kind == "rglru":
        return L.init_rglru_cache(cfg, batch)
    if kind == "ssd":
        return L.init_ssd_cache(cfg, batch)
    if kind == "xattn":
        return init_cross_cache(cfg, batch)  # cross K/V projected at prefill
    return {}


@dataclasses.dataclass(frozen=True)
class StackLayout:
    pattern: tuple[str, ...]
    full_reps: int
    remainder: tuple[str, ...]

    @staticmethod
    def of(n_layers: int, pattern: tuple[str, ...]) -> "StackLayout":
        plen = len(pattern)
        return StackLayout(pattern, n_layers // plen,
                           tuple(pattern[: n_layers % plen]))


def init_stack(key, cfg: ModelConfig, n_layers: int, pattern: tuple[str, ...]):
    lay = StackLayout.of(n_layers, pattern)
    ks = iter(jax.random.split(key, n_layers + 1))
    groups = []
    for kind in lay.pattern:
        stacked = [init_block(next(ks), cfg, kind) for _ in range(lay.full_reps)]
        groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
                      if lay.full_reps else None)
    rem = [init_block(next(ks), cfg, kind) for kind in lay.remainder]
    return {"groups": groups, "rem": rem}


def init_stack_cache(cfg: ModelConfig, n_layers: int, pattern, batch, length):
    lay = StackLayout.of(n_layers, pattern)
    groups = []
    for kind in lay.pattern:
        cs = [init_block_cache(cfg, kind, batch, length)
              for _ in range(lay.full_reps)]
        groups.append(jax.tree.map(lambda *xs: jnp.stack(xs), *cs)
                      if lay.full_reps else None)
    rem = [init_block_cache(cfg, kind, batch, length) for kind in lay.remainder]
    return {"groups": groups, "rem": rem}


def apply_stack(p, cfg: ModelConfig, n_layers: int, pattern, x, ctx: Ctx, cache):
    """Scan over pattern repetitions; unrolled remainder.  Returns
    (x, new_cache, stats) with the blocks' stats added up."""
    lay = StackLayout.of(n_layers, pattern)
    stats = L.no_stats()

    if lay.full_reps:
        # NOTE(§Perf, refuted hypothesis): nesting a per-block jax.checkpoint
        # inside the group body did NOT reduce peak memory (79 -> 77.9 GB on
        # llama-90b/train_4k) and cost +15% recompute FLOPs — the peak is held
        # by matmul-dtype-legalization copies, not multi-block liveness.
        def body(carry, xs):
            h, acc = carry
            params_t, cache_t = xs
            new_caches = []
            for i, kind in enumerate(lay.pattern):
                h, c, st = apply_block(params_t[i], cfg, kind, h, ctx,
                                       cache_t[i] if cache is not None else None)
                acc = L.add_stats(acc, st)
                new_caches.append(c if c is not None else {})
            return (h, acc), tuple(new_caches)

        if cfg.remat and ctx.mode == "train":
            body = jax.checkpoint(body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        cache_groups = (tuple(cache["groups"]) if cache is not None
                        else tuple({} for _ in lay.pattern))
        (x, stats), new_groups = jax.lax.scan(
            body, (x, stats), (tuple(p["groups"]), cache_groups))
        new_groups = list(new_groups)
    else:
        new_groups = []

    new_rem = []
    for i, kind in enumerate(lay.remainder):
        x, c, st = apply_block(p["rem"][i], cfg, kind, x, ctx,
                               cache["rem"][i] if cache is not None else None)
        stats = L.add_stats(stats, st)
        new_rem.append(c if c is not None else {})
    new_cache = ({"groups": new_groups, "rem": new_rem}
                 if cache is not None else None)
    return x, new_cache, stats


# ---------------------------------------------------------------- full model --
def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    dt = L.pdt(cfg)
    V, D = cfg.vocab_size, cfg.d_model
    params = {
        "embed": (jax.random.normal(ks[0], (V, D)) * 0.02).astype(dt),
        "final_norm": jnp.zeros((D,), dt),
        "stack": init_stack(ks[1], cfg, cfg.n_layers, cfg.pattern),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(ks[2], (D, V)) / jnp.sqrt(D)).astype(dt)
    if cfg.enc_layers:
        params["encoder"] = init_stack(ks[3], cfg, cfg.enc_layers, ("attn",))
        params["enc_norm"] = jnp.zeros((D,), dt)
    return params


def embed_tokens(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(L.cdt(cfg))
    if cfg.embed_scale:
        x = x * jnp.sqrt(jnp.asarray(cfg.d_model, L.cdt(cfg)))
    return x


def head_matrix(params, cfg: ModelConfig):
    return (params["embed"].T if cfg.tie_embeddings else params["head"])


def encode_memory(params, cfg: ModelConfig, memory):
    """Whisper-style encoder over stub frame embeddings (non-causal attn)."""
    if not cfg.enc_layers:
        return memory.astype(L.cdt(cfg))  # vision stub: patch embeddings direct
    B, M, _ = memory.shape
    pos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (B, M))
    ctx = Ctx(mode="train", positions=pos, causal=False)
    x = memory.astype(L.cdt(cfg))
    x, _, _ = apply_stack(params["encoder"], cfg, cfg.enc_layers, ("attn",),
                          x, ctx, None)
    return L.rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, tokens, ctx: Ctx, cache=None,
            memory=None):
    """tokens (B, S) -> (hidden (B, S, D), new_cache, stats)."""
    if memory is not None:
        ctx = dataclasses.replace(ctx, memory=encode_memory(params, cfg, memory))
    x = embed_tokens(params, cfg, tokens)
    x = constrain(x, ("batch", "seq", None))
    x, new_cache, stats = apply_stack(params["stack"], cfg, cfg.n_layers,
                                      cfg.pattern, x, ctx, cache)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, new_cache, stats


def init_cache(cfg: ModelConfig, batch: int, length: int):
    return init_stack_cache(cfg, cfg.n_layers, cfg.pattern, batch, length)


def logits_last(params, cfg: ModelConfig, hidden):
    """Final-position logits (serving)."""
    W = head_matrix(params, cfg).astype(L.cdt(cfg))
    logits = hidden[:, -1:] @ W
    if cfg.final_softcap:
        logits = L.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits.astype(jnp.float32)

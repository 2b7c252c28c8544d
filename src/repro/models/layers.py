"""Pure-JAX building blocks for every assigned architecture.

Conventions:
  * params are plain dict pytrees; stored in cfg.param_dtype, cast to
    cfg.compute_dtype at use.
  * activations x: (B, S, D); positions: (B, S) int32.
  * attention is *blocked* over query chunks (lax.scan) so compiled memory stays
    bounded at 32k+ sequence lengths — this pure-jnp path is also the oracle for
    the Pallas flash-attention kernel.
  * every block returns (y, new_cache); cache=None outside decode/prefill.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops as ssd_ops
from ..kernels.ssd import ssd_tiles
from .sharding import active_rules, constrain, constrain_first

Params = dict
Cache = Any

# Attention-internal sharding (whole-spec fallbacks, consistent across the
# score chain so no dot forces a gather):
#   plan A (heads divide TP):  q/k/v/o head-sharded, scores head-sharded;
#   plan B (e.g. 40 heads x 16 TP): q/scores/o sharded on the query-chunk dim,
#   k/v replicated (batch-sharded only) — both dots stay local.
_KV_SPECS = [("batch", None, "tp", None), ("batch", None, None, None)]
_Q5_SPECS = [("batch", None, None, "tp", None),  # (B, nc, qc, H, hd): heads
             ("batch", None, "tp", None, None)]  # qc
_SCORE_SPECS = [("batch", "tp", None, None),  # (B, H, qc, S): heads
                ("batch", None, "tp", None)]  # qc
_O_SPECS = [("batch", None, "tp", None),  # (B, qc, H, hd): heads
            ("batch", "tp", None, None)]  # qc


@partial(jax.tree_util.register_dataclass,
         data_fields=("positions", "memory"),
         meta_fields=("mode", "cache_len", "causal"))
@dataclasses.dataclass
class Ctx:
    """Per-call context threaded through blocks (a pytree: arrays are leaves,
    mode flags are static metadata — so Ctx can cross jit/checkpoint/shard_map
    boundaries)."""

    mode: str  # "train" | "prefill" | "decode"
    positions: jnp.ndarray  # (B, S) int32 absolute positions
    memory: jnp.ndarray | None = None  # (B, M, D) modality / encoder memory
    cache_len: int = 0  # allocated cache length (decode)
    causal: bool = True

    @property
    def decoding(self) -> bool:
        return self.mode == "decode"


def cdt(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdt(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta):
    """x: (B, S, H, hd); positions: (B, S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


# =============================================================== attention ====
def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    D, hd = cfg.d_model, cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, max(1, cfg.n_kv_heads)
    ks = jax.random.split(key, 8)
    dt = pdt(cfg)
    p = {
        "wq": _dense_init(ks[0], (D, Hq * hd), dt),
        "wk": _dense_init(ks[1], (D, Hkv * hd), dt),
        "wv": _dense_init(ks[2], (D, Hkv * hd), dt),
        "wo": _dense_init(ks[3], (Hq * hd, D), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * hd,), dt)
        p["bk"] = jnp.zeros((Hkv * hd,), dt)
        p["bv"] = jnp.zeros((Hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    if cross:
        p["xgate"] = jnp.zeros((), dt)  # llama-vision gated cross-attention
    return p


def _project_qkv(p, cfg: ModelConfig, xq, xkv, q_positions, kv_positions,
                 apply_rope: bool = True):
    B, Sq, D = xq.shape
    hd = cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, max(1, cfg.n_kv_heads)
    dt = cdt(cfg)

    def proj(x, w, b_name, H):
        y = x @ p[w].astype(dt)
        if b_name in p:
            y = y + p[b_name].astype(dt)
        return y.reshape(x.shape[0], x.shape[1], H, hd)

    q = proj(xq, "wq", "bq", Hq)
    k = proj(xkv, "wk", "bk", Hkv)
    v = proj(xkv, "wv", "bv", Hkv)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if apply_rope:
        q = rope(q, q_positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)
    return q, k, v


def _expand_gqa(k, Hq):
    """Repeat kv heads to Hq so the head dim shards over TP even when
    Hkv < |tp| (the repeated tensor is head-sharded; replicating small-Hkv
    tensors instead blocks GSPMD and replicates the O(S^2) scores — §Perf)."""
    Hkv = k.shape[2]
    if Hkv == Hq:
        return k
    return jnp.repeat(k, Hq // Hkv, axis=2)


def _padded_heads(Hq: int, batch: int) -> int:
    """Pad the head count to the TP multiple when heads WILL be TP-sharded: 56
    arctic heads over 16 TP ranks otherwise fall back to REPLICATED k/v and
    scores (~16x attention memory; +14% padded-head FLOPs is the cheap side of
    that trade — §Perf hillclimb #2).  Whether heads shard depends on whether
    the batch consumed the TP axis for THIS tensor (fsdp strategy at full
    batch: yes; prefill/decode prefix-fallback batches: no) — so the decision
    resolves the actual spec instead of inspecting the rules statically."""
    rules = active_rules()
    if rules is None:
        return Hq
    tp = rules.axes_size(rules.tp)
    Hp = -(-Hq // tp) * tp
    spec = rules.resolve(("batch", None, "tp", None), (batch, 1, Hp, 1))
    return Hp if spec[2] is not None else Hq


def _pad_heads(x, Hp: int):
    H = x.shape[2]
    if H == Hp:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))


def blocked_attention(cfg: ModelConfig, q, k, v, q_positions, kv_positions,
                      causal=True, window=None):
    """Memory-bounded attention: scan over query chunks, full K/V per chunk.

    q: (B, Sq, Hq, hd); k, v: (B, Sk, Hkv, hd).  GQA via kv-head repetition
    (head-sharded over TP).  Masking: causal (q_pos >= kv_pos), optional
    sliding window, and kv padding (kv_positions < 0 marks unwritten slots).
    """
    B, Sq, Hq, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    qc = min(cfg.q_chunk, Sq)
    n_chunks = -(-Sq // qc)
    pad = n_chunks * qc - Sq
    if pad:  # ragged tail: pad queries (their pos=-1 rows are discarded below)
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad)), constant_values=-1)
    Hp = _padded_heads(Hq, B)
    k = constrain_first(_pad_heads(_expand_gqa(k, Hq), Hp), _KV_SPECS)
    v = constrain_first(_pad_heads(_expand_gqa(v, Hq), Hp), _KV_SPECS)
    qs = constrain_first(
        _pad_heads(q, Hp).reshape(B, n_chunks, qc, Hp, hd), _Q5_SPECS)
    qpos = q_positions.reshape(B, n_chunks, qc)
    kv_valid = kv_positions >= 0  # (B, Sk)

    def one_chunk(carry, inp):
        qi, qp = inp  # (B, qc, Hq, hd), (B, qc)
        s = jnp.einsum("bqhe,bshe->bhqs", qi, k,
                       preferred_element_type=jnp.float32) * scale
        s = constrain_first(s, _SCORE_SPECS)
        if cfg.attn_softcap:
            s = softcap(s, cfg.attn_softcap)
        mask = kv_valid[:, None, None, :]
        if causal:
            mask = mask & (qp[:, None, :, None]
                           >= kv_positions[:, None, None, :])
        if window is not None:
            mask = mask & (qp[:, None, :, None]
                           - kv_positions[:, None, None, :] < window)
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqs,bshe->bqhe", w, v)
        return carry, constrain_first(o, _O_SPECS)

    # checkpoint per chunk: otherwise the scan's backward linearization stacks
    # every chunk's (qc, Skv) score tile — an O(S^2) HBM buffer per layer that
    # dominated the memory roofline term (§Perf, hillclimb #1)
    one_chunk = jax.checkpoint(one_chunk,
                               policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = jax.lax.scan(one_chunk, None,
                           (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(qpos, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1)[:, :, :, :Hq]  # drop padded heads
    out = out.reshape(B, n_chunks * qc, Hq, hd)
    return out[:, :Sq]


def _decode_attention(cfg, q, k, v, q_positions, kv_positions, window=None):
    """Single-token decode: q (B, 1, Hq, hd) against the full cache.

    Decode keeps the GROUPED (Hkv, G) formulation: repeating KV heads here
    amplifies the step's dominant cost — streaming the KV cache from HBM — by
    Hq/Hkv (measured 0.1-0.5x regressions on the decode_32k cells when the
    train-path expansion was reused; §Perf)."""
    B, _, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qi = q.reshape(B, 1, Hkv, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qi, k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    if cfg.attn_softcap:
        s = softcap(s, cfg.attn_softcap)
    mask = (kv_positions >= 0) & (kv_positions <= q_positions[:, :1])
    if window is not None:
        mask = mask & (q_positions[:, :1] - kv_positions < window)
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v).reshape(B, 1, Hq, hd)


def init_kv_cache(cfg: ModelConfig, batch: int, length: int, dtype=None):
    hd, Hkv = cfg.resolved_head_dim, max(1, cfg.n_kv_heads)
    dtype = dtype or cdt(cfg)
    return {
        "k": jnp.zeros((batch, length, Hkv, hd), dtype),
        "v": jnp.zeros((batch, length, Hkv, hd), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),  # -1 = unwritten
    }


def _cache_write(cache, k_new, v_new, positions, ring_window=None):
    """Write new K/V at ring-buffer slots (position mod cache length)."""
    length = cache["k"].shape[1]
    slots = positions % length  # (B, S)
    bidx = jnp.arange(k_new.shape[0])[:, None]
    k = cache["k"].at[bidx, slots].set(k_new.astype(cache["k"].dtype))
    v = cache["v"].at[bidx, slots].set(v_new.astype(cache["v"].dtype))
    pos = cache["pos"].at[bidx, slots].set(positions)
    return {"k": k, "v": v, "pos": pos}


def attention_block(p, cfg: ModelConfig, x, ctx: Ctx, cache,
                    window=None, cross=False):
    """Self- or cross-attention sublayer (no residual/norm — caller wraps)."""
    if cross:
        dt = cdt(cfg)
        hd, Hq = cfg.resolved_head_dim, cfg.n_heads
        q = (x @ p["wq"].astype(dt))
        if "bq" in p:
            q = q + p["bq"].astype(dt)
        q = q.reshape(x.shape[0], x.shape[1], Hq, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        if cache is not None and ctx.decoding:
            # cross K/V were projected once at prefill; recomputing them per
            # decode step cost ~100x the decoder's own FLOPs (§Perf)
            k, v = cache["k"], cache["v"]
            new_cache = cache
        else:
            mem = ctx.memory
            Hkv = max(1, cfg.n_kv_heads)
            k = (mem @ p["wk"].astype(dt)).reshape(mem.shape[0], -1, Hkv, hd)
            v = (mem @ p["wv"].astype(dt)).reshape(mem.shape[0], -1, Hkv, hd)
            if "bk" in p:
                k = k + p["bk"].astype(dt).reshape(Hkv, hd)
                v = v + p["bv"].astype(dt).reshape(Hkv, hd)
            if cfg.qk_norm:
                k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
            new_cache = ({"k": k.astype(dt), "v": v.astype(dt)}
                         if cache is not None else cache)
        M = k.shape[1]
        mpos = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (k.shape[0], M))
        out = blocked_attention(cfg, q, k, v, ctx.positions, mpos, causal=False)
    elif cache is not None:
        q, k_new, v_new = _project_qkv(p, cfg, x, x, ctx.positions, ctx.positions)
        if ctx.decoding:
            new_cache = _cache_write(cache, k_new, v_new, ctx.positions)
            out = _decode_attention(cfg, q, new_cache["k"], new_cache["v"],
                                    ctx.positions, new_cache["pos"], window)
        else:
            # prefill (from an empty cache): attend over this call's K/V
            # directly; persist only the last `length` tokens (ring buffers
            # would otherwise see unordered duplicate-slot writes).
            W = cache["k"].shape[1]
            S = k_new.shape[1]
            tail = min(W, S)
            new_cache = _cache_write(cache, k_new[:, -tail:], v_new[:, -tail:],
                                     ctx.positions[:, -tail:])
            out = blocked_attention(cfg, q, k_new, v_new,
                                    ctx.positions, ctx.positions, True, window)
    else:  # training: no cache
        q, k, v = _project_qkv(p, cfg, x, x, ctx.positions, ctx.positions)
        out = blocked_attention(cfg, q, k, v, ctx.positions, ctx.positions,
                                ctx.causal, window)
        new_cache = None
    B, S = x.shape[:2]
    out = out.reshape(B, S, -1) @ p["wo"].astype(cdt(cfg))
    if cross and "xgate" in p:
        out = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(out.dtype) * out
    return out, new_cache


# ====================================================================== MLP ====
def init_mlp(key, cfg: ModelConfig, d_ff=None) -> Params:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    dt = pdt(cfg)
    ks = jax.random.split(key, 3)
    if cfg.mlp_variant in ("swiglu", "geglu"):
        return {
            "w_gate": _dense_init(ks[0], (D, F), dt),
            "w_up": _dense_init(ks[1], (D, F), dt),
            "w_down": _dense_init(ks[2], (F, D), dt),
        }
    return {  # plain gelu MLP (starcoder2 / whisper)
        "w_up": _dense_init(ks[0], (D, F), dt),
        "b_up": jnp.zeros((F,), dt),
        "w_down": _dense_init(ks[1], (F, D), dt),
        "b_down": jnp.zeros((D,), dt),
    }


def mlp(p, cfg: ModelConfig, x):
    dt = cdt(cfg)
    if cfg.mlp_variant in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_variant == "swiglu" else partial(
            jax.nn.gelu, approximate=True)
        h = act(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
        return h @ p["w_down"].astype(dt)
    h = jax.nn.gelu(x @ p["w_up"].astype(dt) + p["b_up"].astype(dt),
                    approximate=True)
    return h @ p["w_down"].astype(dt) + p["b_down"].astype(dt)


# ====================================================================== MoE ====
def init_moe(key, cfg: ModelConfig) -> Params:
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    dt = pdt(cfg)
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (D, E), jnp.float32),  # router in fp32
        "w_gate": _dense_init(ks[1], (E, D, F), dt),
        "w_up": _dense_init(ks[2], (E, D, F), dt),
        "w_down": _dense_init(ks[3], (E, F, D), dt),
    }


MOE_GROUP = 1024  # tokens per dispatch group (bounds the one-hot dispatch tensor)


def moe_ffn(p, cfg: ModelConfig, x):
    """GShard-style capacity-based top-k dispatch (EP-shardable einsums).

    Tokens are processed in groups of MOE_GROUP so the (T, E, C) dispatch
    one-hot stays O(T^2 k / E) *per group* instead of per batch.  Load-balance
    auxiliary loss is returned via `moe_ffn.aux` on the fly (summed by caller).
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    T = B * S
    g = min(MOE_GROUP, T)
    n_groups = max(1, T // g)
    toks = x.reshape(n_groups, g, D)
    C = max(1, int(g * k / E * cfg.capacity_factor))

    logits = jnp.einsum("gtd,de->gte", toks.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)  # (G, g, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    # position-in-expert via cumulative counts across the k slots
    mask = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # (G, g, k, E)
    pos_in_slot = jnp.cumsum(mask, axis=1) - mask  # tokens before me, same slot
    offset = jnp.cumsum(mask.sum(axis=1, keepdims=True), axis=2) - mask.sum(
        axis=1, keepdims=True)  # earlier slots' totals
    pos = pos_in_slot + offset  # (G, g, k, E)
    keep = (pos < C) & (mask > 0)
    # dispatch/combine tensors (G, g, E, C); accumulate per slot so the
    # (g, k, E, C) intermediate is never materialized
    disp = jnp.zeros((n_groups, g, E, C), cdt(cfg))
    comb = jnp.zeros((n_groups, g, E, C), jnp.float32)
    for j in range(k):
        oh = jax.nn.one_hot(pos[:, :, j], C, dtype=jnp.float32)  # (G, g, E, C)
        oh = oh * keep[:, :, j, :, None]
        disp = disp + oh.astype(cdt(cfg))
        comb = comb + oh * gate_vals[:, :, j][:, :, None, None]
    # EP layout: token groups on the DP axes, experts on 'model'; the
    # dispatch/combine einsums become the all-to-alls of expert parallelism
    disp = constrain(disp, ("batch", None, "expert", None))
    comb = constrain(comb, ("batch", None, "expert", None))
    expert_in = constrain(jnp.einsum("gtec,gtd->gecd", disp, toks),
                          ("batch", "expert", None, None))
    act = jax.nn.silu if cfg.mlp_variant != "gelu" else jax.nn.gelu
    dt = cdt(cfg)
    h = act(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"].astype(dt))
    h = constrain(h, ("batch", "expert", None, None))
    expert_out = constrain(jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(dt)),
                           ("batch", "expert", None, None))
    out = jnp.einsum("gtec,gecd->gtd", comb.astype(dt), expert_out)

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    f = mask[:, :, 0, :].astype(jnp.float32).mean(axis=1)  # top-1 routing frac
    P = probs.mean(axis=1)
    aux = E * jnp.mean(jnp.sum(f * P, axis=-1))
    return out.reshape(B, S, D), aux


# =================================================================== RG-LRU ====
def init_rglru(key, cfg: ModelConfig) -> Params:
    D = cfg.d_model
    W = cfg.rnn_width or D
    dt = pdt(cfg)
    ks = jax.random.split(key, 6)
    return {
        "w_x": _dense_init(ks[0], (D, W), dt),  # recurrent branch input
        "w_gate_branch": _dense_init(ks[1], (D, W), dt),  # gelu gate branch
        "conv_w": _dense_init(ks[2], (cfg.conv_width, W), dt, scale=0.3),
        "w_input_gate": _dense_init(ks[3], (W, W), dt),
        "w_rec_gate": _dense_init(ks[4], (W, W), dt),
        "lam": jnp.linspace(0.9, 0.999, W).astype(jnp.float32),  # Lambda init
        "w_out": _dense_init(ks[5], (W, D), dt),
    }


def _causal_depthwise_conv(x, w, state=None):
    """x: (B, S, W) causal depthwise conv, kernel (cw, W).

    state: (B, cw-1, W) trailing inputs from the previous call (decode).
    Returns (y, new_state)."""
    cw = w.shape[0]
    hist = state if state is not None else jnp.zeros(
        (x.shape[0], cw - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([hist, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(cw))
    return y, xp[:, -(cw - 1):]


def rglru_scan(a, bx):
    """h_t = a_t * h_{t-1} + bx_t via associative scan over S."""

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    aa, bb = jax.lax.associative_scan(combine, (a, bx), axis=1)
    return bb


def rglru_block(p, cfg: ModelConfig, x, ctx: Ctx, cache):
    """Griffin recurrent block: (conv -> RG-LRU) ⊙ gelu-gate -> out proj."""
    dt = cdt(cfg)
    B, S, _ = x.shape
    u = x @ p["w_x"].astype(dt)  # (B, S, W)
    gate_branch = jax.nn.gelu(x @ p["w_gate_branch"].astype(dt))
    conv_state = cache.get("conv") if cache else None
    u, new_conv = _causal_depthwise_conv(u, p["conv_w"].astype(dt), conv_state)

    i_gate = jax.nn.sigmoid(u @ p["w_input_gate"].astype(dt)).astype(jnp.float32)
    r_gate = jax.nn.sigmoid(u @ p["w_rec_gate"].astype(dt)).astype(jnp.float32)
    log_a = -8.0 * r_gate * jax.nn.softplus(p["lam"])  # RG-LRU gated decay
    a = jnp.exp(log_a)
    bx = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (
        i_gate * u.astype(jnp.float32))
    if ctx.decoding and cache is not None:
        h_prev = cache["h"]  # (B, 1, W) fp32
        h = a * h_prev + bx
        y32 = h
        new_cache = {"h": h, "conv": new_conv}
    else:
        if cache is not None and "h" in cache:  # prefill continuing from state
            bx = bx.at[:, 0].add(a[:, 0] * cache["h"][:, 0])
        y32 = rglru_scan(a, bx)
        new_cache = ({"h": y32[:, -1:], "conv": new_conv}
                     if cache is not None else None)
    y = (y32.astype(dt) * gate_branch) @ p["w_out"].astype(dt)
    return y, new_cache


def init_rglru_cache(cfg: ModelConfig, batch: int):
    W = cfg.rnn_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, 1, W), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, W), cdt(cfg)),
    }


# ================================================================ Mamba-2 SSD ==
def init_ssd(key, cfg: ModelConfig) -> Params:
    D = cfg.d_model
    Di = cfg.ssm_expand * D
    H = Di // cfg.ssm_head_dim
    N = cfg.ssm_state
    dt = pdt(cfg)
    ks = jax.random.split(key, 4)
    conv_dim = Di + 2 * N
    return {
        # projects to [z (Di), x (Di), B (N), C (N), dt (H)]
        "w_in": _dense_init(ks[0], (D, 2 * Di + 2 * N + H), dt),
        "conv_w": _dense_init(ks[1], (cfg.conv_width, conv_dim), dt, scale=0.3),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.zeros((Di,), dt),
        "w_out": _dense_init(ks[2], (Di, D), dt),
    }


def _ssd_chunked(xh, dtv, A, Bm, Cm, h0=None, chunk=256):
    """Chunked SSD scan (Mamba-2 state-space duality, arXiv:2405.21060 Alg. 1).

    xh: (B, S, H, P); dtv: (B, S, H) softplus'd; A: (H,) negative log-decay
    rates (-exp(A_log)); Bm, Cm: (B, S, N).  Returns (y (B,S,H,P), h_last
    (B,H,P,N)).  fp32 math.
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    assert nc * Q == S, "sequence must be divisible by ssm_chunk"
    xc = xh.reshape(Bsz, nc, Q, H, P)
    dtc = dtv.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    dA = dtc * A[None, None, None, :]  # (B, nc, Q, H): -log decay per step
    cum = jnp.cumsum(dA, axis=2)  # within-chunk cumulative
    # intra-chunk (diagonal blocks): causal "attention" with decay weights.
    # Mask the EXPONENT, not the exp: non-causal entries have positive
    # cum_q - cum_k that overflows exp in fp32, and 0 * d(inf) = NaN in the
    # backward pass (exposed by pipeline bubble ticks).
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)  # shared across heads
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    delta = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,K,H)
    decay = jnp.exp(jnp.where(causal, delta, -jnp.inf))
    w = scores[..., None] * decay
    w = w * dtc[:, :, None, :, :]  # dt_k factor (B,nc,Q,K,H)
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", w, xc)

    # chunk states: h_c = sum_k exp(cum_end - cum_k) dt_k B_k x_k
    end_decay = jnp.exp(cum[:, :, -1:, :] - cum)  # (B, nc, Q, H)
    state_w = end_decay * dtc  # (B, nc, Q, H)
    chunk_states = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", state_w, Bc, xc)

    # inter-chunk scan over nc
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B, nc, H) total decay of chunk
    h_init = (h0 if h0 is not None
              else jnp.zeros((Bsz, H, P, N), jnp.float32))

    def step(h, inp):
        cs, cd = inp  # (B,H,P,N), (B,H)
        h_new = h * cd[:, :, None, None] + cs
        return h_new, h

    (h_last, h_prevs) = jax.lax.scan(
        step, h_init, (jnp.moveaxis(chunk_states, 1, 0),
                       jnp.moveaxis(chunk_decay, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (B, nc, H, P, N): state BEFORE chunk
    in_decay = jnp.exp(cum)  # decay from chunk start to position
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, in_decay, h_prevs)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, h_last


def _ssd_scan(x, dtv, A, Bm, Cm, D, h0, chunk):
    """The chunked SSD scan of ``x`` (B, S, H*P) and its skip: ``y = SSD(x)
    + D x``, D: (H,); other arguments as :func:`_ssd_chunked` takes them.
    Lowered for a TPU, where the shapes tile, it runs the Pallas kernel
    pair (``kernels/ssd.py``); everywhere else it runs ``_ssd_chunked``, the
    kernels' oracle.  Returns (y (B, S, H*P), h_last (B, H, P, N)),
    float32."""
    Bsz, S, HP = x.shape
    H, N = dtv.shape[-1], Bm.shape[-1]
    P = HP // H

    def oracle(x, dtv, A, Bm, Cm, D, h0):
        x = x.astype(jnp.float32)
        y, h_last = _ssd_chunked(x.reshape(Bsz, S, H, P), dtv, A, Bm, Cm, h0,
                                 chunk)
        return y.reshape(Bsz, S, HP) + jnp.repeat(D, P) * x, h_last

    if not ssd_tiles(min(chunk, S), H, P, N):
        return oracle(x, dtv, A, Bm, Cm, D, h0)
    return jax.lax.platform_dependent(
        x, dtv, A, Bm, Cm, D, h0, tpu=partial(ssd_ops.ssd_scan, chunk=chunk),
        default=oracle)


def ssd_block(p, cfg: ModelConfig, x, ctx: Ctx, cache):
    dt_ = cdt(cfg)
    B, S, D = x.shape
    Di = cfg.ssm_expand * D
    H = Di // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    with jax.named_scope("ssd_in_proj"):
        zxbcdt = x @ p["w_in"].astype(dt_)
        z, xs, Bm, Cm, dtv = jnp.split(
            zxbcdt, [Di, 2 * Di, 2 * Di + N, 2 * Di + 2 * N], axis=-1)
    with jax.named_scope("ssd_conv"):
        conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
        conv_state = cache.get("conv") if cache else None
        conv_out, new_conv = _causal_depthwise_conv(
            conv_in, p["conv_w"].astype(dt_), conv_state)
        conv_out = jax.nn.silu(conv_out)
        xs, Bm, Cm = jnp.split(conv_out, [Di, Di + N], axis=-1)
    with jax.named_scope("ssd_scan"):
        dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
        A = jnp.exp(p["A_log"])  # (H,) positive rates
        Bm32, Cm32 = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
        if ctx.decoding and cache is not None:
            xh = xs.reshape(B, S, H, P).astype(jnp.float32)
            h0 = cache["h"]  # (B, H, P, N)
            dA = jnp.exp(-dtv[:, 0] * A[None, :])  # (B, H)
            dBx = jnp.einsum("bh,bn,bhp->bhpn", dtv[:, 0], Bm32[:, 0],
                             xh[:, 0])
            h = h0 * dA[:, :, None, None] + dBx
            y = jnp.einsum("bn,bhpn->bhp", Cm32[:, 0], h)[:, None]
            y = (y + p["D_skip"][None, None, :, None] * xh).reshape(B, S, Di)
            new_cache = {"h": h, "conv": new_conv}
        else:
            h0 = cache["h"] if (cache is not None and "h" in cache) else None
            # the scan takes the negative log-decay rates -A
            y, h_last = _ssd_scan(xs, dtv, -A, Bm32, Cm32, p["D_skip"], h0,
                                  cfg.ssm_chunk)
            new_cache = ({"h": h_last, "conv": new_conv} if cache is not None
                         else None)
    with jax.named_scope("ssd_gate_norm"):
        y = rmsnorm(y.astype(dt_) * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    with jax.named_scope("ssd_out_proj"):
        return y @ p["w_out"].astype(dt_), new_cache


def init_ssd_cache(cfg: ModelConfig, batch: int):
    Di = cfg.ssm_expand * cfg.d_model
    H = Di // cfg.ssm_head_dim
    conv_dim = Di + 2 * cfg.ssm_state
    return {
        "h": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim), cdt(cfg)),
    }

"""Pure-JAX building blocks for every assigned architecture.

Conventions:
  * params are plain dict pytrees; stored in cfg.param_dtype, cast to
    cfg.compute_dtype at use.
  * activations x: (B, S, D); positions: (B, S) int32.
  * attention is *blocked* over query chunks (lax.scan) so compiled memory stays
    bounded at 32k+ sequence lengths — this pure-jnp path is also the oracle for
    the Pallas flash-attention kernel pair (``kernels/flash_attention.py``),
    which the training path runs instead when lowered for a TPU where the
    shapes tile (``_train_attention``); the CPU, prefill and decode keep it.
  * every block returns (y, new_cache); cache=None outside decode/prefill.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..configs.base import ModelConfig
from ..kernels import ops as ssd_ops
from ..kernels.flash_attention import flash_attention, flash_tiles
from ..kernels.ssd import ssd_tiles
from .sharding import active_rules, constrain_first

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
    boundaries).

    Every Ctx of a call without a cache (training, the encoder, the
    simulator's stage) has positions ``arange(S)`` in each row, so a key's
    index in the sequence is its position: the flash-attention kernels of
    the training path mask by block index (``_train_attention``), which
    equals the blocked oracle's mask by position only under that rule."""

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


def rmsnorm(x, scale, eps, groups: int = 1):
    """RMS norm over the last axis, or over each of its ``groups`` equal
    slices (Mamba-2's grouped gated norm), scaled by ``1 + scale``."""
    x32 = x.astype(jnp.float32)
    if groups > 1:
        x32 = x32.reshape(x.shape[:-1] + (groups, -1))
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = (x32 * jax.lax.rsqrt(var + eps)).reshape(x.shape)
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
    if apply_rope and cfg.use_rope:
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


# query and key blocks of the flash-attention kernels on the training path
FLASH_BLOCKS = (512, 512)


def _train_attention(cfg: ModelConfig, q, k, v, positions, causal, window):
    """Self-attention of a call without a cache.  Lowered for a TPU, where
    the shapes tile and no mesh rules are active (a Mosaic kernel is not
    partitioned by GSPMD, so heads sharded over TP keep the blocked path),
    it runs the flash-attention kernel pair, which masks by index: the
    positions are ``arange(S)`` (``Ctx``).  Everywhere else, and as its
    oracle, ``blocked_attention``."""
    def oracle(q, k, v, positions):
        return blocked_attention(cfg, q, k, v, positions, positions, causal,
                                 window)

    S, hd = q.shape[1], q.shape[3]
    if active_rules() is not None or not flash_tiles(S, S, hd, *FLASH_BLOCKS):
        return oracle(q, k, v, positions)

    def tpu(q, k, v, positions):
        return flash_attention(
            q, k, v, causal=causal, window=window,
            softcap=cfg.attn_softcap or None, block_q=FLASH_BLOCKS[0],
            block_k=FLASH_BLOCKS[1], interpret=False)

    return jax.lax.platform_dependent(q, k, v, positions, tpu=tpu,
                                      default=oracle)


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
        out = _train_attention(cfg, q, k, v, ctx.positions, ctx.causal,
                               window)
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
    if cfg.mlp_variant == "relu2":  # Nemotron-H: no gate, no bias
        return {"w_up": _dense_init(ks[0], (D, F), dt),
                "w_down": _dense_init(ks[1], (F, D), dt)}
    return {  # plain gelu MLP (starcoder2 / whisper)
        "w_up": _dense_init(ks[0], (D, F), dt),
        "b_up": jnp.zeros((F,), dt),
        "w_down": _dense_init(ks[1], (F, D), dt),
        "b_down": jnp.zeros((D,), dt),
    }


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mlp(p, cfg: ModelConfig, x):
    dt = cdt(cfg)
    if cfg.mlp_variant in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_variant == "swiglu" else partial(
            jax.nn.gelu, approximate=True)
        h = act(x @ p["w_gate"].astype(dt)) * (x @ p["w_up"].astype(dt))
        return h @ p["w_down"].astype(dt)
    if cfg.mlp_variant == "relu2":
        return relu2(x @ p["w_up"].astype(dt)) @ p["w_down"].astype(dt)
    h = jax.nn.gelu(x @ p["w_up"].astype(dt) + p["b_up"].astype(dt),
                    approximate=True)
    return h @ p["w_down"].astype(dt) + p["b_down"].astype(dt)


# ====================================================================== MoE ====
def init_moe(key, cfg: ModelConfig) -> Params:
    """Router over all ``n_experts``; expert weights of the ``n_experts_held``
    experts this device holds; the shared expert, where the config has one."""
    D, F, E, Eh = cfg.d_model, cfg.moe_d_ff, cfg.n_experts, cfg.n_experts_held
    dt = pdt(cfg)
    ks = jax.random.split(key, 5)
    p = {"router": _dense_init(ks[0], (D, E), jnp.float32)}  # router in fp32
    if cfg.moe_router == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if _gated(cfg):
        p["w_gate"] = _dense_init(ks[1], (Eh, D, F), dt)
    p["w_up"] = _dense_init(ks[2], (Eh, D, F), dt)
    p["w_down"] = _dense_init(ks[3], (Eh, F, D), dt)
    if cfg.moe_shared_d_ff:
        p["shared"] = init_mlp(ks[4], cfg, cfg.moe_shared_d_ff)
    return p


def _gated(cfg: ModelConfig) -> bool:
    return cfg.mlp_variant != "relu2"


def route(p, cfg: ModelConfig, x):
    """The router on tokens ``x`` (T, D): top-k expert ids (T, k) and their
    weights (T, k), float32.  ``softmax``: the top-k of the softmax,
    renormalised.  ``sigmoid`` (DeepSeek-V3 / Nemotron-H): sigmoid scores;
    the top-k of the scores plus the selection-only ``router_bias``; the
    chosen scores renormalised.  Both are scaled by ``moe_routed_scale``.
    Logits and selection run in float32 at full matmul precision."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + jax.lax.stop_gradient(p["router_bias"])
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        choice = scores
    _, idx = jax.lax.top_k(choice, cfg.moe_top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.moe_routed_scale, scores


# grouped-matmul tiles (rows, contraction, columns) on the TPU: 256 rows keep
# an expert's partial tiles few at a few hundred rows an expert, and 1024-wide
# blocks keep the grid a few hundred steps (~7 MB of VMEM with buffering)
GMM_TILING = (256, 1024, 1024)


def _grouped_matmul(lhs, rhs, sizes):
    """``lhs`` (m, K) rows sorted by group against ``rhs`` (g, K, N): rows of
    group i times ``rhs[i]``, for the first g of the ``sizes`` (g + 1,)
    groups; the last group's rows (and any past the sizes) give zeros and
    cost no matmul.  On a TPU the megablox grouped-matmul kernel, which
    visits only the tiles of the first g groups; elsewhere ``ragged_dot``."""
    def tpu(lhs, rhs, sizes):
        from jax.experimental.pallas.ops.tpu.megablox import ops as mblx

        return mblx.gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
                        tiling=GMM_TILING, group_offset=jnp.zeros((),
                                                                 jnp.int32))

    def default(lhs, rhs, sizes):
        return jax.lax.ragged_dot(lhs, rhs, sizes[:-1])

    return jax.lax.platform_dependent(lhs, rhs, sizes, tpu=tpu,
                                      default=default)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, order, inv, k):
    """Rows ``x[order // k]`` (T*k, D) of tokens ``x`` (T, D), one for each
    (token, slot) pair in sorted order, where ``order`` is a permutation of
    the pairs and ``inv`` its inverse; the transpose gathers each pair's
    cotangent back (``inv``, not a scatter) and sums a token's ``k`` slots.
    With ``k`` 1 it permutes rows: ``x[order]``."""
    return x[order // k]


def _dispatch_fwd(x, order, inv, k):
    return x[order // k], inv


def _dispatch_bwd(k, inv, g):
    T = inv.shape[0] // k
    return g[inv].reshape(T, k, -1).sum(axis=1), None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


def _routed(p, cfg: ModelConfig, toks, idx, w, first, Eh: int):
    """The part of tokens ``toks`` (T, D), routed to ``idx`` with weights
    ``w`` (T, k), that experts ``[first, first + Eh)`` give, their weights
    ``p`` holding those ``Eh``: (y (T, D), rows of each held expert (Eh,)).

    Every (token, slot) pair is sorted by expert, held experts first; the
    held experts' rows go through grouped matmuls (``_grouped_matmul``) and
    every one of them is computed, none dropped; the other pairs' rows cost
    no expert matmul and add nothing here."""
    T, D = toks.shape
    k = cfg.moe_top_k
    dt = cdt(cfg)
    with jax.named_scope("moe_dispatch"):
        local = idx.reshape(-1) - first
        held = (local >= 0) & (local < Eh)
        key = jnp.where(held, local, Eh).astype(jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        sizes = jnp.bincount(key, length=Eh + 1).astype(jnp.int32)
        rows = _dispatch_rows(toks.astype(dt), order, inv, k)
        pad = -(T * k) % GMM_TILING[0]   # the kernel tiles whole row blocks
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        sizes = sizes.at[Eh].add(pad)
    with jax.named_scope("moe_experts"):
        up = _grouped_matmul(rows, p["w_up"].astype(dt), sizes)
        if _gated(cfg):
            gate = _grouped_matmul(rows, p["w_gate"].astype(dt), sizes)
            act = (jax.nn.silu if cfg.mlp_variant == "swiglu"
                   else partial(jax.nn.gelu, approximate=True))
            h = act(gate) * up
        else:
            h = relu2(up)
        out = _grouped_matmul(h, p["w_down"].astype(dt), sizes)[:T * k]
    with jax.named_scope("moe_combine"):
        pairs = _dispatch_rows(out, inv, order, 1).reshape(T, k, D)
        wk = jnp.where(held.reshape(T, k), w, 0.0).astype(dt)
        y = jnp.einsum("tk,tkd->td", wk, pairs)
    return y, sizes[:Eh]


def _expert_axes(cfg: ModelConfig):
    """The active mesh rules where they shard this layer's experts: every
    expert held here (``moe_experts_held`` 0) and the ``expert`` axes, of
    more than one device, dividing ``n_experts``; else None."""
    rules = active_rules()
    if rules is None or cfg.moe_experts_held:
        return None
    n = rules.axes_size(rules.expert)
    return rules if n > 1 and cfg.n_experts % n == 0 else None


def _routed_expert_parallel(p, cfg: ModelConfig, toks, idx, w, rules):
    """``_routed`` over all experts, as expert parallelism: each device of
    the ``expert`` axes holds ``n_experts / n`` of them (the weights stay
    sharded as the parameter rules lay them out) and computes their share
    of its tokens, ``first`` = its index on those axes times its experts;
    the shares are summed over the axes.  Tokens keep their batch axes
    (those the expert axes do not take)."""
    axes = rules.expert
    Eh = cfg.n_experts // rules.axes_size(axes)
    batch = tuple(a for a in rules.batch if a not in axes)
    while batch and toks.shape[0] % rules.axes_size(batch):
        batch = batch[:-1]
    rows = PartitionSpec(batch or None)
    experts = {n: p[n] for n in ("w_gate", "w_up", "w_down") if n in p}

    def share(experts, toks, idx, w):
        first = jax.lax.axis_index(axes) * Eh
        y, sizes = _routed(experts, cfg, toks, idx, w, first, Eh)
        y = jax.lax.psum(y.astype(jnp.float32), axes)
        return y, (jax.lax.psum(sizes, batch) if batch else sizes)

    return jax.shard_map(
        share, mesh=rules.mesh,
        in_specs=(jax.tree.map(lambda _: PartitionSpec(axes), experts),
                  rows, rows, rows),
        out_specs=(rows, PartitionSpec(axes)), check_vma=False)(experts, toks, idx, w)


def moe_ffn(p, cfg: ModelConfig, x, first: int = 0):
    """Dropless expert-share MoE: routes every token over all ``n_experts``
    and computes the part of the output that the experts this device holds,
    ``[first, first + n_experts_held)``, give (``_routed``), plus the shared
    expert.  Under mesh rules whose ``expert`` axes shard a layer that holds
    every expert, the experts run expert-parallel over those axes
    (``_routed_expert_parallel``).

    Returns (y (B, S, D), stats): the Switch load-balance loss under
    ``aux`` (softmax router; the sigmoid router balances by its bias and
    reads 0), the rows the held experts computed (``moe_rows``) and the
    largest held expert's rows (``moe_max_rows``)."""
    B, S, D = x.shape
    T = B * S
    dt = cdt(cfg)
    toks = x.reshape(T, D)
    with jax.named_scope("moe_router"):
        idx, w, scores = route(p, cfg, toks)
    rules = _expert_axes(cfg)
    if rules is not None:
        y, sizes = _routed_expert_parallel(p, cfg, toks, idx, w, rules)
    else:
        y, sizes = _routed(p, cfg, toks, idx, w, first, cfg.n_experts_held)
    y = y.astype(dt)
    if "shared" in p:
        with jax.named_scope("moe_shared"):
            y = y + mlp(p["shared"], cfg, toks.astype(dt))
    stats = {"aux": jnp.zeros((), jnp.float32),
             "moe_rows": jnp.sum(sizes),
             "moe_max_rows": jnp.max(sizes)}
    if cfg.moe_router == "softmax":
        # load-balance aux loss (Switch): E * sum_e f_e * P_e
        f = jnp.mean(jax.nn.one_hot(idx[:, 0], cfg.n_experts,
                                    dtype=jnp.float32), axis=0)
        stats["aux"] = cfg.n_experts * jnp.sum(f * jnp.mean(scores, axis=0))
    return y.reshape(B, S, D).astype(x.dtype), stats


def no_stats() -> dict:
    """A block's statistics where it has none: the auxiliary loss and the
    MoE counters of ``moe_ffn``."""
    return {"aux": jnp.zeros((), jnp.float32),
            "moe_rows": jnp.zeros((), jnp.int32),
            "moe_max_rows": jnp.zeros((), jnp.int32)}


def add_stats(a: dict, b: dict) -> dict:
    """Two blocks' statistics together: sums, and maxima of ``*max*``."""
    return {k: (jnp.maximum(a[k], b[k]) if "max" in k else a[k] + b[k])
            for k in a}


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
def _ssd_dims(cfg: ModelConfig) -> tuple[int, int, int, int, int]:
    """(Di, H, P, N, G): scan channels, heads, head width, state, groups."""
    Di, P = cfg.ssm_inner, cfg.ssm_head_dim
    return Di, Di // P, P, cfg.ssm_state, cfg.ssm_groups


def init_ssd(key, cfg: ModelConfig) -> Params:
    D = cfg.d_model
    Di, H, _, N, G = _ssd_dims(cfg)
    dt = pdt(cfg)
    ks = jax.random.split(key, 4)
    conv_dim = Di + 2 * G * N
    p = {
        # projects to [z (Di), x (Di), B (G*N), C (G*N), dt (H)]
        "w_in": _dense_init(ks[0], (D, 2 * Di + 2 * G * N + H), dt),
        "conv_w": _dense_init(ks[1], (cfg.conv_width, conv_dim), dt, scale=0.3),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.zeros((Di,), dt),
        "w_out": _dense_init(ks[2], (Di, D), dt),
    }
    if cfg.conv_bias:
        p["conv_b"] = jnp.zeros((conv_dim,), dt)
    return p


def _ssd_chunked(xh, dtv, A, Bm, Cm, h0=None, chunk=256):
    """Chunked SSD scan (Mamba-2 state-space duality, arXiv:2405.21060 Alg. 1).

    xh: (B, S, H, P); dtv: (B, S, H) softplus'd; A: (H,) negative log-decay
    rates (-exp(A_log)); Bm, Cm: (B, S, N), shared by the heads, or (B, S,
    G, N), group g read by heads [g H/G, (g+1) H/G).  Returns (y (B,S,H,P),
    h_last (B,H,P,N)).  fp32 math.
    """
    Bsz, S, H, P = xh.shape
    if Bm.ndim == 3:
        Bm, Cm = Bm[:, :, None], Cm[:, :, None]
    G, N = Bm.shape[2], Bm.shape[3]
    J = H // G                      # heads of a group
    Q = min(chunk, S)
    nc = S // Q
    assert nc * Q == S, "sequence must be divisible by ssm_chunk"
    xc = xh.reshape(Bsz, nc, Q, G, J, P)
    dtc = dtv.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)

    dA = dtc * A[None, None, None, :]  # (B, nc, Q, H): -log decay per step
    cum = jnp.cumsum(dA, axis=2)  # within-chunk cumulative
    # intra-chunk (diagonal blocks): causal "attention" with decay weights.
    # Mask the EXPONENT, not the exp: non-causal entries have positive
    # cum_q - cum_k that overflows exp in fp32, and 0 * d(inf) = NaN in the
    # backward pass (exposed by pipeline bubble ticks).
    scores = jnp.einsum("bcqgn,bckgn->bcqkg", Cc, Bc)  # shared by a group
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    delta = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,K,H)
    decay = jnp.exp(jnp.where(causal, delta, -jnp.inf))
    w = jnp.repeat(scores, J, axis=-1) * decay
    w = w * dtc[:, :, None, :, :]  # dt_k factor (B,nc,Q,K,H)
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", w, xc.reshape(Bsz, nc, Q, H, P))

    # chunk states: h_c = sum_k exp(cum_end - cum_k) dt_k B_k x_k
    end_decay = jnp.exp(cum[:, :, -1:, :] - cum)  # (B, nc, Q, H)
    state_w = (end_decay * dtc).reshape(Bsz, nc, Q, G, J)
    chunk_states = jnp.einsum("bcqgj,bcqgn,bcqgjp->bcgjpn", state_w, Bc,
                              xc).reshape(Bsz, nc, H, P, N)

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
    in_decay = jnp.exp(cum).reshape(Bsz, nc, Q, G, J)  # chunk start -> position
    y_inter = jnp.einsum("bcqgn,bcqgj,bcgjpn->bcqgjp", Cc, in_decay,
                         h_prevs.reshape(Bsz, nc, G, J, P, N))
    y = (y_intra + y_inter.reshape(Bsz, nc, Q, H, P)).reshape(Bsz, S, H, P)
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
    G = Bm.shape[2] if Bm.ndim == 4 else 1
    P = HP // H

    def oracle(x, dtv, A, Bm, Cm, D, h0):
        x = x.astype(jnp.float32)
        y, h_last = _ssd_chunked(x.reshape(Bsz, S, H, P), dtv, A, Bm, Cm, h0,
                                 chunk)
        return y.reshape(Bsz, S, HP) + jnp.repeat(D, P) * x, h_last

    if not ssd_tiles(min(chunk, S), H, P, N, G):
        return oracle(x, dtv, A, Bm, Cm, D, h0)
    return jax.lax.platform_dependent(
        x, dtv, A, Bm, Cm, D, h0, tpu=partial(ssd_ops.ssd_scan, chunk=chunk),
        default=oracle)


def ssd_block(p, cfg: ModelConfig, x, ctx: Ctx, cache):
    dt_ = cdt(cfg)
    B, S, D = x.shape
    Di, H, P, N, G = _ssd_dims(cfg)
    with jax.named_scope("ssd_in_proj"):
        zxbcdt = x @ p["w_in"].astype(dt_)
        z, xs, Bm, Cm, dtv = jnp.split(
            zxbcdt, [Di, 2 * Di, 2 * Di + G * N, 2 * Di + 2 * G * N], axis=-1)
    with jax.named_scope("ssd_conv"):
        conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
        conv_state = cache.get("conv") if cache else None
        conv_out, new_conv = _causal_depthwise_conv(
            conv_in, p["conv_w"].astype(dt_), conv_state)
        if "conv_b" in p:
            conv_out = conv_out + p["conv_b"].astype(dt_)
        conv_out = jax.nn.silu(conv_out)
        xs, Bm, Cm = jnp.split(conv_out, [Di, Di + G * N], axis=-1)
    with jax.named_scope("ssd_scan"):
        dtv = jax.nn.softplus(dtv.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
        A = jnp.exp(p["A_log"])  # (H,) positive rates
        Bm32, Cm32 = Bm.astype(jnp.float32), Cm.astype(jnp.float32)
        if G > 1:
            Bm32, Cm32 = (a.reshape(B, S, G, N) for a in (Bm32, Cm32))
        if ctx.decoding and cache is not None:
            J = H // G
            xh = xs.reshape(B, G, J, P).astype(jnp.float32)  # S == 1
            Bg, Cg = Bm32.reshape(B, G, N), Cm32.reshape(B, G, N)
            h0 = cache["h"]  # (B, H, P, N)
            dA = jnp.exp(-dtv[:, 0] * A[None, :])  # (B, H)
            dBx = jnp.einsum("bgj,bgn,bgjp->bgjpn", dtv[:, 0].reshape(B, G, J),
                             Bg, xh).reshape(B, H, P, N)
            h = h0 * dA[:, :, None, None] + dBx
            y = jnp.einsum("bgn,bgjpn->bgjp", Cg,
                           h.reshape(B, G, J, P, N)).reshape(B, 1, H, P)
            y = (y + p["D_skip"][None, None, :, None]
                 * xh.reshape(B, 1, H, P)).reshape(B, S, Di)
            new_cache = {"h": h, "conv": new_conv}
        else:
            h0 = cache["h"] if (cache is not None and "h" in cache) else None
            # the scan takes the negative log-decay rates -A
            y, h_last = _ssd_scan(xs, dtv, -A, Bm32, Cm32, p["D_skip"], h0,
                                  cfg.ssm_chunk)
            new_cache = ({"h": h_last, "conv": new_conv} if cache is not None
                         else None)
    with jax.named_scope("ssd_gate_norm"):
        # norm_before_gate=False, per group of Di / G channels (Nemotron-H)
        y = rmsnorm(y.astype(dt_) * jax.nn.silu(z), p["norm"], cfg.norm_eps,
                    groups=G)
    with jax.named_scope("ssd_out_proj"):
        return y @ p["w_out"].astype(dt_), new_cache


def init_ssd_cache(cfg: ModelConfig, batch: int):
    Di, H, P, N, G = _ssd_dims(cfg)
    return {
        "h": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, Di + 2 * G * N), cdt(cfg)),
    }

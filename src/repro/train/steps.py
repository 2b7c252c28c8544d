"""Training step: chunked cross-entropy loss, grads, clipping, optimizer update.

Loss is computed in token chunks (`cfg.loss_chunk`) so the (tokens, vocab)
logits are never materialized at once — at 151k vocab x 1M tokens that is the
difference between fitting and not fitting HBM.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models import transformer as T
from ..models.layers import Ctx, softcap
from ..models.sharding import constrain
from ..optim import Optimizer, clip_by_global_norm

AUX_LOSS_COEF = 0.01


def chunked_xent(hidden, head_w, targets, cfg: ModelConfig):
    """hidden (B, S, D), head_w (D, V), targets (B, S) -> mean nll (fp32).

    The chunk COUNT is bounded (<= 8): each scan step re-gathers the sharded
    head matrix, so at 128k+ vocab a fixed 4096-token chunk size meant 256
    gathers of a multi-GB fp32 matrix per step (§Perf).  Chunks exist only to
    cap the live (tokens, vocab) logits block.
    """
    B, S, D = hidden.shape
    T_ = B * S
    # vocab-sharded, D-replicated head (a one-off ~100 MB/device reshard);
    # contracting against the ZeRO-sharded layout instead makes GSPMD gather
    # the multi-GB fp32 (D, V) matrix inside the chunk loop (§Perf)
    head_w = constrain(head_w, (None, "tp"))
    # chunk along SEQUENCE, keeping (B, Sc, D) 3-D chunks: flattening (B, S)
    # merges differently-sharded dims, which GSPMD can only resolve by
    # all-gathering the whole fp32 stack (28 GB on arctic — §Perf)
    n = max(1, min(8, T_ // max(1, cfg.loss_chunk)))
    while S % n:
        n -= 1
    xs = jnp.moveaxis(hidden.reshape(B, n, S // n, D), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, n, S // n), 1, 0)

    def body(acc, inp):
        xc, tc = inp  # (B, Sc, D), (B, Sc)
        logits = (xc @ head_w).astype(jnp.float32)
        if cfg.final_softcap:
            logits = softcap(logits, cfg.final_softcap)
        # tokens on batch/DP axes, vocab on tp: keeps dlogits in the same
        # layout the head gradient needs (the (batch, tp)-flat layout made
        # GSPMD all-gather 62 GB of fp32 logits in the backward — §Perf)
        logits = constrain(logits, ("batch", None, "tp"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction, NOT take_along_axis: gathering along the
        # vocab-sharded dim all-gathers the full (chunk, V) fp32 logits
        onehot = jax.nn.one_hot(tc, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.sum(logits * onehot, axis=-1)
        return acc + jnp.sum(lse - gold), None

    # recompute logits in the backward pass: the scan otherwise stacks every
    # chunk's fp32 (chunk, vocab) logits as saved residuals (§Perf)
    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ts))
    return total / T_


def loss_fn(params, cfg: ModelConfig, batch, mode: str = "train"):
    tokens, targets = batch["tokens"], batch["targets"]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    ctx = Ctx(mode=mode, positions=pos)
    hidden, _, stats = T.forward(params, cfg, tokens, ctx,
                                 memory=batch.get("memory"))
    head_w = T.head_matrix(params, cfg).astype(hidden.dtype)
    nll = chunked_xent(hidden, head_w, targets, cfg)
    return nll + AUX_LOSS_COEF * stats["aux"], {"nll": nll, **stats}


def make_train_step(cfg: ModelConfig, opt: Optimizer, max_grad_norm: float = 1.0):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, batch)
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        params, opt_state = opt.update(grads, opt_state, params)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return params, opt_state, metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, metrics = loss_fn(params, cfg, batch, mode="prefill")
        return dict(metrics, loss=loss)

    return eval_step

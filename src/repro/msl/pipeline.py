"""shard_map microbatch pipeline runtime executing a PipelinePlan.

The paper's service chain made SPMD: mesh ('stage', 'data'); stage k holds its
planner-assigned contiguous group range; smashed data (the residual stream)
moves stage k -> k+1 via `jax.lax.ppermute` — the TPU fabric plays the paper's
physical network, the ppermute schedule is the chaining.  GPipe-style schedule
with M microbatches: T = M + K - 1 ticks, fill/drain bubbles; XLA's async
collective-permute (start/done pairs) overlaps the tick-t transfer with tick-t
compute — compute/comm overlap the paper does not model (a beyond-paper
optimization, EXPERIMENTS.md §Perf).

Backward: plain jax.grad through the shard_map — AD reverses every ppermute,
yielding the paper's reverse-path gradient chaining for free.  Embedding and
the LM head run outside the pipeline region, sharded over 'data' (DESIGN.md).

Stages run one structurally identical program: every stage scans over
`Gmax = ceil(n_groups / K)` group slots; slots beyond the stage's planner
segment carry a False validity flag and pass the residual through unchanged.

Each component of the train step runs under a flat `jax.named_scope` (`embed`,
`restack`, `tick`, `stage`, `bubble`, `ppermute`, `head`, `optimizer`, and the
block scopes of models/), which a profiler trace reads back per scope
(docs/pipeline.md, "Reading a device trace of the train step").
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from ..configs.base import ModelConfig
from ..models import layers as L
from ..models import transformer as T
from ..models.layers import Ctx
from ..train.steps import chunked_xent
from .planner import PipelinePlan, plan_pipeline

def make_pipeline_mesh(n_stages: int, n_data: int) -> Mesh:
    return jax.make_mesh((n_stages, n_data), ("stage", "data"),
                         axis_types=(AxisType.Auto,) * 2)


def plan_on_devices(cfg: ModelConfig, n_stages: int, *, seq_len: int,
                    microbatch: int) -> tuple[PipelinePlan, Mesh]:
    """One pipeline stage per device: the planner's K = `n_stages` chain and
    its (n_stages, 1) mesh.  On one device that is a single stage holding
    every group."""
    plan = plan_pipeline(cfg, seq_len=seq_len, microbatch=microbatch,
                         candidate_K=(n_stages,))
    return plan, make_pipeline_mesh(plan.K, 1)


# ------------------------------------------------------------ param restacking
def stack_for_pipeline(params: dict, cfg: ModelConfig, plan: PipelinePlan):
    """Model 'stack' params (R, ...) per pattern position -> (K, Gmax, ...)
    stage-major layout + validity mask (K, Gmax).  Differentiable (gather)."""
    K = plan.K
    Gmax = max(plan.groups_per_stage)
    R = plan.n_groups
    # index map: slot (k, g) -> source group index (clamped; invalid masked)
    idx = []
    for k, (lo, hi) in enumerate(plan.segments):
        row = [min(lo - 1 + g, R - 1) for g in range(Gmax)]
        idx.append(row)
    idx = jnp.asarray(idx, jnp.int32)  # (K, Gmax)

    def restack(leaf):
        return jnp.take(leaf, idx.reshape(-1), axis=0).reshape(
            (K, Gmax) + leaf.shape[1:])

    groups = tuple(jax.tree.map(restack, g) for g in params["stack"]["groups"])
    valid = jnp.asarray(
        [[g < n for g in range(Gmax)] for n in plan.groups_per_stage], bool)
    return groups, valid


# ------------------------------------------------------------ pipelined forward
def _stage_apply(stage_groups, valid, cfg: ModelConfig, x, ctx: Ctx):
    """Scan this stage's Gmax group slots over the residual stream."""

    def body(carry, xs):
        h, acc = carry
        params_g, valid_g = xs
        h2, stats = h, L.no_stats()
        for i, kind in enumerate(cfg.pattern):
            h2, _, st = T.apply_block(params_g[i], cfg, kind, h2, ctx, None)
            stats = L.add_stats(stats, st)
        h = jnp.where(valid_g, h2, h)
        acc = L.add_stats(acc, jax.tree.map(
            lambda v: jnp.where(valid_g, v, jnp.zeros_like(v)), stats))
        return (h, acc), None

    # the remat unit is one whole group: every pattern position of it
    if cfg.remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    (h, stats), _ = jax.lax.scan(body, (x, L.no_stats()),
                                 (stage_groups, valid))
    return h, stats


def pipelined_apply(groups_stacked, valid, h_mb, *, cfg: ModelConfig, K: int,
                    n_micro: int):
    """Runs INSIDE shard_map over ('stage', 'data').

    groups_stacked: per-pattern-position trees, leading (1, Gmax, ...) local
    (stage-sharded); h_mb: (M, mb_local, S, D) microbatched embeddings
    (replicated over 'stage').  Returns ((M, mb, S, D) outputs — valid on the
    LAST stage's shard — and the stage-local stats, ``layers.no_stats``'s
    keys, each with a leading stage axis of 1)."""
    stage = jax.lax.axis_index("stage")
    M = n_micro
    n_ticks = M + K - 1
    mb, S = h_mb.shape[1], h_mb.shape[2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (mb, S))
    ctx = Ctx(mode="train", positions=positions)
    my_groups = tuple(jax.tree.map(lambda p: p[0], g) for g in groups_stacked)
    my_valid = valid[0]

    def stage_fn(xi):
        with jax.named_scope("stage"):
            return _stage_apply(my_groups, my_valid, cfg, xi, ctx)

    def bubble_fn(xi):
        with jax.named_scope("bubble"):
            return xi, L.no_stats()

    def tick(carry, t):
        received, outs, acc = carry
        inject = h_mb[jnp.clip(t, 0, M - 1)]
        x_in = jnp.where(stage == 0, inject, received)
        # bubble skipping: stage i only has real work for ticks i <= t < i+M;
        # lax.cond (real XLA conditional — not vmapped into a select here)
        # skips the fill/drain garbage compute entirely
        if K == 1:
            # every tick of a one-stage chain is active; without the cond the
            # stage's weights stay loop constants of the tick scan, where a
            # cond's residuals are stacked once a tick
            y, stats = stage_fn(x_in)
        else:
            active = (t >= stage) & (t - stage < M)
            y, stats = jax.lax.cond(active, stage_fn, bubble_fn, x_in)
        # the last stage collects microbatch t - (K - 1)
        oidx = jnp.clip(t - (K - 1), 0, M - 1)
        take = t >= K - 1
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(take, y, outs[oidx]), oidx, 0)
        # ship smashed data along the chain (ring permute; the wrap-around
        # edge K-1 -> 0 is ignored by stage 0's inject select)
        with jax.named_scope("ppermute"):
            nxt = jax.lax.ppermute(y, "stage",
                                   [(i, (i + 1) % K) for i in range(K)])
        return (nxt, outs, L.add_stats(acc, stats)), None

    with jax.named_scope("tick"):
        (_, outs, stats), _ = jax.lax.scan(
            tick, (jnp.zeros_like(h_mb[0]), jnp.zeros_like(h_mb),
                   L.no_stats()),
            jnp.arange(n_ticks))
    return outs, jax.tree.map(lambda v: v[None], stats)


def pipeline_forward(params, batch, cfg: ModelConfig, mesh: Mesh,
                     plan: PipelinePlan, n_micro: int):
    """Embed -> pipelined blocks -> final hidden states (B, S, D) and the
    step's stats: the aux loss averaged over ticks, the MoE counters summed
    over stages (``moe_max_rows``: the largest)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    assert B % n_micro == 0
    mb = B // n_micro
    with jax.named_scope("embed"):
        x = T.embed_tokens(params, cfg, tokens)
    h_mb = x.reshape(n_micro, mb, S, -1)
    with jax.named_scope("restack"):
        groups_stacked, valid = stack_for_pipeline(params, cfg, plan)
    fn = jax.shard_map(
        partial(pipelined_apply, cfg=cfg, K=plan.K, n_micro=n_micro),
        mesh=mesh,
        in_specs=(tuple(jax.tree.map(lambda _: P("stage"), g)
                        for g in groups_stacked), P("stage"),
                  P(None, "data")),
        out_specs=(P("stage", "data"), P("stage")),
        check_vma=False,
    )
    outs, stats = fn(groups_stacked, valid, h_mb)
    # out dim0 is stage-major (K * M); the last stage's block holds the model
    # output microbatches
    with jax.named_scope("head"):
        h_last = outs[-n_micro:]
        hidden = h_last.reshape(B, S, -1)
        hidden = L.rmsnorm(hidden, params["final_norm"], cfg.norm_eps)
    # aux averaged over ticks (bubble ticks process pass-through garbage; the
    # valid-slot masking keeps their contribution bounded)
    return hidden, {"aux": jnp.sum(stats["aux"]) / (n_micro + plan.K - 1),
                    "moe_rows": jnp.sum(stats["moe_rows"]),
                    "moe_max_rows": jnp.max(stats["moe_max_rows"])}


def make_pipeline_train_step(cfg: ModelConfig, mesh: Mesh, plan: PipelinePlan,
                             n_micro: int, opt):
    def loss_fn(params, batch):
        hidden, stats = pipeline_forward(params, batch, cfg, mesh, plan,
                                         n_micro)
        with jax.named_scope("head"):
            head_w = T.head_matrix(params, cfg).astype(hidden.dtype)
            nll = chunked_xent(hidden, head_w, batch["targets"], cfg)
        return nll + 0.01 * stats["aux"], (nll, stats)

    def train_step(params, opt_state, batch):
        """Returns the new state and metrics: the loss, its ``nll`` part,
        and the step's MoE counters (``moe_rows``: rows the held experts
        computed, ``moe_max_rows``: the largest held expert's rows in one
        layer and microbatch; 0 without MoE)."""
        (loss, (nll, stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "nll": nll,
                                   "moe_rows": stats["moe_rows"],
                                   "moe_max_rows": stats["moe_max_rows"]}

    return train_step

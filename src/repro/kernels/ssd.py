"""Mamba-2 chunked SSD scan as a Pallas TPU kernel pair under ``custom_vjp``.

The oracle is ``models.layers._ssd_chunked`` (arXiv:2405.21060, Alg. 1) and
the skip ``D x``; this is the same algorithm with every O(Q^2) tensor kept
in VMEM.  A head group is ``G`` heads whose ``G * P`` lanes make a whole
number of 128-lane groups (two heads of 64 on mamba2-370m); one grid step is
one (batch, chunk, run of head groups up to ``_STEP_LANES`` lanes, inside
one SSM group).  Per head it computes the causal decay ``exp(where(q >= k,
cum_q - cum_k, -inf))``, the weights ``(C B^T) * decay * dt_k`` and, in the
backward, their cotangents; none of them is written to HBM.  ``B`` and ``C``
belong to an SSM group, a run of ``H / n_groups`` heads (all heads on
mamba2-370m, 8 heads of 64 on Nemotron-H, which is one 512-lane step):
``C B^T`` is shared by the group's heads and formed once per (batch, chunk,
SSM group), in VMEM, at the group's first grid step.

The inter-chunk recurrence ``h_c = exp(cum_end) h_{c-1} + S_c`` runs inside
the kernels: the chunk axis of the grid is sequential, and the state of
every head of a batch row stays in VMEM (the ``h_last`` output block) from
chunk to chunk.  The forward saves the states ``h_prev`` entering each chunk
and no (Q, Q) tensor; the backward walks the chunks in reverse, carrying
dL/dh in the ``dh0`` output block.

Layouts: ``x``, ``y``, ``dx``: (B, S, H*P), the layout the block produces
and consumes; ``B``, ``C``: (B, S, n_groups * N), group-major; per-head
vectors ``dt`` and ``cum``
(the within-chunk cumulative sum of ``dt * A``, computed in float32 by the
caller): (B, S, H) for their columns and (B, H, S) for their rows.  A head's
column or row is taken out of the block by a masked sum, exact, as Mosaic
slices no dynamic lane.

Precision is the oracle's at the TPU's default matmul precision: arrays and
accumulation in float32, every dot with bfloat16 operands where that
precision rounds them (``preferred_element_type=float32``); the mask,
``exp`` and the decay products stay float32.  ``dx`` is rounded to ``x``'s
dtype after the skip's ``D dy`` is added, where the XLA path converts it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32, bf16 = jnp.float32, jnp.bfloat16
_VMEM_LIMIT = 64 * 1024 * 1024
_STEP_LANES = 512          # lanes of x a grid step covers, at most


def group_width(P: int) -> int:
    """Lanes of one head group: the least multiple of 128 that holds whole
    heads of width ``P``."""
    return math.lcm(P, 128)


def ssd_tiles(Q: int, H: int, P: int, N: int, n_groups: int = 1) -> bool:
    """Whether the kernels tile these shapes: chunk ``Q`` and state ``N`` in
    whole 128-lane groups, each SSM group's heads in whole head groups."""
    return (Q % 128 == 0 and N % 128 == 0 and H % n_groups == 0
            and (H // n_groups * P) % group_width(P) == 0)


def _dot(a, b, contract):
    """float32 product of bf16-rounded operands over dims ``contract``."""
    return jax.lax.dot_general(a.astype(bf16), b.astype(bf16),
                               (contract, ((), ())),
                               preferred_element_type=f32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _col(block, h):
    """Column ``h`` of an (n, H) block as (n, 1)."""
    return jnp.sum(jnp.where(_iota(block.shape, 1) == h, block, 0.0), axis=1,
                   keepdims=True)


def _row(block, h):
    """Row ``h`` of an (H, Q) block as (1, Q)."""
    return jnp.sum(jnp.where(_iota(block.shape, 0) == h, block, 0.0), axis=0,
                   keepdims=True)


def _heads(vecs, grp, G, P, Q):
    """Per head ``j`` of head group ``grp``: its vectors, and the
    lane-expanded ``exp(cum)``, ``exp(cum_end - cum) dt`` (Q, G*P), ``D``
    (1, G*P) and ``exp(cum_end)`` (G*P, 1) of the whole group."""
    dtc, dtr, cumc, cumr, d = vecs
    lane = _iota((Q, G * P), 1) // P
    row = _iota((G * P, 1), 0) // P
    last = _iota((Q, 1), 0) == Q - 1
    heads, e_l, sw_l, d_l, cd_r = [], 0.0, 0.0, 0.0, 0.0
    for j in range(G):
        h = grp * G + j
        v = dict(h=h, cum_c=_col(cumc, h), cum_r=_row(cumr, h),
                 dt_c=_col(dtc, h), dt_r=_row(dtr, h), D=_col(d, h))
        v["cum_end"] = jnp.sum(jnp.where(last, v["cum_c"], 0.0), axis=0,
                               keepdims=True)                       # (1, 1)
        v["e"] = jnp.exp(v["cum_c"])                                # (Q, 1)
        v["end"] = jnp.exp(v["cum_end"] - v["cum_c"])               # (Q, 1)
        v["cd"] = jnp.exp(v["cum_end"])                             # (1, 1)
        e_l = jnp.where(lane == j, v["e"], e_l)
        sw_l = jnp.where(lane == j, v["end"] * v["dt_c"], sw_l)
        d_l = jnp.where(_iota((1, G * P), 1) // P == j, v["D"], d_l)
        cd_r = jnp.where(row == j, v["cd"], cd_r)
        heads.append(v)
    return heads, e_l, sw_l, d_l, cd_r, lane, row


def _decay(v, Q):
    # mask the exponent: exp of the positive non-causal deltas overflows
    causal = _iota((Q, Q), 0) >= _iota((Q, Q), 1)
    return jnp.exp(jnp.where(causal, v["cum_c"] - v["cum_r"], -jnp.inf))


def _first_of_group(g, spg):
    """Whether grid step ``g`` is the first of its SSM group's ``spg`` steps
    (``spg`` None: one SSM group over every step)."""
    return g == 0 if spg is None else g % spg == 0


def _last_of_group(g, spg):
    if spg is None:
        return g == pl.num_programs(2) - 1
    return g % spg == spg - 1


def _fwd_kernel(x_ref, dtc_ref, dtr_ref, cumc_ref, cumr_ref, b_ref, c_ref,
                d_ref, h0_ref, y_ref, hprev_ref, h_ref, s_scr, *, G, P,
                n_grp, spg):
    c, g = pl.program_id(1), pl.program_id(2)
    Q, gw = x_ref.shape[1], G * P

    @pl.when(_first_of_group(g, spg))
    def _():
        s_scr[...] = _dot(c_ref[0], b_ref[0], _NT)                 # C B^T

    @pl.when(c == 0)
    def _():
        for k in range(n_grp):
            h_ref[0, g * n_grp + k] = h0_ref[0, g * n_grp + k]

    Bm, Cm, s = b_ref[0], c_ref[0], s_scr[...]
    vecs = tuple(r[0] for r in (dtc_ref, dtr_ref, cumc_ref, cumr_ref)) + (
        d_ref[...],)
    for k in range(n_grp):                   # head groups of this step
        grp, lanes = g * n_grp + k, slice(k * gw, (k + 1) * gw)
        x = x_ref[0, :, lanes]                                      # (Q, gw)
        hp = h_ref[0, grp]                                          # (gw, N)
        hprev_ref[0, 0, k] = hp
        heads, e_l, sw_l, d_l, cd_r, lane, _ = _heads(vecs, grp, G, P, Q)
        xf = x.astype(f32)
        y = jnp.zeros((Q, gw), f32)
        for j, v in enumerate(heads):
            w = s * _decay(v, Q) * v["dt_r"]
            y = jnp.where(lane == j, _dot(w, x, _NN), y)
        y_ref[0, :, lanes] = (y + e_l * _dot(Cm, hp, _NT)) + d_l * xf
        u = xf * sw_l
        h_ref[0, grp] = hp * cd_r + _dot(u, Bm, _TN)


def _bwd_kernel(x_ref, dtc_ref, dtr_ref, cumc_ref, cumr_ref, b_ref, c_ref,
                d_ref, hprev_ref, dy_ref, dhl_ref, dx_ref, ddtc_ref, ddtr_ref,
                dcumc_ref, dcumr_ref, db_ref, dc_ref, dd_ref, dh_ref, s_scr,
                ds_scr, *, G, P, n_grp, spg):
    c, g = pl.program_id(1), pl.program_id(2)
    Q, gw = x_ref.shape[1], G * P

    @pl.when(_first_of_group(g, spg))
    def _():
        s_scr[...] = _dot(c_ref[0], b_ref[0], _NT)
        ds_scr[...] = jnp.zeros_like(ds_scr)
        db_ref[0] = jnp.zeros(db_ref.shape[1:], f32)
        dc_ref[0] = jnp.zeros(dc_ref.shape[1:], f32)

    @pl.when(c == 0)                        # the last chunk comes first
    def _():
        for k in range(n_grp):
            dh_ref[0, g * n_grp + k] = dhl_ref[0, g * n_grp + k]

    @pl.when((c == 0) & (g == 0))
    def _():
        dd_ref[...] = jnp.zeros_like(dd_ref)

    Bm, Cm, s, ds = b_ref[0], c_ref[0], s_scr[...], ds_scr[...]
    vecs = tuple(r[0] for r in (dtc_ref, dtr_ref, cumc_ref, cumr_ref)) + (
        d_ref[...],)
    ddtc, dcumc, ddtr, dcumr = (r[0] for r in (ddtc_ref, dcumc_ref, ddtr_ref,
                                               dcumr_ref))
    H, dd = ddtc.shape[1], dd_ref[0]
    lane_h, row_h = _iota((Q, H), 1), _iota((H, Q), 0)
    Q_last = _iota((Q, 1), 0) == Q - 1
    db, dc = db_ref[0], dc_ref[0]
    for k in range(n_grp):
        grp, lanes = g * n_grp + k, slice(k * gw, (k + 1) * gw)
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        xf = x.astype(f32)
        hp, gs = hprev_ref[0, 0, k], dh_ref[0, grp]  # h in, dL/dh out
        heads, e_l, sw_l, d_l, cd_r, lane, row = _heads(vecs, grp, G, P, Q)

        # y_inter = exp(cum) * (C h^T) and the chunk state S = (x sw)^T B
        dv = e_l * dy
        dc += _dot(dv, hp, _NN)
        dhp = cd_r * gs + _dot(dv, Cm, _TN)
        ev = dy * _dot(Cm, hp, _NT)
        du = _dot(Bm, gs, _NT)
        db += _dot(xf * sw_l, gs, _NN)
        dx = sw_l * du
        dux, ghp, dyx = du * xf, gs * hp, dy * xf

        for j, v in enumerate(heads):
            on = lane == j
            L = _decay(v, Q)
            w = s * L * v["dt_r"]
            dw = _dot(jnp.where(on, dy, 0.0), x, _NT)               # (Q, K)
            dx = jnp.where(on, dx + _dot(w, dy, _TN), dx)
            dwl = dw * L
            ds = ds + dwl * v["dt_r"]
            t = dwl * s                             # d/d(dt_k) of w, per q
            t_col = jnp.sum(t, axis=0, keepdims=True)               # (1, K)
            dcum_c = jnp.sum(t * v["dt_r"], axis=1, keepdims=True)  # (Q, 1)
            dcum_c += v["e"] * jnp.sum(jnp.where(on, ev, 0.0), axis=1,
                                       keepdims=True)
            dsw = jnp.sum(jnp.where(on, dux, 0.0), axis=1, keepdims=True)
            dend = dsw * v["dt_c"] * v["end"]
            dcum_c -= dend
            dcum_end = jnp.sum(dend, axis=0, keepdims=True) + v["cd"] * (
                jnp.sum(jnp.sum(jnp.where(row == j, ghp, 0.0), axis=1,
                                keepdims=True), axis=0, keepdims=True))
            dcum_c += jnp.where(Q_last, dcum_end, 0.0)
            # each head's lane or row of the blocks is written once
            h = v["h"]
            ddtc = jnp.where(lane_h == h, dsw * v["end"], ddtc)
            dcumc = jnp.where(lane_h == h, dcum_c, dcumc)
            ddtr = jnp.where(row_h == h, t_col, ddtr)
            dcumr = jnp.where(row_h == h, -v["dt_r"] * t_col, dcumr)
            dd = jnp.where(_iota((1, H), 1) == h, dd + jnp.sum(jnp.sum(
                jnp.where(on, dyx, 0.0), axis=1, keepdims=True), axis=0,
                keepdims=True), dd)
        dx_ref[0, :, lanes] = (dx + d_l * dy).astype(dx_ref.dtype)
        dh_ref[0, grp] = dhp
    ddtc_ref[0], dcumc_ref[0], ddtr_ref[0], dcumr_ref[0] = (ddtc, dcumc, ddtr,
                                                            dcumr)
    dd_ref[0] = dd
    ds_scr[...] = ds
    db_ref[0], dc_ref[0] = db, dc

    @pl.when(_last_of_group(g, spg))   # scores' cotangent, the group's heads
    def _():
        dc_ref[0] += _dot(ds, Bm, _NN)
        db_ref[0] += _dot(ds, Cm, _TN)


def _steps(H, P, n_groups):
    """Head groups of one grid step (as many as fit ``_STEP_LANES`` lanes and
    divide an SSM group's head groups), the head groups, and the grid steps
    of one SSM group (None where one SSM group spans every step)."""
    gw = group_width(P)
    n = H * P // gw
    per_ssm = n // n_groups
    n_grp = max(k for k in range(1, max(1, _STEP_LANES // gw) + 1)
                if per_ssm % k == 0)
    return n_grp, n, (per_ssm // n_grp if n_groups > 1 else None)


def _specs(S, H, P, N, Q, n_groups, reverse):
    """BlockSpecs of the inputs both kernels share, and of the group state."""
    gw, nc = group_width(P), S // Q
    n_grp, nG, spg = _steps(H, P, n_groups)
    ch = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    tile = pl.BlockSpec((1, Q, n_grp * gw), lambda b, c, g: (b, ch(c), g))
    cols = pl.BlockSpec((1, Q, H), lambda b, c, g: (b, ch(c), 0))
    rows = pl.BlockSpec((1, H, Q), lambda b, c, g: (b, 0, ch(c)))
    bc = pl.BlockSpec((1, Q, N), (lambda b, c, g: (b, ch(c), 0)) if spg is None
                      else (lambda b, c, g: (b, ch(c), g // spg)))
    state = pl.BlockSpec((1, nG, gw, N), lambda b, c, g: (b, 0, 0, 0))
    hprev = pl.BlockSpec((1, 1, n_grp, gw, N),
                         lambda b, c, g: (b, ch(c), g, 0, 0))
    return tile, cols, rows, bc, state, hprev


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret)


def _rows(a):
    return jnp.swapaxes(a, 1, 2)


# Jitted, so that the forward's kernel, which the step traces twice (the
# forward and its recompute), is traced once a process.
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _forward(x, dt, cum, Bm, Cm, D, h0, chunk, interpret):
    Bsz, S, HP = x.shape
    H, N = dt.shape[-1], h0.shape[-1]
    P, Q = HP // H, chunk
    W, nc = group_width(P), S // Q
    n_grp, nG, spg = _steps(H, P, Bm.shape[-1] // N)
    tile, cols, rows, bc, state, hprev = _specs(S, H, P, N, Q,
                                                Bm.shape[-1] // N, False)
    heads = pl.BlockSpec((1, H), lambda b, c, g: (0, 0))
    y, hprevs, h_last = _call(
        functools.partial(_fwd_kernel, G=W // P, P=P, n_grp=n_grp, spg=spg),
        "ssd_scan_fwd", (Bsz, nc, nG // n_grp),
        [tile, cols, rows, cols, rows, bc, bc, heads, state],
        [tile, hprev, state],
        [jax.ShapeDtypeStruct((Bsz, S, HP), f32),
         jax.ShapeDtypeStruct((Bsz, nc, nG, W, N), f32),
         jax.ShapeDtypeStruct((Bsz, nG, W, N), f32)],
        [pltpu.VMEM((Q, Q), f32)], interpret,
    )(x, dt, _rows(dt), cum, _rows(cum), Bm, Cm, D[None],
      h0.reshape(Bsz, nG, W, N))
    return y, h_last.reshape(h0.shape), hprevs


@functools.partial(jax.jit, static_argnames=("dx_dtype", "chunk", "interpret"))
def _backward(x, dt, cum, Bm, Cm, D, hprevs, dy, dh_last, dx_dtype, chunk,
              interpret):
    Bsz, S, HP = x.shape
    H, N = dt.shape[-1], dh_last.shape[-1]
    P, Q = HP // H, chunk
    W, nc = group_width(P), S // Q
    n_grp, nG, spg = _steps(H, P, Bm.shape[-1] // N)
    tile, cols, rows, bc, state, hprev = _specs(S, H, P, N, Q,
                                                Bm.shape[-1] // N, True)
    heads = pl.BlockSpec((1, H), lambda b, c, g: (0, 0))
    per_row = pl.BlockSpec((1, 1, H), lambda b, c, g: (b, 0, 0))
    sds = jax.ShapeDtypeStruct
    dx, ddt_c, ddt_r, dcum_c, dcum_r, dB, dC, dD, dh0 = _call(
        functools.partial(_bwd_kernel, G=W // P, P=P, n_grp=n_grp, spg=spg),
        "ssd_scan_bwd", (Bsz, nc, nG // n_grp),
        [tile, cols, rows, cols, rows, bc, bc, heads, hprev, tile, state],
        [tile, cols, rows, cols, rows, bc, bc, per_row, state],
        [sds((Bsz, S, HP), dx_dtype), sds((Bsz, S, H), f32),
         sds((Bsz, H, S), f32), sds((Bsz, S, H), f32), sds((Bsz, H, S), f32),
         sds(Bm.shape, f32), sds(Cm.shape, f32), sds((Bsz, 1, H), f32),
         sds((Bsz, nG, W, N), f32)],
        [pltpu.VMEM((Q, Q), f32), pltpu.VMEM((Q, Q), f32)], interpret,
    )(x, dt, _rows(dt), cum, _rows(cum), Bm, Cm, D[None], hprevs, dy,
      dh_last.reshape(Bsz, nG, W, N))
    return (dx, ddt_c + _rows(ddt_r), dcum_c + _rows(dcum_r), dB, dC,
            jnp.sum(dD, axis=(0, 1)), dh0.reshape(dh_last.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def ssd_scan(x, dt, cum, Bm, Cm, D, h0, chunk, interpret=False):
    """Chunked SSD scan with its skip: ``y = SSD(x) + D x``.

    x: (B, S, H*P), any float dtype, read as bfloat16 (the precision every
    dot rounds it to; its cotangent comes back in its own dtype); dt, cum:
    (B, S, H) float32, ``cum`` the within-chunk cumulative sum of ``dt * A``
    (A < 0); Bm, Cm: (B, S, n_groups * N) float32, group-major (heads
    ``[i H / n_groups, (i + 1) H / n_groups)`` read group ``i``), read as
    bfloat16; D: (H,) float32; h0: (B, H, P, N) float32.  ``chunk`` divides S and the shapes satisfy
    :func:`ssd_tiles`.  Returns (y (B, S, H*P) float32, h_last (B, H, P, N)
    float32)."""
    return _ssd_fwd(x, dt, cum, Bm, Cm, D, h0, chunk, interpret)[0]


def _ssd_fwd(x, dt, cum, Bm, Cm, D, h0, chunk, interpret):
    Bm, Cm = Bm.astype(bf16), Cm.astype(bf16)
    with jax.named_scope("ssd_scan"):
        y, h_last, hprevs = _forward(x.astype(bf16), dt, cum, Bm, Cm, D, h0,
                                     chunk=chunk, interpret=interpret)
    return (y, h_last), (x, dt, cum, Bm, Cm, D, hprevs)


def _ssd_bwd(chunk, interpret, res, cts):
    x, *res = res
    with jax.named_scope("ssd_scan"):
        return _backward(x.astype(bf16), *res, *cts, dx_dtype=x.dtype,
                         chunk=chunk, interpret=interpret)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)

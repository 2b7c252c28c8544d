"""Flash attention as a Pallas TPU kernel pair under ``custom_vjp`` (GQA,
causal, sliding window, softcap).

The oracle is ``models.layers.blocked_attention`` (and
``ref.reference_attention``); this is the same attention with every score
tile kept in VMEM.  Nothing of shape (Sq, Sk) reaches HBM, a kv block that
the mask hides from a whole q block is never computed, and the K/V heads are
read where they are: the BlockSpec of query head ``h`` reads kv head
``h // G`` (``G = Hq / Hkv``), so no repeated (B, S, Hq, hd) K or V is built.

Layout.  q, k, v keep the model's (B, S, H, hd) layout, seen as (B, S/b, b,
H*hd): a block is one head's ``hd`` lanes of ``b`` rows, and a tile of
queries or keys is picked by a leading index.  Every tile is key-major:
the scores of a (kv block, q block) pair are ``s^T = K Q^T``, (bk, bq), so
the per-query statistics (running max, normaliser, log-sum-exp, and the
backward's ``D = rowsum(dO * O)``) are lane-dense rows (1, bq) that
broadcast over sublanes.  V (forward) and K (backward) also come as (hd,
bk) tiles, transposed once in XLA (they have Hkv heads), so that every
product in a kernel is a plain or right-transposed matmul.

Forward (grid: batch, query head, q block).  The head's whole K and V stay
in VMEM across its q blocks (their block index does not change, so they are
fetched once a kv head); the kernel loops over the kv blocks, computes a
block only where the mask leaves some key of it visible to some query of
the q block, and builds the iota mask only where it hides some of it (the
diagonal blocks of a causal mask, the window's edge, padded keys).  It
writes the output and the per-query log-sum-exp, the backward's residual.

Backward (grid: batch, kv head, the G query heads of that kv head).  One
grid step is one query head over the whole sequence: for each kv block and
each visible q block it recomputes ``P^T = exp(s^T - lse)`` from q, k and
the log-sum-exp, and accumulates dV += P^T dO, dK += dS^T Q and dQ^T +=
K^T dS^T, with ``dS^T = P^T (dP^T - D)``.  dQ stays in VMEM over the kv
blocks and is written at the end of the step; dK and dV stay in VMEM over
the G query heads (the sequential grid axis), so they leave the kernel
summed over the kv head's group.

Precision is the oracle's: q, k, v, dO enter the MXU in their own dtype
(bf16 on the training path) with float32 accumulation; the scale, softcap,
mask, softmax and its derivative are float32; P and dS are cast to the
operand dtype before their products, as the oracle casts its weights.

Masks are by index: query ``i`` sees key ``j`` where ``j <= i`` (causal)
and ``i - j < window``.  That equals the oracle's mask by position where
positions are ``arange(S)``, as every training ``Ctx`` builds them.
``interpret=True`` runs the kernels in the Pallas interpreter (the CPU
tests); otherwise they lower through Mosaic, which needs ``head_dim`` a
multiple of 128 and blocks that are multiples of 128 (``flash_tiles``).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG_INF = -1e30
# grid (batch, head, q block or query head of a group): the last axis runs
# in order, as the backward sums dK and dV over it
_COMPILER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=96 * 1024 * 1024)
_NT, _NN = ((1,), (1,)), ((1,), (0,))


def flash_tiles(Sq: int, Sk: int, hd: int, block_q: int, block_k: int) -> bool:
    """Whether the kernels lower through Mosaic at these shapes: whole
    128-lane head tiles and whole blocks of 128 rows."""
    return (hd % 128 == 0 and block_q % 128 == 0 and block_k % 128 == 0
            and Sq % block_q == 0 and Sk % block_k == 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=f32)


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """The static mask and tiling of one attention call."""

    causal: bool
    window: int | None
    softcap: float | None
    scale: float
    block_q: int
    block_k: int
    seq_k: int

    def visible(self, i, j):
        """(some key of kv block j is visible to some query of q block i,
        every key of it is visible to every query) — scalar predicates."""
        bq, bk = self.block_q, self.block_k
        q_lo, q_hi = i * bq, i * bq + bq - 1
        k_lo, k_hi = j * bk, j * bk + bk - 1
        some, every = True, True
        if self.causal:
            some = k_lo <= q_hi
            every = k_hi <= q_lo
        if self.window is not None:
            some = jnp.logical_and(some, q_lo - k_hi < self.window)
            every = jnp.logical_and(every, q_hi - k_lo < self.window)
        if self.seq_k % bk:
            every = jnp.logical_and(every, k_hi < self.seq_k)
        return some, every

    def scores(self, k, q, i, j, masked):
        """Scaled, capped and (where ``masked``) masked key-major scores of
        kv tile ``k`` (bk, hd) against q tile ``q`` (bq, hd), float32, and
        ``tanh`` of the capped ones (None without a softcap)."""
        s = _dot(k, q, _NT) * self.scale
        t = None
        if self.softcap is not None:
            t = jnp.tanh(s / self.softcap)
            s = self.softcap * t
        if masked:
            shape = (self.block_k, self.block_q)
            kk = j * self.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            qq = i * self.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            keep = kk < self.seq_k
            if self.causal:
                keep = jnp.logical_and(keep, kk <= qq)
            if self.window is not None:
                keep = jnp.logical_and(keep, qq - kk < self.window)
            s = jnp.where(keep, s, NEG_INF)
        return s, t


def _visit(geo, i, j, step):
    """Run ``step(masked)`` for the pair (q block i, kv block j) where some
    of it is visible, with the mask only where some of it is hidden."""
    some, every = geo.visible(i, j)
    if some is True and every is True:
        step(False)
        return
    pl.when(jnp.logical_and(some, every))(lambda: step(False))
    pl.when(jnp.logical_and(some, jnp.logical_not(every)))(lambda: step(True))


def _fwd_kernel(q_ref, k_ref, vt_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, geo, nk):
    i = pl.program_id(2)
    q = q_ref[...]                                    # (bq, hd)
    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, f32)
    l_scr[...] = jnp.zeros(l_scr.shape, f32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    def step(j, masked):
        s, _ = geo.scores(k_ref[j], q, i, j, masked)  # (bk, bq)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + _dot(
            vt_ref[j], p.astype(vt_ref.dtype), _NN)   # (hd, bq)
        m_scr[...] = m_new

    def body(j, carry):
        _visit(geo, i, j, functools.partial(step, j))
        return carry

    jax.lax.fori_loop(0, nk, body, 0)
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_scr[...] / l).T.astype(o_ref.dtype)
    lse_ref[...] = m_scr[...] + jnp.log(l)


def _bwd_kernel(q_ref, k_ref, v_ref, kt_ref, do_ref, lse_ref, d_ref,
                dq_ref, dk_ref, dv_ref, dqt_scr, dk_scr, dv_scr, *, geo,
                nq, nk, G):
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, f32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, f32)

    dqt_scr[...] = jnp.zeros(dqt_scr.shape, f32)

    def step(i, j, masked):
        q, do = q_ref[i], do_ref[i]                   # (bq, hd)
        k, v = k_ref[j], v_ref[j]                     # (bk, hd)
        s, t = geo.scores(k, q, i, j, masked)         # (bk, bq)
        p = jnp.exp(s - lse_ref[i])
        dv_scr[j] += _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - d_ref[i])
        if t is not None:
            ds = ds * (1.0 - t * t)
        ds = ds * geo.scale
        dk_scr[j] += _dot(ds.astype(q.dtype), q, _NN)
        dqt_scr[i] += _dot(kt_ref[j], ds.astype(kt_ref.dtype), _NN)

    def over_q(j, carry):
        def body(i, carry):
            _visit(geo, i, j, functools.partial(step, i, j))
            return carry
        return jax.lax.fori_loop(0, nq, body, carry)

    jax.lax.fori_loop(0, nk, over_q, 0)

    def write_dq(i, carry):
        dq_ref[i] = dqt_scr[i].T.astype(dq_ref.dtype)
        return carry

    jax.lax.fori_loop(0, nq, write_dq, 0)

    @pl.when(g == G - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _blocked(x, block):
    """(B, S, ...) -> (B, S/block, block, ...), S padded to the block."""
    n = -(-x.shape[1] // block)
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, n * block - x.shape[1])
    return jnp.pad(x, pad).reshape(x.shape[0], n, block, *x.shape[2:])


def _tiles(x, block):
    """(B, S, H, hd) -> (B, S/block, block, H*hd)."""
    x = _blocked(x, block)
    return x.reshape(*x.shape[:3], -1)


def _tiles_t(x, block):
    """(B, S, H, hd) -> (B, H, S/block, hd, block): each block transposed."""
    return jnp.transpose(_blocked(x, block), (0, 3, 1, 4, 2))


def _rows(x, block):
    """(B, S, H) -> (B, H, S/block, 1, block)."""
    return jnp.transpose(_blocked(x, block), (0, 3, 1, 2))[:, :, :, None]


def _untile(x, S, H):
    """(B, n, block, H*hd) -> (B, S, H, hd)."""
    B, n, block, Hd = x.shape
    return x.reshape(B, n * block, H, Hd // H)[:, :S]


def _forward(q, k, v, geo, interpret):
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    bq, bk = geo.block_q, geo.block_k
    qt, kt, vt = _tiles(q, bq), _tiles(k, bk), _tiles_t(v, bk)
    nq, nk = qt.shape[1], kt.shape[1]
    q_spec = pl.BlockSpec((None, None, bq, hd), lambda b, h, i: (b, i, 0, h))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, geo=geo, nk=nk),
        grid=(B, Hq, nq),
        in_specs=[
            q_spec,
            pl.BlockSpec((None, nk, bk, hd), lambda b, h, i: (b, 0, 0, h // G)),
            pl.BlockSpec((None, None, nk, hd, bk),
                         lambda b, h, i: (b, h // G, 0, 0, 0)),
        ],
        out_specs=[
            q_spec,
            pl.BlockSpec((None, None, None, 1, bq),
                         lambda b, h, i: (b, h, i, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, nq, 1, bq), f32)],
        scratch_shapes=[pltpu.VMEM((1, bq), f32), pltpu.VMEM((1, bq), f32),
                        pltpu.VMEM((hd, bq), f32)],
        name="flash_attention_fwd", compiler_params=_COMPILER,
        interpret=interpret,
    )(qt, kt, vt)
    return _untile(o, Sq, Hq), lse


def _backward(q, k, v, o, lse, do, geo, interpret):
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    bq, bk = geo.block_q, geo.block_k
    d = _rows(jnp.sum(do.astype(f32) * o.astype(f32), axis=-1), bq)
    qt, dot_ = _tiles(q, bq), _tiles(do, bq)
    kt, vt, ktt = _tiles(k, bk), _tiles(v, bk), _tiles_t(k, bk)
    nq, nk = qt.shape[1], kt.shape[1]
    q_spec = pl.BlockSpec((None, nq, bq, hd),
                          lambda b, hk, g: (b, 0, 0, hk * G + g))
    kv_spec = pl.BlockSpec((None, nk, bk, hd), lambda b, hk, g: (b, 0, 0, hk))
    row_spec = pl.BlockSpec((None, None, nq, 1, bq),
                            lambda b, hk, g: (b, hk * G + g, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, geo=geo, nq=nq, nk=nk, G=G),
        grid=(B, Hkv, G),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((None, None, nk, hd, bk),
                         lambda b, hk, g: (b, hk, 0, 0, 0)),
            q_spec, row_spec, row_spec,
        ],
        out_specs=[q_spec, kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, q.dtype),
                   jax.ShapeDtypeStruct(kt.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((nq, hd, bq), f32),
                        pltpu.VMEM((nk, bk, hd), f32),
                        pltpu.VMEM((nk, bk, hd), f32)],
        name="flash_attention_bwd", compiler_params=_COMPILER,
        interpret=interpret,
    )(qt, kt, vt, ktt, dot_, lse, d)
    return _untile(dq, Sq, Hq), _untile(dk, Sk, Hkv), _untile(dv, Sk, Hkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, geo, interpret):
    return _forward(q, k, v, geo, interpret)[0]


def _flash_fwd(q, k, v, geo, interpret):
    o, lse = _forward(q, k, v, geo, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(geo, interpret, res, do):
    return _backward(*res, do, geo, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q",
                     "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    softcap: float | None = None, scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q: (B, Sq, Hq, hd); k, v: (B, Sk, Hkv, hd), Hkv dividing Hq ->
    (B, Sq, Hq, hd), differentiable in q, k and v.

    Query ``i`` attends to key ``j`` where ``j <= i`` (``causal``) and ``i -
    j < window`` (a window given), with scores ``softcap * tanh(s /
    softcap)`` (a softcap given) of ``s = scale * q . k``, ``scale``
    ``1 / sqrt(hd)`` by default.  Sequences that are not whole blocks are
    padded and the padded keys masked.  ``interpret`` None runs the
    interpreter on the CPU backend and Mosaic elsewhere."""
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"{Hq} query heads do not share {Hkv} kv heads")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    geo = _Geometry(causal=causal, window=window, softcap=softcap,
                    scale=scale if scale is not None else 1.0 / math.sqrt(hd),
                    block_q=min(block_q, max(Sq, 1)),
                    block_k=min(block_k, max(Sk, 1)), seq_k=Sk)
    return _flash(q, k, v, geo, interpret)

"""Tropical (min-plus) matmul Pallas kernel for frontier composition.

The DFTS tour relaxation (core/dfts.py) and its batched JAX port
(core/jax_solvers.py) compose per-stage frontier matrices in the tropical
semiring: ``val[m, n] = min_k a[m, k] + b[k, n]`` with the *first* minimizing
``k`` returned as a predecessor index (ties resolve to the lowest index, the
np/jnp ``argmin`` convention the NumPy oracle relies on for bit-parity).

Per batch element the kernel keeps the whole (padded) tile in VMEM and scans
the contraction axis with a strict-``<`` running min/argmin, so the result is
independent of accumulation order (IEEE min is associative/commutative for
the +inf-padded, NaN-free cost matrices the solvers produce).  +inf is the
semiring zero: padded rows/columns are absorbing and can never win a min
against a finite entry, which is what makes shape padding safe.

Validated in interpret mode on CPU (the CI path); on TPU it compiles through
Mosaic in float32 (tests/test_tpu_compile.py).
The jnp oracle is :func:`repro.kernels.ref.reference_minplus`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Tile floors for TPU layout: second-to-last dim multiples of 8, last dim
# multiples of 128.  Frontier matrices are tiny (S <= ~16 candidates), so a
# single padded block per batch element is the whole problem.
_BM = 8
_BK = 128
_BN = 128


def _minplus_kernel(a_ref, b_ref, val_ref, idx_ref):
    a = a_ref[0]  # (M, K)
    m, k = a.shape
    n = b_ref.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (m, k), 1)

    def body(j, carry):
        val, idx = carry
        # Column j of `a` as a masked lane min: Mosaic lowers no dynamic lane
        # slice, and min over +inf fill returns a[:, j] exactly.
        col = jnp.min(jnp.where(lane == j, a, jnp.inf), axis=1, keepdims=True)
        cand = col + b_ref[0, pl.ds(j, 1), :]  # (M, 1) + (1, N)
        better = cand < val  # strict: first minimum wins (argmin convention)
        return (jnp.where(better, cand, val),
                jnp.where(better, j, idx))

    val0 = jnp.full((m, n), jnp.inf, dtype=val_ref.dtype)
    idx0 = jnp.zeros((m, n), dtype=jnp.int32)
    val, idx = jax.lax.fori_loop(0, k, body, (val0, idx0))
    val_ref[0] = val
    idx_ref[0] = idx


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def minplus_matmul(a, b, *, interpret: bool | None = None):
    """Batched tropical matmul: a (..., M, K) ∘ b (..., K, N).

    Returns ``(val, idx)`` with ``val[..., m, n] = min_k a[..., m, k] +
    b[..., k, n]`` and ``idx`` the first minimizing ``k`` (int32; 0 when the
    whole column is +inf, matching ``jnp.argmin``).  Inputs are padded with
    +inf to TPU tile multiples and the padding is sliced back off, so any
    shapes (including non-tile-multiples) are accepted.

    ``interpret`` defaults to the backend: the Pallas interpreter on CPU,
    Mosaic otherwise.  Mosaic has no float64, so a compiled call with f64
    operands raises ``TypeError``.
    """
    if a.ndim != b.ndim or a.shape[:-2] != b.shape[:-2]:
        raise ValueError(f"batch dims must match, got {a.shape} vs {b.shape}")
    if a.shape[-1] != b.shape[-2]:
        raise ValueError(f"contraction dims must match, got {a.shape} vs "
                         f"{b.shape}")
    batch = a.shape[:-2]
    M, K = a.shape[-2:]
    N = b.shape[-1]
    a3 = _pad_to(_pad_to(a.reshape((-1, M, K)), 1, _BM), 2, _BK)
    b3 = _pad_to(_pad_to(b.reshape((-1, K, N)), 1, _BK), 2, _BN)
    B, Mp, Kp = a3.shape
    Np = b3.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if not interpret and jnp.float64 in (a.dtype, b.dtype):
        raise TypeError("minplus_matmul: Mosaic has no float64; compile the "
                        "kernel with float32 operands or run it with "
                        "interpret=True")
    val, idx = pl.pallas_call(
        _minplus_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Mp, Kp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Kp, Np), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Mp, Np), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Mp, Np), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Mp, Np), a3.dtype),
            jax.ShapeDtypeStruct((B, Mp, Np), jnp.int32),
        ],
        interpret=interpret,
    )(a3, b3)
    val = val[:, :M, :N].reshape(batch + (M, N))
    idx = idx[:, :M, :N].reshape(batch + (M, N))
    return val, idx
